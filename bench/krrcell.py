"""What the KRR drivers share: the program's fit on seeded data, and the
comparison of what the timed path produced with the plain reference."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import data

#: queries per block of the reference's predictions
BLOCK = 4096
#: the reference's matmul precision, whatever the run's (a control lowers it)
REFERENCE_MATMUL = "highest"


@dataclasses.dataclass
class Problem:
    """Seeded data and the program's fit of one configuration."""

    x: jax.Array
    y: jax.Array
    xt: jax.Array
    yt: jax.Array
    sigma: float
    key: jax.Array
    fit: object          # () -> HCKRegressor, the program's krr.fit


def problem(ctx) -> Problem:
    """Data made on the device from ``--seed`` and the program's fit."""
    from repro.core import krr
    from repro.core.kernels_fn import BaseKernel
    from repro.kernels.registry import SolveConfig

    cfg = ctx.cfg
    with jax.default_matmul_precision(cfg["precision"]["matmul"]):
        x, y, xt, yt, sigma = data.dataset(cfg, ctx.seed)   # also for a control
    key = jax.random.fold_in(data.seed_key(ctx.seed), 1)
    kernel = BaseKernel(cfg["kernel"], sigma=sigma, jitter=cfg["jitter"])
    control = ctx.control or {}
    config = SolveConfig(backend=control.get("backend", "auto"),
                         precision=control.get("policy"))

    def fit():
        return krr.fit(x, y, kernel=kernel, lam=cfg["lam"], rank=cfg["rank"],
                       leaf_size=cfg["leaf"], key=key,
                       classification=cfg["task"] == "multiclass",
                       solve_config=config)

    return Problem(x, y, xt, yt, sigma, key, fit)


def model_arrays(model) -> tuple:
    """Every array a fit returns: factors, alpha, plan and the inverse."""
    return (model.factors, model.alpha, model.plan, model.inverse,
            model.leaf_lo)


def truth(ctx, yt) -> np.ndarray:
    """Held-out targets as the predictions give them: (q, k)."""
    cfg = ctx.cfg
    return np.asarray(data.targets(yt, cfg["task"], cfg["classes"]))


def rel(a, b) -> float:
    """Relative 2-norm error of ``a`` against ``b``, on the device."""
    return float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))


@dataclasses.dataclass
class Reference:
    """The reference fit on the checked tree."""

    misplaced: int
    directions: list
    thresholds: list
    x_sorted: jax.Array
    factors: dict
    alpha: jax.Array
    d_leaf: jax.Array
    y_sorted: jax.Array
    residuals: list      # relative residual before each refinement step


def reference_fit(ctx, prob: Problem, perm, x_sorted) -> Reference:
    """Check the program's tree and fit the reference on it."""
    ref = ctx.cell.reference()
    cfg = ctx.cfg
    with jax.default_matmul_precision(REFERENCE_MATMUL):
        xp, yp, kbuild = ref.pad(prob.x, prob.y, cfg["leaf"], cfg["levels"],
                                 prob.key)
        bad, dirs, thrs = ref.check_tree(xp, perm, x_sorted, kbuild,
                                         levels=cfg["levels"])
        xs = xp[perm]
        ys = data.targets(yp, cfg["task"], cfg["classes"])[perm]
        f, alpha, d_leaf, residuals = ref.fit(
            xs, kbuild, ys, sigma=prob.sigma, jitter=cfg["jitter"],
            lam=cfg["lam"], levels=cfg["levels"], rank=cfg["rank"])
    ctx.say("reference residual by refinement step: "
            + " ".join(f"{r:.3e}" for r in residuals))
    return Reference(int(bad), dirs, thrs, xs, f, alpha, d_leaf, ys,
                     residuals)


def reference_predict(ctx, prob: Problem, ref: Reference, idx):
    """Reference predictions for held-out rows ``idx`` (host array), in
    blocks of :data:`BLOCK` queries, and the mask of queries whose route
    rounding decides."""
    mod = ctx.cell.reference()
    n = len(idx)
    padded = np.concatenate([idx, np.full(-n % BLOCK, idx[-1])])
    preds, nears = [], []
    with jax.default_matmul_precision(REFERENCE_MATMUL):
        for i in range(0, len(padded), BLOCK):
            q = prob.xt[jnp.asarray(padded[i:i + BLOCK])]
            leaf, near = mod.route(ref.directions, ref.thresholds, q,
                                   levels=ctx.cfg["levels"])
            preds.append(mod.predict(ref.factors, ref.x_sorted, ref.alpha,
                                     ref.d_leaf, leaf, q, prob.sigma))
            nears.append(near)
    return (np.concatenate([np.asarray(p) for p in preds])[:n],
            np.concatenate([np.asarray(m) for m in nears])[:n])


def alpha_residual(ctx, ref: Reference, alpha) -> float:
    """Relative residual of the program's ``alpha`` under the reference's
    operator, in float64 on the host (the two operators differ by the
    factors' rounding, so this is a reading, not a comparison)."""
    mod = ctx.cell.reference()
    return mod.residual(mod.host64(ref.factors), alpha, ref.y_sorted,
                        ctx.cfg["lam"])


def factor_error(factors, ref: Reference) -> float:
    """Largest relative error over the factor stacks of the fit."""
    f = ref.factors
    errs = [rel(factors.adiag, f["adiag"]), rel(factors.u, f["u"])]
    for name in ("landmarks", "sigma", "w"):
        errs += [rel(a, b) for a, b in zip(getattr(factors, name), f[name])]
    return max(errs)


def prediction_errors(ctx, prob, ref, idx, pred) -> dict:
    """How far the predictions ``pred`` of held-out rows ``idx`` lie from
    the reference's, in units of the reference's own test error.

    ``share`` is ``|pred - ref| / |ref - truth|`` over all compared
    queries and ``worst`` the largest single query's ``|pred - ref|`` over
    the root-mean-square ``|ref - truth|``.  ``centered`` and
    ``centered_worst`` are the same two after the deviation's mean over
    the queries (``offset``, also in those units) is taken out: a float32
    solve of this ill-conditioned system moves every prediction by about
    the same amount, and a wrong answer moves its own.  ``near`` counts
    the queries left out because rounding decides their route.
    """
    ref_pred, near = reference_predict(ctx, prob, ref, idx)
    keep = ~near
    t = truth(ctx, prob.yt[jnp.asarray(idx)])
    pred, ref_pred, t = (np.asarray(a, np.float64)[keep]
                         for a in (pred, ref_pred, t))
    dev = pred - ref_pred
    offset = dev.mean(axis=0)
    err = np.linalg.norm(ref_pred - t, axis=1)
    rms = np.sqrt(np.mean(err ** 2))
    full = np.linalg.norm(dev, axis=1)
    cent = np.linalg.norm(dev - offset, axis=1)
    return {"share": float(np.linalg.norm(full) / np.linalg.norm(err)),
            "worst": float(np.max(full) / rms),
            "centered": float(np.linalg.norm(cent) / np.linalg.norm(err)),
            "centered_worst": float(np.max(cent) / rms),
            "offset": float(np.linalg.norm(offset) / rms),
            "near": int(near.sum())}


def check(name: str, value, limit) -> dict:
    """One compared number with its limit (the limit lives in the
    configuration's ``limits``)."""
    return {"name": name, "value": value, "limit": limit}
