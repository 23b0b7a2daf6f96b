"""Bring-up smoke test: the HCK fit -> publish -> serve path on a TPU.

  python chip_smoke.py             # one chip: fit, publish, serve, update
  python chip_smoke.py --chips 4   # four chips: the mesh path only

One process, float32, the public entry points, and data drawn from
``--seed``: a synthetic stand-in with the shape of the YearPredictionMSD
row of the paper's Table 1 (``configs/hck_krr.py``: n = 463,518 training
points, d = 90, 51,630 test points, regression), at rank = leaf size = 128,
which pads n to 524,288 = 128 * 2**12.

One chip: ``krr.fit`` with ``SolveConfig()`` (backend "auto", compiled
kernels), ``ModelRegistry(model, warmup=True)``, ``KRRServeLoop.serve`` over
the whole test set, one ``registry.update_and_publish`` of new points, and
a few more batches.  Checks: no failed, retried or degraded batch; finite
outputs; test RMSE below that of predicting the training mean; the same
factors re-solved and predicted with ``SolveConfig(backend="xla")`` agree
with the Pallas run; and at n = 4096 ``hmatrix.matvec``/``solve`` agree
with the dense oracle ``to_dense(f)``.

Four chips: ``dist_build_hck`` on ``kernel_mesh(4)``, ``hmatrix.solve`` on
the sharded factors and ``model.engine.on_mesh(mesh)`` predictions, each
compared with one device in the same process (see :func:`four_chip`);
``u`` must be split across all four devices.

Exits non-zero, printing no result, when JAX finds no TPU or a check
fails.  Otherwise the last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

LEAF = 128          # rank = leaf size (configs/hck_krr.py)
N_NEW = 256         # points absorbed by the online update
MICRO_BATCH = 1024  # queries per served batch
ORACLE_N = 4096     # size of the dense-oracle check

# Pass bounds, all relative 2-norm errors in float32.
OPERATOR_TOL = 1e-4   # SolveConfig.precision: f32 matvec / OOS bound
SOLVE_TOL = 1e-3      # a solve on the same factors: the f32 rounding,
#                       amplified by the conditioning of K + lam I
FACTOR_TOL = 1e-5     # mesh vs one-device Gram factors on one tree
# Solve then predict, end to end, as a share of the model's own test error
# (||pred_a - pred_b|| / ||pred_b - y||).  At this shape on a TPU v5e the
# Pallas and XLA f32 solves of the same factors predict 0.105-0.107 of the
# test error apart, while one f32 rounding of y moves either one's
# predictions by under 0.003 of it: the gap is the two solves' own
# rounding, which grows with n (on the CPU too), not the data's.  A kernel
# fault shows as a share near or above 1.
PREDICT_SHARE = 0.2


def _rel(a, b) -> float:
    """Relative 2-norm error of ``a`` against ``b``, on the host (the two
    may live on different devices)."""
    import numpy as np

    a = np.asarray(a, np.float64).reshape(-1)
    b = np.asarray(b, np.float64).reshape(-1)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _share(pred, ref, y) -> float:
    """||pred - ref|| as a share of ``ref``'s own error ||ref - y||."""
    import numpy as np

    pred, ref, y = (np.asarray(a, np.float64).reshape(-1)
                    for a in (pred, ref, y))
    return float(np.linalg.norm(pred - ref) / np.linalg.norm(ref - y))


def _stacked(a):
    """A factor stack, or a tuple of per-level stacks, as one flat host
    vector (for :func:`_rel`)."""
    import numpy as np

    parts = a if isinstance(a, tuple) else (a,)
    return np.concatenate([np.asarray(p, np.float64).reshape(-1)
                           for p in parts])


def _timed(fn, *args, **kwargs):
    import jax

    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def problem(seed: int, n_train: int | None = None, n_test: int | None = None,
            n_new: int = N_NEW):
    """(x, y), (xt, yt), (x_new, y_new), kernel, ridge for the smoke shape.

    The new points come from the same draw as the training set, so they
    follow the same target function.  The Gaussian bandwidth is the median
    pairwise distance of the first 1024 training points.
    """
    import jax
    import jax.numpy as jnp

    from repro.configs.hck_krr import DATASETS
    from repro.core.kernels_fn import BaseKernel
    from repro.data.pipeline import regression_dataset

    cfg = DATASETS["yearpredictionmsd"]
    n_train = n_train or cfg.n_train
    cfg = dataclasses.replace(cfg, n_train=n_train + n_new,
                              n_test=n_test or cfg.n_test)
    (x, y), (xt, yt) = jax.jit(regression_dataset, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))
    sub = x[:1024]
    d2 = jnp.sum((sub[:, None, :] - sub[None, :, :]) ** 2, axis=-1)
    sigma = float(jnp.sqrt(jnp.median(d2)))
    kernel = BaseKernel(cfg.kernel, sigma=sigma)
    return ((x[:n_train], y[:n_train]), (xt, yt),
            (x[n_train:], y[n_train:]), kernel, cfg.lam)


def padded(x, y, key):
    """The padded, tree-ordered inputs ``krr.fit`` builds on: (x, y, key,
    levels) with ``key`` the build key ``krr.fit`` derives from ``key``."""
    import jax

    from repro.core.partition import auto_levels_ceil, pad_points

    levels = max(1, auto_levels_ceil(x.shape[0], LEAF))
    kpad, kbuild = jax.random.split(key)
    xp, yp, _ = pad_points(x, y, LEAF, levels, kpad)
    return xp, yp, kbuild, levels


def stage_backends(config, *, d: int) -> dict:
    """The backend ``resolve_backend`` picks for each main-path stage at
    the smoke's shapes (n0 = r = 128, one right-hand side)."""
    import jax.numpy as jnp

    from repro.kernels.registry import resolve_backend

    shapes = {
        "build_gram": dict(n0=LEAF, r=LEAF, d=d),
        "build_cross": dict(n0=2 * LEAF, r=LEAF, d=d),
        "leaf_factor": dict(n0=LEAF, r=LEAF),
        "leaf_matvec": dict(n0=LEAF, r=LEAF, k=1),
        "leaf_solve": dict(n0=LEAF, r=LEAF, k=1),
        "leaf_project": dict(n0=LEAF, r=LEAF),
        "oos_local": dict(n0=LEAF, r=LEAF, k=1),
        "oos_walk": dict(n0=LEAF, r=LEAF, k=1),
    }
    return {s: resolve_backend(config, s, dtype=jnp.float32, **kw)
            for s, kw in shapes.items()}


def one_chip(seed: int, config=None, *, n_train=None, n_test=None,
             oracle_n: int = ORACLE_N, say=print) -> list:
    """The one-chip phases; returns the names of the failed checks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import hmatrix, krr, oos
    from repro.core.hck import build_hck, to_dense
    from repro.kernels.registry import SolveConfig, resolve_backend
    from repro.serving.predict_service import ModelRegistry
    from repro.serving.serve_loop import KRRServeLoop

    config = config if config is not None else SolveConfig()
    failed = []

    def check(name, ok, detail):
        say(f"check {name}: {'pass' if ok else 'FAIL'} ({detail})")
        if not ok:
            failed.append(name)

    (x, y), (xt, yt), (x_new, y_new), ker, lam = problem(seed, n_train,
                                                        n_test)
    say(f"data: n={x.shape[0]} d={x.shape[1]} test={xt.shape[0]} "
        f"new={x_new.shape[0]} sigma={ker.sigma:.4f} lam={lam} "
        f"dtype={x.dtype}")
    say(f"SolveConfig: backend={config.backend} "
        f"interpret={config.interpret}")
    for stage, backend in stage_backends(config, d=x.shape[1]).items():
        say(f"stage {stage}: {backend}")

    key = jax.random.PRNGKey(seed + 1)
    model, t_fit = _timed(krr.fit, x, y, kernel=ker, lam=lam, rank=LEAF,
                          leaf_size=LEAF, key=key, solve_config=config)
    f = model.factors
    say(f"fit: {t_fit:.3f} s (levels={f.levels} n_padded={f.n} "
        f"leaf={f.leaf_size} rank={f.rank})")

    buckets = dict(min_bucket=MICRO_BATCH // 4, max_bucket=MICRO_BATCH)
    t0 = time.perf_counter()
    registry = ModelRegistry(model, tag="fit", warmup=True, **buckets)
    say(f"publish+warmup: {time.perf_counter() - t0:.3f} s")

    loop = KRRServeLoop(registry)
    t0 = time.perf_counter()
    served = loop.run(xt, MICRO_BATCH)
    pred = jnp.concatenate([b.z[:, 0] for b in served])
    jax.block_until_ready(pred)
    t_serve = time.perf_counter() - t0
    rmse = float(jnp.sqrt(jnp.mean((pred - yt) ** 2)))
    rmse_mean = float(jnp.sqrt(jnp.mean((jnp.mean(y) - yt) ** 2)))
    say(f"serve v1: {xt.shape[0]} queries in {len(served)} batches, "
        f"{t_serve:.3f} s; test RMSE {rmse:.6f} (training-mean predictor "
        f"{rmse_mean:.6f})")

    t0 = time.perf_counter()
    version, info = registry.update_and_publish(x_new, y_new, tag="update",
                                                warmup=True)
    say(f"update_and_publish: {time.perf_counter() - t0:.3f} s "
        f"({x_new.shape[0]} points, k={info.record.k} rows/leaf, residual "
        f"{info.residual:.3e}, converged {info.converged}, version "
        f"{version})")
    say("stage leaf_update: " + resolve_backend(
        config, "leaf_update", dtype=jnp.float32, n0=LEAF, r=LEAF,
        k=info.record.k))
    after = loop.run(xt[:4 * MICRO_BATCH], MICRO_BATCH)
    outs = [b.z for b in served + after]
    stats = loop.stats()
    say(f"serve v2: {sum(b.z.shape[0] for b in after)} queries; "
        f"loop stats {stats}")
    say(f"queries served: {sum(o.shape[0] for o in outs)}")

    check("serve_loop_clean",
          stats["failures"] == 0 and stats["retries"] == 0
          and stats["degraded_batches"] == 0
          and stats["versions_served"] == [1, 2],
          f"failures={stats['failures']} retries={stats['retries']} "
          f"degraded={stats['degraded_batches']} "
          f"versions={stats['versions_served']}")
    check("outputs_finite",
          all(bool(jnp.all(jnp.isfinite(o))) for o in outs)
          and bool(jnp.all(jnp.isfinite(model.alpha))), "alpha and served")
    check("rmse_beats_mean", rmse < rmse_mean,
          f"{rmse:.6f} < {rmse_mean:.6f}")

    # the same factors and targets, re-solved and predicted on XLA
    xla = SolveConfig(backend="xla")
    _, yp, _, _ = padded(x, y, key)
    y_sorted = yp[f.tree.perm][:, None]
    mv_p = hmatrix.matvec(f, y_sorted, config)
    mv_x = hmatrix.matvec(f, y_sorted, xla)
    alpha_x = hmatrix.solve(f, y_sorted, ridge=lam, config=xla)
    q_cmp = xt[:MICRO_BATCH]
    pred_x = oos.apply_plan(f, oos.prepare(f, alpha_x, xla), q_cmp, ker, xla)
    oos_x = oos.apply_plan(f, model.plan, q_cmp, ker, xla)
    oos_p = oos.apply_plan(f, model.plan, q_cmp, ker, config)
    e_mv, e_oos = _rel(mv_p, mv_x), _rel(oos_p, oos_x)
    e_alpha, e_pred = _rel(model.alpha, alpha_x), _rel(pred[:MICRO_BATCH],
                                                       pred_x[:, 0])
    y_cmp = yt[:MICRO_BATCH]
    share = _share(pred[:MICRO_BATCH], pred_x[:, 0], y_cmp)
    rmse_p = float(jnp.sqrt(jnp.mean((pred[:MICRO_BATCH] - y_cmp) ** 2)))
    rmse_x = float(jnp.sqrt(jnp.mean((pred_x[:, 0] - y_cmp) ** 2)))
    say(f"pallas vs xla: matvec {e_mv:.3e}, oos {e_oos:.3e}, "
        f"alpha {e_alpha:.3e}, solve+predict {e_pred:.3e} "
        f"({share:.3e} of the model's test error), test RMSE "
        f"{rmse_p:.6f} vs {rmse_x:.6f}")
    check("pallas_xla_matvec", e_mv <= OPERATOR_TOL,
          f"{e_mv:.3e} <= {OPERATOR_TOL:g}")
    check("pallas_xla_oos", e_oos <= OPERATOR_TOL,
          f"{e_oos:.3e} <= {OPERATOR_TOL:g}")
    check("pallas_xla_solve", e_alpha <= SOLVE_TOL,
          f"{e_alpha:.3e} <= {SOLVE_TOL:g}")
    check("pallas_xla_predict", share <= PREDICT_SHARE,
          f"{share:.3e} of the test error <= {PREDICT_SHARE:g}")

    # dense oracle at n = 4096 on the chip
    xo = x[:oracle_n]
    fo = build_hck(xo, levels=max(1, int(np.log2(oracle_n // LEAF))),
                   rank=LEAF, key=jax.random.PRNGKey(seed + 2), kernel=ker,
                   config=config)
    b = y[:oracle_n][fo.tree.perm][:, None]
    dense = to_dense(fo)
    e_dmv = _rel(hmatrix.matvec(fo, b, config), dense @ b)
    xs = hmatrix.solve(fo, b, ridge=lam, config=config)
    a_reg = dense + lam * jnp.eye(oracle_n, dtype=dense.dtype)
    e_res = _rel(a_reg @ xs, b)
    e_dsolve = _rel(xs, jnp.linalg.solve(a_reg, b))
    say(f"dense oracle n={oracle_n}: matvec {e_dmv:.3e}, solve residual "
        f"{e_res:.3e}, solve vs dense solve {e_dsolve:.3e}")
    check("oracle_matvec", e_dmv <= OPERATOR_TOL,
          f"{e_dmv:.3e} <= {OPERATOR_TOL:g}")
    check("oracle_solve", e_res <= OPERATOR_TOL and e_dsolve <= SOLVE_TOL,
          f"residual {e_res:.3e} <= {OPERATOR_TOL:g}, "
          f"forward {e_dsolve:.3e} <= {SOLVE_TOL:g}")

    mem = jax.devices()[0].memory_stats() or {}
    say(f"peak device bytes: {mem.get('peak_bytes_in_use', 'not reported')}")
    return failed


def four_chip(seed: int, config=None, *, n_devices: int = 4, n_train=None,
              n_test=None, say=print) -> list:
    """The mesh phases and their one-device comparison; returns the names
    of the failed checks.

    ``dist_build_hck`` and ``krr.fit`` build at the same key, so the mesh
    factors must be the one-device build's: the same tree and landmarks,
    Gram factors to round-off and the matvec within the f32 operator
    bound.  The sharded solve is held to the one-device solve, the mesh
    engine's predictions to the one-device engine's.
    """
    import jax
    import numpy as np

    from repro.core import hmatrix, krr, oos
    from repro.kernels.registry import SolveConfig
    from repro.launch.dist_hck import dist_build_hck
    from repro.launch.mesh import kernel_mesh

    config = config if config is not None else SolveConfig()
    failed = []

    def check(name, ok, detail):
        say(f"check {name}: {'pass' if ok else 'FAIL'} ({detail})")
        if not ok:
            failed.append(name)

    (x, y), (xt, yt), _, ker, lam = problem(seed, n_train, n_test)
    key = jax.random.PRNGKey(seed + 1)
    say(f"data: n={x.shape[0]} d={x.shape[1]} sigma={ker.sigma:.4f} "
        f"SolveConfig: backend={config.backend} interpret={config.interpret}")

    model, t_fit = _timed(krr.fit, x, y, kernel=ker, lam=lam, rank=LEAF,
                          leaf_size=LEAF, key=key, solve_config=config)
    f_one = model.factors
    say(f"one-device fit: {t_fit:.3f} s")

    mesh = kernel_mesh(n_devices)
    xp, yp, kbuild, levels = padded(x, y, key)
    fm, t_build = _timed(dist_build_hck, xp, levels=levels, rank=LEAF,
                         key=kbuild, kernel=ker, mesh=mesh, config=config)
    say(f"dist_build_hck on {mesh.size} devices: {t_build:.3f} s")

    shards = fm.u.addressable_shards
    owners = {s.device for s in shards}
    rows = sorted(s.data.shape[0] for s in shards)
    check("u_split", len(owners) == mesh.size
          and rows == [fm.u.shape[0] // mesh.size] * mesh.size,
          f"u {fm.u.shape} over {len(owners)} devices, rows {rows}")

    # the mesh factors against the one-device build at the same key
    moved = int(np.sum(np.asarray(fm.tree.perm)
                       != np.asarray(f_one.tree.perm)))
    err = {name: _rel(_stacked(getattr(fm, name)),
                      _stacked(getattr(f_one, name)))
           for name in ("landmarks", "sigma", "adiag", "sigma_cho", "w",
                        "u")}
    y_sorted = yp[f_one.tree.perm][:, None]
    f_gathered = jax.device_put(fm, jax.devices()[0])
    e_mv = _rel(hmatrix.matvec(f_gathered, y_sorted, config),
                hmatrix.matvec(f_one, y_sorted, config))
    say("mesh vs one-device factors: " + ", ".join(
        f"{k} {v:.3e}" for k, v in err.items()) + f"; matvec {e_mv:.3e}")
    check("mesh_factors",
          moved == 0 and err["landmarks"] == 0.0
          and err["sigma"] <= FACTOR_TOL and err["adiag"] <= FACTOR_TOL
          and e_mv <= OPERATOR_TOL,
          f"{moved} of {fm.n} tree positions differ, landmarks "
          f"{err['landmarks']:.3e} == 0, sigma {err['sigma']:.3e} and "
          f"adiag {err['adiag']:.3e} <= {FACTOR_TOL:g}, matvec "
          f"{e_mv:.3e} <= {OPERATOR_TOL:g}")

    with jax.set_mesh(mesh):     # GSPMD solve: Pallas stages run per device
        a_mesh, t_solve = _timed(hmatrix.solve, fm, y_sorted, ridge=lam,
                                 config=config)
    a_one = hmatrix.solve(f_one, y_sorted, ridge=lam, config=config)
    e_solve = _rel(a_mesh, a_one)
    say(f"hmatrix.solve on sharded factors: {t_solve:.3f} s")
    check("mesh_solve", e_solve <= SOLVE_TOL,
          f"{e_solve:.3e} <= {SOLVE_TOL:g}")

    q, yq = xt[:4 * MICRO_BATCH], yt[:4 * MICRO_BATCH]
    z_one = model.predict(q)
    z_mesh, t_pred = _timed(model.engine.on_mesh(mesh), q)
    e_pred = _rel(z_mesh[:, 0], z_one)
    say(f"mesh predictions: {q.shape[0]} queries, {t_pred:.3f} s")
    check("mesh_predict", e_pred <= OPERATOR_TOL
          and bool(np.all(np.isfinite(np.asarray(z_mesh)))),
          f"{e_pred:.3e} <= {OPERATOR_TOL:g}")

    # end to end: the model built, solved and prepared on the mesh
    a_gathered = jax.device_put(a_mesh, jax.devices()[0])
    z_built = oos.apply_plan(
        f_gathered, oos.prepare(f_gathered, a_gathered, config), q, ker,
        config)[:, 0]
    say(f"mesh-built model vs one-device model: predictions differ by "
        f"{_share(z_built, z_one, yq):.3e} of the test error")

    for i, dev in enumerate(jax.devices()[:n_devices]):
        mem = dev.memory_stats() or {}
        say(f"peak device bytes [{i}]: "
            f"{mem.get('peak_bytes_in_use', 'not reported')}")
    return failed


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip main path; 4: the mesh path only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.platform import setup_platform

    setup_platform()
    import jax

    devices = jax.devices()
    found = (f"{devices[0].platform} ({devices[0].device_kind}) "
             f"x{len(devices)}")
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {found}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices; JAX found {found}", file=sys.stderr)
        return 1
    if jax.config.jax_enable_x64:
        print("chip_smoke: runs in float32; unset JAX_ENABLE_X64",
              file=sys.stderr)
        return 1
    print(f"device: {found}; jax {jax.__version__}; compile cache "
          f"{jax.config.jax_compilation_cache_dir}", flush=True)

    def say(msg):
        print(msg, flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        failed = four_chip(args.seed, say=say)
    else:
        failed = one_chip(args.seed, say=say)
    say(f"total: {time.perf_counter() - t0:.3f} s")
    if failed:
        print(f"chip_smoke: failed checks {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
