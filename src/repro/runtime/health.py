"""Numerical health probes at stage boundaries (DESIGN.md §11).

The paper's strict positive-definiteness guarantee is exactly what finite
precision and online mutation quietly break: a bf16 build whose ridge
sits under the ``n0·eps`` floor NaNs the leaf Schur Cholesky, a poisoned
collective NaNs every CG column, a bad insert ships garbage to serving.
This module turns those silent failures into structured
:class:`NumericalFailure` diagnostics raised from CHEAP probes run where
stage outputs are already concrete:

  * factor diagonals after ``build_gram`` / ``build_cross`` stages
    (:func:`probe_factors`),
  * the leaf Schur Cholesky after ``leaf_factor`` / ``leaf_update``
    (:func:`probe_leaf_factor` — finiteness AND positive diagonal, the
    definiteness witness),
  * residual traces of :class:`repro.solvers.cg.CGResult`
    (:func:`probe_cg` / :func:`cg_diagnose` — the stall/divergence
    detector),
  * served predictions at ``PredictEngine.apply`` / the registry canary
    (:func:`probe_predictions`).

Probes are gated by ``SolveConfig.checks`` with the ``REPRO_STRICT_FINITE``
env var as the default policy, and every probe no-ops on traced values —
they run at stage boundaries OUTSIDE jit, so compiled programs are
bitwise identical with checks on or off and the checks-off hot path pays
one predicate per boundary.  The serving loop's probe is forced, not
gated: on one TPU v5e it takes 1.19–1.22 ms per served batch (three
small programs and a synchronous read) against a median request latency
of 12.3 ms at 16,000 requests/s (PERF.md, ``msd.serve``).  A probe that
runs records a ``hck.probe.<stage>`` span.
"""
from __future__ import annotations

import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.trace import span

Array = jax.Array


class NumericalFailure(RuntimeError):
    """A numerical invariant broke at a named stage boundary.

    Carries everything a recovery ladder (or a human reading a serving
    log) needs to act without re-running the failure: the stage, the
    offending node/leaf, the operand dtype, the backend that produced it
    and the statistic that tripped.
    """

    def __init__(self, stage: str, *, statistic: str, value,
                 leaf: int | None = None, node: int | None = None,
                 dtype=None, backend: str | None = None, detail: str = ""):
        self.stage = stage
        self.statistic = statistic
        self.value = value
        self.leaf = leaf
        self.node = node
        self.dtype = str(dtype) if dtype is not None else None
        self.backend = backend
        self.detail = detail
        parts = [f"[{stage}] {statistic}={value!r}"]
        if leaf is not None:
            parts.append(f"leaf={leaf}")
        if node is not None:
            parts.append(f"node={node}")
        if self.dtype is not None:
            parts.append(f"dtype={self.dtype}")
        if backend is not None:
            parts.append(f"backend={backend}")
        if detail:
            parts.append(detail)
        super().__init__(" ".join(parts))

    def to_dict(self) -> dict[str, Any]:
        """JSON-serializable form (audit trails, CI fault matrices)."""
        return {
            "stage": self.stage,
            "statistic": self.statistic,
            "value": repr(self.value),
            "leaf": self.leaf,
            "node": self.node,
            "dtype": self.dtype,
            "backend": self.backend,
            "detail": self.detail,
        }


# ---------------------------------------------------------------------------
# policy
# ---------------------------------------------------------------------------

def strict_finite_env() -> bool:
    """The ``REPRO_STRICT_FINITE`` policy bit (default off)."""
    return os.environ.get("REPRO_STRICT_FINITE", "0").lower() not in (
        "", "0", "false", "off")


def checks_enabled(config=None) -> bool:
    """Whether probes run for ``config``.

    ``config.checks`` wins when set; the default (None, or no config)
    defers to ``REPRO_STRICT_FINITE`` at call time, so an env flip takes
    effect without constructing a new SolveConfig anywhere.
    """
    checks = getattr(config, "checks", None)
    if checks is None:
        return strict_finite_env()
    return bool(checks)


def _concrete(x) -> bool:
    """Probes only look at materialized stage outputs — a traced value
    means the caller is inside jit, where raising is impossible and the
    boundary probe will run on the concrete result instead."""
    return not isinstance(x, jax.core.Tracer)


def _gate(config, force: bool, *arrays) -> bool:
    if not (force or checks_enabled(config)):
        return False
    return all(_concrete(a) for a in arrays if a is not None)


def _backend_of(config) -> str | None:
    return getattr(config, "backend", None)


# ---------------------------------------------------------------------------
# probes
# ---------------------------------------------------------------------------

def _first_bad_leaf(bad: Array, leaf_axis: int | None) -> int | None:
    """Index along ``leaf_axis`` of the first offending entry."""
    if leaf_axis is None:
        return None
    axes = tuple(i for i in range(bad.ndim) if i != leaf_axis)
    per_leaf = jnp.any(bad, axis=axes) if axes else bad
    return int(jnp.argmax(per_leaf))


def check_finite(stage: str, x: Array, *, config=None, force: bool = False,
                 statistic: str = "nonfinite_count",
                 leaf_axis: int | None = None, detail: str = "") -> bool:
    """Raise :class:`NumericalFailure` if ``x`` has NaN/Inf entries.

    Returns True when the probe RAN (enabled and concrete), False when it
    was skipped — callers never branch on the value, but the robustness
    tests assert probes actually fire under ``REPRO_STRICT_FINITE``.
    """
    if not _gate(config, force, x):
        return False
    with span("probe." + stage):
        bad = ~jnp.isfinite(x)
        if bool(jnp.any(bad)):
            raise NumericalFailure(
                stage, statistic=statistic, value=int(jnp.sum(bad)),
                leaf=_first_bad_leaf(bad, leaf_axis), dtype=x.dtype,
                backend=_backend_of(config), detail=detail)
    return True


@jax.jit
def _all_finite_pd(leaves, chos):
    """Fused happy-path predicate: every array finite AND every Cholesky
    diagonal positive, as ONE compiled program (eager dispatch of ~26
    small reductions costs ~7 ms on CPU; compiled it is microseconds)."""
    flags = [jnp.all(jnp.isfinite(a)) for a in leaves]
    flags += [jnp.all(jnp.diagonal(c, axis1=-2, axis2=-1) > 0)
              for c in chos]
    return jnp.stack(flags).all()


def probe_factors(factors, config=None, *, force: bool = False,
                  op: str = "build") -> bool:
    """Finiteness of every HCK factor, attributed to its producing stage.

    ``adiag`` / ``sigma`` / ``sigma_cho`` come out of the ``build_gram``
    stage (plus a positive-diagonal check on the Cholesky — the
    definiteness witness); ``u`` / ``w`` come out of ``build_cross``.
    ``op`` tags the message with the caller ("build", "update.insert",
    "refit_frozen") so an audit trail reads without a stack trace.
    """
    if not _gate(config, force, factors.adiag, factors.u):
        return False
    with span("probe." + op):
        # happy path: one fused compiled predicate over the whole factor
        # pytree and a SINGLE host sync — per-factor probes cost ~1 ms each
        # in syncs and dispatch, which is the difference between probes
        # cheap enough to leave on in production and probes that blow the
        # bench_update recovery-overhead budget.  The per-factor attribution
        # below only runs once something is already known to be bad.
        leaves = [factors.adiag, factors.u, *factors.sigma, *factors.sigma_cho,
                  *factors.w]
        if bool(_all_finite_pd(leaves, list(factors.sigma_cho))):
            return True
        check_finite("build_gram", factors.adiag, config=config, force=True,
                     leaf_axis=0, detail=f"op={op} factor=adiag")
        for lvl, (sig, cho) in enumerate(zip(factors.sigma,
                                             factors.sigma_cho)):
            check_finite("build_gram", sig, config=config, force=True,
                         leaf_axis=0,
                         detail=f"op={op} factor=sigma level={lvl}")
            check_finite("build_gram", cho, config=config, force=True,
                         leaf_axis=0,
                         detail=f"op={op} factor=sigma_cho level={lvl}")
            diag = jnp.diagonal(cho, axis1=-2, axis2=-1)
            if bool(jnp.any(diag <= 0)):
                raise NumericalFailure(
                    "build_gram", statistic="min_cholesky_diag",
                    value=float(jnp.min(diag)),
                    node=_first_bad_leaf(diag <= 0, 0), dtype=cho.dtype,
                    backend=_backend_of(config),
                    detail=f"op={op} Sigma Cholesky not PD at level {lvl}")
        check_finite("build_cross", factors.u, config=config, force=True,
                     leaf_axis=0, detail=f"op={op} factor=u")
        for lvl, w in enumerate(factors.w):
            check_finite("build_cross", w, config=config, force=True,
                         leaf_axis=0, detail=f"op={op} factor=w level={lvl}")
        return True


def probe_leaf_factor(lo: Array, config=None, *, force: bool = False,
                      stage: str = "leaf_factor") -> bool:
    """Definiteness witness of the ridged leaf Schur complements.

    ``lo`` is the (P, n0, n0) Cholesky stack from ``invert_with_leaf`` /
    ``invert_extend``; a NaN or non-positive diagonal entry means the
    Schur complement went indefinite under the current ridge — the bf16
    ridge-floor failure (SolveConfig.precision docs) lands exactly here.
    Pass ``stage="leaf_update"`` for the bordered-extension pair.
    """
    if not _gate(config, force, lo):
        return False
    with span("probe." + stage):
        diag = jnp.diagonal(lo, axis1=-2, axis2=-1)        # (P, n0)
        bad = ~jnp.isfinite(diag) | (diag <= 0)
        if bool(jnp.any(bad)):
            raise NumericalFailure(
                stage, statistic="min_schur_cholesky_diag",
                value=float(jnp.min(jnp.where(jnp.isfinite(diag), diag,
                                              -jnp.inf))),
                leaf=_first_bad_leaf(bad, 0), dtype=lo.dtype,
                backend=_backend_of(config),
                detail="leaf Schur complement indefinite or non-finite "
                       "(raise the ridge, promote precision, or refit)")
    return True


def cg_diagnose(result, *, tol: float) -> str:
    """Classify a concrete :class:`~repro.solvers.cg.CGResult` trace.

    Returns one of ``"converged"`` / ``"nonfinite"`` / ``"diverged"``
    (final residual grew ≥ 10× past the start) / ``"stalled"`` (ran out
    of iterations with < 10% total progress over the trailing window —
    the classic-PCG-with-inexact-preconditioner signature measured in
    PR 5) / ``"maxiter"`` (still converging, just slowly).
    """
    # one device->host transfer for the whole trace; everything below is
    # host arithmetic (a float() per comparison costs a sync each)
    trace = np.asarray(result.residuals)
    it = int(result.iterations)
    final = float(trace[it])
    if not np.isfinite(trace[: it + 1]).all():
        return "nonfinite"
    if bool(result.converged):
        return "converged"
    if final > 10.0 * float(trace[0]) + 1e-30:
        return "diverged"
    window = min(10, it) if it > 0 else 0
    if window and final > 0.9 * float(trace[it - window]) and final > tol:
        return "stalled"
    return "maxiter"


def probe_cg(result, *, tol: float, config=None, force: bool = False,
             context: str = "") -> str | None:
    """Stall/divergence detector on a CG residual trace.

    Raises :class:`NumericalFailure` (stage ``solvers.cg``) on
    ``nonfinite`` / ``diverged`` / ``stalled`` verdicts; returns the
    verdict string otherwise (None when the probe was skipped).
    """
    if not _gate(config, force, result.x):
        return None
    verdict = cg_diagnose(result, tol=tol)
    if verdict in ("nonfinite", "diverged", "stalled"):
        it = int(result.iterations)
        raise NumericalFailure(
            "solvers.cg", statistic=f"residual_{verdict}",
            value=float(result.residuals[it]), dtype=result.x.dtype,
            backend=_backend_of(config),
            detail=f"after {it} iterations (tol={tol:g}) {context}".strip())
    return verdict


def probe_predictions(z: Array, config=None, *, force: bool = False,
                      stage: str = "predict") -> bool:
    """Finiteness of a served prediction batch (engine / canary gate)."""
    return check_finite(stage, z, config=config, force=force,
                        statistic="nonfinite_predictions")
