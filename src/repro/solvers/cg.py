"""Batched preconditioned conjugate gradients on any matvec-free operator.

The solve side of the iterative subsystem: multi-RHS PCG on
``(A + ridge·I) x = b`` where ``A`` is anything with a matvec — the
chunked exact-kernel operator, the O(n·r) HCK matvec, a distributed
shard_map body.  The HCK structured inverse
(:func:`repro.core.hmatrix.apply_inverse`, the Algorithm-2 factors) is
the intended preconditioner: the paper's whole §3 argument is that
K_hck ≈ K with a strictly-PD cheap inverse, which is exactly the
spectrum-clustering property a CG preconditioner needs — measured ≥4×
fewer iterations than unpreconditioned CG on the exact kernel
(``benchmarks/bench_cg.py`` tracks the ratio).

Every RHS column runs its own scalar recurrence (per-column α/β), so one
operator sweep serves the whole block — multi-class KRR shares the
matvec like it shares the factorization in the direct path.

The inner product is injectable (``dot=``): under ``shard_map`` the
distributed path wraps the local reduction in a ``psum`` so the SAME
solver drives single-device and mesh solves
(:func:`repro.launch.dist_hck.dist_solve`).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

Array = jax.Array

#: denominator guard (matches the legacy dist_hck CG helper): a converged
#: direction yields α = rz/ε·0-ish instead of 0/0 NaN poisoning the whole
#: batch.  True CURVATURE breakdowns (pᵀAp ≈ 0 with rz large — an exactly
#: singular operator fed an inconsistent RHS) are handled separately by
#: the per-column freeze in :func:`pcg`'s step, because the ε clamp alone
#: turns them into a runaway α that overflows the iterate.
_EPS = 1e-30


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CGResult:
    """Outcome of one :func:`pcg` call.

    ``x`` keeps the RHS shape ((n,) or (n, k)); ``residuals[i]`` is the
    max-over-columns RELATIVE residual after i iterations (entries past
    ``iterations`` repeat the final value, so the trace is plot-ready
    without masking); ``iterations`` is the count actually run and
    ``converged`` whether every column met ``tol`` before ``maxiter``.
    """

    x: Array
    iterations: Array          # scalar int32
    residuals: Array           # (maxiter + 1,) relative residual trace
    converged: Array           # scalar bool

    def tree_flatten(self):
        """Pytree protocol: all fields are children."""
        return (self.x, self.iterations, self.residuals, self.converged), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Pytree protocol: rebuild from flattened children."""
        return cls(*children)


def column_dot(u: Array, v: Array) -> Array:
    """Column-wise inner products: (n, k), (n, k) -> (k,)."""
    return jnp.sum(u * v, axis=0)


def axis_dot(axis: str) -> Callable[[Array, Array], Array]:
    """Mesh-wide :func:`column_dot` for ``shard_map`` bodies.

    Returns a ``dot`` suitable for :func:`pcg`'s / the Lanczos
    recurrence's injectable inner product: the local column sums psum
    over ``axis``, so α/β (and therefore the iteration count) are
    IDENTICAL to a single-host solve on the concatenated vectors — the
    mesh-invariance gate in ``benchmarks/bench_dist.py`` pins this.
    Outside shard_map (plain jit on sharded arrays) no hook is needed:
    GSPMD already composes the partial sums.

    ``jax.shard_map``'s default ``check_vma=True`` accepts a body that
    calls :func:`pcg`: the psum'd scalars type as mesh-invariant, so the
    solver's ``lax.while_loop`` carries check as they are.
    """
    def dot(u: Array, v: Array) -> Array:
        return jax.lax.psum(jnp.sum(u * v, axis=0), axis)

    return dot


def run_traced_iteration(step, state0, r0, bb, *, tol: float, maxiter: int,
                         dot=column_dot) -> tuple:
    """Shared scaffolding for residual-traced iterative solvers.

    Runs ``state, r = step(state, r, it)`` under ``lax.while_loop`` until
    the max-over-columns relative residual ‖r‖/‖b‖ drops to ``tol`` or
    ``maxiter`` iterations, recording the trace exactly as
    :class:`CGResult` documents (entry 0 = initial residual, entries past
    the exit iteration frozen at the final value).  Both :func:`pcg` and
    the EigenPro Richardson loop run on this one implementation, so the
    trace/convergence contract cannot drift between solvers.

    Returns ``(state, iterations, trace, converged)``.
    """
    bnorm = jnp.sqrt(jnp.maximum(dot(bb, bb), _EPS))     # (k,)

    def rel_of(r):
        return jnp.max(jnp.sqrt(jnp.maximum(dot(r, r), 0.0)) / bnorm)

    trace = jnp.full((maxiter + 1,), rel_of(r0), dtype=bnorm.dtype)

    def cond(carry):
        _, _, it, trace = carry
        return jnp.logical_and(it < maxiter, trace[it] > tol)

    def body(carry):
        state, r, it, trace = carry
        state, r = step(state, r, it)
        it = it + 1
        trace = jax.lax.dynamic_update_index_in_dim(trace, rel_of(r), it, 0)
        return state, r, it, trace

    it0 = jnp.asarray(0, jnp.int32)
    state, r, it, trace = jax.lax.while_loop(
        cond, body, (state0, r0, it0, trace))

    # freeze the trace past the exit point so it plots without masking
    idx = jnp.arange(maxiter + 1)
    trace = jnp.where(idx <= it, trace, trace[it])
    return state, it, trace, trace[it] <= tol


def pcg(
    matvec: Callable[[Array], Array],
    b: Array,
    *,
    ridge: Array | float = 0.0,
    precond: Callable[[Array], Array] | None = None,
    tol: float = 1e-6,
    maxiter: int = 100,
    dot: Callable[[Array, Array], Array] | None = None,
    x0: Array | None = None,
    flexible: bool = True,
) -> CGResult:
    """Preconditioned CG on ``(A + ridge·I) x = b``, batched over columns.

    Parameters
    ----------
    matvec:   v -> A v for v of the same shape as ``b`` (must accept the
              batched (n, k) form; both repro operators and
              ``hmatrix.matvec`` do).
    b:        (n,) or (n, k) right-hand sides; the result matches.
    ridge:    λ added to the operator diagonal (the KRR/GP ridge).
    precond:  r -> M⁻¹ r, an SPD approximation of (A + ridge·I)⁻¹ — pass
              ``lambda r: hmatrix.apply_inverse(inv, r, cfg)`` for the
              HCK-preconditioned exact solve.  None = identity.
    tol:      relative-residual target ‖b − A x‖/‖b‖ per column;
              ``tol=0`` runs exactly ``maxiter`` iterations (the legacy
              fixed-iteration distributed semantics).
    maxiter:  iteration cap (static: sizes the residual trace).
    dot:      column-wise inner product (u, v) -> (k,); inject a
              psum-wrapped reduction for global products under shard_map.
    x0:       warm start (defaults to zeros).
    flexible: use the Polak–Ribière β (flexible PCG, default) instead of
              Fletcher–Reeves.  Identical in exact arithmetic, but the
              PR form stays convergent when the preconditioner is
              INEXACT — the float32 Algorithm-2 structured inverse loses
              digits through the level-telescoped SMW, and classic PCG
              was measured to stall at ~1e-2 relative residual with it
              while the flexible form converges to the f32 floor.

    Returns a :class:`CGResult`; runs eagerly traceable (pure lax), so
    callers may wrap it in jit with ``matvec``/``precond`` closed over.
    """
    dot = dot if dot is not None else column_dot
    squeeze = b.ndim == 1
    bb = b[:, None] if squeeze else b

    def _col(u):
        return u if u.ndim == 2 else u[:, None]

    def amv(v):
        # 1-D callers get 1-D vectors back (legacy dist_solve closures and
        # diagonal preconditioners broadcast wrongly against (n, 1))
        av = matvec(v[:, 0]) if squeeze else matvec(v)
        return _col(av) + ridge * v

    def psolve(r):
        if precond is None:
            return r
        return _col(precond(r[:, 0])) if squeeze else precond(r)

    x = jnp.zeros_like(bb) if x0 is None else (
        x0[:, None] if squeeze else x0)
    r0 = bb - amv(x)
    z = psolve(r0)

    def step(state, r, it):
        del it
        x, z, p, rz = state
        ap = amv(p)
        pap = dot(p, ap)                                 # (k,) curvature
        # breakdown freeze: on a singular (or indefinite) operator the
        # search direction collapses into the near-null space, where
        # α = rz/pᵀAp compounds geometrically and overflows the iterate.
        # A column whose Rayleigh quotient pᵀAp/pᵀp drops below a few ulps
        # is frozen for this step (α = β = 0): it keeps its current
        # iterate and restarts from steepest descent, while the healthy
        # columns — whose quotient is bounded below by λ_min + ridge —
        # never trip the test and see bit-identical arithmetic.
        eps = jnp.finfo(pap.dtype).eps
        broken = pap <= 8.0 * eps * jnp.maximum(dot(p, p), _EPS)
        alpha = jnp.where(broken, 0.0,
                          rz / jnp.maximum(pap, _EPS))   # (k,)
        x = x + alpha[None, :] * p
        r_new = r - alpha[None, :] * ap
        z_new = psolve(r_new)
        rz_new = dot(r_new, z_new)
        if flexible:                      # Polak–Ribière: robust to an
            num = dot(r_new - r, z_new)   # inexact (f32) preconditioner
        else:                             # Fletcher–Reeves (textbook PCG)
            num = rz_new
        beta = jnp.where(broken, 0.0, num / jnp.maximum(rz, _EPS))
        p = z_new + beta[None, :] * p
        return (x, z_new, p, rz_new), r_new

    state, it, trace, converged = run_traced_iteration(
        step, (x, z, z, dot(r0, z)), r0, bb,
        tol=tol, maxiter=maxiter, dot=dot)
    x = state[0]
    out = x[:, 0] if squeeze else x
    return CGResult(out, it, trace, converged)
