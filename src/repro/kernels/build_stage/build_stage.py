"""Pallas TPU kernels: fused HCK construction stages (Algorithm 2).

Two kernels cover the whole factor-instantiation hot path of the batched
build engine (``repro.core.hck.build_hck``):

  * ``gram_chol_kernel`` — one program per tree node: load the node's
    (m, d) point/landmark block, form the pairwise distances (MXU matmul
    identity for L2 kernels, VPU broadcast for L1), apply the base-kernel
    nonlinearity — the same epilogue body as ``kernel_tile`` — add the
    size-scaled jitter to the diagonal, and (optionally) factorize the
    block in VMEM with a right-looking Cholesky.  The (m, m) Gram tile
    never round-trips to HBM between evaluation and factorization.

  * ``cross_solve_kernel`` — grid (node, row-tile): load a (bm, d) row
    block of the node's points, the node's parent landmarks (r, d) and the
    parent's precomputed inverse Cholesky factor ``Linv`` (r, r); form the
    cross-kernel tile and apply ``Sigma^{-1} = Linv^T Linv`` as two MXU
    GEMMs, writing only the (bm, r) projected basis ``U = K(P, Z)
    Sigma^{-1}``.  ``Linv`` is computed once per parent from the
    ``build_gram`` Cholesky (``repro.core.hck.sigma_linv``) — the two
    GEMMs beat a per-row-block triangular solve by ~7x on CPU/XLA, are
    the native MXU form on TPU, and keep cho_solve-grade accuracy (the
    factored form does not square the condition number).

Both kernels also come in *distance-cached* form for the hyperparameter
sweep engine (``gram_chol_dist_kernel`` / ``cross_solve_dist_kernel``):
the pairwise metric distances are bandwidth-independent, so a σ-grid
computes them once and each per-σ program skips the distance pass —
loading the precomputed (m, m) / (bm, r) distance tile from HBM and
running only the elementwise kernel nonlinearity plus the factorize /
project epilogue.  That converts the per-grid-point cost from O(m d) MXU
distance work + O(m^3/3) factorization into the factorization alone.

The factorization loop is expressed with one-hot masked updates (no
dynamic slicing), so the same body runs under both the Mosaic compiler
and interpret mode.  Accumulation dtype follows the input: float32 for
<=32-bit inputs (MXU path), float64 for float64 inputs (interpret-mode
oracle parity).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.kernel_tile.kernel_tile import SUPPORTED, kernel_epilogue

Array = jax.Array


def _acc_dtype(*arrays: Array):
    if any(a.dtype == jnp.float64 for a in arrays):
        return jnp.float64
    return jnp.float32


def _pairwise(x: Array, y: Array, *, l1: bool, epilogue, acc) -> Array:
    """In-VMEM kernel values K(x, y): (n, d), (m, d) -> (n, m)."""
    if l1:
        dist = jnp.sum(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)
    else:
        xy = jax.lax.dot_general(
            x, y, (((1,), (1,)), ((), ())), preferred_element_type=acc)
        dist = jnp.maximum(
            jnp.sum(x * x, axis=-1)[:, None]
            + jnp.sum(y * y, axis=-1)[None, :] - 2.0 * xy, 0.0)
    return epilogue(dist).astype(acc)


def _cholesky_in_vmem(a: Array, m: int, acc) -> Array:
    """Right-looking Cholesky of an SPD (m, m) tile via one-hot updates.

    Column ``j`` of the factor is picked out with a one-hot masked lane
    reduction and the trailing Schur complement is updated with a masked
    outer product — no dynamic slicing and no vector-shaped dot (Mosaic
    refuses a contraction with a 1-D operand), so the loop lowers on TPU
    and in interpret mode alike.  Every operand stays 2-D: columns are
    (m, 1), rows (1, m).  O(m^3/3) flops over an m-step sequential loop
    (the tile stays in VMEM throughout).
    """
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
    eye = (rows == cols).astype(acc)

    def body(j, a):
        ej = (cols == j).astype(acc)                       # one-hot (1, m)
        acol = jnp.sum(a * ej, axis=1, keepdims=True)      # column j (m, 1)
        # no pivot clamp: a singular/indefinite block must yield NaN, the
        # same loud failure mode as the xla backend's jnp.linalg.cholesky
        pivot = jnp.sqrt(jnp.sum(jnp.where(rows == j, acol, 0.0),
                                 axis=0, keepdims=True))   # (1, 1)
        col = jnp.where(rows >= j, acol / pivot, 0.0)      # column j of L
        tail = jnp.where(rows > j, col, 0.0)
        tail_row = jnp.sum(tail * eye, axis=0, keepdims=True)   # (1, m)
        a = a - tail * tail_row                            # Schur update
        return a * (1.0 - ej) + col * ej

    a = jax.lax.fori_loop(0, m, body, a)
    return a * (rows >= cols).astype(acc)


def _gram_chol_body(pts_ref, gram_ref, chol_ref, *, l1: bool, epilogue,
                    jitter: float, acc):
    pts = pts_ref[0]                                       # (m, d)
    m = pts.shape[0]
    eye = (jax.lax.iota(jnp.int32, m)[:, None]
           == jax.lax.iota(jnp.int32, m)[None, :]).astype(acc)
    gram = _pairwise(pts, pts, l1=l1, epilogue=epilogue, acc=acc)
    gram = gram + (jitter * m) * eye
    gram_ref[0] = gram
    if chol_ref is not None:
        chol_ref[0] = _cholesky_in_vmem(gram, m, acc)


def _cross_solve_body(pts_ref, lm_ref, linv_ref, u_ref, *, l1: bool,
                      epilogue, acc):
    pts = pts_ref[0]                                       # (bm, d)
    lm = lm_ref[0]                                         # (r, d)
    linv = linv_ref[0]                                     # (r, r) lower
    kxu = _pairwise(pts, lm, l1=l1, epilogue=epilogue, acc=acc)
    y = jax.lax.dot_general(                               # K Linv^T
        kxu, linv, (((1,), (1,)), ((), ())), preferred_element_type=acc)
    u_ref[0] = jax.lax.dot_general(                        # ... Linv
        y, linv, (((1,), (0,)), ((), ())), preferred_element_type=acc)


@functools.partial(jax.jit, static_argnames=("name", "sigma", "jitter",
                                             "want_chol", "interpret"))
def gram_chol_kernel(
    points: Array, *, name: str = "gaussian", sigma: float = 1.0,
    jitter: float = 0.0, want_chol: bool = True, interpret: bool = True,
) -> tuple[Array, Array | None]:
    """(B, m, d) -> gram (B, m, m) [+ lower Cholesky or None]."""
    if name not in SUPPORTED:
        raise ValueError(f"{name!r} not in {SUPPORTED}")
    bsz, m, d = points.shape
    acc = _acc_dtype(points)
    body = functools.partial(
        _gram_chol_body, l1=(name == "laplace"),
        epilogue=kernel_epilogue(name, sigma), jitter=jitter, acc=acc)
    out_shape = [jax.ShapeDtypeStruct((bsz, m, m), acc)]
    out_specs = [pl.BlockSpec((1, m, m), lambda i: (i, 0, 0))]
    if want_chol:
        out_shape.append(jax.ShapeDtypeStruct((bsz, m, m), acc))
        out_specs.append(pl.BlockSpec((1, m, m), lambda i: (i, 0, 0)))
    else:
        body = functools.partial(
            lambda inner, p_ref, g_ref: inner(p_ref, g_ref, None), body)
    out = pl.pallas_call(
        body,
        grid=(bsz,),
        in_specs=[pl.BlockSpec((1, m, d), lambda i: (i, 0, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(points.astype(acc))
    return (out[0], out[1]) if want_chol else (out[0], None)


@functools.partial(jax.jit, static_argnames=("name", "sigma", "bm",
                                             "interpret"))
def cross_solve_kernel(
    points: Array, landmarks: Array, linv: Array, *,
    name: str = "gaussian", sigma: float = 1.0, bm: int = 128,
    interpret: bool = True,
) -> Array:
    """(B, m, d), (B, r, d), (B, r, r) -> U (B, m, r); m must divide ``bm``
    (use ops.build_cross for the tile-snapped general entry point)."""
    if name not in SUPPORTED:
        raise ValueError(f"{name!r} not in {SUPPORTED}")
    bsz, m, d = points.shape
    r = landmarks.shape[1]
    assert m % bm == 0, (m, bm)
    acc = _acc_dtype(points, landmarks, linv)
    body = functools.partial(
        _cross_solve_body, l1=(name == "laplace"),
        epilogue=kernel_epilogue(name, sigma), acc=acc)
    return pl.pallas_call(
        body,
        grid=(bsz, m // bm),
        in_specs=[
            pl.BlockSpec((1, bm, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, r, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, r, r), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, r), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, m, r), acc),
        interpret=interpret,
    )(points.astype(acc), landmarks.astype(acc), linv.astype(acc))


# ---------------------------------------------------------------------------
# Distance-cached variants (hyperparameter sweep engine)
# ---------------------------------------------------------------------------

def _gram_chol_dist_body(dist_ref, gram_ref, chol_ref, *, epilogue,
                         jitter: float, acc):
    dist = dist_ref[0]                                     # (m, m) cached
    m = dist.shape[0]
    eye = (jax.lax.iota(jnp.int32, m)[:, None]
           == jax.lax.iota(jnp.int32, m)[None, :]).astype(acc)
    gram = epilogue(dist).astype(acc) + (jitter * m) * eye
    gram_ref[0] = gram
    if chol_ref is not None:
        chol_ref[0] = _cholesky_in_vmem(gram, m, acc)


def _cross_solve_dist_body(dist_ref, linv_ref, u_ref, *, epilogue, acc):
    dist = dist_ref[0]                                     # (bm, r) cached
    linv = linv_ref[0]                                     # (r, r) lower
    kxu = epilogue(dist).astype(acc)
    y = jax.lax.dot_general(                               # K Linv^T
        kxu, linv, (((1,), (1,)), ((), ())), preferred_element_type=acc)
    u_ref[0] = jax.lax.dot_general(                        # ... Linv
        y, linv, (((1,), (0,)), ((), ())), preferred_element_type=acc)


@functools.partial(jax.jit, static_argnames=("name", "sigma", "jitter",
                                             "want_chol", "interpret"))
def gram_chol_dist_kernel(
    dist: Array, *, name: str = "gaussian", sigma: float = 1.0,
    jitter: float = 0.0, want_chol: bool = True, interpret: bool = True,
) -> tuple[Array, Array | None]:
    """(B, m, m) cached metric distances -> gram (B, m, m) [+ Cholesky].

    The per-σ program of the sweep engine: elementwise kernel nonlinearity
    on the precomputed distance tile, size-scaled jitter, in-VMEM
    right-looking Cholesky.  No distance pass — the MXU work left is the
    O(m^3/3) factorization.
    """
    if name not in SUPPORTED:
        raise ValueError(f"{name!r} not in {SUPPORTED}")
    bsz, m, _ = dist.shape
    acc = _acc_dtype(dist)
    body = functools.partial(
        _gram_chol_dist_body, epilogue=kernel_epilogue(name, sigma),
        jitter=jitter, acc=acc)
    out_shape = [jax.ShapeDtypeStruct((bsz, m, m), acc)]
    out_specs = [pl.BlockSpec((1, m, m), lambda i: (i, 0, 0))]
    if want_chol:
        out_shape.append(jax.ShapeDtypeStruct((bsz, m, m), acc))
        out_specs.append(pl.BlockSpec((1, m, m), lambda i: (i, 0, 0)))
    else:
        body = functools.partial(
            lambda inner, d_ref, g_ref: inner(d_ref, g_ref, None), body)
    out = pl.pallas_call(
        body,
        grid=(bsz,),
        in_specs=[pl.BlockSpec((1, m, m), lambda i: (i, 0, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(dist.astype(acc))
    return (out[0], out[1]) if want_chol else (out[0], None)


@functools.partial(jax.jit, static_argnames=("name", "sigma", "bm",
                                             "interpret"))
def cross_solve_dist_kernel(
    dist: Array, linv: Array, *, name: str = "gaussian", sigma: float = 1.0,
    bm: int = 128, interpret: bool = True,
) -> Array:
    """(B, m, r) cached distances, (B, r, r) -> U (B, m, r); ``bm`` must
    divide m (use ops.build_cross_dist for the tile-snapped entry point)."""
    if name not in SUPPORTED:
        raise ValueError(f"{name!r} not in {SUPPORTED}")
    bsz, m, r = dist.shape
    assert m % bm == 0, (m, bm)
    acc = _acc_dtype(dist, linv)
    body = functools.partial(
        _cross_solve_dist_body, epilogue=kernel_epilogue(name, sigma),
        acc=acc)
    return pl.pallas_call(
        body,
        grid=(bsz, m // bm),
        in_specs=[
            pl.BlockSpec((1, bm, r), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, r, r), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, r), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, m, r), acc),
        interpret=interpret,
    )(dist.astype(acc), linv.astype(acc))
