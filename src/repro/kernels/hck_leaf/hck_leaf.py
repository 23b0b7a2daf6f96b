"""Pallas TPU kernels: fused HCK leaf stages of Algorithms 1 and 2.

The leaf stages of the hierarchical matvec/solve read the big per-leaf
operands (A_diag or Linv, shape (P, n0, n0); U, shape (P, n0, r)) once and
produce both the local block product AND the upward Nyström coefficients:

  matvec:  y_i = A_ii b_i                 c_i = U_i^T b_i
  solve:   x_i = Linv_i^T Linv_i b_i
               + U_i Sig_i U_i^T b_i      c_i = U_i^T b_i

Fusing halves the HBM traffic on ``b`` and keeps the leaf working set
resident in VMEM — the leaf stage is ~2/3 of the 18nr matvec flops (paper
§4.5), and for Algorithm 2's apply it folds the block-Cholesky triangular
pair plus the self low-rank correction into one VMEM-resident pass.

Grid: one program per leaf (``hck_leaf_factor``: several leaves per
program, so their serial factorization chains interleave); for the matvec
the n0 dimension is additionally
row-tiled by the registry's per-shape
:func:`repro.kernels.registry.tile_config` when a leaf does not fit the
VMEM budget (default n0<=512 fits whole).  ``hck_leaf_solve`` chains two
n0 x n0 products (Linv then Linv^T), so it processes whole leaves — its
working set is ~2x the matvec tile; keep leaf sizes <= ~512 on real
hardware (row-tiling the triangular pair is future work).

Accumulation dtype follows the input: float32 for <=32-bit inputs (MXU
path), float64 for float64 inputs (interpret-mode oracle parity — real TPUs
have no f64 MXU, but CI runs these bodies interpreted on CPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.build_stage.build_stage import (_bdot, _cholesky_in_vmem,
                                                   _pad_tiles, _panel_width,
                                                   _tiles)

Array = jax.Array


def _acc_dtype(*arrays: Array):
    if any(a.dtype == jnp.float64 for a in arrays):
        return jnp.float64
    return jnp.float32


def _dot(a: Array, b: Array, *, trans_a: bool = False, acc=jnp.float32):
    dims = (((0,), (0,)), ((), ())) if trans_a else (((1,), (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, preferred_element_type=acc)


# ---------------------------------------------------------------------------
# Fused leaf matvec (Algorithm 1)
# ---------------------------------------------------------------------------

def _matvec_body(a_ref, u_ref, b_ref, y_ref, c_ref, *, bn: int, acc):
    j = pl.program_id(1)
    a = a_ref[0]                                   # (bn, n0) rows of A_ii
    u = u_ref[0]                                   # (bn, r)  rows of U_i
    b = b_ref[0]                                   # (n0, k)  whole leaf rhs
    y_ref[0] = _dot(a, b, acc=acc)                 # (bn, k)
    b_rows = b_ref[0, pl.ds(j * bn, bn), :]        # (bn, k) matching rows

    @pl.when(j == 0)
    def _init():
        c_ref[0] = jnp.zeros_like(c_ref[0])

    c_ref[0] += _dot(u, b_rows, trans_a=True, acc=acc)


@functools.partial(jax.jit, static_argnames=("interpret", "block_n0"))
def hck_leaf_matvec(
    adiag: Array, u: Array, b: Array, *,
    interpret: bool = True, block_n0: int | None = None,
) -> tuple[Array, Array]:
    """(P, n0, n0), (P, n0, r), (P, n0, k) -> y (P, n0, k), c (P, r, k)."""
    p, n0, _ = adiag.shape
    r = u.shape[-1]
    k = b.shape[-1]
    acc = _acc_dtype(adiag, u, b)
    if block_n0 is None or block_n0 >= n0 or n0 % block_n0 != 0:
        bn = n0
    else:
        bn = block_n0
    nb = n0 // bn
    y, c = pl.pallas_call(
        functools.partial(_matvec_body, bn=bn, acc=acc),
        grid=(p, nb),
        in_specs=[
            pl.BlockSpec((1, bn, n0), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bn, r), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, n0, k), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn, k), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, r, k), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, n0, k), acc),
            jax.ShapeDtypeStruct((p, r, k), acc),
        ],
        interpret=interpret,
    )(adiag, u, b)
    return y, c


# ---------------------------------------------------------------------------
# Fused leaf solve (Algorithm 2 apply)
# ---------------------------------------------------------------------------

def _solve_body(linv_ref, u_ref, sig_ref, b_ref, x_ref, c_ref, *, acc):
    linv = linv_ref[0]                             # (n0, n0) inv Cholesky
    u = u_ref[0]                                   # (n0, r)
    sig = sig_ref[0]                               # (r, r) self middle factor
    b = b_ref[0]                                   # (n0, k)
    t = _dot(linv, b, acc=acc)                     # Linv b
    x = _dot(linv, t, trans_a=True, acc=acc)       # Linv^T Linv b = D^{-1} b
    c = _dot(u, b, trans_a=True, acc=acc)          # U^T b (upward coeffs)
    x += _dot(u, _dot(sig, c, acc=acc), acc=acc)   # self low-rank correction
    x_ref[0] = x
    c_ref[0] = c


@functools.partial(jax.jit, static_argnames=("interpret",))
def hck_leaf_solve(
    linv: Array, u: Array, sig: Array, b: Array, *, interpret: bool = True,
) -> tuple[Array, Array]:
    """Fused block-Cholesky apply + upward projection.

    (P, n0, n0), (P, n0, r), (P, r, r), (P, n0, k)
        -> x (P, n0, k) = Linv^T Linv b + U Sig U^T b,  c (P, r, k) = U^T b.
    """
    p, n0, _ = linv.shape
    r = u.shape[-1]
    k = b.shape[-1]
    acc = _acc_dtype(linv, u, sig, b)
    return pl.pallas_call(
        functools.partial(_solve_body, acc=acc),
        grid=(p,),
        in_specs=[
            pl.BlockSpec((1, n0, n0), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n0, r), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, r, r), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n0, k), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n0, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, r, k), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, n0, k), acc),
            jax.ShapeDtypeStruct((p, r, k), acc),
        ],
        interpret=interpret,
    )(linv, u, sig, b)


# ---------------------------------------------------------------------------
# Leaf Schur-complement factorization (Algorithm 2 inversion)
# ---------------------------------------------------------------------------

def _tri_inv_onehot(lo: Array, m: int, acc) -> Array:
    """Inverse of lower-triangular (..., m, m) tiles by m one-hot steps of
    forward substitution, each a masked rank-1 update of the whole tile."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
    eye = (rows == cols).astype(acc)

    def body(i, x):
        ei_col = (rows == i).astype(acc)                   # one-hot (m, 1)
        ei_row = (cols == i).astype(acc)                   # one-hot (1, m)
        lrow = jnp.sum(lo * ei_col, axis=-2, keepdims=True)    # row i (1, m)
        lcol = jnp.sum(lrow * eye, axis=-1, keepdims=True)     # as (m, 1)
        s = jnp.sum(lcol * x, axis=-2, keepdims=True)      # uses rows < i
        pivot = jnp.sum(lrow * ei_row, axis=-1, keepdims=True)  # lo[i, i]
        newrow = (ei_row - s) / pivot
        return x + ei_col * newrow

    return jax.lax.fori_loop(0, m, body, jnp.zeros(lo.shape, acc))


def _tri_inv_panel(rp: Array, z: Array, r0, m: int) -> Array:
    """Solve ``L_kk X_k = z`` for the (T, b, m) row panel ``X_k`` of the
    inverse, where ``rp`` holds rows ``r0 .. r0+b`` of ``R = L^T`` (so
    ``L_kk[i', i] = rp[i, r0 + i']``): b one-hot substitution steps on the
    panel alone."""
    b = rp.shape[-2]
    rows = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
    diag = cols == rows + r0                               # (b, m)

    def step(i, z):
        rrow = jnp.sum(jnp.where(rows == i, rp, 0.0), axis=-2, keepdims=True)
        lcol = jnp.sum(jnp.where(diag, rrow, 0.0), axis=-1,
                       keepdims=True)                      # L[r0 + ., r0 + i]
        pivot = jnp.sum(jnp.where(rows == i, lcol, 0.0), axis=-2,
                        keepdims=True)
        x = jnp.sum(jnp.where(rows == i, z, 0.0), axis=-2,
                    keepdims=True) / pivot                 # row i of X_k
        return jnp.where(rows == i, x,
                         z - jnp.where(rows > i, lcol, 0.0) * x)

    return jax.lax.fori_loop(0, b, step, z, unroll=True)


def _tri_inv_in_vmem(lo: Array, m: int, acc) -> Array:
    """Inverse of lower-triangular (T, m, m) or (m, m) tiles, in VMEM.

    Blocked by row panels, right-looking like the Cholesky: the right sides
    start as ``I``; panel ``k`` of ``X = lo^{-1}`` solves ``L_kk X_k =
    B_k`` by b one-hot substitution steps on the (b, m) panel alone
    (:func:`_tri_inv_panel`), and the rows below take ``B -= L_:k X_k`` as
    one MXU product at ``Precision.HIGHEST`` — the blocked recursion
    ``ref.tril_inverse`` runs in XLA, ``X21 = -L22^{-1} L21 L11^{-1}``,
    taken one block column at a time.  ``L``'s column panels are read as
    row panels of ``lo^T``, transposed once at the start.  Panel width, the
    ``fori_loop`` over panels, the whole-tile update and the small-tile
    fallback to the unblocked loop (:func:`_tri_inv_onehot`) follow the
    Cholesky (``build_stage._cholesky_in_vmem``).
    """
    b = _panel_width(m)
    if b is None:
        return _tri_inv_onehot(lo, m, acc)
    if lo.ndim == 2:
        return _tri_inv_in_vmem(lo[None], m, acc)[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)).astype(acc)

    def invert(r_ref, x_ref):
        r_ref[...] = jnp.swapaxes(lo, 1, 2)
        x_ref[...] = jnp.broadcast_to(eye, lo.shape)   # right sides, in place

        def panel(k, carry):
            r0 = pl.multiple_of(k * b, b)
            rp = r_ref[:, pl.ds(r0, b), :]
            xk = _tri_inv_panel(rp, x_ref[:, pl.ds(r0, b), :], r0, m)
            x_ref[...] -= _bdot(jnp.swapaxes(rp, 1, 2), xk, acc)
            x_ref[:, pl.ds(r0, b), :] = xk
            return carry

        jax.lax.fori_loop(0, m // b, panel, 0)
        return x_ref[...]

    return pl.run_scoped(invert, pltpu.VMEM(lo.shape, acc),
                         pltpu.VMEM(lo.shape, acc))


def _factor_body(dleaf_ref, lo_ref, linv_ref, *, acc):
    d = dleaf_ref[...]                                     # (T, n0, n0) SPD
    m = d.shape[-1]
    lo = _cholesky_in_vmem(d, m, acc)
    lo_ref[...] = lo
    linv_ref[...] = _tri_inv_in_vmem(lo, m, acc)


@functools.partial(jax.jit, static_argnames=("interpret",))
def hck_leaf_factor(
    dleaf: Array, *, interpret: bool = True,
) -> tuple[Array, Array]:
    """Fused leaf factorization: Cholesky + triangular inverse in VMEM.

    (P, n0, n0) SPD leaf Schur complements -> (lo, linv), both (P, n0, n0)
    lower triangular with ``linv = lo^{-1}`` (so ``D^{-1} = linv^T linv``).
    Each program factors as many leaves as
    :func:`repro.kernels.registry.tile_config` fits in the VMEM budget, so
    their serial panel chains interleave; a batch that is not a multiple is
    padded with copies of its first tile.  Both halves are blocked by row
    panels whose off-diagonal work runs on the MXU (``_cholesky_in_vmem``,
    ``_tri_inv_in_vmem``); tiles of at most one panel take the unblocked
    one-hot loops.  The tile never round-trips to HBM between factorization
    and inversion.  Grid-batched over all leaves — ``invert_multi`` stacks
    a whole (ridge-grid x leaves) batch into one launch.
    """
    p, n0, _ = dleaf.shape
    acc = _acc_dtype(dleaf)
    tiles = _tiles("leaf_factor", n0, 0, acc)
    a = _pad_tiles(dleaf, tiles)
    spec = pl.BlockSpec((tiles, n0, n0), lambda i: (i, 0, 0))
    lo, linv = pl.pallas_call(
        functools.partial(_factor_body, acc=acc),
        grid=(a.shape[0] // tiles,),
        in_specs=[spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(a.shape, acc)] * 2,
        interpret=interpret,
    )(a)
    return lo[:p], linv[:p]


# ---------------------------------------------------------------------------
# Leaf projection (OOS / distributed upward pass)
# ---------------------------------------------------------------------------

def _project_body(u_ref, b_ref, c_ref, *, acc):
    c_ref[0] = _dot(u_ref[0], b_ref[0], trans_a=True, acc=acc)


@functools.partial(jax.jit, static_argnames=("interpret",))
def hck_leaf_project(
    u: Array, b: Array, *, interpret: bool = True,
) -> Array:
    """(P, n0, r), (P, n0, k) -> c (P, r, k) = U^T b."""
    p, n0, r = u.shape
    k = b.shape[-1]
    acc = _acc_dtype(u, b)
    return pl.pallas_call(
        functools.partial(_project_body, acc=acc),
        grid=(p,),
        in_specs=[
            pl.BlockSpec((1, n0, r), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n0, k), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, r, k), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((p, r, k), acc),
        interpret=interpret,
    )(u, b)
