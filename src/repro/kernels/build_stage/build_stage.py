"""Pallas TPU kernels: fused HCK construction stages (Algorithm 2).

Two kernels cover the whole factor-instantiation hot path of the batched
build engine (``repro.core.hck.build_hck``):

  * ``gram_chol_kernel`` — one program per group of tree nodes: load each
    node's (m, d) point/landmark block, form the pairwise distances (MXU
    matmul identity for L2 kernels, VPU broadcast for L1), apply the
    base-kernel nonlinearity — the same epilogue body as ``kernel_tile`` —
    add the size-scaled jitter to the diagonal, and (optionally) factorize
    the blocks in VMEM with the blocked Cholesky (``_cholesky_in_vmem``).
    The (m, m) Gram tiles never round-trip to HBM between evaluation and
    factorization.

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

The factorization is blocked by row panels: one-hot masked steps on an
(8, m) panel, MXU products for the rest, and sublane-aligned panel slices
of VMEM scratch as the only dynamic slicing, so the same body runs under
both the Mosaic compiler and interpret mode.  ``hck_leaf_factor`` runs the
same Cholesky.  Accumulation dtype follows the input: float32 for <=32-bit
inputs (MXU path), float64 for float64 inputs (interpret-mode oracle
parity).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# Rows per panel of the blocked factorizations: one float32 sublane group,
# so a (b, m) row panel is a single row of vregs.
_PANEL = 8
# Leading rows of each panel whose Schur update runs as exact rank-1 VPU
# updates: they carry most of it, and the MXU product of the rest is then
# summed at the complement's own scale (float32 round-off stays at the
# one-hot loop's level).
_LEAD = 2
_HIGHEST = jax.lax.Precision.HIGHEST


def _panel_width(m: int) -> int | None:
    """Panel rows for an (m, m) tile, or None where the one-hot loop runs
    (a tile of at most one panel, or one the panels do not divide)."""
    return _PANEL if m > _PANEL and m % _PANEL == 0 else None


def _bdot(a: Array, b: Array, acc) -> Array:
    """Batched ``a @ b`` over (T, i, k) x (T, k, j) at full float32
    precision on the MXU."""
    return jax.lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))),
                               precision=_HIGHEST, preferred_element_type=acc)


def _cholesky_onehot(a: Array, m: int, acc) -> Array:
    """Unblocked right-looking Cholesky of SPD (..., m, m) tiles: m one-hot
    steps, each a masked lane reduction and a full-tile rank-1 update (no
    dynamic slicing and no vector-shaped dot, which Mosaic refuses)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
    eye = (rows == cols).astype(acc)

    def body(j, a):
        ej = (cols == j).astype(acc)                       # one-hot (1, m)
        acol = jnp.sum(a * ej, axis=-1, keepdims=True)     # column j (m, 1)
        # no pivot clamp: a singular/indefinite block must yield NaN, the
        # same loud failure mode as the xla backend's jnp.linalg.cholesky
        pivot = jnp.sum(jnp.where(rows == j, acol, 0.0), axis=-2,
                        keepdims=True)
        col = jnp.where(rows >= j, acol / jnp.sqrt(pivot), 0.0)
        tail = jnp.where(rows > j, col, 0.0)
        tail_row = jnp.sum(tail * eye, axis=-2, keepdims=True)  # (1, m)
        a = a - tail * tail_row                            # Schur update
        return a * (1.0 - ej) + col * ej

    a = jax.lax.fori_loop(0, m, body, a)
    return a * (rows >= cols).astype(acc)


def _chol_panel(p: Array, r0, m: int, acc) -> Array:
    """Rows ``r0 .. r0+b`` of the upper factor ``R = L^T`` from the (T, b,
    m) row panel ``p`` of the Schur complement: b one-hot steps on the
    panel alone, its long side on lanes."""
    b = p.shape[-2]
    rows = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
    diag = cols == rows + r0                               # (b, m)

    def step(j, p):
        c = r0 + j
        row = jnp.sum(jnp.where(rows == j, p, 0.0), axis=-2, keepdims=True)
        # no pivot clamp: an indefinite tile yields NaN (see the one-hot loop)
        pivot = jnp.sum(jnp.where(cols == c, row, 0.0), axis=-1,
                        keepdims=True)
        r = jnp.where(cols >= c, row / jnp.sqrt(pivot), 0.0)    # row j of R
        coef = jnp.sum(jnp.where(diag & (rows > j), r, 0.0), axis=-1,
                       keepdims=True)                      # R[j, r0 + i]
        return jnp.where(rows == j, r, p - coef * r)

    return jax.lax.fori_loop(0, b, step, p, unroll=True)


def _cholesky_in_vmem(a: Array, m: int, acc) -> Array:
    """Lower Cholesky factors of SPD (T, m, m) or (m, m) tiles, in VMEM.

    Blocked by row panels of the upper form ``R = L^T``, b = 8 rows (one
    float32 sublane group) at a time: panel ``k`` (rows ``r0 .. r0+b`` of
    the Schur complement, a (b, m) block with its long side on lanes) is
    factored by b one-hot steps on the panel alone (:func:`_chol_panel`),
    and the complement takes the Schur update ``A22 -= R12^T R12``: its
    first ``_LEAD`` rows as rank-1 VPU updates, the rest as one MXU product
    at ``Precision.HIGHEST``.  The update spans the whole tile — rows above
    the panel gain exact zeros, the panel's own rows are never read again —
    so the panel loop is a ``fori_loop`` whose only dynamic slices are
    sublane-aligned row panels of VMEM scratch.  The factor is transposed
    once at the end.  Leading tiles are independent: a program that holds
    several interleaves their serial chains.  A tile of at most one panel,
    or one the panels do not divide (``leaf_update``'s k x k block), runs
    the unblocked one-hot loop (:func:`_cholesky_onehot`).  No pivot clamp:
    an indefinite tile yields NaN.
    """
    b = _panel_width(m)
    if b is None:
        return _cholesky_onehot(a, m, acc)
    if a.ndim == 2:
        return _cholesky_in_vmem(a[None], m, acc)[0]
    lead = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1) < _LEAD

    def factor(w_ref, r_ref):
        w_ref[...] = a                     # the Schur complement, updated

        def panel(k, carry):
            r0 = pl.multiple_of(k * b, b)
            rp = _chol_panel(w_ref[:, pl.ds(r0, b), :], r0, m, acc)
            r_ref[:, pl.ds(r0, b), :] = rp
            rpt = jnp.swapaxes(rp, 1, 2)                   # (T, m, b)
            w = w_ref[...]
            for j in range(_LEAD):
                w = w - rpt[:, :, j:j + 1] * rp[:, j:j + 1, :]
            w_ref[...] = w - _bdot(jnp.where(lead, 0.0, rpt), rp, acc)
            return carry

        jax.lax.fori_loop(0, m // b, panel, 0)
        return jnp.swapaxes(r_ref[...], 1, 2)

    return pl.run_scoped(factor, pltpu.VMEM(a.shape, acc),
                         pltpu.VMEM(a.shape, acc))


def _gram_chol_body(pts_ref, gram_ref, chol_ref, *, l1: bool, epilogue,
                    jitter: float, acc):
    tiles, m, _ = pts_ref.shape                            # (T, m, d)
    eye = (jax.lax.iota(jnp.int32, m)[:, None]
           == jax.lax.iota(jnp.int32, m)[None, :]).astype(acc)
    for t in range(tiles):
        pts = pts_ref[t]
        gram_ref[t] = (_pairwise(pts, pts, l1=l1, epilogue=epilogue, acc=acc)
                       + (jitter * m) * eye)
    if chol_ref is not None:
        chol_ref[...] = _cholesky_in_vmem(gram_ref[...], m, acc)


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


def _tiles(stage: str, m: int, d: int, acc) -> int:
    """Tiles per program of a factoring launch: as many as
    :func:`repro.kernels.registry.tile_config` fits in the VMEM budget."""
    from repro.kernels.registry import tile_config

    return tile_config(stage, n0=m, r=m, k=1, d=d,
                       itemsize=jnp.dtype(acc).itemsize).tiles


def _pad_tiles(x: Array, tiles: int) -> Array:
    """Pad a batch to a multiple of ``tiles`` with copies of its first tile
    (SPD where the batch is; the padded outputs are dropped)."""
    pad = -x.shape[0] % tiles
    if not pad:
        return x
    return jnp.concatenate([x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])])


def _gram_launch(body, x: Array, m: int, tiles: int, want_chol: bool,
                 interpret: bool) -> tuple[Array, Array | None]:
    """One program per ``tiles`` tiles of (B, m, *) inputs -> gram (B, m, m)
    [+ lower Cholesky]."""
    bsz = x.shape[0]
    x = _pad_tiles(x, tiles)
    out_shape = [jax.ShapeDtypeStruct((x.shape[0], m, m), x.dtype)]
    spec = pl.BlockSpec((tiles, m, m), lambda i: (i, 0, 0))
    out_specs = [spec]
    if want_chol:
        out_shape.append(out_shape[0])
        out_specs.append(spec)
    else:
        body = functools.partial(
            lambda inner, x_ref, g_ref: inner(x_ref, g_ref, None), body)
    out = pl.pallas_call(
        body,
        grid=(x.shape[0] // tiles,),
        in_specs=[pl.BlockSpec((tiles,) + x.shape[1:], lambda i: (i, 0, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(x)
    return (out[0][:bsz], out[1][:bsz] if want_chol else None)


@functools.partial(jax.jit, static_argnames=("name", "sigma", "jitter",
                                             "want_chol", "interpret"))
def gram_chol_kernel(
    points: Array, *, name: str = "gaussian", sigma: float = 1.0,
    jitter: float = 0.0, want_chol: bool = True, interpret: bool = True,
) -> tuple[Array, Array | None]:
    """(B, m, d) -> gram (B, m, m) [+ lower Cholesky or None].

    A factoring launch holds as many nodes per program as
    :func:`repro.kernels.registry.tile_config` fits in the VMEM budget, so
    their serial panel chains interleave; a Gram-only launch holds one.
    """
    if name not in SUPPORTED:
        raise ValueError(f"{name!r} not in {SUPPORTED}")
    _, m, d = points.shape
    acc = _acc_dtype(points)
    tiles = _tiles("build_gram", m, d, acc) if want_chol else 1
    body = functools.partial(
        _gram_chol_body, l1=(name == "laplace"),
        epilogue=kernel_epilogue(name, sigma), jitter=jitter, acc=acc)
    return _gram_launch(body, points.astype(acc), m, tiles, want_chol,
                        interpret)


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
    dist = dist_ref[...]                                   # (T, m, m) cached
    m = dist.shape[-1]
    eye = (jax.lax.iota(jnp.int32, m)[:, None]
           == jax.lax.iota(jnp.int32, m)[None, :]).astype(acc)
    gram = epilogue(dist).astype(acc) + (jitter * m) * eye
    gram_ref[...] = gram
    if chol_ref is not None:
        chol_ref[...] = _cholesky_in_vmem(gram, m, acc)


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
    on the precomputed distance tile, size-scaled jitter, in-VMEM blocked
    Cholesky.  No distance pass — the work left is the O(m^3/3)
    factorization.  Tiles per program as in :func:`gram_chol_kernel`.
    """
    if name not in SUPPORTED:
        raise ValueError(f"{name!r} not in {SUPPORTED}")
    _, m, _ = dist.shape
    acc = _acc_dtype(dist)
    tiles = _tiles("build_gram_dist", m, 0, acc) if want_chol else 1
    body = functools.partial(
        _gram_chol_dist_body, epilogue=kernel_epilogue(name, sigma),
        jitter=jitter, acc=acc)
    return _gram_launch(body, dist.astype(acc), m, tiles, want_chol,
                        interpret)


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
