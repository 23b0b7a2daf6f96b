"""Pallas TPU kernel: fused bordered leaf-factor extension (rank-k update).

One program per leaf: the existing ``(n0, n0)`` Cholesky factor and its
inverse stay resident in VMEM while the appended rows' cross block is
triangular-solved (as a GEMM against ``linv``), the ``(k, k)`` Schur
complement is formed, factored and inverted by the same in-VMEM helpers
as ``build_gram``/``leaf_factor`` (a k x k block smaller than one of their
panels takes their unblocked one-hot loops), and both extended
``(n0+k, n0+k)`` factors are assembled
and written once — the update never re-reads or re-factors the old
block, so its cost is O(k n0^2 + k^2 n0 + k^3) per leaf instead of the
O(n0^3) full re-factorization.

Accumulation dtype follows the input: float32 for <=32-bit inputs (MXU
path), float64 for float64 inputs (interpret-mode oracle parity).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.hck_leaf.hck_leaf import _acc_dtype, _dot, _tri_inv_in_vmem

Array = jax.Array


def _dot_nt(a: Array, b: Array, *, acc=jnp.float32):
    """a @ b^T with an explicit accumulation dtype."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=acc)


def _update_body(lo_ref, linv_ref, b_ref, c_ref, lo_out_ref, linv_out_ref,
                 *, acc):
    from repro.kernels.build_stage.build_stage import _cholesky_in_vmem

    lo = lo_ref[0]                                 # (n0, n0) lower factor
    linv = linv_ref[0]                             # (n0, n0) = lo^{-1}
    b = b_ref[0]                                   # (k, n0) cross block
    c = c_ref[0]                                   # (k, k) appended block
    n0 = lo.shape[0]
    k = c.shape[0]
    l21 = _dot_nt(b, linv, acc=acc)                # B linv^T  (k, n0)
    s = c - _dot_nt(l21, l21, acc=acc)             # appended Schur (k, k)
    l22 = _cholesky_in_vmem(s, k, acc)
    linv22 = _tri_inv_in_vmem(l22, k, acc)
    linv21 = -_dot(linv22, _dot(l21, linv, acc=acc), acc=acc)
    z_tr = jnp.zeros((n0, k), acc)
    lo_out_ref[0] = jnp.concatenate([
        jnp.concatenate([lo, z_tr], axis=1),
        jnp.concatenate([l21, l22], axis=1),
    ], axis=0)
    linv_out_ref[0] = jnp.concatenate([
        jnp.concatenate([linv, z_tr], axis=1),
        jnp.concatenate([linv21, linv22], axis=1),
    ], axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def hck_leaf_update(
    lo: Array, linv: Array, b: Array, c: Array, *, interpret: bool = True,
) -> tuple[Array, Array]:
    """Fused bordered extension of batched leaf Cholesky factors.

    (P, n0, n0) ``lo``/``linv``, (P, k, n0) cross block, (P, k, k)
    appended block -> ``(lo_ext, linv_ext)``, both (P, n0+k, n0+k), with
    the leading (n0, n0) quadrants equal to the inputs (exact truncation
    = exact downdate).  One program per leaf; the old factor, the new
    blocks and both extended outputs share one VMEM residency.
    """
    p, n0, _ = lo.shape
    k = b.shape[1]
    acc = _acc_dtype(lo, linv, b, c)
    return pl.pallas_call(
        functools.partial(_update_body, acc=acc),
        grid=(p,),
        in_specs=[
            pl.BlockSpec((1, n0, n0), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n0, n0), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, k, n0), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, k, k), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n0 + k, n0 + k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n0 + k, n0 + k), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, n0 + k, n0 + k), acc),
            jax.ShapeDtypeStruct((p, n0 + k, n0 + k), acc),
        ],
        interpret=interpret,
    )(lo, linv, b, c)
