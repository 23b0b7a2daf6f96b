"""Pallas TPU kernel: fused per-query cross-kernel tile + weight contraction.

The batched Algorithm-3 prediction path (repro.core.oos.apply_plan) needs,
for every query in a leaf-sorted batch, the contraction

    z_i = W_i^T k(P_i, x_i)

where ``P_i`` is the query's own (m, d) point block (its leaf's training
points for the ``oos_local`` stage, its leaf parent's landmarks for the
``oos_walk`` stage) and ``W_i`` its (m, k) weight block.  Materializing the
(q, m) kernel values in HBM between the two steps doubles the write
traffic of the stage; this kernel keeps them in VMEM and writes only the
(q, k) output.

Grid: one program per block of ``bq`` queries; each program loads the
block's points/weights/queries, forms the pairwise distances (MXU matmul
identity for L2 kernels, VPU broadcast for L1), applies the kernel
nonlinearity — the same epilogue body as ``kernel_tile`` — and contracts
against the weights on the MXU.

Accumulation dtype follows the input: float32 for <=32-bit inputs (MXU
path), float64 for float64 inputs (interpret-mode oracle parity).
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


def _bdot(a: Array, b: Array, acc) -> Array:
    """Per-query a @ b^T: (bq, s, c), (bq, t, c) -> (bq, s, t).

    Both operands keep a free dim, so Mosaic lowers it as a batched MXU
    matmul (it refuses a contraction whose operand has no free dim).  Full
    f32 contract precision: the distance identity below cancels large
    terms and a single bf16 pass would lose the kernel values.
    """
    return jax.lax.dot_general(
        a, b, (((2,), (2,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=acc)


def _contract_body(pts_ref, wt_ref, q_ref, o_ref, *, l1: bool, epilogue, acc):
    pts = pts_ref[...]                                 # (bq, m, d)
    wt = wt_ref[...]                                   # (bq, k, m)
    x = q_ref[...]                                     # (bq, 1, d)
    ones = jnp.ones(x.shape, acc)
    if l1:
        # the all-ones contraction sums over d AND lays the result out
        # along lanes, (bq, 1, m), ready for the weight contraction
        dist = _bdot(ones, jnp.abs(pts - x), acc)
    else:
        # ||p - x||^2 = ||p||^2 + ||x||^2 - 2 p.x ; p.x is a batched MXU
        # contraction over the feature dim
        dist = jnp.maximum(
            _bdot(ones, pts * pts, acc)
            + jnp.sum(x * x, axis=-1, keepdims=True)
            - 2.0 * _bdot(x, pts, acc), 0.0)
    kv = epilogue(dist).astype(acc)                    # (bq, 1, m)
    o_ref[...] = _bdot(kv, wt, acc)                    # (bq, 1, k)


@functools.partial(jax.jit, static_argnames=("name", "sigma", "bq",
                                             "interpret"))
def oos_contract_kernel(
    points: Array, weights: Array, queries: Array, *,
    name: str = "gaussian", sigma: float = 1.0, bq: int = 128,
    interpret: bool = True,
) -> Array:
    """(q, m, d), (q, m, k), (q, d) -> z (q, k); q must divide ``bq``
    (use ops.oos_contract for the padded general entry point).

    The weights go in transposed, (q, k, m), and the queries as (q, 1, d),
    so that the kernel values, the weights and the queries all keep the
    contraction dim on lanes: no in-kernel relayout, and a one-column
    weight block costs a sublane tile, not a lane tile, of VMEM.
    """
    if name not in SUPPORTED:
        raise ValueError(f"{name!r} not in {SUPPORTED}")
    q, m, d = points.shape
    k = weights.shape[-1]
    assert q % bq == 0, (q, bq)
    acc = _acc_dtype(points, weights, queries)
    body = functools.partial(
        _contract_body, l1=(name == "laplace"),
        epilogue=kernel_epilogue(name, sigma), acc=acc)
    out = pl.pallas_call(
        body,
        grid=(q // bq,),
        in_specs=[
            pl.BlockSpec((bq, m, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((bq, k, m), lambda i: (i, 0, 0)),
            pl.BlockSpec((bq, 1, d), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bq, 1, k), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((q, 1, k), acc),
        interpret=interpret,
    )(points, jnp.swapaxes(weights, 1, 2), queries[:, None, :])
    return out[:, 0, :]
