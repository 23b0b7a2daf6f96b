"""Distributed HCK: the partition tree's top levels ARE the device mesh.

The paper's own scaling story (DESIGN.md §4): with n points split over P
devices, the top log2(P) tree levels map 1:1 onto mesh coordinates — device
p owns the contiguous leaf range whose path prefix equals p.

  * Algorithm 1's leaf stage and every level BELOW the device level run as
    purely local batched einsums (the existing repro.core.hmatrix code);
  * the device level connects through per-device transfer operators
    ``w_dev`` (the W factor of each device-root node);
  * the tiny top tree (log2 P levels of (r, r) factors) is REPLICATED and
    evaluated redundantly on every device from one all_gather of the
    per-device root coefficients — O(P r k) wire bytes per matvec, the
    parallel-FMM "replicate the tree top" trick.  The collective term is
    O(P r k / link_bw), negligible against the O((n/P) r) local work.

Distributed KRR = CG on the distributed matvec, preconditioned by the
purely-local structured inverse (Algorithm 2 below the device level).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import hmatrix
from repro.core.hck import (HCKFactors, _middle_factors, _transfer_ops,
                            build_hck, landmark_indices, leaf_stage_factors)
from repro.core.kernels_fn import KERNEL_METRIC, BaseKernel
from repro.kernels.registry import (DEFAULT_CONFIG, SolveConfig, get_impl,
                                    resolve_backend)

Array = jax.Array


def device_level(n_devices: int) -> int:
    lvl = 0
    while (1 << lvl) < n_devices:
        lvl += 1
    if (1 << lvl) != n_devices:
        raise ValueError(f"device count {n_devices} must be a power of two")
    return lvl


@functools.partial(jax.jit,
                   static_argnames=("kernel", "rank", "local_levels"))
def build_local_factors(x_local: Array, *, kernel: BaseKernel, rank: int,
                        local_levels: int, key: Array) -> HCKFactors:
    """Per-device factor build for the device's contiguous block (the
    below-device-level subtree); partition/landmark randomness per device."""
    return build_hck(x_local, levels=local_levels, rank=rank, key=key,
                     kernel=kernel)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TopFactors:
    """Replicated factors of the top log2(P) tree levels.

    landmarks[l]: (2**l, r, d) for top levels l = 0..T-1
    sigma[l]:     (2**l, r, r)
    w[l]:         (2**l, r, r) for l = 1..T-1   (internal top transfers)
    w_dev:        (P, r, r)  — device-root -> top-parent transfer
    """

    landmarks: tuple
    sigma: tuple
    w: tuple
    w_dev: Array

    def tree_flatten(self):
        return (self.landmarks, self.sigma, self.w, self.w_dev), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def build_top_factors(local_root_landmarks: Array, *, kernel: BaseKernel,
                      key: Array) -> TopFactors:
    """Build the replicated top tree from the per-device root landmark sets.

    local_root_landmarks: (P, r, d) — each device's subtree-root landmarks
    (``local_f.landmarks[0]``), gathered once at setup.  Top-node landmarks
    are uniform subsamples of the union over each node's span (§4.2);
    factors are O(P r^2) — replicated by construction.
    """
    p, r, d = local_root_landmarks.shape
    levels = device_level(p)
    # top landmarks: for level l node i, sample r points from its span
    landmarks = []
    for lvl in range(levels):
        nodes = 1 << lvl
        span = p // nodes
        pool = local_root_landmarks.reshape(nodes, span * r, d)
        key, sub = jax.random.split(key)
        idx = jax.vmap(lambda k: jax.random.permutation(k, span * r)[:r])(
            jax.random.split(sub, nodes))
        landmarks.append(jnp.take_along_axis(pool, idx[:, :, None], axis=1))
    gram = jax.vmap(kernel.gram)
    sigma = tuple(gram(lm) for lm in landmarks)
    cho = tuple(jnp.linalg.cholesky(s) for s in sigma)

    def transfer(lm_child, lm_parent, cho_parent):
        kcp = jax.vmap(kernel.cross)(lm_child, lm_parent)      # (B, r, r)
        sol = jax.vmap(lambda c, b: jax.scipy.linalg.cho_solve((c, True), b))(
            cho_parent, jnp.swapaxes(kcp, -1, -2))
        return jnp.swapaxes(sol, -1, -2)

    w = tuple(
        transfer(landmarks[lvl], jnp.repeat(landmarks[lvl - 1], 2, axis=0),
                 jnp.repeat(cho[lvl - 1], 2, axis=0))
        for lvl in range(1, levels))
    w_dev = transfer(local_root_landmarks,
                     jnp.repeat(landmarks[-1], 2, axis=0),
                     jnp.repeat(cho[-1], 2, axis=0))
    return TopFactors(tuple(landmarks), sigma, w, w_dev)


# ---------------------------------------------------------------------------
# Distributed Algorithm 1
# ---------------------------------------------------------------------------

def local_root_coeff(f: HCKFactors, b: Array,
                     config: SolveConfig | None = None) -> Array:
    """Upward pass to the local subtree root: returns (r, k) in the local
    root's landmark basis (the device-level W is applied by the caller).

    The leaf projection routes through the solve-engine registry so the
    distributed path shares backends with the single-device engine."""
    config = config if config is not None else DEFAULT_CONFIG
    if b.ndim == 1:
        b = b[:, None]
    n0 = f.leaf_size
    bb = b.reshape(f.num_leaves, n0, -1)
    backend = resolve_backend(config, "leaf_project", dtype=b.dtype,
                              n0=n0, r=f.rank)
    c = get_impl("leaf_project", backend)(
        f.u, bb, interpret=config.interpret).astype(bb.dtype)
    for lvl in range(f.levels - 1, 0, -1):
        s = c.reshape(c.shape[0] // 2, 2, *c.shape[1:]).sum(1)
        c = jnp.einsum("pab,pak->pbk", f.w[lvl - 1], s)
    return c.reshape(c.shape[0] // 2, 2, *c.shape[1:]).sum(1)[0]


def apply_root_d(f: HCKFactors, d_root: Array) -> Array:
    """Push a local-root-basis d down the local tree to leaf outputs:
    returns (n_local, k)."""
    d = jnp.repeat(d_root[None], 2, axis=0)          # level-1 children
    for lvl in range(1, f.levels):
        d = jnp.einsum("pab,pbk->pak", f.w[lvl - 1], d)
        d = jnp.repeat(d, 2, axis=0)
    y = jnp.einsum("pnr,prk->pnk", f.u, d)
    return y.reshape(-1, y.shape[-1])


def top_tree_exchange(c_all: Array, top: TopFactors, my_idx: Array) -> Array:
    """Algorithm 1's exchange over the replicated top tree.

    c_all: (P, r, k) LOCAL-ROOT-basis coefficients from every device.
    Returns this device's d in its local-root basis.
    """
    p = c_all.shape[0]
    levels = device_level(p)
    # ascend into the top tree: device nodes sit at top level `levels`
    c = {levels: jnp.einsum("pab,pak->pbk", top.w_dev, c_all)}
    for lvl in range(levels - 1, 0, -1):
        s = c[lvl + 1].reshape(-1, 2, *c_all.shape[1:]).sum(1)
        c[lvl] = jnp.einsum("pab,pak->pbk", top.w[lvl - 1], s)

    d = {}
    for lvl in range(1, levels + 1):
        cs = c[lvl].reshape(-1, 2, *c_all.shape[1:])[:, ::-1]
        cs = cs.reshape(-1, *c_all.shape[1:])
        sig = jnp.repeat(top.sigma[lvl - 1], 2, axis=0)
        d[lvl] = jnp.einsum("pab,pbk->pak", sig, cs)
    for lvl in range(1, levels):
        push = jnp.einsum("pab,pbk->pak", top.w[lvl - 1], d[lvl])
        d[lvl + 1] = d[lvl + 1] + jnp.repeat(push, 2, axis=0)
    # back into the device's local-root basis: d_local = W_dev @ d_top
    d_dev = jnp.einsum("pab,pbk->pak", top.w_dev, d[levels])
    return d_dev[my_idx]


def make_dist_matvec(axis: str, config: SolveConfig | None = None):
    """shard_map body: (local_factors, top, b_local) -> y_local.

    ``config`` is the shared SolveConfig applied to the purely-local stages
    (the top-tree exchange is O(P r k) and stays as tiny einsums)."""

    def matvec(local_f: HCKFactors, top: TopFactors, b_local: Array):
        squeeze = b_local.ndim == 1
        bl = b_local[:, None] if squeeze else b_local
        y = hmatrix.matvec(local_f, bl, config)
        c_dev = local_root_coeff(local_f, bl, config)          # (r, k)
        c_all = jax.lax.all_gather(c_dev, axis)                # (P, r, k)
        d_dev = top_tree_exchange(c_all, top, jax.lax.axis_index(axis))
        y = y + apply_root_d(local_f, d_dev)
        return y[:, 0] if squeeze else y

    return matvec


def dist_solve(matvec_fn, b: Array, *, ridge: float, iters: int = 50,
               precond=None, all_reduce=None, tol: float = 0.0,
               flexible: bool = True):
    """Distributed KRR solve: PCG on (A + ridge I) x = b through the
    shared solver engine (:func:`repro.solvers.cg.pcg`).

    ``all_reduce`` injects the global reduction for the CG inner
    products: under ``shard_map`` pass ``lambda s:
    jax.lax.psum(s, axis)`` so every dot product sums over the mesh; the
    default (None) keeps local sums — correct under pjit, where the
    partial sums compose, and on a single device.  ``precond`` is
    typically the purely-local Algorithm-2 structured inverse (the
    block-diagonal preconditioner of the distributed-KRR story above).
    ``tol=0`` (default) runs exactly ``iters`` iterations — the legacy
    fixed-budget semantics of the deleted ``dist_solve_cg`` helper; with
    ``flexible=False`` the iteration is arithmetically IDENTICAL to that
    helper (Fletcher–Reeves β, same ε guards — the parity test pins
    this), while the default flexible (Polak–Ribière) form additionally
    tolerates an inexact float32 preconditioner.  A positive ``tol``
    enables the engine's early exit on the global relative residual.
    """
    from repro.solvers.cg import pcg

    if all_reduce is not None:
        def dot(u, v):
            return all_reduce(jnp.sum(u * v, axis=0))
    else:
        dot = None
    return pcg(matvec_fn, b, ridge=ridge, precond=precond, tol=tol,
               maxiter=iters, dot=dot, flexible=flexible).x


# ---------------------------------------------------------------------------
# Dense oracle of the distributed structure (tests)
# ---------------------------------------------------------------------------

def dist_to_dense(local_fs: list, top: TopFactors) -> Array:
    """Materialize the global kernel matrix implied by (local trees + top
    tree).  Host loop; test oracle only."""
    from repro.core.hck import to_dense

    p = len(local_fs)
    levels = device_level(p)
    n_loc = local_fs[0].n
    n = p * n_loc
    a = jnp.zeros((n, n), jnp.float32)
    for i, f in enumerate(local_fs):
        sl = slice(i * n_loc, (i + 1) * n_loc)
        a = a.at[sl, sl].set(to_dense(f))

    # effective basis of each device block: local U-chain up to local root,
    # then w_dev
    def device_basis(f: HCKFactors) -> Array:
        ub = [f.u[i] for i in range(f.num_leaves)]
        for lvl in range(f.levels - 1, 0, -1):
            ub = [jnp.concatenate([ub[2 * q], ub[2 * q + 1]], 0)
                  @ f.w[lvl - 1][q] for q in range(1 << lvl)]
        return jnp.concatenate([ub[0], ub[1]], 0)       # (n_loc, r) local-root

    ubig = {levels: [device_basis(f) @ top.w_dev[i]
                     for i, f in enumerate(local_fs)]}
    for lvl in range(levels - 1, 0, -1):
        ubig[lvl] = [
            jnp.concatenate([ubig[lvl + 1][2 * q], ubig[lvl + 1][2 * q + 1]], 0)
            @ top.w[lvl - 1][q] for q in range(1 << lvl)]
    for lvl in range(levels, 0, -1):
        block = n // (1 << lvl)
        for q in range(1 << (lvl - 1)):
            i, j = 2 * q, 2 * q + 1
            cross = ubig[lvl][i] @ top.sigma[lvl - 1][q] @ ubig[lvl][j].T
            ri = slice(i * block, (i + 1) * block)
            rj = slice(j * block, (j + 1) * block)
            a = a.at[ri, rj].set(cross)
            a = a.at[rj, ri].set(cross.T)
    return a


# ---------------------------------------------------------------------------
# Mesh-sharded end-to-end build: one controller, P devices, subtree
# ownership.  Unlike the per-device build above (independent local trees +
# a separately-sampled top tree), these functions reproduce the EXACT
# single-host build_hck / build_hck_streaming factors — same key tree, same
# stage launches — just partitioned over the mesh, so every single-host
# parity/oracle test doubles as a distributed correctness gate.
# ---------------------------------------------------------------------------

def shard_by_subtree(tree_like, mesh: Mesh, axis: str = "dev"):
    """device_put a factor pytree into the subtree layout on ``mesh``.

    Node-stacked leading axes — leaf stacks ``u``/``adiag``, ``x_sorted``
    rows, landmark/sigma/W levels with at least P nodes — shard over
    ``axis`` whenever the leading dim divides by P; everything else (the
    top log2(P) levels whose stacks are smaller than the mesh,
    permutations, thresholds) replicates.  Works for
    :class:`~repro.core.hck.HCKFactors`,
    :class:`~repro.core.hck.SweepPlan`,
    :class:`~repro.core.oos.OOSPlan` and plain arrays alike.
    """
    p = mesh.size
    node_sh = NamedSharding(mesh, P(axis))
    rep_sh = NamedSharding(mesh, P())

    def put(a):
        if (getattr(a, "ndim", 0) >= 2 and a.shape[0] >= p
                and a.shape[0] % p == 0):
            return jax.device_put(a, node_sh)
        return jax.device_put(a, rep_sh)

    return jax.tree.map(put, tree_like)


# build_hck's stage groups, each one jitted program: fused as build_hck
# fuses them, so the mesh build rounds as the single-host build does
# (op-by-op dispatch would not), and partitioned over the context mesh
_middle_factors_jit = jax.jit(_middle_factors, static_argnums=(1, 2))
_leaf_stage_factors_jit = jax.jit(leaf_stage_factors, static_argnums=(3, 4))
_transfer_ops_jit = jax.jit(_transfer_ops, static_argnums=(2, 3))


def dist_build_hck(x: Array, *, levels: int, rank: int, key: Array,
                   kernel: BaseKernel, mesh: Mesh, method: str = "rp",
                   config: SolveConfig | None = None,
                   axis: str = "dev", policy=None,
                   rank_budget: int | None = None) -> HCKFactors:
    """Mesh-parallel :func:`repro.core.hck.build_hck` (Algorithm 2).

    Same key tree (partition subkey first, then one landmark subkey per
    level) and same registry stages as the single-host batched engine,
    so the returned factors MATCH ``build_hck`` on the same key —
    ``tests/test_dist_build.py`` pins the parity at 1e-12 in f64 on the
    CPU.  The partition is ``build_hck``'s own compiled program,
    :func:`~repro.core.partition.build_partition` on ``x`` as given (the
    tree is a discrete function of rounded projections, so only the same
    program is sure to give the same tree on every backend; pass ``x`` on
    one device, as ``build_hck`` gets it).  The factors follow the
    subtree ownership of this module's header: device p owns the
    contiguous leaf range whose root-path prefix is p, levels with < P
    nodes are replicated from one gather, deeper levels are node-sharded,
    and the stage launches are :func:`build_hck`'s own, run with ``mesh``
    as the context mesh: XLA stages partition under GSPMD and Pallas
    stages run per device on the rows each owns (the leaf U stage uses
    leaf granularity with parents repeated, so sibling pairs never
    straddle devices).

    ``policy`` / ``rank_budget`` mirror :func:`~repro.core.hck.build_hck`:
    the uniform policy stays INDEX-bitwise with the single-host build
    (pure integer PRNG); clustered/leverage policies run the same jitted
    :func:`~repro.landmarks.policy.select_indices` on the (transiently
    device-resident) sorted blocks, keeping factor parity at the usual
    1e-12 f64 gate.  Budget masks are computed from the (replicated or
    node-sharded) landmark Grams exactly as the single-host path does and
    land on the mesh via :func:`shard_by_subtree` with every other factor.

    ``levels`` must be at least max(log2(P), 1) so each device owns at
    least one leaf.  Returns factors committed via
    :func:`shard_by_subtree`.
    """
    from repro.core.hck import _apply_rank_masks, _mask_transfer_ops
    from repro.core.partition import build_partition
    from repro.landmarks.policy import (UniformPolicy, get_policy,
                                        select_indices)

    config = config if config is not None else DEFAULT_CONFIG
    policy = get_policy(policy)
    p = mesh.size
    t = device_level(p)
    n = x.shape[0]
    n_leaves = 1 << levels
    if levels < max(t, 1):
        raise ValueError(
            f"levels={levels} too shallow for {p} devices: need >= "
            f"log2(P)={t} so each device owns at least one leaf")
    if n % n_leaves != 0:
        raise ValueError(f"n={n} not divisible by 2**levels={n_leaves}")
    if rank > n // n_leaves:
        raise ValueError(f"rank {rank} exceeds leaf size {n // n_leaves} "
                         "(paper §4.4)")

    kpart, key = jax.random.split(key)
    x_sorted, tree = build_partition(x, levels, kpart, method=method)
    xs_host = np.asarray(x_sorted)
    metric = KERNEL_METRIC.get(kernel.name, "l2")
    n, d = x.shape
    n0 = n // n_leaves
    node_sh = NamedSharding(mesh, P(axis))
    rep_sh = NamedSharding(mesh, P())
    x_sorted = jax.device_put(x_sorted, node_sh)

    with jax.set_mesh(mesh):
        # landmarks: engine-identical indices (same per-level subkeys as
        # build_hck); top-tree stacks (< P nodes) replicate on every device
        # — the one-all_gather "replicate the tree top" move — and deeper
        # stacks are committed node-sharded.
        landmarks = []
        for lvl in range(levels):
            key, sub = jax.random.split(key)
            bsz, m = 1 << lvl, n >> lvl
            if isinstance(policy, UniformPolicy):
                idx = np.asarray(landmark_indices(sub, bsz, m, rank))
            else:
                blocks = jnp.asarray(xs_host).reshape(bsz, m, d)
                idx = np.asarray(select_indices(policy, sub, blocks, rank,
                                                metric=metric, config=config))
            rows = (np.arange(bsz)[:, None] * m + idx).reshape(-1)
            lm = jnp.asarray(xs_host[rows]).reshape(bsz, rank, d)
            landmarks.append(
                jax.device_put(lm, node_sh if bsz >= p else rep_sh))
        landmarks = tuple(landmarks)

        sigma, sigma_cho, sigma_li = _middle_factors_jit(landmarks, kernel,
                                                         config)

        rank_mask = None
        if rank_budget is not None:
            from repro.landmarks.budget import allocate_rank_masks

            rank_mask = allocate_rank_masks(sigma, rank_budget, rank)
            sigma, sigma_cho, sigma_li = _apply_rank_masks(
                rank_mask, sigma, sigma_cho, sigma_li)

        # leaf factors at leaf granularity, parent stacks repeated per leaf
        # (the streaming engine's layout), on the row-sharded leaves
        adiag, u = _leaf_stage_factors_jit(
            x_sorted.reshape(n_leaves, n0, d),
            jnp.repeat(landmarks[-1], 2, axis=0),
            jnp.repeat(sigma_li[-1], 2, axis=0), kernel, config)

        w = _transfer_ops_jit(landmarks, sigma_li, kernel, config)
        if rank_mask is not None:
            u = u * jnp.repeat(rank_mask[-1], 2, axis=0)[:, None, :]
            w = _mask_transfer_ops(w, rank_mask)
        f = HCKFactors(x_sorted, tree, landmarks, tuple(sigma),
                       tuple(sigma_cho), w, u, adiag, rank_mask)
        return shard_by_subtree(f, mesh, axis=axis)


def dist_build_hck_streaming(source, *, levels: int, rank: int, key: Array,
                             kernel: BaseKernel, mesh: Mesh,
                             method: str = "rp",
                             config: SolveConfig | None = None,
                             leaf_batch: int = 64, chunk_rows: int = 1 << 16,
                             axis: str = "dev", policy=None,
                             rank_budget: int | None = None) -> HCKFactors:
    """Mesh-parallel :func:`repro.core.hck.build_hck_streaming`.

    Same key tree and stage numerics as the streaming engine (which in
    turn matches ``build_hck``), so factors agree with BOTH single-host
    builds at round-off.  The partition streams through
    :func:`repro.data.pipeline.stream_partition` with its projection
    chunks committed row-sharded (``mesh=``), landmark rows gather on
    the host, and leaf batches whose size divides P are row-sharded over
    the mesh before their leaf stages — ragged tails stay on one device
    (stage rows are independent, so the values are identical either way).
    """
    from repro.data.pipeline import stream_partition
    from repro.landmarks.policy import UniformPolicy, get_policy

    config = config if config is not None else DEFAULT_CONFIG
    if not isinstance(get_policy(policy), UniformPolicy):
        raise ValueError(
            "dist_build_hck_streaming supports the uniform landmark policy "
            "only: node blocks are never device-resident in one piece — "
            "use dist_build_hck for clustered/leverage selection")
    if rank_budget is not None:
        raise ValueError(
            "dist_build_hck_streaming does not support rank_budget; use "
            "dist_build_hck for budgeted adaptive rank")
    p = mesh.size
    t = device_level(p)
    n, d = source.n, source.dim
    n_leaves = 1 << levels
    if levels < max(t, 1):
        raise ValueError(
            f"levels={levels} too shallow for {p} devices: need >= "
            f"log2(P)={t} so each device owns at least one leaf")
    if n % n_leaves != 0:
        raise ValueError(f"n={n} not divisible by 2**levels={n_leaves}")
    n0 = n // n_leaves
    if rank > n0:
        raise ValueError(f"rank {rank} exceeds leaf size {n0} (paper §4.4)")

    with jax.set_mesh(mesh):
        kpart, key = jax.random.split(key)
        perm_np, tree = stream_partition(source, levels, kpart, method=method,
                                         chunk_rows=chunk_rows, mesh=mesh,
                                         mesh_axis=axis)

        landmarks = []
        for lvl in range(levels):
            key, sub = jax.random.split(key)
            bsz, m = 1 << lvl, n >> lvl
            idx = np.asarray(landmark_indices(sub, bsz, m, rank))
            rows = perm_np[(np.arange(bsz)[:, None] * m + idx).reshape(-1)]
            landmarks.append(jnp.asarray(source.take(rows)).reshape(bsz, rank, d))
        landmarks = tuple(landmarks)

        sigma, sigma_cho, sigma_li = _middle_factors(landmarks, kernel, config)

        lm_parent = jnp.repeat(landmarks[-1], 2, axis=0)
        linv_parent = jnp.repeat(sigma_li[-1], 2, axis=0)
        row_sh = NamedSharding(mesh, P(axis))
        adiag_parts, u_parts, x_parts = [], [], []
        for start in range(0, n_leaves, leaf_batch):
            stop = min(start + leaf_batch, n_leaves)
            rows = perm_np[start * n0:stop * n0]
            blk = jnp.asarray(source.take(rows)).reshape(stop - start, n0, d)
            x_parts.append(blk.reshape(-1, d))
            if (stop - start) % p == 0:
                blk = jax.device_put(blk, row_sh)
            a, ub = leaf_stage_factors(blk, lm_parent[start:stop],
                                       linv_parent[start:stop], kernel, config)
            adiag_parts.append(a)
            u_parts.append(ub)
        adiag = jnp.concatenate(adiag_parts, axis=0)
        u = jnp.concatenate(u_parts, axis=0)
        x_sorted = jnp.concatenate(x_parts, axis=0)

        w = _transfer_ops(landmarks, sigma_li, kernel, config)
        f = HCKFactors(x_sorted, tree, landmarks, tuple(sigma), tuple(sigma_cho),
                       w, u, adiag)
        return shard_by_subtree(f, mesh, axis=axis)


def dist_sweep_factors(plan, kernel: BaseKernel, mesh: Mesh,
                       config: SolveConfig | None = None,
                       axis: str = "dev",
                       rank_budget: int | None = None) -> HCKFactors:
    """Sweep-engine factor instantiation on a subtree-sharded plan.

    :func:`repro.core.hck.sweep_factors` is already one batched
    ``build_gram_dist`` / ``build_cross_dist`` stage launch per level
    inside one jit, so mesh parallelism here is pure data placement:
    commit the cached distance tiles node-sharded (top levels
    replicated) via :func:`shard_by_subtree` and GSPMD partitions every
    stage launch over the mesh.  Values are placement-invariant — the
    σ-sweep parity tests pass unchanged on the sharded plan.
    ``rank_budget`` passes through to the sweep engine's budgeted
    adaptive rank.
    """
    from repro.core.hck import sweep_factors

    with jax.set_mesh(mesh):
        plan = shard_by_subtree(plan, mesh, axis=axis)
        return shard_by_subtree(
            sweep_factors(plan, kernel, config, rank_budget=rank_budget), mesh,
            axis=axis)
