"""Hierarchically Compositional Kernel — factor construction (paper §2–§3).

Builds the recursively off-diagonal low-rank (ROLR) representation of
``K_hck(X, X)`` for a balanced binary partition tree:

  * ``Adiag[i] = K(X_i, X_i) (+ jitter)``                leaf blocks (n0, n0)
  * ``U[i]    = K(X_i, Xl_p) K(Xl_p, Xl_p)^-1``          leaf bases  (n0, r)
  * ``Sigma[l][p] = K(Xl_p, Xl_p) (+ jitter)``           middle factors (r, r)
  * ``W[l][i] = K(Xl_i, Xl_p) K(Xl_p, Xl_p)^-1``         transfer ops (r, r)

All factors are stacked per tree level so every traversal in
``repro.core.hmatrix`` is a batched einsum (see DESIGN.md §2), and —
since the build-engine refactor — every factor *instantiation* is one of
two backend-registry stages batched over all nodes of a level
(DESIGN.md §8):

  * ``build_gram``:  node blocks -> Gram (+ jitter) and its Cholesky
  * ``build_cross``: node blocks + parent landmarks/``Sigma^{-1}`` ->
                     the projected cross block (U and W factors)

:func:`build_hck` is the batched engine (xla einsum or fused Pallas
backends, selected by ``SolveConfig``); :func:`build_hck_reference` keeps
the per-node host-loop transcription of the paper's Algorithm 2 as the
float64 parity oracle and the ``bench_build.py`` baseline;
:func:`build_hck_streaming` stages leaf blocks from a host-resident
:class:`repro.data.pipeline.ChunkSource` through the same engine for fits
whose raw data does not fit device memory.

Landmarks ``Xl_i`` are uniform random subsamples of each node's points
(paper §4.2).  Setting ``shared_landmarks=True`` reuses the root landmark
set at every node, which by the §4.2 remark reproduces the *flat*
compositional kernel ``k_compositional`` exactly — used as a baseline and in
the Theorem-4 test.
"""
from __future__ import annotations

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernels_fn import KERNEL_METRIC, BaseKernel
from repro.core.partition import PartitionTree, build_partition
from repro.kernels.registry import (DEFAULT_CONFIG, SolveConfig, get_impl,
                                    precision_policy, resolve_backend,
                                    tile_config)
from repro.runtime.trace import span

Array = jax.Array


#: min/max per-node rank and the global Σ_nodes r_node of a factor set.
RankSummary = collections.namedtuple("RankSummary", ("min", "max", "total"))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class HCKFactors:
    """Stacked ROLR factors of K_hck(X, X) (+ the partition metadata)."""

    x_sorted: Array            # (n, d) points in tree order
    tree: PartitionTree
    landmarks: tuple           # levels 0..L-1: (2**l, r, d)
    sigma: tuple               # levels 0..L-1: (2**l, r, r)   K(Xl, Xl)+jit
    sigma_cho: tuple           # cholesky(lower) of sigma, same shapes
    w: tuple                   # levels 1..L-1: (2**l, r, r)
    u: Array                   # (2**L, n0, r)
    adiag: Array               # (2**L, n0, n0)
    rank_mask: tuple | None = None   # levels 0..L-1: (2**l, r) prefix masks
    #                                  (None = every slot active; see
    #                                  repro.landmarks.budget)

    # -- static metadata -------------------------------------------------
    @property
    def levels(self) -> int:
        """Tree depth L."""
        return len(self.landmarks)

    @property
    def num_leaves(self) -> int:
        """Leaf count 2**L."""
        return self.adiag.shape[0]

    @property
    def leaf_size(self) -> int:
        """Points per leaf n0 = n / 2**L."""
        return self.adiag.shape[1]

    @property
    def rank(self) -> int:
        """Pad-bucket rank r: landmark SLOTS per node (0 for a 0-level
        build).  With a rank budget this is the padded bucket every
        stacked factor is shaped to — the shape-relevant quantity all
        engines consume; per-node ACTIVE ranks live in :attr:`ranks`."""
        return self.landmarks[0].shape[1] if self.landmarks else 0

    @property
    def ranks(self) -> RankSummary:
        """Per-node active-rank summary: (min, max, Σ over all nodes).

        Uniform-rank factors report min == max == :attr:`rank`; budgeted
        factors count the active prefix of each node's
        :attr:`rank_mask`.  Host-side metadata (concrete ints).
        """
        n_nodes = sum(1 << lvl for lvl in range(self.levels))
        if not self.landmarks:
            return RankSummary(0, 0, 0)
        if self.rank_mask is None:
            return RankSummary(self.rank, self.rank, self.rank * n_nodes)
        per = jnp.concatenate(
            [jnp.sum(m, axis=1) for m in self.rank_mask])
        return RankSummary(int(jnp.min(per)), int(jnp.max(per)),
                           int(jnp.sum(per)))

    @property
    def n(self) -> int:
        """Total training points."""
        return self.x_sorted.shape[0]

    def tree_flatten(self):
        """Pytree protocol: all fields are children."""
        leaves = (
            self.x_sorted, self.tree, self.landmarks, self.sigma,
            self.sigma_cho, self.w, self.u, self.adiag, self.rank_mask,
        )
        return leaves, None

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Pytree protocol: rebuild from flattened children."""
        return cls(*children)


def landmark_indices(key: Array, bsz: int, m: int, r: int) -> Array:
    """Per-node landmark row indices: (B, r) int32 positions inside each
    node block.

    One subkey per node (``jax.random.split``), one uniform permutation per
    node — the counter-based PRNG makes this reproducible from any path
    (batched engine, per-node reference, streaming ingestion), which is
    what the factor-parity gates rely on.
    """
    keys = jax.random.split(key, bsz)
    return jax.vmap(lambda k: jax.random.permutation(k, m)[:r])(keys)


def _sample_landmarks(key: Array, blocks: Array, r: int) -> Array:
    """Uniform sample of r points per block: (B, m, d) -> (B, r, d)."""
    bsz, m, d = blocks.shape
    idx = landmark_indices(key, bsz, m, r)                            # (B, r)
    flat = (idx + jnp.arange(bsz)[:, None] * m).reshape(-1)
    return jnp.take(blocks.reshape(bsz * m, d), flat, axis=0).reshape(bsz, r, d)


def _draw_level_landmarks(key: Array, x_sorted: Array, levels: int,
                          rank: int, policy, metric: str,
                          config: SolveConfig | None) -> list:
    """Per-level landmark draw shared by the build and sweep engines.

    Consumes one ``jax.random.split`` per level in the pre-policy order —
    the key tree all parity gates pin.  ``policy=None``/uniform routes
    through the exact pre-existing :func:`_sample_landmarks` call (bitwise
    guarantee); other policies select per-node row indices on the same
    reshaped blocks and reuse the identical flat-take gather.
    """
    from repro.landmarks.policy import UniformPolicy, gather_block_rows

    n, d = x_sorted.shape
    landmarks = []
    for lvl in range(levels):
        key, sub = jax.random.split(key)
        blocks = x_sorted.reshape(1 << lvl, n >> lvl, d)
        if policy is None or isinstance(policy, UniformPolicy):
            landmarks.append(_sample_landmarks(sub, blocks, rank))
        else:
            idx = policy.select(sub, blocks, rank, metric=metric,
                                config=config)
            landmarks.append(gather_block_rows(blocks, idx))
    return landmarks


def _apply_rank_masks(rank_mask, sigma, sigma_cho, sigma_li):
    """Identity-pad the middle factors to their active-prefix ranks.

    For prefix masks the Cholesky leading-submatrix property makes the
    padded ``(sigma, cho, linv)`` EXACTLY the factors of the truncated
    Gram — no refactorization (see ``repro.landmarks.budget``).  Must run
    BEFORE any build_cross launch: U/W built against the full ``linv``
    cannot be column-masked after the fact, since the leading block of
    ``Sigma_full^{-1}`` is not ``(Sigma_aa)^{-1}``.
    """
    from repro.landmarks.budget import masked_identity_pad

    sigma = tuple(masked_identity_pad(s, mk)
                  for s, mk in zip(sigma, rank_mask))
    sigma_cho = tuple(masked_identity_pad(c, mk)
                      for c, mk in zip(sigma_cho, rank_mask))
    sigma_li = [masked_identity_pad(li, mk)
                for li, mk in zip(sigma_li, rank_mask)]
    return sigma, sigma_cho, sigma_li


def _mask_transfer_ops(w: tuple, rank_mask: tuple) -> tuple:
    """Zero W rows/cols touching inactive slots (child rows, parent cols)."""
    return tuple(
        w[lvl - 1] * rank_mask[lvl][:, :, None]
        * jnp.repeat(rank_mask[lvl - 1], 2, axis=0)[:, None, :]
        for lvl in range(1, len(rank_mask)))


def _stage_build_gram(blocks: Array, kernel: BaseKernel,
                      config: SolveConfig, *, want_chol: bool = True):
    """Dispatch one level's node blocks through the ``build_gram`` stage.

    Under a mixed-precision policy (``config.precision``) the point
    blocks are cast to the GEMM data dtype before dispatch (every backend
    accumulates in >= float32) and the Gram/Cholesky outputs are stored in
    the factor dtype; without a policy the stage is dtype-preserving.
    """
    pol = precision_policy(config)
    out_dt = blocks.dtype if pol is None else pol[1]
    if pol is not None:
        blocks = blocks.astype(pol[0])
    _, m, d = blocks.shape
    backend = resolve_backend(config, "build_gram", dtype=blocks.dtype,
                              n0=m, r=m, d=d)
    gram, chol = get_impl("build_gram", backend)(
        blocks, name=kernel.name, sigma=kernel.sigma, jitter=kernel.jitter,
        want_chol=want_chol, interpret=config.interpret)
    gram = gram.astype(out_dt)
    return gram, None if chol is None else chol.astype(out_dt)


def sigma_linv(chol: Array) -> Array:
    """Explicit inverse Cholesky factors ``Linv = L^{-1}`` per node.

    (B, r, r) lower factors -> (B, r, r) lower ``Linv``, computed ONCE per
    node so every ``build_cross`` launch applies ``Sigma^{-1} = Linv^T
    Linv`` as two pure GEMMs — on CPU/XLA the per-child batched triangular
    solve this replaces runs ~7x slower than the equivalent GEMMs, and on
    the MXU the GEMM is the native form.  Keeping the factored (not
    squared) form preserves cho_solve-grade accuracy: each GEMM mirrors
    one backward-stable substitution, where a pre-squared ``Sigma^{-1}``
    doubles the condition number and (empirically, float32) breaks the
    downstream Algorithm-2 Schur Cholesky.  Sibling nodes share a parent,
    so one factor serves both children; this is the same object the solve
    engine keeps as ``InverseFactors.linv`` for its leaf stage.
    """
    # blocked recursion: inv([[A,0],[B,C]]) = [[Ai,0],[-Ci B Ai, Ci]] —
    # substitution only at the <=64 base, everything above is GEMMs
    # (XLA CPU's batched triangular solve runs far below GEMM throughput);
    # shared with the solve engine's leaf_factor stage
    from repro.kernels.hck_leaf.ref import tril_inverse

    return tril_inverse(chol)


def _stage_build_cross(blocks: Array, lm_parent: Array, linv_parent: Array,
                       kernel: BaseKernel, config: SolveConfig) -> Array:
    """Dispatch one level's cross blocks through the ``build_cross`` stage.

    Mixed precision: the kernel-evaluation *data* (points + landmarks) is
    cast to the policy's GEMM dtype; ``linv_parent`` is a factor (already
    factor-dtype from ``_stage_build_gram``) and stays >= float32 so the
    Sigma^{-1} application keeps triangular-solve-grade accuracy.  The
    projected basis is stored in the factor dtype.
    """
    pol = precision_policy(config)
    out_dt = blocks.dtype if pol is None else pol[1]
    if pol is not None:
        blocks = blocks.astype(pol[0])
        lm_parent = lm_parent.astype(pol[0])
        linv_parent = linv_parent.astype(pol[1])
    _, m, d = blocks.shape
    r = lm_parent.shape[1]
    backend = resolve_backend(config, "build_cross", dtype=blocks.dtype,
                              n0=m, r=r, d=d)
    kwargs = {}
    if backend == "pallas":
        kwargs["block_m"] = tile_config(
            "build_cross", n0=m, r=r, k=r, d=d,
            itemsize=blocks.dtype.itemsize,
            leaf_block=config.leaf_block).block_n0
    return get_impl("build_cross", backend)(
        blocks, lm_parent, linv_parent, name=kernel.name, sigma=kernel.sigma,
        interpret=config.interpret, **kwargs).astype(out_dt)


def leaf_stage_factors(blocks: Array, lm_parent: Array, linv_parent: Array,
                       kernel: BaseKernel, config: SolveConfig):
    """Leaf-granularity Adiag + U stage pair for a group of leaf blocks.

    ``blocks`` (B, n0, d) are leaf point blocks, ``lm_parent`` /
    ``linv_parent`` the PER-LEAF parent landmark and inverse-Cholesky
    stacks (i.e. already repeated to leaf granularity — leaf groups need
    not align with sibling pairs).  Returns ``(adiag (B, n0, n0),
    u (B, n0, r))``.  Both the streaming engine and the mesh-sharded
    distributed build (``repro.launch.dist_hck``) stage their leaves
    through this one function: every stage row is independent, so
    leaf-granularity launches are bit-identical to :func:`build_hck`'s
    paired-sibling launches — the parity gates rely on that.
    """
    adiag, _ = _stage_build_gram(blocks, kernel, config, want_chol=False)
    u = _stage_build_cross(blocks, lm_parent, linv_parent, kernel, config)
    return adiag, u


def _broadcast_shared_landmarks(landmarks: list, rank: int, d: int) -> list:
    """§4.2 remark: reuse the root landmark set at every node (-> flat
    k_compositional)."""
    root = landmarks[0]
    return [jnp.broadcast_to(root, (1 << lvl, rank, d)).reshape(1 << lvl, rank, d)
            for lvl in range(len(landmarks))]


def _middle_factors(landmarks: tuple, kernel: BaseKernel,
                    config: SolveConfig):
    """Sigma, Cholesky, and Linv for every level.

    One ``build_gram`` stage launch per level plus the per-node inverse
    Cholesky factor (:func:`sigma_linv`) — shared by the in-memory and
    streaming engines so their factor numerics can never diverge.
    """
    sigma, sigma_cho, sigma_li = [], [], []
    for lm in landmarks:
        s, c = _stage_build_gram(lm, kernel, config)
        sigma.append(s)
        sigma_cho.append(c)
        sigma_li.append(sigma_linv(c))
    return tuple(sigma), tuple(sigma_cho), sigma_li


def _transfer_ops(landmarks: tuple, sigma_li: list, kernel: BaseKernel,
                  config: SolveConfig) -> tuple:
    """W factors at levels 1..L-1 via paired-sibling build_cross launches.

    Sibling nodes share their parent's landmarks and Linv, so each level's
    cross stage runs at PARENT granularity (paired child blocks) — no
    repeated landmark/factor stacks.  Shared by both engines.
    """
    rank, d = landmarks[0].shape[1], landmarks[0].shape[2]
    w = []
    for lvl in range(1, len(landmarks)):
        pair_lm = landmarks[lvl].reshape(1 << (lvl - 1), 2 * rank, d)
        w.append(_stage_build_cross(
            pair_lm, landmarks[lvl - 1], sigma_li[lvl - 1], kernel,
            config).reshape(1 << lvl, rank, rank))
    return tuple(w)


def build_hck(
    x: Array,
    *,
    levels: int,
    rank: int,
    key: Array,
    kernel: BaseKernel,
    method: str = "rp",
    shared_landmarks: bool = False,
    config: SolveConfig | None = None,
    policy=None,
    rank_budget: int | None = None,
) -> HCKFactors:
    """Partition ``x`` and instantiate all HCK factors (batched engine).

    Level-synchronous Algorithm 2: the partition splits all nodes of a
    level in one vmapped pass, and every factor is instantiated by one of
    two registry stages batched over the level — ``build_gram`` (Sigma +
    Cholesky, and the leaf Adiag blocks) and ``build_cross`` (the
    Sigma^{-1}-projected U and W blocks).  Cost (paper §4.5): O(n d
    log(n/r)) partitioning + O(n r (r + d)) factor instantiation.

    Parameters
    ----------
    x:       (n, d) training points; n must be divisible by ``2**levels``
             (:func:`repro.core.partition.pad_points` pads).  float32 or
             float64 (the factors keep x's dtype; the Pallas backend
             computes sub-f32 inputs in f32).
    levels:  tree depth L >= 0 (0 degenerates to one dense leaf block).
    rank:    landmarks per node r <= n / 2**levels (paper §4.4).
    key:     PRNG key consumed by the partition and landmark sampling.
    kernel:  base kernel closed over (name, sigma, jitter); static.
    method:  partitioning rule, "rp" (recommended) or "pca".
    shared_landmarks: reuse the root landmark set at every node (§4.2
             remark: collapses to the flat compositional kernel).
    config:  :class:`~repro.kernels.registry.SolveConfig` selecting the
             stage backends (``backend``, ``interpret``, ``leaf_block``
             are honored); None = DEFAULT_CONFIG ("auto").
    policy:  landmark-selection policy — None/"uniform" (bitwise-identical
             to the pre-policy engine), "kmeans"/"leverage", or a
             :class:`~repro.landmarks.policy.LandmarkPolicy` instance.
             The partition is drawn BEFORE any landmark key split, so all
             policies share one hierarchy.
    rank_budget: optional global rank budget Σ_nodes r_node <= budget,
             allocated per node proportional to landmark-Gram spectral
             mass and realized as prefix masks over the ``rank`` pad
             bucket (``HCKFactors.rank_mask``; see
             ``repro.landmarks.budget``).  None = full rank everywhere.

    Returns
    -------
    :class:`HCKFactors` with all per-level factor stacks.

    The partition runs as its own compiled program, :func:`build_partition`
    on ``x`` as given, and the factors as a second one.  The tree is a
    discrete function of rounded projections: a program that fused the
    projections differently could round a point at a node's median to the
    other side.  Keeping the partition program apart lets
    :func:`repro.launch.dist_hck.dist_build_hck` run the very same one, so
    both builds get the same tree on any backend.
    """
    n = x.shape[0]
    n_leaves = 1 << levels
    if n % n_leaves != 0:
        raise ValueError(f"n={n} not divisible by 2**levels={n_leaves}")
    if rank > n // n_leaves:
        raise ValueError(f"rank {rank} exceeds leaf size {n // n_leaves} "
                         "(paper §4.4)")
    if rank_budget is not None and levels == 0:
        raise ValueError("rank_budget requires levels >= 1 "
                         "(a 0-level build has no low-rank factors)")

    kpart, key = jax.random.split(key)
    with span("partition"):
        x_sorted, tree = build_partition(x, levels, kpart, method=method)
    with span("build"):
        return _build_on_tree(x_sorted, tree, key, levels=levels, rank=rank,
                              kernel=kernel, shared_landmarks=shared_landmarks,
                              config=config, policy=policy,
                              rank_budget=rank_budget)


@functools.partial(
    jax.jit,
    static_argnames=("levels", "rank", "shared_landmarks", "kernel", "config",
                     "policy", "rank_budget"),
)
def _build_on_tree(x_sorted: Array, tree: PartitionTree, key: Array, *,
                   levels: int, rank: int, kernel: BaseKernel,
                   shared_landmarks: bool, config: SolveConfig | None,
                   policy, rank_budget: int | None) -> HCKFactors:
    """:func:`build_hck` after the partition: landmarks and every factor on
    the tree-ordered points ``x_sorted``, with ``key`` the key left after
    the partition subkey is split off."""
    from repro.landmarks.policy import get_policy

    config = config if config is not None else DEFAULT_CONFIG
    policy = get_policy(policy)
    n, d = x_sorted.shape
    n_leaves = 1 << levels
    n0 = n // n_leaves

    # --- landmarks: per-node subsample under the selection policy --------
    landmarks = _draw_level_landmarks(
        key, x_sorted, levels, rank, policy,
        KERNEL_METRIC.get(kernel.name, "l2"), config)
    if shared_landmarks and levels > 0:
        landmarks = _broadcast_shared_landmarks(landmarks, rank, d)
    landmarks = tuple(landmarks)

    # --- middle factors Sigma, their Cholesky, and Linv ------------------
    # (build_gram stage; the inverse Cholesky factor is computed once per
    # node so every downstream cross block is two GEMMs — see sigma_linv)
    sigma, sigma_cho, sigma_li = _middle_factors(landmarks, kernel, config)

    # --- budgeted adaptive per-node rank: prefix-mask the middle factors
    # BEFORE any cross launch so U/W are built against the truncated
    # Sigma^{-1} (see _apply_rank_masks)
    rank_mask = None
    if rank_budget is not None:
        from repro.landmarks.budget import allocate_rank_masks

        rank_mask = allocate_rank_masks(sigma, rank_budget, rank)
        sigma, sigma_cho, sigma_li = _apply_rank_masks(
            rank_mask, sigma, sigma_cho, sigma_li)

    # --- leaf factors (build_gram without Cholesky + build_cross) --------
    leaves = x_sorted.reshape(n_leaves, n0, d)
    adiag, _ = _stage_build_gram(leaves, kernel, config, want_chol=False)
    if levels == 0:
        return HCKFactors(x_sorted, tree, (), (), (), (),
                          jnp.zeros((1, n0, 0), x_sorted.dtype), adiag)

    # U_i = K(X_i, Xl_p) inv(K(Xl_p, Xl_p)); parent of leaf i is i//2.
    # Sibling leaves share their parent's landmarks and Linv, so the cross
    # stage runs at PARENT granularity (paired child blocks) — no repeated
    # landmark/factor stacks, half the landmark-norm work.
    paired = leaves.reshape(n_leaves // 2, 2 * n0, d)
    u = _stage_build_cross(paired, landmarks[-1], sigma_li[-1],
                           kernel, config).reshape(n_leaves, n0, rank)

    # --- transfer operators W at levels 1..L-1 (build_cross stage) -------
    w = _transfer_ops(landmarks, sigma_li, kernel, config)
    if rank_mask is not None:
        u = u * jnp.repeat(rank_mask[-1], 2, axis=0)[:, None, :]
        w = _mask_transfer_ops(w, rank_mask)
    return HCKFactors(x_sorted, tree, landmarks, sigma, sigma_cho, w, u,
                      adiag, rank_mask)


# ---------------------------------------------------------------------------
# Hyperparameter sweep engine — build the hierarchy once, re-instantiate
# the factors for every bandwidth from cached distance tiles.
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SweepPlan:
    """σ-independent precomputation for a bandwidth/regularization grid.

    For every base kernel in :data:`repro.core.kernels_fn.KERNEL_METRIC`
    the kernel value is an elementwise function of a
    bandwidth-independent metric distance, and the partition tree plus the
    landmark draw depend only on the PRNG key and the (unscaled) data — so
    a (σ, λ) grid search needs exactly ONE partition + landmark pass and
    ONE O(n r (r + d)) distance pass.  The plan caches:

      * ``x_sorted`` / ``tree`` / ``landmarks`` — the reusable hierarchy
        (argsort scale invariance: see ``partition.rescale_tree``).
      * ``lm_self[l]``   (2**l, r, r)      landmark self distances
      * ``lm_cross[l-1]`` (2**(l-1), 2r, r) landmark→parent cross distances
      * ``leaf_self``    (2**L, n0, n0)    leaf-block self distances
      * ``leaf_cross``   (2**(L-1), 2n0, r) leaf→parent-landmark distances

    :func:`sweep_factors` turns the plan into :class:`HCKFactors` at any
    bandwidth via the ``build_gram_dist`` / ``build_cross_dist`` registry
    stages — elementwise nonlinearity + factorize only, no distance work —
    and matches :func:`build_hck` on the same key to float round-off.
    """

    x_sorted: Array
    tree: PartitionTree
    landmarks: tuple           # levels 0..L-1: (2**l, r, d)
    lm_self: tuple             # levels 0..L-1: (2**l, r, r)
    lm_cross: tuple            # levels 1..L-1: (2**(l-1), 2r, r)
    leaf_self: Array           # (2**L, n0, n0)
    leaf_cross: Array          # (2**(L-1), 2*n0, r)
    metric: str = "l2"         # static: "l2" (gaussian/imq) or "l1" (laplace)

    @property
    def levels(self) -> int:
        """Tree depth L."""
        return len(self.landmarks)

    @property
    def num_leaves(self) -> int:
        """Leaf count 2**L."""
        return self.leaf_self.shape[0]

    @property
    def leaf_size(self) -> int:
        """Points per leaf n0."""
        return self.leaf_self.shape[1]

    @property
    def rank(self) -> int:
        """Landmarks per node r."""
        return self.landmarks[0].shape[1]

    def tree_flatten(self):
        """Pytree protocol: ``metric`` is static aux data."""
        leaves = (self.x_sorted, self.tree, self.landmarks, self.lm_self,
                  self.lm_cross, self.leaf_self, self.leaf_cross)
        return leaves, self.metric

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Pytree protocol: rebuild from flattened children."""
        return cls(*children, metric=aux)


def _plan_tiles(x_sorted, tree, landmarks, metric, levels, rank, n0):
    """Distance tiles for a fixed hierarchy + landmark set -> SweepPlan."""
    from repro.kernels.build_stage.ref import pairwise_dist_ref

    n_leaves = 1 << levels
    d = x_sorted.shape[1]
    lm_self = tuple(pairwise_dist_ref(lm, lm, metric) for lm in landmarks)
    lm_cross = tuple(
        pairwise_dist_ref(
            landmarks[lvl].reshape(1 << (lvl - 1), 2 * rank, d),
            landmarks[lvl - 1], metric)
        for lvl in range(1, levels))
    leaves = x_sorted.reshape(n_leaves, n0, d)
    leaf_self = pairwise_dist_ref(leaves, leaves, metric)
    leaf_cross = pairwise_dist_ref(
        leaves.reshape(n_leaves // 2, 2 * n0, d), landmarks[-1], metric)
    return SweepPlan(x_sorted, tree, landmarks, lm_self, lm_cross,
                     leaf_self, leaf_cross, metric=metric)


@functools.partial(
    jax.jit, static_argnames=("levels", "rank", "method", "shared_landmarks",
                              "name", "policy", "config"),
)
def build_sweep_plan(
    x: Array,
    *,
    levels: int,
    rank: int,
    key: Array,
    name: str = "gaussian",
    method: str = "rp",
    shared_landmarks: bool = False,
    policy=None,
    config: SolveConfig | None = None,
) -> SweepPlan:
    """Partition once and cache all bandwidth-independent distance tiles.

    Consumes the SAME key tree as :func:`build_hck` (partition subkey
    first, then one landmark subkey per level), so
    ``sweep_factors(plan, kernel)`` reproduces
    ``build_hck(x, ..., kernel=kernel)`` for every kernel sharing
    ``name``'s metric.  O(n d log(n/r)) partition + O(n (n0 + r)) distance
    entries, all reused across the whole (σ, λ) grid.

    ``policy`` adds the sweep's LANDMARK-POLICY axis: selection is
    σ-independent by design (see ``repro.landmarks.policy``), so a plan
    per policy shares the hierarchy — :func:`replan_policy` re-draws the
    landmarks of an existing plan without re-partitioning.  ``config``
    only steers the policy's inner ``policy_dist`` stage (unused for
    uniform).

    ``levels`` must be >= 1 (a 0-level build is one dense block with no
    σ-independent structure worth caching — call :func:`build_hck`).
    """
    from repro.landmarks.policy import get_policy

    if name not in KERNEL_METRIC:
        raise ValueError(
            f"kernel {name!r} has no registered bandwidth-independent "
            f"metric; sweepable kernels: {sorted(KERNEL_METRIC)}")
    if levels < 1:
        raise ValueError("build_sweep_plan needs levels >= 1 "
                         "(a 0-level build is one dense block)")
    metric = KERNEL_METRIC[name]
    n, d = x.shape
    n_leaves = 1 << levels
    if n % n_leaves != 0:
        raise ValueError(f"n={n} not divisible by 2**levels={n_leaves}")
    n0 = n // n_leaves
    if rank > n0:
        raise ValueError(f"rank {rank} exceeds leaf size {n0} (paper §4.4)")

    kpart, key = jax.random.split(key)
    x_sorted, tree = build_partition(x, levels, kpart, method=method)

    landmarks = _draw_level_landmarks(key, x_sorted, levels, rank,
                                      get_policy(policy), metric, config)
    if shared_landmarks:
        landmarks = _broadcast_shared_landmarks(landmarks, rank, d)
    landmarks = tuple(landmarks)
    return _plan_tiles(x_sorted, tree, landmarks, metric, levels, rank, n0)


@functools.partial(jax.jit, static_argnames=("rank", "policy", "config"))
def replan_policy(
    plan: SweepPlan,
    *,
    rank: int,
    key: Array,
    policy,
    config: SolveConfig | None = None,
) -> SweepPlan:
    """Re-draw an existing plan's landmarks under a different policy.

    The policy axis of a sweep: reuses ``plan.x_sorted``/``plan.tree``
    (no re-partition) and consumes the same key tree as
    :func:`build_sweep_plan` — the partition subkey is split off and
    discarded, then one landmark subkey per level — so
    ``replan_policy(build_sweep_plan(x, ..., key=k), ..., key=k,
    policy=p)`` equals ``build_sweep_plan(x, ..., key=k, policy=p)``.
    ``rank`` may differ from the source plan's (accuracy-vs-rank curves
    on a fixed hierarchy).
    """
    from repro.landmarks.policy import get_policy

    levels = plan.levels
    n0 = plan.x_sorted.shape[0] >> levels
    if rank > n0:
        raise ValueError(f"rank {rank} exceeds leaf size {n0} (paper §4.4)")
    _, key = jax.random.split(key)   # discard the partition subkey
    landmarks = tuple(_draw_level_landmarks(
        key, plan.x_sorted, levels, rank, get_policy(policy), plan.metric,
        config))
    return _plan_tiles(plan.x_sorted, plan.tree, landmarks, plan.metric,
                       levels, rank, n0)


def _stage_gram_dist(dist: Array, kernel: BaseKernel, config: SolveConfig,
                     *, want_chol: bool = True):
    """Dispatch cached distance tiles through the ``build_gram_dist`` stage.

    Mixed precision mirrors :func:`_stage_build_gram`: distance tiles are
    the kernel-evaluation data (GEMM dtype), Gram/Cholesky outputs are
    stored in the factor dtype.
    """
    pol = precision_policy(config)
    out_dt = dist.dtype if pol is None else pol[1]
    if pol is not None:
        dist = dist.astype(pol[0])
    _, m, _ = dist.shape
    backend = resolve_backend(config, "build_gram_dist", dtype=dist.dtype,
                              n0=m, r=m)
    gram, chol = get_impl("build_gram_dist", backend)(
        dist, name=kernel.name, sigma=kernel.sigma, jitter=kernel.jitter,
        want_chol=want_chol, interpret=config.interpret)
    gram = gram.astype(out_dt)
    return gram, None if chol is None else chol.astype(out_dt)


def _stage_cross_dist(dist: Array, linv_parent: Array, kernel: BaseKernel,
                      config: SolveConfig) -> Array:
    """Dispatch cached cross tiles through the ``build_cross_dist`` stage.

    Mixed precision mirrors :func:`_stage_build_cross`: distance data in
    the GEMM dtype, inverse-Cholesky factor and output in factor dtype.
    """
    pol = precision_policy(config)
    out_dt = dist.dtype if pol is None else pol[1]
    if pol is not None:
        dist = dist.astype(pol[0])
        linv_parent = linv_parent.astype(pol[1])
    _, m, r = dist.shape
    backend = resolve_backend(config, "build_cross_dist", dtype=dist.dtype,
                              n0=m, r=r)
    kwargs = {}
    if backend == "pallas":
        kwargs["block_m"] = tile_config(
            "build_cross_dist", n0=m, r=r, k=r,
            itemsize=dist.dtype.itemsize,
            leaf_block=config.leaf_block).block_n0
    return get_impl("build_cross_dist", backend)(
        dist, linv_parent, name=kernel.name, sigma=kernel.sigma,
        interpret=config.interpret, **kwargs).astype(out_dt)


@functools.partial(jax.jit,
                   static_argnames=("kernel", "config", "rank_budget"))
def sweep_factors(
    plan: SweepPlan,
    kernel: BaseKernel,
    config: SolveConfig | None = None,
    *,
    rank_budget: int | None = None,
) -> HCKFactors:
    """Instantiate :class:`HCKFactors` at one bandwidth from a
    :class:`SweepPlan` — the per-σ pass of the sweep engine.

    Every launch is elementwise-nonlinearity + factorize on a cached
    distance tile (``build_gram_dist`` / ``build_cross_dist`` stages): no
    partition, no landmark draw, no pairwise-distance MXU work.  With the
    plan built from the same key, the result matches
    ``build_hck(x, ..., kernel=kernel, ...)`` to float round-off for any
    ``kernel`` whose metric equals ``plan.metric``.

    ``kernel`` and ``config`` are static (hashable) jit arguments, exactly
    as in :func:`build_hck`; ``rank_budget`` mirrors :func:`build_hck`'s
    budgeted adaptive per-node rank (masks recomputed per σ, since the
    landmark Gram — hence spectral mass — is bandwidth-dependent).
    """
    config = config if config is not None else DEFAULT_CONFIG
    if KERNEL_METRIC.get(kernel.name) != plan.metric:
        raise ValueError(
            f"kernel {kernel.name!r} (metric "
            f"{KERNEL_METRIC.get(kernel.name)!r}) does not match the plan's "
            f"cached metric {plan.metric!r}; rebuild the plan with "
            f"name={kernel.name!r}")
    levels, rank = plan.levels, plan.rank
    n_leaves, n0 = plan.num_leaves, plan.leaf_size

    sigma, sigma_cho, sigma_li = [], [], []
    for lvl in range(levels):
        s, c = _stage_gram_dist(plan.lm_self[lvl], kernel, config)
        sigma.append(s)
        sigma_cho.append(c)
        sigma_li.append(sigma_linv(c))
    sigma, sigma_cho = tuple(sigma), tuple(sigma_cho)

    rank_mask = None
    if rank_budget is not None:
        from repro.landmarks.budget import allocate_rank_masks

        rank_mask = allocate_rank_masks(sigma, rank_budget, rank)
        sigma, sigma_cho, sigma_li = _apply_rank_masks(
            rank_mask, sigma, sigma_cho, sigma_li)

    adiag, _ = _stage_gram_dist(plan.leaf_self, kernel, config,
                                want_chol=False)
    u = _stage_cross_dist(plan.leaf_cross, sigma_li[-1], kernel,
                          config).reshape(n_leaves, n0, rank)
    w = tuple(
        _stage_cross_dist(plan.lm_cross[lvl - 1], sigma_li[lvl - 1], kernel,
                          config).reshape(1 << lvl, rank, rank)
        for lvl in range(1, levels))
    if rank_mask is not None:
        u = u * jnp.repeat(rank_mask[-1], 2, axis=0)[:, None, :]
        w = _mask_transfer_ops(w, rank_mask)
    return HCKFactors(plan.x_sorted, plan.tree, plan.landmarks,
                      sigma, sigma_cho, w, u, adiag, rank_mask)


# ---------------------------------------------------------------------------
# Per-node reference construction — the paper's Algorithm 2 as written
# (oracle + benchmark baseline; host loop over every tree node).
# ---------------------------------------------------------------------------

def build_hck_reference(
    x: Array,
    *,
    levels: int,
    rank: int,
    key: Array,
    kernel: BaseKernel,
    method: str = "rp",
    shared_landmarks: bool = False,
) -> HCKFactors:
    """Per-node transcription of Algorithm 2 — the pre-engine build path.

    Walks the whole construction one node at a time: the sequential
    splitter (:func:`repro.core.partition.build_partition_sequential`)
    splits node by node, then each node gets one Gram, one Cholesky, one
    cross-solve — O(4^L) host dispatches instead of one batched stage
    launch per level.  It consumes the SAME key tree as :func:`build_hck`
    (partition subkey first, then one landmark subkey per level, split per
    node) and the sequential splitter produces the identical tree, so with
    a fixed key the two paths must agree to factorization round-off;
    ``bench_build.py`` gates the engine against this at 1e-6 in float64
    and reports the engine's speedup over it.
    """
    from repro.core.partition import build_partition_sequential

    n, d = x.shape
    n_leaves = 1 << levels
    if n % n_leaves != 0:
        raise ValueError(f"n={n} not divisible by 2**levels={n_leaves}")
    n0 = n // n_leaves
    if rank > n0:
        raise ValueError(f"rank {rank} exceeds leaf size {n0} (paper §4.4)")

    kpart, key = jax.random.split(key)
    x_sorted, tree = build_partition_sequential(x, levels, kpart, method=method)

    # landmarks: one permutation draw + gather per node (the counter-based
    # PRNG makes these bit-identical to the engine's vmapped draws)
    landmarks = []
    for lvl in range(levels):
        key, sub = jax.random.split(key)
        bsz, m = 1 << lvl, n >> lvl
        node_keys = jax.random.split(sub, bsz)
        lm = []
        for b in range(bsz):
            idx = jax.random.permutation(node_keys[b], m)[:rank]
            lm.append(x_sorted[b * m:(b + 1) * m][idx])
        landmarks.append(jnp.stack(lm))
    if shared_landmarks and levels > 0:
        root = landmarks[0]
        landmarks = [jnp.broadcast_to(root, (1 << lvl, rank, d)).reshape(1 << lvl, rank, d)
                     for lvl in range(levels)]
    landmarks = tuple(landmarks)

    # Sigma + Cholesky, one node at a time
    sigma, sigma_cho = [], []
    for lm in landmarks:
        s = [kernel.gram(lm[p]) for p in range(lm.shape[0])]
        sigma.append(jnp.stack(s))
        sigma_cho.append(jnp.stack([jnp.linalg.cholesky(sp) for sp in s]))
    sigma, sigma_cho = tuple(sigma), tuple(sigma_cho)

    # leaf blocks, one leaf at a time
    leaves = x_sorted.reshape(n_leaves, n0, d)
    adiag = jnp.stack([kernel.gram(leaves[i]) for i in range(n_leaves)])
    if levels == 0:
        return HCKFactors(x_sorted, tree, (), (), (), (),
                          jnp.zeros((1, n0, 0), x.dtype), adiag)

    def cross_node(pts, lm_p, cho_p):
        kxu = kernel.cross(pts, lm_p)
        return jax.scipy.linalg.cho_solve((cho_p, True), kxu.T).T

    u = jnp.stack([
        cross_node(leaves[i], landmarks[-1][i >> 1], sigma_cho[-1][i >> 1])
        for i in range(n_leaves)])
    w = []
    for lvl in range(1, levels):
        w.append(jnp.stack([
            cross_node(landmarks[lvl][i], landmarks[lvl - 1][i >> 1],
                       sigma_cho[lvl - 1][i >> 1])
            for i in range(1 << lvl)]))
    return HCKFactors(x_sorted, tree, landmarks, sigma, sigma_cho, tuple(w), u, adiag)


# ---------------------------------------------------------------------------
# Streaming construction — host-resident data staged through the engine.
# ---------------------------------------------------------------------------

def build_hck_streaming(
    source,
    *,
    levels: int,
    rank: int,
    key: Array,
    kernel: BaseKernel,
    method: str = "rp",
    shared_landmarks: bool = False,
    config: SolveConfig | None = None,
    leaf_batch: int = 64,
    chunk_rows: int = 1 << 16,
    policy=None,
    rank_budget: int | None = None,
) -> HCKFactors:
    """Build HCK factors from a host-resident :class:`ChunkSource`.

    The raw (n, d) data never becomes device-resident in one piece: the
    partition streams per-node projection chunks
    (:func:`repro.data.pipeline.stream_partition`), landmark rows are
    gathered by index, and the leaf factor stages (``build_gram`` /
    ``build_cross``) consume groups of ``leaf_batch`` leaves at a time.
    Output factors are the usual O(n(n0 + r)) device arrays.

    Uses the same key tree as :func:`build_hck`, and
    ``stream_partition`` reproduces the batched splitter exactly, so a
    source wrapping an in-memory array yields identical factors — the
    streaming-equality test in ``test_build_engine.py`` gates this.

    Parameters
    ----------
    source:     :class:`repro.data.pipeline.ChunkSource` (``n``/``dim``
                properties, ``chunk``/``take`` row access).
    leaf_batch: leaves staged to the device per build_gram/build_cross
                launch (bounds device working memory by
                ``leaf_batch * n0 * (n0 + r + d)`` elements).
    chunk_rows: rows per device transfer inside the streaming partition.
    levels, rank, key, kernel, method, shared_landmarks, config: as in
                :func:`build_hck` (``levels >= 1``: a degenerate 0-level
                build is a single dense block — load it directly).
    """
    from repro.data.pipeline import stream_partition
    from repro.landmarks.policy import UniformPolicy, get_policy

    config = config if config is not None else DEFAULT_CONFIG
    if levels < 1:
        raise ValueError("build_hck_streaming needs levels >= 1 "
                         "(a 0-level build is one dense block)")
    if not isinstance(get_policy(policy), UniformPolicy):
        raise ValueError(
            "build_hck_streaming supports the uniform landmark policy "
            "only: node blocks are never device-resident, so clustered/"
            "leverage selection has nothing to scan — build in memory or "
            "distributed instead")
    if rank_budget is not None:
        raise ValueError(
            "build_hck_streaming does not support rank_budget; use "
            "build_hck or dist_build_hck for budgeted adaptive rank")
    n, d = source.n, source.dim
    n_leaves = 1 << levels
    if n % n_leaves != 0:
        raise ValueError(f"n={n} not divisible by 2**levels={n_leaves}")
    n0 = n // n_leaves
    if rank > n0:
        raise ValueError(f"rank {rank} exceeds leaf size {n0} (paper §4.4)")

    kpart, key = jax.random.split(key)
    perm_np, tree = stream_partition(source, levels, kpart, method=method,
                                     chunk_rows=chunk_rows)

    # landmarks: engine-identical indices, gathered from the host source
    landmarks = []
    for lvl in range(levels):
        key, sub = jax.random.split(key)
        bsz, m = 1 << lvl, n >> lvl
        idx = np.asarray(landmark_indices(sub, bsz, m, rank))
        rows = perm_np[(np.arange(bsz)[:, None] * m + idx).reshape(-1)]
        landmarks.append(jnp.asarray(source.take(rows)).reshape(bsz, rank, d))
    if shared_landmarks:
        landmarks = _broadcast_shared_landmarks(landmarks, rank, d)
    landmarks = tuple(landmarks)

    sigma, sigma_cho, sigma_li = _middle_factors(landmarks, kernel, config)

    # leaf factors: stage leaf_batch leaves through the engine at a time
    # (leaf groups need not align with sibling pairs, so the parent
    # landmark/Linv stacks are repeated per leaf here)
    lm_parent = jnp.repeat(landmarks[-1], 2, axis=0)         # (2**L, r, d)
    linv_parent = jnp.repeat(sigma_li[-1], 2, axis=0)
    adiag_parts, u_parts, x_parts = [], [], []
    for start in range(0, n_leaves, leaf_batch):
        stop = min(start + leaf_batch, n_leaves)
        rows = perm_np[start * n0:stop * n0]
        blk = jnp.asarray(source.take(rows)).reshape(stop - start, n0, d)
        x_parts.append(blk.reshape(-1, d))
        a, ub = leaf_stage_factors(blk, lm_parent[start:stop],
                                   linv_parent[start:stop], kernel, config)
        adiag_parts.append(a)
        u_parts.append(ub)
    adiag = jnp.concatenate(adiag_parts, axis=0)
    u = jnp.concatenate(u_parts, axis=0)
    x_sorted = jnp.concatenate(x_parts, axis=0)

    w = _transfer_ops(landmarks, sigma_li, kernel, config)
    return HCKFactors(x_sorted, tree, landmarks, sigma, sigma_cho, w, u, adiag)


# ---------------------------------------------------------------------------
# Dense reconstruction — oracle for tests/benchmarks only (O(n^2) memory).
# ---------------------------------------------------------------------------

def to_dense(f: HCKFactors) -> Array:
    """Materialize K_hck(X, X) from the factors (test oracle, host loop)."""
    n0, levels = f.leaf_size, f.levels
    n = f.n
    if levels == 0:
        return f.adiag[0]
    a = jnp.zeros((n, n), dtype=f.adiag.dtype)
    # leaf diagonal blocks
    for i in range(f.num_leaves):
        sl = slice(i * n0, (i + 1) * n0)
        a = a.at[sl, sl].set(f.adiag[i])
    # effective bases per level: ubig[l][i] spans node i's whole block
    ubig = [np.empty(0)] * (levels + 1)
    ubig[levels] = [f.u[i] for i in range(f.num_leaves)]
    for lvl in range(levels - 1, 0, -1):
        cur = []
        for p in range(1 << lvl):
            stacked = jnp.concatenate(
                [ubig[lvl + 1][2 * p], ubig[lvl + 1][2 * p + 1]], axis=0)
            cur.append(stacked @ f.w[lvl - 1][p])
        ubig[lvl] = cur
    # off-diagonal sibling blocks at every level
    for lvl in range(levels, 0, -1):
        block = n // (1 << lvl)
        for p in range(1 << (lvl - 1)):
            i, j = 2 * p, 2 * p + 1
            ui, uj = ubig[lvl][i], ubig[lvl][j]
            cross = ui @ f.sigma[lvl - 1][p] @ uj.T
            ri = slice(i * block, (i + 1) * block)
            rj = slice(j * block, (j + 1) * block)
            a = a.at[ri, rj].set(cross)
            a = a.at[rj, ri].set(cross.T)
    return a


def dense_reference_kernel(
    x_sorted: Array, f: HCKFactors, kernel: BaseKernel
) -> Array:
    """Direct evaluation of k_hck via the recursive *definition* (Eq. 13-16).

    Independent of the factor algebra — validates ``to_dense`` and the whole
    construction against the paper's formulas.  O(n^2 r) host loop; tests only.
    """
    n0, levels = f.leaf_size, f.levels
    n = x_sorted.shape[0]
    if levels == 0:
        return kernel.gram(x_sorted)

    def psi_chain(pts: Array, leaf: int, up_to_level: int) -> Array:
        """psi^{(anc)}(pts, Xl_anc) for the ancestor of ``leaf`` at tree level
        ``up_to_level`` (0-based internal level).  Eq. (14) expansion."""
        node = leaf >> 1  # parent at level L-1
        lvl = levels - 1
        phi = kernel.cross(pts, f.landmarks[lvl][node])      # k(x, Xl_p)
        while lvl > up_to_level:
            # move one level up: phi <- phi K(Xl,Xl)^-1 K(Xl, Xl_parent)
            parent = node >> 1
            kup = kernel.cross(f.landmarks[lvl][node], f.landmarks[lvl - 1][parent])
            sol = jax.scipy.linalg.cho_solve((f.sigma_cho[lvl][node], True), kup)
            phi = phi @ sol
            node, lvl = parent, lvl - 1
        return phi, node

    a = jnp.zeros((n, n), dtype=x_sorted.dtype)
    leaves = x_sorted.reshape(f.num_leaves, n0, -1)
    for i in range(f.num_leaves):
        for j in range(i, f.num_leaves):
            ri = slice(i * n0, (i + 1) * n0)
            rj = slice(j * n0, (j + 1) * n0)
            if i == j:
                a = a.at[ri, rj].set(kernel.gram(leaves[i]))
                continue
            # least common ancestor: differs in the top bit_length(i^j) bits,
            # so the LCA sits at internal level  levels - bit_length(i^j).
            lca_level = levels - (i ^ j).bit_length()
            phi_i, node_i = psi_chain(leaves[i], i, lca_level)
            phi_j, node_j = psi_chain(leaves[j], j, lca_level)
            assert node_i == node_j
            mid = jax.scipy.linalg.cho_solve(
                (f.sigma_cho[lca_level][node_i], True), phi_j.T)
            cross = phi_i @ mid
            a = a.at[ri, rj].set(cross)
            a = a.at[rj, ri].set(cross.T)
    return a
