"""Backend registry for the HCK solve engine (DESIGN.md §5).

Every compute *stage* of the Algorithm 1/2 hot path (and of the other
custom-kernel hot spots in this package) is registered here under a
``(stage, backend)`` key.  ``repro.core.hmatrix`` asks the registry for an
implementation instead of hard-coding einsums or threading ad-hoc
``leaf_backend`` strings through every caller:

    impl = get_impl("leaf_matvec", resolve_backend(cfg, "leaf_matvec",
                                                   dtype=b.dtype, n0=n0, r=r))
    y, c = impl(adiag, u, b, interpret=cfg.interpret)

Backends:
  * ``xla``    — dtype-preserving batched einsums; the oracle-grade path
                 (float64 capable) and the CPU default.
  * ``pallas`` — fused Pallas TPU kernels (interpret mode on CPU).  Keeps
                 the leaf working set in VMEM; the deployment path.

``SolveConfig`` is the single, hashable knob object shared by all solver
consumers (krr/gp/kpca/oos/launch); it is a static jit argument.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax.numpy as jnp

BACKENDS = ("xla", "pallas")

#: mixed-precision policies for build + predict (see SolveConfig.precision):
#: policy -> (GEMM data dtype, factor/output dtype).
PRECISIONS = ("bf16", "f32", "f64")

#: stages of the hierarchical solve engine (plus the other kernel packages'
#: hot spots, so one registry covers every custom kernel in the repo).
STAGES = (
    "leaf_matvec",     # y_i = A_ii b_i            ; c_i = U_i^T b_i
    "leaf_solve",      # x_i = A_ii^{-1} b_i (+lr) ; c_i = U_i^T b_i
    "leaf_factor",     # D_i -> chol(D_i), chol(D_i)^{-1}  (Algorithm-2 inv)
    "leaf_update",     # bordered rank-k extension of (chol, chol^{-1})
    "leaf_project",    # c_i = U_i^T b_i           (OOS common-upward)
    "oos_local",       # z_i = w_i^T k(Xleaf_i, x_i)   (Algorithm-3 exact term)
    "oos_walk",        # z_i = c~_i^T k(Xl_i, x_i)     (flattened root path)
    "build_gram",      # G_b = K(P_b, P_b)+jit I (+Cholesky)  (Algorithm 2)
    "build_cross",     # U_b = K(P_b, Z_b) Sigma_b^{-1}       (Algorithm 2)
    "build_gram_dist",  # G_b = κ_σ(D_b)+jit I (+Chol)  (sweep engine, per σ)
    "build_cross_dist",  # U_b = κ_σ(D_b) Sigma_b^{-1}  (sweep engine, per σ)
    "policy_dist",      # D_b = dist(P_b, Z_b)  (landmark-policy inner loops)
    "kernel_matvec",    # z = K(Xc, Y) V  (matvec-free exact-kernel operator)
    "pairwise_kernel",  # K(X, Y) tiles            (kernel_tile)
    "attention",        # flash attention          (flash_attention)
    "ssd_intra_chunk",  # SSD intra-chunk scan     (ssd_chunk)
)

#: prediction-engine stages: per-query point/weight blocks, tiled over the
#: query batch instead of over leaf rows.
OOS_STAGES = ("oos_local", "oos_walk")

#: construction-engine stages: per-node blocks stacked over one tree level
#: (the batched Algorithm-2 build; see repro.kernels.build_stage).  The
#: ``*_dist`` variants consume precomputed bandwidth-independent distance
#: tiles instead of raw points (the sweep engine's per-σ pass).
BUILD_STAGES = ("build_gram", "build_cross",
                "build_gram_dist", "build_cross_dist")

#: landmark-policy stages: per-node batched metric-distance tiles between
#: node point blocks and candidate centers (k-means / leverage-score inner
#: loops; see repro.landmarks and repro.kernels.policy_stage).
POLICY_STAGES = ("policy_dist",)

#: stages whose every array operand is stacked along one leading (leaf,
#: node or query) axis of independent rows: a launch can run on each
#: device over the rows it owns (see :func:`get_impl`).
ROW_STAGES = ("leaf_matvec", "leaf_solve", "leaf_factor", "leaf_update",
              "leaf_project") + OOS_STAGES + BUILD_STAGES + POLICY_STAGES


# ---------------------------------------------------------------------------
# SolveConfig — the one shared knob object
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def accelerator_present() -> bool:
    """True when the default jax backend is a real accelerator (not CPU).

    A backend that fails to initialize raises: a broken accelerator must
    not quietly turn every Pallas stage into CPU interpret mode.
    """
    import jax

    return jax.default_backend() != "cpu"


@dataclasses.dataclass(frozen=True)
class SolveConfig:
    """Hashable solve-engine configuration (static under jit).

    backend         "auto" picks per stage from dtype/shape (float32 +
                    tile-friendly leaves -> pallas, else xla); "xla"/"pallas"
                    force a backend for every stage.  When the autotune tile
                    DB (repro.kernels.autotune) holds a measured winner for
                    the (stage, shape bucket, device, dtype), "auto" uses it
                    instead of the heuristics.
    interpret       run Pallas bodies in interpret mode.  The default None
                    auto-detects at construction: interpret only when no
                    accelerator is attached (CPU containers emulate the
                    kernels; on a real GPU/TPU the bodies compile).  Pass an
                    explicit bool to force either mode — parity tests force
                    True, compiled smoke paths force False.  After
                    construction the field is always a concrete bool, so
                    configs stay hashable/static under jit.
    refine_steps    iterative-refinement rounds in :func:`repro.core.
                    hmatrix.solve` (each is one matvec + one inverse apply).
    leaf_block      override the leaf tile size (None = autotuned when the
                    tile DB has this shape, else whole leaf per program; see
                    :func:`tile_config`).
    min_pallas_leaf leaf sizes must be a multiple of this for "auto" to
                    pick pallas (float32 sublane granularity).
    precision       mixed-precision policy for build + predict.  None keeps
                    today's dtype-preserving behavior (compute in the input
                    dtype).  "bf16": kernel/Gram/cross GEMM *data* is cast
                    to bfloat16 (accumulation stays >= float32 in every
                    backend) and all stored factors / Cholesky / triangular
                    solves run in float32.  "f32": data and factors in
                    float32.  "f64": everything in float64 (requires
                    jax_enable_x64; the oracle policy).  Tree construction
                    (partitioning, landmark draws) always runs in the input
                    dtype *before* any cast, so a mixed-precision build is
                    bitwise the same tree as the f64 oracle and the parity
                    gates measure pure arithmetic error.  Documented bounds
                    vs the f64 oracle (gaussian kernel, jitter 1e-4 smoke
                    problems; gated in benchmarks/bench_build.py /
                    bench_oos.py): Gram-family factors (adiag, sigma,
                    sigma_cho) rel err <= 2e-2 bf16 / <= 1e-4 f32; the
                    Sigma^{-1}-projected bases (u, w) are kappa(Sigma)-
                    amplified and NOT gated element-wise — the meaningful
                    bounds are operator-level: matvec and OOS predictions
                    rel err <= 5e-2 bf16 / <= 1e-4 f32.  INVERSION of
                    bf16-built factors additionally needs ridge >~
                    n0 * eps_bf16 (~1e-1 at n0=32): the leaf Schur
                    complement inherits the O(eps) factor error and goes
                    indefinite under a smaller ridge, NaN-ing the
                    Cholesky.  f32 builds invert at any ridge the f64
                    oracle tolerates.
    checks          runtime health probes (repro.runtime.health): finite/
                    definiteness checks on factor diagonals, CG residual
                    traces and served predictions at stage BOUNDARIES
                    (never inside a jitted body, so compiled programs are
                    identical either way).  True/False force the probes
                    on/off; the default None defers to the
                    ``REPRO_STRICT_FINITE`` env var *at probe time* —
                    flipping the env needs no new SolveConfig (and no
                    retrace, since the probes live outside jit).  Off
                    means the hot path pays literally one predicate per
                    boundary.
    """

    backend: str = "auto"
    interpret: bool | None = None
    refine_steps: int = 2
    leaf_block: int | None = None
    min_pallas_leaf: int = 8
    precision: str | None = None
    checks: bool | None = None

    def __post_init__(self):
        if self.backend not in ("auto",) + BACKENDS:
            raise ValueError(
                f"backend {self.backend!r} not in {('auto',) + BACKENDS}")
        if self.precision is not None and self.precision not in PRECISIONS:
            raise ValueError(
                f"precision {self.precision!r} not in {PRECISIONS} (or None)")
        if self.checks is not None:
            object.__setattr__(self, "checks", bool(self.checks))
        if self.interpret is None:
            object.__setattr__(self, "interpret", not accelerator_present())

    def with_backend(self, backend: str) -> "SolveConfig":
        """Copy of this config with ``backend`` replaced."""
        return dataclasses.replace(self, backend=backend)


def precision_policy(config: "SolveConfig | None"):
    """(GEMM data dtype, factor/output dtype) of ``config.precision``.

    Returns None when no policy is set (dtype-preserving behavior).  The
    GEMM dtype is what kernel-evaluation inputs are cast to before the
    stage dispatch; the factor dtype is what stage outputs (Gram blocks,
    Cholesky factors, bases) are stored and solved in.
    """
    if config is None or config.precision is None:
        return None
    gemm = {"bf16": jnp.bfloat16, "f32": jnp.float32,
            "f64": jnp.float64}[config.precision]
    fac = jnp.float64 if config.precision == "f64" else jnp.float32
    return jnp.dtype(gemm), jnp.dtype(fac)


DEFAULT_CONFIG = SolveConfig()

# VMEM working-set budget per program instance (bytes); half of a 16 MB
# TPU core VMEM, leaving headroom for double buffering.
_VMEM_BUDGET = 8 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """Per-shape tile choice for a leaf-stage Pallas launch."""

    block_n0: int          # rows of the leaf block each program handles
    vmem_bytes: int        # working-set estimate at that tile size

    @property
    def fits(self) -> bool:
        """Whether the working set fits the per-program VMEM budget."""
        return self.vmem_bytes <= _VMEM_BUDGET


def _autotuned_block(stage: str, *, n0: int, r: int, k: int, d: int,
                     itemsize: int) -> int | None:
    """Measured tile for this shape bucket from the autotune DB, or None.

    Any failure (missing DB, corrupt file, import problem) degrades to
    None so the heuristics below stay the cold-cache behavior.
    """
    try:
        from repro.kernels import autotune

        if not autotune.lookups_enabled():
            return None
        return autotune.lookup_block(stage, n0=n0, r=r, k=k, d=d,
                                     itemsize=itemsize)
    except Exception:   # noqa: BLE001 — autotune is strictly best-effort
        return None


def _measured_backend(stage: str, *, dtype, n0: int, r: int, k: int,
                      d: int) -> str | None:
    """Measured backend winner from the autotune DB, or None."""
    try:
        from repro.kernels import autotune

        if not autotune.lookups_enabled():
            return None
        return autotune.lookup_backend(stage, dtype=dtype, n0=n0, r=r,
                                       k=k, d=d)
    except Exception:   # noqa: BLE001 — autotune is strictly best-effort
        return None


def tile_config(stage: str, *, n0: int, r: int, k: int, d: int = 0,
                itemsize: int = 4, leaf_block: int | None = None) -> TileConfig:
    """Pick the leaf tile for ``stage`` at shape (n0, r, k[, d]).

    Leaf stages: the working set is A-tile (block_n0 * n0) + U tile
    (block_n0 * r) + b (n0 * k) + outputs; shrink block_n0 by powers of two
    until it fits the VMEM budget.  ``leaf_block`` (from SolveConfig)
    overrides.  The returned block always divides n0 (snapped down to the
    nearest divisor), so the kernel launch never silently falls back to
    whole-leaf tiles.

    OOS stages (``oos_local`` / ``oos_walk``): ``block_n0`` is the *query*
    block of the fused contraction — every query carries its own (n0, d)
    point block and (n0, k) weight block (n0 here is the contraction size:
    the leaf size for oos_local, the rank for oos_walk).  The query batch
    is padded to a block multiple by the ops wrapper, so no divisor snap.

    Build stages: ``build_gram`` keeps a whole node per program (the (n0,
    n0) Gram tile is factorized in place, so it cannot row-tile; the
    returned config reports whether that working set fits).  ``build_cross``
    row-tiles the node block like the leaf stages: pts (bn, d) + parent
    landmarks (r, d) + parent inverse Cholesky factor (r, r) + out (bn, r).
    The distance-cached sweep variants follow the same split with the point
    blocks replaced by distance tiles: ``build_gram_dist`` holds dist +
    gram + Cholesky (3 n0^2), ``build_cross_dist`` holds dist (bn, r) +
    Linv (r, r) + out (bn, r).  ``policy_dist`` (the landmark-policy inner
    loop) row-tiles like ``build_cross`` minus the Linv factor: pts (bn,
    d) + centers (r, d) + dist out (bn, r).  ``leaf_factor`` factorizes the whole (n0,
    n0) leaf Schur tile in place (dist-in, chol + inverse out: 3 n0^2).
    ``leaf_update`` (the bordered rank-k extension) also processes whole
    leaves; here ``k`` is the number of appended rows, so the working set
    is 2 n0^2 + k n0 + k^2 in plus two (n0+k)^2 extended factors out.

    When no explicit ``leaf_block`` is given and the autotune tile DB
    (:mod:`repro.kernels.autotune`) holds a measured winner for this
    (stage, shape bucket, device, dtype), that tile is used as the
    override — still snapped to a divisor and VMEM-checked — so the
    heuristics below are only the cold-cache fallback.
    """
    if leaf_block is None:
        leaf_block = _autotuned_block(stage, n0=n0, r=r, k=k, d=d,
                                      itemsize=itemsize)

    if stage in ("build_gram", "build_gram_dist", "leaf_factor",
                 "leaf_update"):
        if stage == "build_gram":
            usage_g = (n0 * d + 2 * n0 * n0) * itemsize
        elif stage == "leaf_update":
            # old factors (2 n0^2) + cross/appended blocks (k n0 + k^2)
            # + two extended (n0+k, n0+k) outputs, whole-leaf per program
            usage_g = (2 * n0 * n0 + k * n0 + k * k
                       + 2 * (n0 + k) * (n0 + k)) * itemsize
        else:   # dist tile (or SPD tile) in, two (n0, n0) factors out
            usage_g = 3 * n0 * n0 * itemsize
        return TileConfig(n0, usage_g)

    if stage in ("build_cross", "build_cross_dist", "policy_dist"):
        def usage(bn: int) -> int:
            if stage == "build_cross_dist":
                return (2 * bn * r + r * r) * itemsize
            if stage == "policy_dist":
                # pts row tile (bn, d) + centers (r, d) + dist out (bn, r)
                return (bn * (d + r) + r * d) * itemsize
            return (bn * (d + r) + r * d + r * r) * itemsize

        def snap(bn: int) -> int:
            bn = max(1, min(bn, n0))
            while n0 % bn != 0:
                bn -= 1
            return bn

        bn = snap(leaf_block) if leaf_block is not None else n0
        while bn > 8 and usage(bn) > _VMEM_BUDGET:
            bn = snap(bn // 2)
        return TileConfig(bn, usage(bn))

    if stage == "kernel_matvec":
        # per (bn, bm=128) program: x (bn, d) + y (bm, d) + v (bm, k) +
        # kernel tile (bn, bm) + out (bn, k)
        bm = 128

        def usage(bn: int) -> int:
            return (bn * (d + bm + k) + bm * (d + k)) * itemsize

        bn = leaf_block if leaf_block is not None else 128
        bn = max(8, bn)
        while bn > 8 and usage(bn) > _VMEM_BUDGET:
            bn = max(8, bn // 2)    # floor at f32 sublane granularity
        return TileConfig(bn, usage(bn))

    if stage in OOS_STAGES:
        def usage(bq: int) -> int:
            # VMEM tiles as laid out (sublanes padded to 8, lanes to 128):
            # points (n0, d) + transposed weights (k, n0) + query row
            # (1, d) + output row (1, k)
            def tile(rows: int, cols: int) -> int:
                return -(-rows // 8) * 8 * (-(-cols // 128) * 128)

            per_query = (tile(n0, d) + tile(k, n0) + tile(1, d)
                         + tile(1, k))
            return bq * per_query * itemsize

        bq = leaf_block if leaf_block is not None else 128
        bq = max(8, bq)
        while bq > 8 and usage(bq) > _VMEM_BUDGET:
            bq = max(8, bq // 2)    # floor at f32 sublane granularity
        return TileConfig(bq, usage(bq))

    def usage(bn: int) -> int:
        a_tile = bn * n0                       # A_ii or Linv row-block
        u_tile = bn * r
        io = n0 * k + bn * k + r * k
        extra = r * r if stage == "leaf_solve" else 0
        return (a_tile + u_tile + io + extra) * itemsize

    def snap(bn: int) -> int:
        bn = max(1, min(bn, n0))
        while n0 % bn != 0:
            bn -= 1
        return bn

    if leaf_block is not None:
        bn = snap(leaf_block)
        return TileConfig(bn, usage(bn))
    bn = n0
    while bn > 8 and usage(bn) > _VMEM_BUDGET:
        bn = snap(bn // 2)
    return TileConfig(bn, usage(bn))


# ---------------------------------------------------------------------------
# Registry proper
# ---------------------------------------------------------------------------

_REGISTRY: dict[tuple[str, str], Callable] = {}


def register(stage: str, backend: str):
    """Decorator: register ``fn`` as the ``backend`` implementation of
    ``stage``.  Later registrations override earlier ones (tests use this
    to inject counting shims)."""
    if stage not in STAGES:
        raise ValueError(f"unknown stage {stage!r}; stages: {STAGES}")
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; backends: {BACKENDS}")

    def deco(fn: Callable) -> Callable:
        _REGISTRY[(stage, backend)] = fn
        return fn

    return deco


def get_impl(stage: str, backend: str) -> Callable:
    """Implementation registered for (stage, backend); KeyError if none.

    Pallas launches of the :data:`ROW_STAGES` go through
    :func:`_launch_on_mesh`, so they also run where GSPMD partitions the
    surrounding program over a context mesh (``jax.set_mesh``).
    """
    try:
        impl = _REGISTRY[(stage, backend)]
    except KeyError:
        have = sorted(k for k in _REGISTRY if k[0] == stage)
        raise KeyError(
            f"no implementation registered for stage={stage!r} "
            f"backend={backend!r}; registered: {have}") from None
    if backend == "pallas" and stage in ROW_STAGES:
        return functools.partial(_launch_on_mesh, impl)
    return impl


def _launch_on_mesh(impl: Callable, *args, **kwargs):
    """Run one row-stage launch per device of the context mesh.

    GSPMD cannot partition a Mosaic kernel: on a TPU mesh a Pallas call
    must sit in a ``shard_map`` body.  Under a context mesh with automatic
    axes the launch is wrapped in one, its rows split over every mesh axis
    when the row count divides the mesh (otherwise every device runs all
    rows, as for the replicated top of the tree).  With no context mesh,
    or inside a ``shard_map`` body already, the launch runs as it is.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.manual_axes:
        return impl(*args, **kwargs)
    spec = P(mesh.axis_names) if args[0].shape[0] % mesh.size == 0 else P()
    # check_vma=False: Pallas declares its outputs without the
    # varying-mesh-axes type that check_vma requires
    return jax.shard_map(functools.partial(impl, **kwargs),
                         in_specs=(spec,) * len(args), out_specs=spec,
                         check_vma=False)(*args)


def registered(stage: str | None = None) -> list[tuple[str, str]]:
    """Sorted (stage, backend) keys, optionally filtered to one stage."""
    keys = sorted(_REGISTRY)
    return [k for k in keys if stage is None or k[0] == stage]


def resolve_backend(config: SolveConfig | None, stage: str, *,
                    dtype, n0: int, r: int, k: int = 1, d: int = 0) -> str:
    """Map ``config.backend`` ("auto" included) to a concrete backend for
    one stage at one shape.

    When the autotune tile DB holds a measured winner for this (stage,
    shape bucket, device, dtype), "auto" returns it (a measured "pallas"
    still requires compiled execution and sublane-granular leaves — the
    hard correctness constraints are never overridden by timings).  On a
    cold cache the heuristics below apply:

    "auto" picks pallas only where the fused kernels win and stay exact
    enough: compiled execution (``interpret=False`` — interpret mode is CPU
    emulation, an order of magnitude slower than the XLA einsums, so it is
    never chosen automatically), float32 data (the MXU path; float64
    oracles stay on xla unless forced), tile-friendly leaves, a real
    hierarchy (r > 0), and — for the stages that cannot row-tile
    (leaf_solve chains two n0 x n0 products over the whole leaf) — a
    working set inside the VMEM budget.

    The OOS prediction stages (``oos_local`` / ``oos_walk``) follow the
    same rules with ``n0`` meaning the per-query contraction size (the
    leaf size for oos_local, the rank for oos_walk): the fused kernel
    row-tiles over the query batch, so any contraction size that meets the
    sublane granularity qualifies.

    The construction stages (``build_gram`` / ``build_cross`` and their
    distance-cached ``*_dist`` sweep variants) follow the leaf-stage rules
    with ``n0`` meaning the per-node block row count (the node/landmark
    block size); ``build_gram``/``build_gram_dist`` factorize the whole
    (n0, n0) Gram tile per program and ``leaf_factor`` the whole leaf
    Schur tile, so — like ``leaf_solve`` — they additionally require the
    whole-node working set to fit the VMEM budget.

    The matvec-free exact-kernel stage (``kernel_matvec``) tiles both the
    row chunk and the contraction dim, so — like ``leaf_matvec`` — any
    shape that meets the sublane granularity qualifies (``n0`` is the row
    chunk handed over by :class:`repro.solvers.operators.ExactKernelOp`).
    """
    config = config or DEFAULT_CONFIG
    if config.backend != "auto":
        return config.backend
    if config.interpret:
        return "xla"
    if r <= 0:
        return "xla"
    measured = _measured_backend(stage, dtype=dtype, n0=n0, r=r, k=k, d=d)
    if measured == "xla":
        return "xla"
    if measured == "pallas" and n0 % config.min_pallas_leaf == 0:
        return "pallas"
    if jnp.dtype(dtype) != jnp.float32:
        return "xla"
    if n0 % config.min_pallas_leaf != 0:
        return "xla"
    if stage in ("leaf_solve", "build_gram", "build_gram_dist",
                 "leaf_factor", "leaf_update"):
        whole = tile_config(stage, n0=n0, r=r, k=k, d=d,
                            itemsize=jnp.dtype(dtype).itemsize,
                            leaf_block=n0)
        if not whole.fits:
            return "xla"
    return "pallas"


# ---------------------------------------------------------------------------
# XLA implementations of the solve-engine leaf stages: the single source of
# the leaf math is repro.kernels.hck_leaf.ref (the same oracles the kernel
# tests compare against); outputs are restored to the rhs dtype so sub-f32
# inputs keep their API dtype while accumulating in at least f32.
# ---------------------------------------------------------------------------

@register("leaf_matvec", "xla")
def _leaf_matvec_xla(adiag, u, b, *, interpret: bool = True):
    """(P,n0,n0),(P,n0,r),(P,n0,k) -> y (P,n0,k), c (P,r,k)."""
    del interpret
    from repro.kernels.hck_leaf.ref import hck_leaf_matvec_ref

    y, c = hck_leaf_matvec_ref(adiag, u, b)
    return y.astype(b.dtype), c.astype(b.dtype)


@register("leaf_solve", "xla")
def _leaf_solve_xla(linv, u, sig, b, *, interpret: bool = True):
    """Fused leaf stage of the structured-inverse apply (oracle form).

    x_i = Linv_i^T (Linv_i b_i) + U_i (Sig_i (U_i^T b_i)),  c_i = U_i^T b_i
    with Linv the inverse Cholesky factor of the leaf Schur complement and
    Sig the parent-level corrected middle factor (self term of A~_ii).

    Note: ``hmatrix.apply_inverse`` does NOT call this on its xla path — it
    multiplies the explicit inverse diagonal blocks via leaf_matvec instead
    (one GEMM per leaf vs the two triangular GEMMs here); this entry is the
    parity oracle for the fused pallas kernel.
    """
    del interpret
    from repro.kernels.hck_leaf.ref import hck_leaf_solve_ref

    x, c = hck_leaf_solve_ref(linv, u, sig, b)
    return x.astype(b.dtype), c.astype(b.dtype)


@register("leaf_project", "xla")
def _leaf_project_xla(u, b, *, interpret: bool = True):
    """(P,n0,r),(P,n0,k) -> c (P,r,k)."""
    del interpret
    from repro.kernels.hck_leaf.ref import hck_leaf_project_ref

    return hck_leaf_project_ref(u, b).astype(b.dtype)


@register("leaf_factor", "xla")
def _leaf_factor_xla(dleaf, *, interpret: bool = True):
    """(P,n0,n0) SPD -> (chol, chol^{-1}), both (P,n0,n0) lower.

    The leaf Schur-complement factorization of Algorithm 2 (inversion),
    batched over leaves — and, via ``hmatrix.invert_multi``, over a whole
    (ridge-grid x leaves) stack in one call.
    """
    del interpret
    from repro.kernels.hck_leaf.ref import hck_leaf_factor_ref

    lo, linv = hck_leaf_factor_ref(dleaf)
    return lo.astype(dleaf.dtype), linv.astype(dleaf.dtype)


@register("leaf_update", "xla")
def _leaf_update_xla(lo, linv, b, c, *, interpret: bool = True):
    """Bordered rank-k extension of batched leaf Cholesky factors.

    (P,n0,n0) lo/linv, (P,k,n0) cross block, (P,k,k) appended block ->
    (lo_ext, linv_ext), both (P,n0+k,n0+k); the leading (n0,n0)
    quadrants are the inputs unchanged (exact-truncation downdate).
    """
    del interpret
    from repro.kernels.update_stage.ref import leaf_update_ref

    lo_ext, linv_ext = leaf_update_ref(lo, linv, b, c)
    return lo_ext.astype(lo.dtype), linv_ext.astype(lo.dtype)


# ---------------------------------------------------------------------------
# Pallas implementations — lazy imports so plain-XLA users never pay the
# pallas import, and so this module has no import cycle with the kernel
# packages.
# ---------------------------------------------------------------------------

@register("leaf_matvec", "pallas")
def _leaf_matvec_pallas(adiag, u, b, *, interpret: bool = True,
                        block_n0: int | None = None):
    from repro.kernels.hck_leaf.ops import leaf_matvec

    return leaf_matvec(adiag, u, b, interpret=interpret, block_n0=block_n0)


@register("leaf_solve", "pallas")
def _leaf_solve_pallas(linv, u, sig, b, *, interpret: bool = True):
    from repro.kernels.hck_leaf.ops import leaf_solve

    return leaf_solve(linv, u, sig, b, interpret=interpret)


@register("leaf_project", "pallas")
def _leaf_project_pallas(u, b, *, interpret: bool = True):
    from repro.kernels.hck_leaf.ops import leaf_project

    return leaf_project(u, b, interpret=interpret)


@register("leaf_factor", "pallas")
def _leaf_factor_pallas(dleaf, *, interpret: bool = True):
    from repro.kernels.hck_leaf.ops import leaf_factor

    return leaf_factor(dleaf, interpret=interpret)


@register("leaf_update", "pallas")
def _leaf_update_pallas(lo, linv, b, c, *, interpret: bool = True):
    from repro.kernels.update_stage.ops import leaf_update

    return leaf_update(lo, linv, b, c, interpret=interpret)


@register("oos_local", "xla")
def _oos_local_xla(points, weights, queries, *, name="gaussian", sigma=1.0,
                   interpret: bool = True):
    """(q,n0,d),(q,n0,k),(q,d) -> z (q,k) = w_i^T k(Xleaf_i, x_i)."""
    del interpret
    from repro.kernels.oos_stage.ref import oos_contract_ref

    return oos_contract_ref(points, weights, queries, name=name,
                            sigma=sigma).astype(weights.dtype)


@register("oos_walk", "xla")
def _oos_walk_xla(points, weights, queries, *, name="gaussian", sigma=1.0,
                  interpret: bool = True):
    """(q,r,d),(q,r,k),(q,d) -> z (q,k) = c~_i^T k(Xl_i, x_i).

    The weights are the plan's pushed-down root-path coefficients, so this
    single contraction replaces the per-level walk-up loop of Algorithm 3.
    """
    del interpret
    from repro.kernels.oos_stage.ref import oos_contract_ref

    return oos_contract_ref(points, weights, queries, name=name,
                            sigma=sigma).astype(weights.dtype)


@register("oos_local", "pallas")
def _oos_local_pallas(points, weights, queries, *, name="gaussian",
                      sigma=1.0, interpret: bool = True,
                      block_q: int | None = None):
    from repro.kernels.oos_stage.ops import oos_contract

    return oos_contract(points, weights, queries, name=name, sigma=sigma,
                        interpret=interpret, block_q=block_q)


@register("oos_walk", "pallas")
def _oos_walk_pallas(points, weights, queries, *, name="gaussian",
                     sigma=1.0, interpret: bool = True,
                     block_q: int | None = None):
    from repro.kernels.oos_stage.ops import oos_contract

    return oos_contract(points, weights, queries, name=name, sigma=sigma,
                        interpret=interpret, block_q=block_q)


@register("build_gram", "xla")
def _build_gram_xla(points, *, name="gaussian", sigma=1.0, jitter=0.0,
                    want_chol=True, interpret: bool = True):
    """(B,m,d) -> gram (B,m,m) + jitter*m I [, lower Cholesky or None]."""
    del interpret
    from repro.kernels.build_stage.ref import build_gram_ref

    gram, chol = build_gram_ref(points, name=name, sigma=sigma,
                                jitter=jitter, want_chol=want_chol)
    return gram.astype(points.dtype), (
        None if chol is None else chol.astype(points.dtype))


@register("build_cross", "xla")
def _build_cross_xla(points, landmarks, linv, *, name="gaussian",
                     sigma=1.0, interpret: bool = True):
    """(B,m,d),(B,r,d),(B,r,r) -> U (B,m,r) = K(P,Z) Linv^T Linv."""
    del interpret
    from repro.kernels.build_stage.ref import build_cross_ref

    return build_cross_ref(points, landmarks, linv, name=name,
                           sigma=sigma).astype(points.dtype)


@register("build_gram_dist", "xla")
def _build_gram_dist_xla(dist, *, name="gaussian", sigma=1.0, jitter=0.0,
                         want_chol=True, interpret: bool = True):
    """(B,m,m) cached distances -> gram κ_σ(D)+jit I [, Cholesky or None]."""
    del interpret
    from repro.kernels.build_stage.ref import build_gram_dist_ref

    gram, chol = build_gram_dist_ref(dist, name=name, sigma=sigma,
                                     jitter=jitter, want_chol=want_chol)
    return gram.astype(dist.dtype), (
        None if chol is None else chol.astype(dist.dtype))


@register("build_cross_dist", "xla")
def _build_cross_dist_xla(dist, linv, *, name="gaussian", sigma=1.0,
                          interpret: bool = True):
    """(B,m,r) cached distances, (B,r,r) -> U = κ_σ(D) Linv^T Linv."""
    del interpret
    from repro.kernels.build_stage.ref import build_cross_dist_ref

    return build_cross_dist_ref(dist, linv, name=name,
                                sigma=sigma).astype(dist.dtype)


@register("build_gram", "pallas")
def _build_gram_pallas(points, *, name="gaussian", sigma=1.0, jitter=0.0,
                       want_chol=True, interpret: bool = True):
    from repro.kernels.build_stage.ops import build_gram

    return build_gram(points, name=name, sigma=sigma, jitter=jitter,
                      want_chol=want_chol, interpret=interpret)


@register("build_gram_dist", "pallas")
def _build_gram_dist_pallas(dist, *, name="gaussian", sigma=1.0, jitter=0.0,
                            want_chol=True, interpret: bool = True):
    from repro.kernels.build_stage.ops import build_gram_dist

    return build_gram_dist(dist, name=name, sigma=sigma, jitter=jitter,
                           want_chol=want_chol, interpret=interpret)


@register("build_cross_dist", "pallas")
def _build_cross_dist_pallas(dist, linv, *, name="gaussian", sigma=1.0,
                             interpret: bool = True,
                             block_m: int | None = None):
    from repro.kernels.build_stage.ops import build_cross_dist

    return build_cross_dist(dist, linv, name=name, sigma=sigma,
                            interpret=interpret, block_m=block_m)


@register("build_cross", "pallas")
def _build_cross_pallas(points, landmarks, linv, *, name="gaussian",
                        sigma=1.0, interpret: bool = True,
                        block_m: int | None = None):
    from repro.kernels.build_stage.ops import build_cross

    return build_cross(points, landmarks, linv, name=name, sigma=sigma,
                       interpret=interpret, block_m=block_m)


@register("policy_dist", "xla")
def _policy_dist_xla(blocks, centers, *, metric="l2",
                     interpret: bool = True):
    """(B,m,d),(B,r,d) -> dist (B,m,r) ("l2" squared Euclidean / "l1")."""
    del interpret
    from repro.kernels.policy_stage.ref import policy_dist_ref

    return policy_dist_ref(blocks, centers, metric=metric)


@register("policy_dist", "pallas")
def _policy_dist_pallas(blocks, centers, *, metric="l2",
                        interpret: bool = True,
                        block_m: int | None = None):
    from repro.kernels.policy_stage.ops import policy_dist

    return policy_dist(blocks, centers, metric=metric, interpret=interpret,
                       block_m=block_m)


@register("kernel_matvec", "xla")
def _kernel_matvec_xla(xc, y, v, *, name="gaussian", sigma=1.0,
                       interpret: bool = True):
    """(b,d),(m,d),(m,k) -> z (b,k) = K(Xc, Y) V (dtype-preserving)."""
    del interpret
    from repro.kernels.matvec_stage.ref import kernel_matvec_ref

    return kernel_matvec_ref(xc, y, v, name=name,
                             sigma=sigma).astype(v.dtype)


@register("kernel_matvec", "pallas")
def _kernel_matvec_pallas(xc, y, v, *, name="gaussian", sigma=1.0,
                          interpret: bool = True,
                          block_n: int | None = None):
    from repro.kernels.matvec_stage.ops import kernel_matvec

    return kernel_matvec(xc, y, v, name=name, sigma=sigma,
                         interpret=interpret, block_n=block_n)


@register("pairwise_kernel", "xla")
def _pairwise_xla(x, y, *, name="gaussian", sigma=1.0, interpret: bool = True):
    del interpret
    from repro.kernels.kernel_tile.ref import pairwise_kernel_ref

    return pairwise_kernel_ref(x, y, name=name, sigma=sigma)


@register("pairwise_kernel", "pallas")
def _pairwise_pallas(x, y, *, name="gaussian", sigma=1.0,
                     interpret: bool = True):
    from repro.kernels.kernel_tile.ops import pairwise_kernel

    return pairwise_kernel(x, y, name=name, sigma=sigma, interpret=interpret)


@register("attention", "xla")
def _attention_xla(q, k, v, *, causal=True, interpret: bool = True):
    del interpret
    from repro.kernels.flash_attention.ref import attention_ref

    return attention_ref(q, k, v, causal=causal)


@register("attention", "pallas")
def _attention_pallas(q, k, v, *, causal=True, interpret: bool = True):
    from repro.kernels.flash_attention.flash_attention import flash_attention

    return flash_attention(q, k, v, causal=causal, interpret=interpret)


@register("ssd_intra_chunk", "xla")
def _ssd_xla(c, b, xdt, cs, *, interpret: bool = True):
    del interpret
    from repro.kernels.ssd_chunk.ref import ssd_intra_chunk_ref

    return ssd_intra_chunk_ref(c, b, xdt, cs)


@register("ssd_intra_chunk", "pallas")
def _ssd_pallas(c, b, xdt, cs, *, interpret: bool = True):
    from repro.kernels.ssd_chunk.ssd_chunk import ssd_intra_chunk

    return ssd_intra_chunk(c, b, xdt, cs, interpret=interpret)
