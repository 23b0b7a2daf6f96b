"""Shape-bucketed Algorithm-3 prediction service (DESIGN.md §7).

``repro.core.oos.apply_plan`` is jit-compiled per query-batch shape; a
serving frontend that forwards raw request batches recompiles on every new
batch size.  :class:`PredictEngine` kills those recompiles by padding every
batch up to a power-of-two *shape bucket*, so at most ``log2(max_bucket /
min_bucket) + 1`` programs are ever compiled per feature dim, and exposes:

  * ``apply(queries)`` / ``__call__`` — synchronous prediction; batches
    larger than ``max_bucket`` are transparently micro-batched.
  * ``warmup(d)`` — precompile every bucket ahead of traffic.
  * ``stats`` — calls, queries served, pad waste, per-bucket hit counts.

The engine is the single prediction frontend: ``HCKRegressor.predict``,
the GP posterior mean, the KPCA out-of-sample transform and
``launch/serve.py --task krr`` all route through it.

:class:`ModelRegistry` stacks a versioned hot-swap layer on top: each
published model gets an immutable (model, engine, version) entry, serving
reads ONE atomic snapshot reference per request, and ``publish`` /
``rollback`` re-point that reference — so an online update
(``krr.fit_incremental``) can be built, warmed and swapped in under a
live request stream with zero downtime, and a bad version can be rolled
back to the bitwise-identical previous entry.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import oos
from repro.core.hck import HCKFactors
from repro.core.kernels_fn import BaseKernel
from repro.kernels.registry import SolveConfig
from repro.runtime import health
from repro.runtime.trace import span

Array = jax.Array


def bucket_size(q: int, min_bucket: int, max_bucket: int) -> int:
    """Smallest power-of-two bucket >= q (floored at min_bucket, capped at
    max_bucket; q above the cap is the caller's micro-batching problem)."""
    if q < 1:
        raise ValueError(f"bucket_size needs q >= 1, got {q}")
    b = min_bucket
    while b < q:
        b <<= 1
    return min(b, max_bucket)


def validate_queries(queries: Array, x_sorted: Array) -> None:
    """Reject malformed query batches BEFORE any stage launch.

    A bad batch that reaches ``oos.apply_plan`` fails deep inside a
    jitted stage with a shape-mismatch traceback naming nothing the
    caller typed; this front-door check names the actual contract:
    (q, d) with the training feature dim and the training float dtype.
    """
    if getattr(queries, "ndim", None) != 2:
        raise ValueError(
            f"queries must be a 2-D (q, d) batch, got shape "
            f"{getattr(queries, 'shape', None)}")
    d = x_sorted.shape[1]
    if queries.shape[1] == 0:
        raise ValueError(
            f"queries have 0 features; the model was trained with d={d}")
    if queries.shape[1] != d:
        raise ValueError(
            f"query feature dim {queries.shape[1]} != training dim {d}")
    if queries.dtype != x_sorted.dtype:
        raise ValueError(
            f"query dtype {queries.dtype} != training dtype "
            f"{x_sorted.dtype}; cast the batch (implicit promotion would "
            f"silently retrace every bucket)")


@dataclasses.dataclass
class PredictEngine:
    """Precompiled, bucketed Algorithm-3 inference over one fitted plan.

    ``apply`` maps (q, d) query batches (d = the training feature dim, any
    float dtype matching the factors) to (q, k) outputs, padding q up to a
    power-of-two bucket in [min_bucket, max_bucket] and micro-batching
    beyond it.  ``config`` is the shared
    :class:`~repro.kernels.registry.SolveConfig`: ``backend``/``interpret``
    select the ``oos_local``/``oos_walk`` stage implementations and
    ``leaf_block`` overrides their query-block tile.
    """

    factors: HCKFactors
    plan: oos.OOSPlan
    kernel: BaseKernel
    config: SolveConfig | None = None
    min_bucket: int = 64
    max_bucket: int = 4096

    def __post_init__(self):
        if self.min_bucket < 1 or self.max_bucket < self.min_bucket:
            raise ValueError(
                f"bad bucket range [{self.min_bucket}, {self.max_bucket}]")
        self._bucket_hits: dict[int, int] = {}
        self._calls = 0
        self._queries = 0
        self._padded = 0

    # -- construction -----------------------------------------------------
    @classmethod
    def from_weights(
        cls, factors: HCKFactors, w: Array, kernel: BaseKernel, *,
        config: SolveConfig | None = None, **kwargs,
    ) -> "PredictEngine":
        """Build the phase-1 plan for ``w`` (tree order) and wrap it."""
        plan = oos.prepare(factors, w if w.ndim > 1 else w[:, None], config)
        return cls(factors, plan, kernel, config=config, **kwargs)

    @classmethod
    def attach(cls, model, *, weights: Array | None = None,
               **kwargs) -> "PredictEngine":
        """Build-or-return the engine cached on ``model._engine`` — the one
        lazy ``.engine`` implementation shared by HCKRegressor,
        HCKGaussianProcess and KPCAModel (factors/kernel/solve_config are
        read off the model; pass ``weights`` to go through from_weights
        instead of the model's existing plan)."""
        if model._engine is None:
            if weights is None:
                model._engine = cls(model.factors, model.plan, model.kernel,
                                    config=model.solve_config, **kwargs)
            else:
                model._engine = cls.from_weights(
                    model.factors, weights, model.kernel,
                    config=model.solve_config, **kwargs)
        return model._engine

    # -- serving ----------------------------------------------------------
    def apply(self, queries: Array) -> Array:
        """(q, d) -> (q, k).  Pads to the shape bucket (edge-replicated
        rows route like real queries and are sliced off), micro-batching
        anything beyond ``max_bucket``; empty batches short-circuit to an
        empty result (a serving frontend may forward them).  Malformed
        batches (wrong rank/feature dim/dtype) raise ``ValueError`` here,
        not deep inside a stage launch; with health checks on
        (``SolveConfig.checks`` / ``REPRO_STRICT_FINITE``) non-finite
        predictions raise a structured ``NumericalFailure``."""
        with span("engine"):
            validate_queries(queries, self.factors.x_sorted)
            q = queries.shape[0]
            if q == 0:
                k = self.plan.w_leaf.shape[-1]
                return jnp.zeros((0, k), self.plan.w_leaf.dtype)
            if q > self.max_bucket:
                return jnp.concatenate(
                    [self.apply(queries[i:i + self.max_bucket])
                     for i in range(0, q, self.max_bucket)], axis=0)
            b = bucket_size(q, self.min_bucket, self.max_bucket)
            padded = jnp.pad(queries, ((0, b - q), (0, 0)), mode="edge")
            z = oos.apply_plan(self.factors, self.plan, padded, self.kernel,
                               self.config)
            z = z[:q]
            health.probe_predictions(z, self.config)
        self._calls += 1
        self._queries += q
        self._padded += b - q
        self._bucket_hits[b] = self._bucket_hits.get(b, 0) + 1
        return z

    __call__ = apply

    def on_mesh(self, mesh, *, axis: str = "dev",
                **kwargs) -> "MeshPredictEngine":
        """Distributed twin of this engine: same factors/plan/kernel,
        queries routed to the owning device
        (:class:`MeshPredictEngine`)."""
        return MeshPredictEngine(self.factors, self.plan, self.kernel,
                                 mesh, config=self.config, axis=axis,
                                 **kwargs)

    def warmup(self) -> list[int]:
        """Compile every bucket up front (queries must match the training
        feature dim, so there is nothing else to warm); returns the bucket
        sizes touched."""
        d = self.factors.x_sorted.shape[1]
        buckets, b = [], self.min_bucket
        while b <= self.max_bucket:
            buckets.append(b)
            b <<= 1
        dummy = jnp.zeros((1, d), self.factors.x_sorted.dtype)
        for b in buckets:
            jax.block_until_ready(self.apply(jnp.broadcast_to(dummy, (b, d))))
        return buckets

    @property
    def stats(self) -> dict:
        """Serving counters (calls, queries, pad waste, bucket hits)."""
        return {
            "calls": self._calls,
            "queries": self._queries,
            "padded_queries": self._padded,
            "bucket_hits": dict(sorted(self._bucket_hits.items())),
        }


@dataclasses.dataclass
class MeshPredictEngine:
    """Device-routed Algorithm-3 inference on a subtree-sharded hierarchy.

    Under the distributed layout (``repro.launch.dist_hck``) device p
    owns the contiguous leaf range whose root-path prefix is p, so a
    query's prediction is computable entirely on the device owning its
    leaf — the OOS plan's pushed-down ``c_tilde`` already folded the
    whole root path into per-leaf coefficients.  ``apply`` therefore:

      1. routes the batch on the host (the tree record is replicated)
         and maps leaves to owners (top log2(P) path bits,
         :func:`repro.core.partition.owner_device`);
      2. stable-sorts queries by owner, pads each device's segment to a
         shared power-of-two bucket, and ships ONE (P, bucket, d) stack
         plus (P, bucket) device-local leaf indices, row-sharded;
      3. runs one ``shard_map`` body per bucket size — each device
         gathers leaf blocks / weights / parent landmarks / ``c_tilde``
         from the shards it owns and calls
         :func:`repro.core.oos.apply_segments`, the same launches as the
         single-host engine;
      4. gathers the (P, bucket, k) result and unsorts on the host.

    Factors and plan are committed via ``shard_by_subtree`` at
    construction; values match :class:`PredictEngine` at round-off (the
    distributed bench/tests pin 1e-6 in f64 end to end).
    """

    factors: HCKFactors
    plan: oos.OOSPlan
    kernel: BaseKernel
    mesh: object
    config: SolveConfig | None = None
    axis: str = "dev"
    min_bucket: int = 64
    max_bucket: int = 4096

    def __post_init__(self):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.launch.dist_hck import device_level, shard_by_subtree

        if self.min_bucket < 1 or self.max_bucket < self.min_bucket:
            raise ValueError(
                f"bad bucket range [{self.min_bucket}, {self.max_bucket}]")
        p = self.mesh.size
        t = device_level(p)
        levels = self.factors.levels
        if levels < max(t, 1):
            raise ValueError(
                f"levels={levels} too shallow for {p} devices: need >= "
                f"log2(P)={t} so each device owns at least one leaf")
        self.factors = shard_by_subtree(self.factors, self.mesh,
                                        axis=self.axis)
        self.plan = shard_by_subtree(self.plan, self.mesh, axis=self.axis)
        f = self.factors
        n0 = f.leaf_size
        self._leaves_per_dev = f.num_leaves // p
        # leaf-granularity shard stacks: everything a device needs for a
        # query routed to one of its leaves, indexed by LOCAL leaf id
        spec = NamedSharding(self.mesh, P(self.axis))
        self._x_leaf = jax.device_put(
            f.x_sorted.reshape(f.num_leaves, n0, -1), spec)
        self._lm_leaf = jax.device_put(
            jnp.repeat(f.landmarks[levels - 1], 2, axis=0), spec)
        kernel, config = self.kernel, self.config

        def body(x_leaf, w_leaf, lm_leaf, ct_leaf, qs, lleaf):
            qs, lleaf = qs[0], lleaf[0]
            z = oos.apply_segments(x_leaf[lleaf], w_leaf[lleaf],
                                   lm_leaf[lleaf], ct_leaf[lleaf], qs,
                                   kernel, config)
            return z[None]

        sp = P(self.axis)
        # check_vma=False: the oos_* Pallas stages declare their outputs
        # without the varying-mesh-axes type that check_vma requires
        self._fn = jax.jit(jax.shard_map(
            body, mesh=self.mesh, in_specs=(sp,) * 6, out_specs=sp,
            check_vma=False))
        self._calls = 0
        self._queries = 0
        self._padded = 0
        self._bucket_hits: dict[int, int] = {}

    def warmup(self) -> list[int]:
        """Compile the single-device bucket path (parity with
        :meth:`PredictEngine.warmup`): touches every bucket size once."""
        d = self.factors.x_sorted.shape[1]
        buckets, b = [], self.min_bucket
        while b <= self.max_bucket:
            buckets.append(b)
            b <<= 1
        dummy = jnp.zeros((1, d), self.factors.x_sorted.dtype)
        for b in buckets:
            jax.block_until_ready(self.apply(jnp.broadcast_to(dummy, (b, d))))
        return buckets

    def apply(self, queries: Array) -> Array:
        """(q, d) -> (q, k), each query served by its leaf's owner."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.core.partition import owner_device, route

        validate_queries(queries, self.factors.x_sorted)
        q = queries.shape[0]
        k = self.plan.w_leaf.shape[-1]
        if q == 0:
            return jnp.zeros((0, k), self.plan.w_leaf.dtype)
        if q > self.max_bucket:
            return jnp.concatenate(
                [self.apply(queries[i:i + self.max_bucket])
                 for i in range(0, q, self.max_bucket)], axis=0)
        p = self.mesh.size
        levels = self.factors.levels
        leaf = np.asarray(route(self.factors.tree, queries))
        dev = np.asarray(owner_device(leaf, levels, p))
        order = np.argsort(dev, kind="stable")
        counts = np.bincount(dev, minlength=p)
        b = bucket_size(max(int(counts.max()), 1), self.min_bucket,
                        self.max_bucket)

        q_host = np.asarray(queries)
        stacked_q = np.zeros((p, b, q_host.shape[1]), q_host.dtype)
        stacked_leaf = np.zeros((p, b), np.int32)
        starts = np.cumsum(counts) - counts
        pos = np.arange(q) - starts[dev[order]]          # rank inside segment
        stacked_q[dev[order], pos] = q_host[order]
        stacked_leaf[dev[order], pos] = (
            leaf[order] - dev[order] * self._leaves_per_dev)

        spec = NamedSharding(self.mesh, P(self.axis))
        z = self._fn(self._x_leaf, self.plan.w_leaf, self._lm_leaf,
                     self.plan.c_tilde,
                     jax.device_put(jnp.asarray(stacked_q), spec),
                     jax.device_put(jnp.asarray(stacked_leaf), spec))
        zflat = np.asarray(z).reshape(p * b, k)
        out = np.empty((q, k), zflat.dtype)
        out[order] = zflat[dev[order] * b + pos]
        out_j = jnp.asarray(out)
        health.probe_predictions(out_j, self.config)
        self._calls += 1
        self._queries += q
        self._padded += p * b - q
        self._bucket_hits[b] = self._bucket_hits.get(b, 0) + 1
        return out_j

    __call__ = apply

    @property
    def stats(self) -> dict:
        """Serving counters (calls, queries, pad waste, bucket hits)."""
        return {
            "calls": self._calls,
            "queries": self._queries,
            "padded_queries": self._padded,
            "bucket_hits": dict(sorted(self._bucket_hits.items())),
        }


@dataclasses.dataclass(frozen=True)
class ModelVersion:
    """One immutable registry entry: a model, its engine, its number.

    Entries are never mutated after publish — rolling back to a stored
    version re-points serving at the SAME engine object over the SAME
    factor arrays, so its predictions are bitwise identical to what that
    version served before the swap.
    """

    version: int
    model: object               # the fitted model (HCKRegressor-like)
    engine: object              # PredictEngine | MeshPredictEngine
    tag: str = ""
    published_at: float = 0.0


class ModelRegistry:
    """Versioned hot-swap serving over the bucketed prediction engines.

    The swap protocol (DESIGN.md §10): every request reads the live
    :class:`ModelVersion` snapshot through ONE reference load at call
    entry and serves the whole batch from it, and :meth:`publish` /
    :meth:`rollback` replace that reference with ONE store — a single
    attribute assignment is atomic under the interpreter, so a request
    stream concurrent with a swap sees either the old version or the new
    one for any given request, never a mix, and never blocks (the build
    and optional warmup of the incoming engine happen entirely OFF the
    serving path, before the store).  The lock only serializes writers
    (publish / rollback / retire), not readers.

    ``mesh`` builds a :class:`MeshPredictEngine` per version instead, so
    distributed serving swaps with the same protocol.

    ``canary`` (held-back queries) arms the guarded-publish gate: every
    :meth:`publish` serves the canary batch from the INCOMING engine
    before the swap, requires it finite, and — when a version is already
    live — within ``canary_tol`` relative drift of the outgoing
    version's answers.  A failing canary auto-rolls-back: the swap never
    happens, the outgoing version keeps serving, registry state is
    bitwise unchanged, and the publish raises the structured
    :class:`~repro.runtime.health.NumericalFailure` (recorded in
    ``stats``).  A poisoned online update therefore cannot reach
    traffic.
    """

    def __init__(self, model=None, *, tag: str = "", mesh=None,
                 axis: str = "dev", warmup: bool = False,
                 canary: Array | None = None, canary_tol: float = 1e-3,
                 **engine_kwargs):
        self._lock = threading.Lock()
        self._versions: dict[int, ModelVersion] = {}
        self._live: ModelVersion | None = None
        self._next = 1
        self._mesh = mesh
        self._axis = axis
        self._engine_kwargs = dict(engine_kwargs)
        self._swaps = 0
        self._canary = canary
        self._canary_tol = canary_tol
        self._canary_rejects = 0
        self._last_reject: dict | None = None
        if model is not None:
            self.publish(model, tag=tag, warmup=warmup)

    # -- writers ----------------------------------------------------------
    def _canary_gate(self, engine, canary, tol: float) -> None:
        """Validate the incoming engine on held-back queries BEFORE the
        swap; raises NumericalFailure (and records the reject) on a
        non-finite or drifted canary response."""
        if canary is None:
            return
        try:
            try:
                z_new = engine(canary)
            except health.NumericalFailure as e:
                # the engine's own probe tripped first; re-attribute to the
                # gate so the reject reads as what it is
                raise health.NumericalFailure(
                    "serving.canary", statistic=e.statistic, value=e.value,
                    leaf=e.leaf, node=e.node, dtype=e.dtype,
                    backend=e.backend,
                    detail=f"incoming engine failed the canary probe: "
                           f"{e.detail}") from e
            health.probe_predictions(z_new, force=True,
                                     stage="serving.canary")
            live = self._live
            if live is not None:
                z_old = live.engine(canary)
                scale = float(jnp.linalg.norm(z_old)) or 1.0
                drift = float(jnp.linalg.norm(z_new - z_old)) / scale
                if not np.isfinite(drift) or drift > tol:
                    raise health.NumericalFailure(
                        "serving.canary", statistic="canary_drift",
                        value=drift, dtype=z_new.dtype,
                        detail=f"vs live version {live.version} "
                               f"(tol={tol:g})")
        except health.NumericalFailure as e:
            self._canary_rejects += 1
            self._last_reject = e.to_dict()
            raise

    def publish(self, model, *, tag: str = "", warmup: bool = False,
                canary: Array | None = None,
                canary_tol: float | None = None) -> int:
        """Register ``model`` and atomically make it the live version.

        The engine is built (and optionally warmed: every shape bucket
        compiled) BEFORE the swap, so in-flight and subsequent requests
        never pay a cold compile; then the canary gate (see class docs)
        validates it, still before the swap; the store itself is one
        reference assignment.  Returns the new version number.
        ``canary``/``canary_tol`` override the registry-wide gate for
        this publish only.
        """
        engine = PredictEngine(model.factors, model.plan, model.kernel,
                               config=model.solve_config,
                               **self._engine_kwargs)
        if self._mesh is not None:
            engine = engine.on_mesh(self._mesh, axis=self._axis)
        if warmup:
            engine.warmup()
        self._canary_gate(engine,
                          canary if canary is not None else self._canary,
                          canary_tol if canary_tol is not None
                          else self._canary_tol)
        with self._lock:
            v = self._next
            self._next += 1
            entry = ModelVersion(v, model, engine, tag=tag,
                                 published_at=time.monotonic())
            self._versions[v] = entry
            self._live = entry          # atomic reference store: the swap
            self._swaps += 1
        return v

    def rollback(self, version: int | None = None) -> int:
        """Re-point serving at a stored version (default: the previous one).

        The entry is reused as stored — same engine, same arrays — so the
        rolled-back predictions are bitwise identical to what that
        version served before it was swapped out.
        """
        with self._lock:
            if not self._versions:
                raise ValueError("registry has no versions")
            if version is None:
                live = self._live.version if self._live else None
                older = [v for v in self._versions if v != live]
                if not older:
                    raise ValueError("no previous version to roll back to")
                version = max(older)
            if version not in self._versions:
                raise KeyError(f"version {version} not in registry "
                               f"(have {sorted(self._versions)})")
            self._live = self._versions[version]
            self._swaps += 1
        return version

    def retire(self, version: int) -> None:
        """Drop a stored version (frees its factors; the live version
        cannot be retired)."""
        with self._lock:
            if self._live is not None and self._live.version == version:
                raise ValueError(f"version {version} is live; publish or "
                                 "rollback first")
            self._versions.pop(version)

    def update_and_publish(self, x_new, y_new, *, tag: str = "",
                           warmup: bool = False, guarded: bool = False,
                           **update_kwargs):
        """Online insert + hot swap: ``live.model.update`` then publish.

        The update runs against the live model's immutable state while
        that model keeps serving; the new version swaps in only when its
        engine is ready.  Returns ``(version, info)`` — ``info`` is the
        :class:`repro.core.krr.UpdateInfo`, whose ``needs_rebuild`` flag
        is the caller's cue to schedule a full background refit and
        publish THAT when done.

        The whole call is TRANSACTIONAL: the update builds an entirely
        new model off-path and nothing registry-side mutates until the
        canary-gated publish commits under the lock, so an insert /
        re-solve / canary failure anywhere leaves the live version, the
        version list and every cached engine bitwise unchanged (the
        exception propagates; the reject is visible in ``stats``).
        ``guarded=True`` routes the update through the
        :func:`repro.runtime.recover.update_guarded` ladder (fresh
        inverse → exact bordered → full re-factorization) before
        publishing.
        """
        entry = self._live
        if entry is None:
            raise ValueError("registry has no live model to update")
        if guarded:
            from repro.runtime.recover import update_guarded

            model_new, info, _audit = update_guarded(
                entry.model, x_new, y_new, **update_kwargs)
        else:
            model_new, info = entry.model.update(x_new, y_new,
                                                 **update_kwargs)
        version = self.publish(model_new, tag=tag, warmup=warmup)
        return version, info

    # -- readers (lock-free) ----------------------------------------------
    def predict(self, queries: Array) -> tuple[Array, int]:
        """Serve one batch from the live version: ``(z, version)``.

        One snapshot read at entry — a publish/rollback racing with this
        call flips requests atomically from one version to the next.
        """
        entry = self._live
        if entry is None:
            raise ValueError("registry has no live model")
        return entry.engine(queries), entry.version

    __call__ = predict

    @property
    def live_version(self) -> int | None:
        """Version number currently serving (None before first publish)."""
        entry = self._live
        return entry.version if entry is not None else None

    @property
    def live(self) -> ModelVersion | None:
        """The live snapshot entry itself."""
        return self._live

    def versions(self) -> list[int]:
        """Stored version numbers, ascending."""
        with self._lock:
            return sorted(self._versions)

    def get(self, version: int) -> ModelVersion:
        """Stored entry by number (KeyError if retired/unknown)."""
        return self._versions[version]

    @property
    def stats(self) -> dict:
        """Registry counters (live version, stored versions, swap count,
        canary rejects and the last reject's diagnostics)."""
        return {
            "live_version": self.live_version,
            "versions": self.versions(),
            "swaps": self._swaps,
            "canary_rejects": self._canary_rejects,
            "last_reject": self._last_reject,
        }
