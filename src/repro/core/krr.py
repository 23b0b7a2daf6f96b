"""Kernel ridge regression / classification with the HCK kernel (Eq. 2).

fit:      alpha = (K_hck + lambda I)^{-1} y        — Algorithm 2, O(n r^2)
predict:  f(x)  = alpha^T k_hck(X, x)              — Algorithm 3, O(r^2 log n) /query

Classification follows the paper's protocol: binary as ridge on ±1 labels
with a sign readout, multi-class as one-vs-all ridge (multi-RHS solve —
the factorization is shared across classes).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import hmatrix, oos
from repro.core.hck import HCKFactors, build_hck, build_hck_streaming
from repro.core.kernels_fn import BaseKernel
from repro.core.partition import auto_levels, auto_levels_ceil, pad_points
from repro.kernels.registry import SolveConfig
from repro.runtime import health
from repro.runtime.trace import span

Array = jax.Array


def _encode_targets(y: Array, classification: bool):
    """Shared target encoding: (targets (n, k), classes | None, squeeze)."""
    if classification:
        classes = jnp.unique(y)
        if classes.shape[0] == 2:           # ±1 coding, single RHS
            targets = jnp.where(y == classes[1], 1.0, -1.0)[:, None]
        else:                               # one-vs-all
            targets = jnp.where(y[:, None] == classes[None, :], 1.0, -1.0)
        return targets, classes, False
    return (y if y.ndim > 1 else y[:, None]), None, y.ndim == 1


@dataclasses.dataclass
class HCKRegressor:
    """Fitted HCK kernel ridge model.

    ``squeeze`` is recorded at fit time (caller passed 1-D regression
    targets) so predict's output shape is consistent regardless of how many
    RHS columns the internal solve used: 1-D ``y`` -> ``(q,)``, 2-D ``y``
    (even with one column) -> ``(q, k)``; classification scores are always
    ``(q, n_scores)``.
    """

    kernel: BaseKernel
    factors: HCKFactors
    plan: oos.OOSPlan          # Algorithm-3 precomputation over alpha
    alpha: Array               # (n, k) dual coefficients, tree order
    classes: Array | None = None
    squeeze: bool = False
    solve_config: SolveConfig | None = None
    lam: float | None = None            # fit ridge (needed by online updates)
    base_leaf_size: int | None = None   # leaf size the λ' diagonal froze at
    inverse: hmatrix.InverseFactors | None = None  # cached Algorithm-2 inverse
    leaf_lo: Array | None = None        # its leaf Schur Cholesky (update path)

    def __post_init__(self):
        self._engine = None
        self._leaf_linv = None

    @property
    def leaf_linv(self) -> Array:
        """Leaf-granularity inverse Cholesky of the last-level ``Sigma``.

        The hierarchy's landmark factors are FROZEN, so this (P, r, r)
        stack never changes across online inserts — it is computed once
        on first use and handed to :func:`repro.core.update.insert`,
        keeping the structural insert free of the per-call triangular
        inversion.
        """
        if self._leaf_linv is None:
            from repro.core.hck import sigma_linv

            self._leaf_linv = jnp.repeat(
                sigma_linv(self.factors.sigma_cho[-1]), 2, axis=0)
        return self._leaf_linv

    @property
    def engine(self):
        """Shape-bucketed prediction service over the fitted plan (built
        lazily; see repro.serving.predict_service)."""
        from repro.serving.predict_service import PredictEngine

        return PredictEngine.attach(self)

    def predict(self, queries: Array) -> Array:
        """(q, d) -> (q,) when fit with 1-D y, else (q, k) scores."""
        z = self.engine(queries)
        return z[:, 0] if self.squeeze else z

    def predict_class(self, queries: Array) -> Array:
        """(q, d) -> (q,) predicted class labels (classification fits)."""
        if self.classes is None:
            raise ValueError("model was fit for regression")
        z = self.engine(queries)
        if z.shape[1] == 1:  # binary ±1
            return jnp.where(z[:, 0] > 0, self.classes[1], self.classes[0])
        return self.classes[jnp.argmax(z, axis=1)]

    def update(self, x_new: Array, y_new: Array, **kwargs):
        """Absorb new points online: ``fit_incremental(self, ...)``.

        Returns ``(model, info)`` — the model is a NEW instance (this one
        is untouched, so serving registries can keep it live while the
        update builds).  See :func:`fit_incremental`.
        """
        return fit_incremental(self, x_new, y_new, **kwargs)


def fit(
    x: Array,
    y: Array,
    *,
    kernel: BaseKernel,
    lam: float,
    rank: int,
    leaf_size: int | None = None,
    levels: int | None = None,
    key: Array | None = None,
    method: str = "rp",
    classification: bool = False,
    shared_landmarks: bool = False,
    solve_config: SolveConfig | None = None,
    landmarks=None,
    rank_budget: int | None = None,
) -> HCKRegressor:
    """Fit KRR with the paper's sizing rule (Eq. 22) unless levels given.

    Parameters
    ----------
    x:         (n, d) training points (float32/float64; factors keep it).
    y:         (n,) or (n, k) targets; classification reads class labels
               from a 1-D ``y``.
    kernel:    base kernel (name, sigma, jitter); static under jit.
    lam:       ridge strength of the Algorithm-2 solve.
    rank:      landmarks per node; ``leaf_size`` defaults to it (Eq. 22).
    levels:    tree depth override; default sizes by ``auto_levels_ceil``
               with at least one level (inputs that do not fill the tree
               are padded by :func:`repro.core.partition.pad_points`).
    key:       PRNG key for padding, partition, landmarks.
    solve_config: :class:`~repro.kernels.registry.SolveConfig` — selects
               the stage backends of BOTH the build engine
               (``build_gram``/``build_cross``) and the multi-RHS
               Algorithm-2 solve, plus ``interpret``/``refine_steps``/
               ``leaf_block``.  One-vs-all classification shares the
               factorization across all class columns.
    landmarks: landmark-selection policy — None/"uniform" (the default,
               bitwise-identical build), "kmeans", "leverage", or a
               :class:`~repro.landmarks.policy.LandmarkPolicy` instance.
    rank_budget: global rank budget for budgeted adaptive per-node rank
               (see :func:`repro.core.hck.build_hck`).
    """
    with span("fit"):
        with span("fit.pad"):
            key = key if key is not None else jax.random.PRNGKey(0)
            n = x.shape[0]
            leaf_size = leaf_size if leaf_size is not None else rank
            if levels is None:
                levels = max(1, auto_levels_ceil(n, leaf_size))
            kpad, kbuild = jax.random.split(key)
            x, y, mask = pad_points(x, y, leaf_size, levels, kpad)

            targets, classes, squeeze = _encode_targets(y, classification)
            del mask  # padded rows carry duplicated targets (see pad_points)

        factors = build_hck(
            x, levels=levels, rank=rank, key=kbuild, kernel=kernel,
            method=method, shared_landmarks=shared_landmarks,
            config=solve_config, policy=landmarks, rank_budget=rank_budget,
        )
        health.probe_factors(factors, solve_config, op="build")
        # solve via the leaf-aware inverse and CACHE it on the model: the
        # pair is what fit_incremental's bordered extension reuses, so the
        # FIRST online update is as cheap as the rest (inv equals
        # hmatrix.invert's, so alpha is the same solve as before)
        with span("invert"):
            inv, lo = hmatrix.invert_with_leaf(factors, lam, solve_config)
        health.probe_leaf_factor(lo, solve_config)
        with span("solve"):
            y_sorted = targets[factors.tree.perm]
            alpha = hmatrix.solve_with_inverse(factors, inv, y_sorted,
                                               ridge=lam, config=solve_config)
        health.check_finite("solve", alpha, config=solve_config,
                            detail="dual coefficients (fit)")
        with span("prepare"):
            plan = oos.prepare(factors, alpha, solve_config)
        return HCKRegressor(kernel, factors, plan, alpha, classes,
                            squeeze=squeeze, solve_config=solve_config,
                            lam=lam, base_leaf_size=factors.leaf_size,
                            inverse=inv, leaf_lo=lo)


def fit_streaming(
    source,
    y: Array,
    *,
    kernel: BaseKernel,
    lam: float,
    rank: int,
    leaf_size: int | None = None,
    levels: int | None = None,
    key: Array | None = None,
    classification: bool = False,
    solve_config: SolveConfig | None = None,
    leaf_batch: int = 64,
    chunk_rows: int = 1 << 16,
    landmarks=None,
    rank_budget: int | None = None,
) -> HCKRegressor:
    """Fit KRR from a host-resident :class:`repro.data.pipeline.ChunkSource`.

    Same model as :func:`fit`, but the raw points are never device-resident
    in one piece: the partition streams per-node projection chunks and the
    factor stages consume ``leaf_batch`` leaves per launch
    (:func:`repro.core.hck.build_hck_streaming`).  Inputs that do not fill
    the tree are padded host-side with the same duplicate-and-jitter rule
    as :func:`repro.core.partition.pad_points`.

    ``y`` is an (n,) or (n, k) array (targets are O(n k) — they stay
    device-side); ``solve_config`` selects build and solve backends as in
    :func:`fit`.
    """
    from repro.data.pipeline import pad_source

    key = key if key is not None else jax.random.PRNGKey(0)
    n = source.n
    leaf_size = leaf_size if leaf_size is not None else rank
    if levels is None:
        levels = max(1, auto_levels_ceil(n, leaf_size))
    kpad, kbuild = jax.random.split(key)
    source, y, _ = pad_source(source, y, leaf_size, levels, kpad)

    targets, classes, squeeze = _encode_targets(jnp.asarray(y), classification)
    factors = build_hck_streaming(
        source, levels=levels, rank=rank, key=kbuild, kernel=kernel,
        config=solve_config, leaf_batch=leaf_batch, chunk_rows=chunk_rows,
        policy=landmarks, rank_budget=rank_budget,
    )
    health.probe_factors(factors, solve_config, op="build")
    y_sorted = targets[factors.tree.perm]
    # cache the leaf-aware inverse exactly as fit() does, so streamed-in
    # models take online updates without re-running Algorithm 2 first
    inv, lo = hmatrix.invert_with_leaf(factors, lam, solve_config)
    health.probe_leaf_factor(lo, solve_config)
    alpha = hmatrix.solve_with_inverse(factors, inv, y_sorted, ridge=lam,
                                       config=solve_config)
    plan = oos.prepare(factors, alpha, solve_config)
    return HCKRegressor(kernel, factors, plan, alpha, classes,
                        squeeze=squeeze, solve_config=solve_config,
                        lam=lam, base_leaf_size=factors.leaf_size,
                        inverse=inv, leaf_lo=lo)


@dataclasses.dataclass
class UpdateInfo:
    """Diagnostics of one :func:`fit_incremental` round.

    ``iterations``/``residual``/``converged`` describe the re-solve
    (warm-started CG counts for ``refresh="stale"``; refinement-polished
    structured solve for ``refresh="inverse"``, where ``iterations`` is
    0).  ``cold_iterations`` is the unwarmed CG count when
    ``measure_cold=True`` (the warm-vs-cold gate of bench_update).
    ``needs_rebuild`` is the :class:`repro.core.update.RebuildPolicy`
    verdict — True means schedule a full :func:`fit` rebuild.
    """

    record: object             # repro.core.update.InsertRecord
    refresh: str
    iterations: int
    residual: float
    converged: bool
    cold_iterations: int | None = None
    needs_rebuild: bool = False


def fit_incremental(
    model: HCKRegressor,
    x_new: Array,
    y_new: Array,
    *,
    refresh: str = "inverse",
    policy=None,
    key: Array | None = None,
    tol: float = 1e-8,
    maxiter: int = 200,
    measure_cold: bool = False,
) -> tuple[HCKRegressor, UpdateInfo]:
    """Absorb a batch of new points into a fitted model without rebuilding.

    The online-update path (DESIGN.md §10): new points are routed down
    the FROZEN tree and appended to their owning leaves
    (:func:`repro.core.update.insert` — landmarks, ``Sigma``, ``W`` and
    the fit-time λ′ diagonal are all untouched), then the dual
    coefficients are re-solved on the union:

    ``refresh="inverse"`` (default, the parity path): the cached leaf
      Schur Cholesky pair is extended by the bordered ``leaf_update``
      stage (:func:`repro.core.hmatrix.invert_extend` — O(k n0^2) per
      leaf, never re-factoring the old block) and the refreshed exact
      structured inverse solves as in :func:`fit`.  Predictions match a
      from-scratch :func:`repro.core.update.refit_frozen` rebuild to
      float64 round-off.

    ``refresh="exact"`` (the recovery path): the cached pair is NOT
      reused at all — a full from-scratch Algorithm-2 inversion of the
      extended hierarchy (:func:`repro.core.hmatrix.invert_with_leaf`),
      O(n0^3) per leaf.  Numerically independent of any carried state,
      which is why the :func:`repro.runtime.recover.update_guarded`
      ladder terminates here when a poisoned cached inverse breaks the
      cheaper modes.

    ``refresh="stale"`` (the cheap path): NO re-factorization at all —
      CG on the extended operator, warm-started from the previous
      ``alpha`` (lifted with zeros on the appended rows) and
      preconditioned by the STALE structured inverse lifted the same way
      (old rows through the old inverse, appended rows Jacobi-scaled;
      block-diagonal, hence still SPD).  The preconditioner's staleness
      contract: it was exact for the pre-insert operator, so its quality
      degrades with accumulated growth — :class:`RebuildPolicy` watches
      the iteration count for exactly this drift.

    The fit-time targets are reconstructed exactly from the model itself
    (``y = (K_hck + λ)α``, one Algorithm-1 matvec) so nothing beyond the
    fitted state is needed.  ``y_new`` uses the model's fit-time
    encoding (regression columns, or ±1 against ``model.classes``; new
    class labels are rejected).  Returns ``(model_new, info)`` — the
    input model is untouched and stays servable during the update.
    """
    from repro.core.update import RebuildPolicy, insert
    from repro.solvers.cg import pcg

    if model.lam is None:
        raise ValueError("model carries no fit ridge (built before the "
                         "online-update engine?) — refit with krr.fit")
    f = model.factors
    lam = model.lam
    cfg = model.solve_config
    base = model.base_leaf_size or f.leaf_size
    key = key if key is not None else jax.random.PRNGKey(f.n)
    policy = policy if policy is not None else RebuildPolicy()

    # encode arrivals with the FIT-TIME convention
    if model.classes is not None:
        known = jnp.isin(y_new, model.classes)
        if not bool(jnp.all(known)):
            raise ValueError("y_new contains labels outside the fitted "
                             "classes; a full refit is required")
        if model.classes.shape[0] == 2:
            targets_new = jnp.where(y_new == model.classes[1], 1.0, -1.0)[:, None]
        else:
            targets_new = jnp.where(
                y_new[:, None] == model.classes[None, :], 1.0, -1.0)
    else:
        targets_new = y_new if y_new.ndim > 1 else y_new[:, None]

    # exact fit-time targets, reconstructed: y_sorted = (K_hck + lam) alpha
    y_sorted = hmatrix.matvec(f, model.alpha, cfg) + lam * model.alpha

    f_new, y_sorted_new, rec = insert(
        f, x_new, model.kernel, key=key, config=cfg,
        y_new=targets_new, y_sorted=y_sorted, jitter_rows=base,
        linv_leaf=model.leaf_linv)
    if rec.k == 0:  # empty batch: exact no-op
        info = UpdateInfo(rec, refresh, 0, 0.0, True)
        return model, info
    health.probe_factors(f_new, cfg, op="update.insert")

    n0_old = f.leaf_size
    inv_base, lo_base = model.inverse, model.leaf_lo
    if inv_base is None or lo_base is None or inv_base.leaf_size != n0_old:
        inv_base, lo_base = hmatrix.invert_with_leaf(f, lam, cfg)

    cold_iters = None
    if refresh == "inverse":
        inv_new, lo_new = hmatrix.invert_extend(
            f_new, lo_base, inv_base.linv, n0_base=n0_old, ridge=lam,
            config=cfg)
        health.probe_leaf_factor(lo_new, cfg, stage="leaf_update")
        alpha_new = hmatrix.solve_with_inverse(
            f_new, inv_new, y_sorted_new, ridge=lam, config=cfg)
        iters = 0
    elif refresh == "exact":
        # from-scratch re-factorization: no reuse of the cached pair (the
        # recovery ladder's terminal rung; also the honest cold baseline)
        inv_new, lo_new = hmatrix.invert_with_leaf(f_new, lam, cfg)
        health.probe_leaf_factor(lo_new, cfg)
        alpha_new = hmatrix.solve_with_inverse(
            f_new, inv_new, y_sorted_new, ridge=lam, config=cfg)
        iters = 0
    elif refresh == "stale":
        p_leaves, n0_new = f_new.num_leaves, f_new.leaf_size
        kcols = model.alpha.shape[1]
        # lifted stale preconditioner: the 2x2 block-inverse congruence
        #   P = [I -A⁻¹Bᵀ; 0 I] blkdiag(A⁻¹, S~⁻¹) [I 0; -BA⁻¹ I]
        # with A⁻¹ the UNREFRESHED old structured inverse, B the exact
        # old/appended operator coupling (read off two Algorithm-1
        # matvecs — no block is ever materialized), and S~ the
        # leaf-local appended Schur complement from blocks already in
        # hand.  SPD by congruence; exact up to the inter-leaf coupling
        # S~ drops.  A block-diagonal lift (dropping the off-diagonal
        # congruence) was measured WORSE than no preconditioner for
        # near-duplicate arrivals — the omitted A⁻¹BᵀS⁻¹BA⁻¹ mass is
        # exactly what resolves a duplicated row.
        bb, cc = hmatrix.extension_blocks(f_new, n0_base=n0_old, ridge=lam)
        l21 = jnp.einsum("pkn,pmn->pkm", bb, inv_base.linv)
        s_inv = jnp.linalg.inv(cc - jnp.einsum("pij,pkj->pik", l21, l21))

        def _split(v: Array) -> tuple[Array, Array]:
            vb = v.reshape(p_leaves, n0_new, -1)
            return vb[:, :n0_old], vb[:, n0_old:]

        def _join(v_old: Array, v_app: Array, ncols: int) -> Array:
            return jnp.concatenate([v_old, v_app], axis=1).reshape(-1, ncols)

        def precond(r: Array) -> Array:
            ncols = r.shape[-1] if r.ndim > 1 else 1
            r_old, r_app = _split(r)
            z1 = hmatrix.apply_inverse(
                inv_base, r_old.reshape(-1, ncols), cfg)
            z1b = z1.reshape(p_leaves, n0_old, ncols)
            # B z1 = appended rows of (A + lam)(z1; 0)
            _, bz1 = _split(hmatrix.matvec(
                f_new, _join(z1b, jnp.zeros_like(r_app), ncols), cfg)
                + lam * _join(z1b, jnp.zeros_like(r_app), ncols))
            z_app = jnp.einsum("pij,pjc->pic", s_inv, r_app - bz1)
            # Bᵀ z_app = old rows of (A + lam)(0; z_app)
            btz, _ = _split(hmatrix.matvec(
                f_new, _join(jnp.zeros_like(z1b), z_app, ncols), cfg)
                + lam * _join(jnp.zeros_like(z1b), z_app, ncols))
            z_old = z1b - hmatrix.apply_inverse(
                inv_base, btz.reshape(-1, ncols), cfg).reshape(
                    p_leaves, n0_old, ncols)
            return _join(z_old, z_app, ncols).reshape(r.shape)

        x0 = jnp.zeros((p_leaves, n0_new, kcols), model.alpha.dtype)
        x0 = x0.at[:, :n0_old].set(
            model.alpha.reshape(p_leaves, n0_old, kcols)).reshape(-1, kcols)

        def amv(v: Array) -> Array:
            return hmatrix.matvec(f_new, v, cfg)

        res = pcg(amv, y_sorted_new, ridge=lam, precond=precond,
                  x0=x0, tol=tol, maxiter=maxiter)
        health.probe_cg(res, tol=tol, config=cfg, context="refresh=stale")
        alpha_new, iters = res.x, int(res.iterations)
        if measure_cold:
            # cold = no carried state at all: neither the stale inverse
            # nor the previous alpha (what a from-scratch CG would pay)
            res_cold = pcg(amv, y_sorted_new, ridge=lam,
                           tol=tol, maxiter=maxiter)
            cold_iters = int(res_cold.iterations)
        inv_new, lo_new = inv_base, lo_base  # kept stale for the next lift
    else:
        raise ValueError(f"unknown refresh {refresh!r}; use 'inverse', "
                         "'exact' or 'stale'")

    health.check_finite("solve", alpha_new, config=cfg,
                        detail=f"dual coefficients (refresh={refresh})")
    resid = y_sorted_new - (hmatrix.matvec(f_new, alpha_new, cfg)
                            + lam * alpha_new)
    rel = float(jnp.linalg.norm(resid.reshape(-1))
                / jnp.linalg.norm(y_sorted_new.reshape(-1)))
    plan = oos.prepare(f_new, alpha_new, cfg)
    model_new = HCKRegressor(
        model.kernel, f_new, plan, alpha_new, model.classes,
        squeeze=model.squeeze, solve_config=cfg, lam=lam,
        base_leaf_size=base, inverse=inv_new, leaf_lo=lo_new)
    model_new._leaf_linv = model._leaf_linv  # frozen landmarks: carry over
    needs_rebuild = policy.should_rebuild(
        base_leaf_size=base, leaf_size=f_new.leaf_size,
        warm_iters=iters if refresh == "stale" else None,
        update_error=rel)
    info = UpdateInfo(rec, refresh, iters, rel,
                      converged=(rel <= max(tol, 1e-6)
                                 or refresh in ("inverse", "exact")),
                      cold_iterations=cold_iters, needs_rebuild=needs_rebuild)
    return model_new, info


@dataclasses.dataclass
class KRRPath:
    """A fitted regularization path: one hierarchy, G ridge solutions.

    ``alphas[g]`` are the dual coefficients at ``lams[g]`` (tree order);
    ``scores[g]`` the validation metric at that λ (relative error for
    regression, misclassification rate for classification — lower is
    better in both), or None when no validation set was given.
    :meth:`model` materializes the :class:`HCKRegressor` at one grid
    index; :meth:`best` picks the score argmin.
    """

    kernel: BaseKernel
    factors: HCKFactors
    lams: Array                # (G,)
    alphas: Array              # (G, n, k) dual coefficients, tree order
    scores: Array | None       # (G,) validation scores, or None
    classes: Array | None = None
    squeeze: bool = False
    solve_config: SolveConfig | None = None

    def model(self, g: int) -> HCKRegressor:
        """Materialize the fitted model at grid index ``g`` (prepares the
        Algorithm-3 plan for that λ's coefficients)."""
        plan = oos.prepare(self.factors, self.alphas[g], self.solve_config)
        return HCKRegressor(self.kernel, self.factors, plan, self.alphas[g],
                            self.classes, squeeze=self.squeeze,
                            solve_config=self.solve_config,
                            lam=float(self.lams[g]),
                            base_leaf_size=self.factors.leaf_size)

    def best(self) -> HCKRegressor:
        """Model at the validation-score argmin (requires scores)."""
        if self.scores is None:
            raise ValueError("fit_path was called without a validation set; "
                             "pick an index and call .model(g)")
        return self.model(int(jnp.argmin(self.scores)))


def fit_path(
    x: Array,
    y: Array,
    *,
    kernel: BaseKernel,
    lams,
    rank: int | None = None,
    leaf_size: int | None = None,
    levels: int | None = None,
    key: Array | None = None,
    method: str = "rp",
    classification: bool = False,
    shared_landmarks: bool = False,
    solve_config: SolveConfig | None = None,
    x_val: Array | None = None,
    y_val: Array | None = None,
    factors: HCKFactors | None = None,
    landmarks=None,
    rank_budget: int | None = None,
) -> KRRPath:
    """Fit the whole regularization path in one build (sweep engine λ-axis).

    The HCK factors are λ-independent, so where a naive grid search runs
    ``fit`` per λ — G full rebuilds — this partitions, samples and
    factorizes ONCE, stacks all G leaf Schur factorizations into a single
    ``leaf_factor`` stage launch (:func:`repro.core.hmatrix.invert_multi`),
    and shares the Algorithm-1 refinement operator across the grid.
    Validation scoring batches all λ through ONE Algorithm-3 pass: the
    prediction is linear in alpha, so the G coefficient vectors ride as
    extra RHS columns of a single OOS plan.

    Parameters are as in :func:`fit` with ``lams`` an array-like of ridge
    values; ``x_val``/``y_val`` (optional) score every λ on held-out data.
    ``factors`` (optional) supplies a prebuilt hierarchy — e.g. one σ of a
    :func:`repro.core.hck.sweep_factors` grid, or a policy-swept build
    (``sweep_factors`` on a ``build_sweep_plan(policy=...)`` plan, with or
    without ``rank_budget``) — in which case ``x``/``y`` must already
    match its padded size and tree, and the build (including padding) is
    skipped; ``rank``/``leaf_size``/``levels``/``key``/``landmarks``/
    ``rank_budget`` are ignored.
    """
    if factors is None:
        if rank is None:
            raise ValueError("rank is required when no prebuilt factors "
                             "are given")
        key = key if key is not None else jax.random.PRNGKey(0)
        n = x.shape[0]
        leaf_size = leaf_size if leaf_size is not None else rank
        if levels is None:
            levels = max(1, auto_levels_ceil(n, leaf_size))
        kpad, kbuild = jax.random.split(key)
        x, y, _ = pad_points(x, y, leaf_size, levels, kpad)
        factors = build_hck(
            x, levels=levels, rank=rank, key=kbuild, kernel=kernel,
            method=method, shared_landmarks=shared_landmarks,
            config=solve_config, policy=landmarks, rank_budget=rank_budget,
        )
    elif y.shape[0] != factors.n or x.shape[0] != factors.n:
        raise ValueError(
            f"prebuilt factors cover n={factors.n} points but x has "
            f"{x.shape[0]} and y has {y.shape[0]} rows — pad x/y to the "
            "factor tree first")

    targets, classes, squeeze = _encode_targets(y, classification)
    y_sorted = targets[factors.tree.perm]
    lams = jnp.asarray(lams)
    invs = hmatrix.invert_multi(factors, lams, solve_config)
    alphas = jnp.stack([
        hmatrix.solve_with_inverse(
            factors, jax.tree_util.tree_map(lambda a, g=g: a[g], invs),
            y_sorted, ridge=lams[g], config=solve_config)
        for g in range(lams.shape[0])])                      # (G, n, k)

    scores = None
    if x_val is not None:
        if y_val is None:
            raise ValueError("x_val given without y_val")
        g_count, _, k = alphas.shape
        # one OOS pass for ALL lambdas: predictions are linear in alpha,
        # so the G coefficient sets are just extra RHS columns
        alpha_cols = jnp.moveaxis(alphas, 0, 2).reshape(-1, g_count * k)
        plan = oos.prepare(factors, alpha_cols, solve_config)
        z = oos.apply_plan(factors, plan, x_val, kernel, solve_config)
        z = z.reshape(-1, k, g_count)                        # (q, k, G)
        if classification:
            if classes.shape[0] == 2:
                pred = jnp.where(z[:, 0, :] > 0, classes[1], classes[0])
            else:
                pred = classes[jnp.argmax(z, axis=1)]        # (q, G)
            scores = jnp.mean((pred != y_val[:, None]).astype(jnp.float32),
                              axis=0)
        else:
            yv = y_val if y_val.ndim > 1 else y_val[:, None]
            scores = (jnp.linalg.norm(z - yv[:, :, None], axis=(0, 1))
                      / jnp.linalg.norm(yv))
    return KRRPath(kernel, factors, lams, alphas, scores, classes,
                   squeeze=squeeze, solve_config=solve_config)


@dataclasses.dataclass
class ExactKRR:
    """Exact-kernel KRR model trained by a matvec-free iterative solver.

    Unlike :class:`HCKRegressor` (whose predictions go through the
    Algorithm-3 plan of the APPROXIMATE kernel), this model's dual
    coefficients solve ``(K(X, X) + λI) α = y`` for the exact base
    kernel, and predict is the exact cross kernel applied chunk by chunk
    — the accuracy ceiling every Fig-5/6 comparison implicitly targets.
    ``alpha`` is in the ORIGINAL row order of the training ``x`` (no
    tree permutation: the hierarchy only ever acts as preconditioner).
    ``result`` carries the solver trace (iterations, relative residuals,
    converged flag) for diagnostics.
    """

    kernel: BaseKernel
    x: Array                   # (n, d) training points, original order
    alpha: Array               # (n, k) dual coefficients, original order
    lam: float
    result: object             # repro.solvers.cg.CGResult solver trace
    classes: Array | None = None
    squeeze: bool = False
    solve_config: SolveConfig | None = None
    row_chunk: int = 1024

    def _op(self):
        from repro.solvers.operators import ExactKernelOp

        return ExactKernelOp(self.x, self.kernel, self.solve_config,
                             row_chunk=self.row_chunk)

    def predict(self, queries: Array) -> Array:
        """(q, d) -> (q,) when fit with 1-D y, else (q, k) scores."""
        z = self._op().cross_matvec(queries, self.alpha)
        return z[:, 0] if self.squeeze else z

    def predict_class(self, queries: Array) -> Array:
        """(q, d) -> (q,) predicted class labels (classification fits)."""
        if self.classes is None:
            raise ValueError("model was fit for regression")
        z = self._op().cross_matvec(queries, self.alpha)
        if z.shape[1] == 1:  # binary ±1
            return jnp.where(z[:, 0] > 0, self.classes[1], self.classes[0])
        return self.classes[jnp.argmax(z, axis=1)]


def _hck_preconditioner(x, *, kernel, lam, rank, leaf_size, levels, key,
                        method, solve_config):
    """Build the Algorithm-2 structured inverse as a CG preconditioner.

    The hierarchy is built on a PADDED copy of ``x`` (the tree wants
    leaf_size·2^L rows; padding duplicates existing points with jitter)
    and applied through a weighted embed/extract pair ``P = Aᵀ M A``
    with ``A = E D^{-1/2}`` — E the duplication map, D its column
    multiplicities.  Ignoring the pad jitter, the push-through identity
    gives ``P = (D^{1/2} K_hck D^{1/2} + λ)^{-1}``: spectrally within a
    factor ``max mᵢ`` (≈2 for uniform draws) of the target inverse, and
    SPD by construction.  A plain 0-fill/restrict pair is NOT close —
    the inverse splits duplicated points' mass across their copies, and
    dropping the copies was measured to make CG converge slower than
    with no preconditioner at all.
    """
    n = x.shape[0]
    leaf_size = leaf_size if leaf_size is not None else rank
    if levels is None:
        # the preconditioner is free to choose its own tree sizing, so
        # minimize padding: FLOOR levels with a ceil leaf size pads less
        # than one row per leaf (leaf' >= rank holds because
        # rank·2^L <= n).  auto_levels_ceil + pad (what fit must do to
        # solve the padded problem exactly) can duplicate up to half the
        # rows, which was measured to make the restricted inverse WORSE
        # than no preconditioner at all.
        levels = max(1, auto_levels(n, leaf_size))
        # the rank floor keeps landmark sampling valid when n < 2·rank
        leaf_size = max(-(-n // (1 << levels)), leaf_size)
    kpad, kbuild = jax.random.split(key)
    target = leaf_size * (1 << levels)
    if n > target:
        raise ValueError(
            f"n={n} exceeds the preconditioner tree capacity {target} "
            f"(leaf_size={leaf_size} x 2**{levels}); raise levels or "
            "leaf_size, or leave them None for automatic sizing")
    if n == target:
        x_pad = x
        src = jnp.arange(n)
        row_w = jnp.ones((n,), x.dtype)
    else:
        # same duplicate-and-jitter rule as partition.pad_points, but the
        # duplicate indices are kept for the weighted embed/extract
        k1, k2 = jax.random.split(kpad)
        idx = jax.random.randint(k1, (target - n,), 0, n)
        noise = 1e-4 * jax.random.normal(k2, (target - n, x.shape[1]),
                                         dtype=x.dtype)
        x_pad = jnp.concatenate([x, x[idx] + noise], axis=0)
        src = jnp.concatenate([jnp.arange(n), idx])        # originals per row
        mult = jnp.zeros((n,), x.dtype).at[src].add(1.0)
        row_w = (1.0 / jnp.sqrt(mult))[src]                # D^{-1/2} per row
    factors = build_hck(x_pad, levels=levels, rank=rank, key=kbuild,
                        kernel=kernel, method=method, config=solve_config)
    inv = hmatrix.invert(factors, ridge=lam, config=solve_config)
    # tree position of padded row j: argsort(perm) inverts the gather
    # x_sorted = x_pad[perm]
    pos = jnp.argsort(factors.tree.perm)

    def precond(r: Array) -> Array:
        rp = jnp.zeros((factors.n, r.shape[1]), r.dtype)
        rp = rp.at[pos].set(r[src] * row_w[:, None])
        z = hmatrix.apply_inverse(inv, rp, solve_config)[pos]
        return jnp.zeros_like(r).at[src].add(z * row_w[:, None])

    return precond, factors, inv


def fit_exact(
    x: Array,
    y: Array,
    *,
    kernel: BaseKernel,
    lam: float,
    rank: int = 64,
    leaf_size: int | None = None,
    levels: int | None = None,
    key: Array | None = None,
    method: str = "rp",
    solver: str = "cg",
    precondition: bool = True,
    tol: float = 1e-6,
    maxiter: int = 300,
    classification: bool = False,
    solve_config: SolveConfig | None = None,
    row_chunk: int = 1024,
    eigenpro_components: int = 160,
    eigenpro_subsample: int = 2048,
) -> ExactKRR:
    """Train EXACT-kernel KRR without ever materializing K(X, X).

    The solve side of the iterative subsystem (:mod:`repro.solvers`):
    CG runs on the chunked matvec-free exact-kernel operator
    (O(row_chunk · n) memory per sweep), preconditioned by the HCK
    structured inverse — the paper's factorization used for what it is
    best at, a strictly-PD spectral surrogate of K.  Measured ≥4× fewer
    iterations than unpreconditioned CG at n = 4096 (bench_cg.py gates
    the ratio), and the result matches a dense
    ``jnp.linalg.solve(kernel.gram(x) + λI, y)`` fit to solver
    tolerance.

    Parameters
    ----------
    x, y:      training data as in :func:`fit` (classification reads
               class labels from a 1-D ``y``).
    kernel:    base kernel; ``kernel.gram``'s jitter·n diagonal is part
               of the operator, so the dense oracle is
               ``kernel.gram(x) + λI``.
    lam:       ridge of the exact solve.
    rank, leaf_size, levels, method:
               sizing of the PRECONDITIONER hierarchy (same defaults as
               :func:`fit`); ignored when ``precondition=False`` or
               ``solver="eigenpro"``.  ``key`` seeds the preconditioner
               build — and, for ``solver="eigenpro"``, the Nyström
               subsample draw — so it is never ignored.
    solver:    "cg" (HCK-preconditioned CG, default) or "eigenpro"
               (truncated-eigenspectrum preconditioned Richardson,
               :mod:`repro.solvers.eigenpro` — the learned-baseline
               rival; ``eigenpro_*`` size its Nyström eigensystem).
    precondition: disable the HCK preconditioner (plain CG) — the
               baseline the ≥4× iteration claim is measured against.
    tol, maxiter: relative-residual target and iteration cap.
    solve_config: backends for the ``kernel_matvec`` stage and the
               preconditioner build/apply.
    row_chunk: rows of the kernel matrix evaluated per chunk (memory
               knob: peak transient is row_chunk · n kernel entries).
    """
    from repro.solvers.cg import pcg
    from repro.solvers.eigenpro import eigenpro_solve
    from repro.solvers.operators import ExactKernelOp

    key = key if key is not None else jax.random.PRNGKey(0)
    targets, classes, squeeze = _encode_targets(y, classification)
    op = ExactKernelOp(x, kernel, solve_config, row_chunk=row_chunk)

    if solver == "eigenpro":
        res = eigenpro_solve(op, targets, ridge=lam, key=key,
                             n_components=eigenpro_components,
                             subsample=eigenpro_subsample,
                             tol=tol, maxiter=maxiter)
    elif solver == "cg":
        precond = None
        if precondition:
            precond, _, _ = _hck_preconditioner(
                x, kernel=kernel, lam=lam, rank=rank, leaf_size=leaf_size,
                levels=levels, key=key, method=method,
                solve_config=solve_config)
        res = pcg(op.matvec, targets, ridge=lam, precond=precond,
                  tol=tol, maxiter=maxiter)
    else:
        raise ValueError(f"unknown solver {solver!r}; use 'cg' or 'eigenpro'")

    return ExactKRR(kernel, x, res.x, lam, res, classes, squeeze=squeeze,
                    solve_config=solve_config, row_chunk=row_chunk)


def relative_error(pred: Array, truth: Array) -> Array:
    """Paper's regression metric: ||pred - y|| / ||y||."""
    return jnp.linalg.norm(pred - truth) / jnp.linalg.norm(truth)


def accuracy(pred: Array, truth: Array) -> Array:
    """Fraction of exact label matches (classification metric)."""
    return jnp.mean((pred == truth).astype(jnp.float32))
