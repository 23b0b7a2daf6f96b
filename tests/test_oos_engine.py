"""Parity + serving tests for the batched Algorithm-3 prediction engine.

Acceptance: the leaf-grouped engine path (xla and pallas-interpret
backends) agrees with the dense OOS oracle ``oos_vector_reference`` to
1e-6 in float64 across odd leaf sizes, multi-RHS plans and query counts
that are not bucket multiples; the shape-bucketed PredictEngine is
bit-identical to the unbucketed path modulo padding.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import oos
from repro.core.hck import build_hck
from repro.core.kernels_fn import BaseKernel
from repro.core.partition import group_by_leaf, route
from repro.kernels.registry import (SolveConfig, registered, resolve_backend,
                                    tile_config)

BACKENDS = ["xla", "pallas"]


def _problem(*, n, levels, rank, d=5, k=2, name="gaussian", seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, d),
                          dtype=jnp.float64)
    ker = BaseKernel(name, sigma=1.5, jitter=1e-10)
    f = build_hck(x, levels=levels, rank=rank,
                  key=jax.random.PRNGKey(seed + 1), kernel=ker)
    w = jax.random.normal(jax.random.PRNGKey(seed + 2), (n, k),
                          dtype=jnp.float64)
    return f, ker, w


# ---------------------------------------------------------------------------
# Engine parity vs the dense oracle and the legacy walk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("n,levels,rank", [
    (256, 3, 16),     # aligned leaves (n0 = 32)
    (108, 2, 16),     # odd leaf size (n0 = 27)
    (120, 2, 1),      # rank 1
    (64, 1, 8),       # single split
])
def test_apply_plan_parity_vs_oracle(f64, backend, k, n, levels, rank):
    f, ker, w = _problem(n=n, levels=levels, rank=rank, k=k)
    q = jax.random.normal(jax.random.PRNGKey(7), (33, 5), dtype=jnp.float64)
    cfg = SolveConfig(backend=backend)
    plan = oos.prepare(f, w, cfg)
    got = oos.apply_plan(f, plan, q, ker, cfg)
    assert got.shape == (33, k)
    want = oos.oos_reference_batch(f, q, ker) @ w
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # the pre-refactor per-level walk is a second oracle for the same plan
    walk = oos.apply_plan_walk(f, plan, q, ker)
    np.testing.assert_allclose(np.asarray(got), np.asarray(walk),
                               rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", ["laplace", "imq"])
def test_apply_plan_other_base_kernels(f64, backend, name):
    """The fused stages evaluate every supported base kernel identically to
    the kernels_fn substrate the oracle uses."""
    f, ker, w = _problem(n=128, levels=2, rank=8, name=name)
    q = jax.random.normal(jax.random.PRNGKey(8), (9, 5), dtype=jnp.float64)
    got = oos.predict(f, w, q, ker, SolveConfig(backend=backend))
    want = oos.oos_reference_batch(f, q, ker) @ w
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_flat_tree_levels0(f64):
    f, ker, w = _problem(n=32, levels=0, rank=4)
    q = jax.random.normal(jax.random.PRNGKey(9), (5, 5), dtype=jnp.float64)
    got = oos.predict(f, w, q, ker)
    want = oos.oos_reference_batch(f, q, ker) @ w
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-8, atol=1e-8)


def test_group_by_leaf_segments(f64):
    f, ker, _ = _problem(n=256, levels=3, rank=16)
    q = jax.random.normal(jax.random.PRNGKey(10), (40, 5), dtype=jnp.float64)
    leaf = route(f.tree, q)
    order, counts, starts = group_by_leaf(leaf, f.num_leaves)
    ls = np.asarray(leaf)[np.asarray(order)]
    assert (np.diff(ls) >= 0).all()                 # sorted => segmented
    assert int(counts.sum()) == 40
    np.testing.assert_array_equal(
        np.asarray(starts), np.cumsum(np.asarray(counts)) - np.asarray(counts))
    # each segment holds exactly the queries routed to that leaf
    for p in range(f.num_leaves):
        seg = ls[int(starts[p]):int(starts[p]) + int(counts[p])]
        assert (seg == p).all()


# ---------------------------------------------------------------------------
# PredictEngine: shape buckets, micro-batching, stats
# ---------------------------------------------------------------------------

def test_engine_bucketing_matches_direct(f64):
    from repro.serving.predict_service import PredictEngine, bucket_size

    f, ker, w = _problem(n=256, levels=3, rank=16)
    plan = oos.prepare(f, w)
    engine = PredictEngine(f, plan, ker, min_bucket=16, max_bucket=64)
    for q in (1, 9, 16, 17, 33):                    # none a bucket multiple
        queries = jax.random.normal(jax.random.PRNGKey(q), (q, 5),
                                    dtype=jnp.float64)
        got = engine(queries)
        want = oos.apply_plan(f, plan, queries, ker)
        assert got.shape == want.shape == (q, 2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-12, atol=1e-12)
    hits = engine.stats["bucket_hits"]
    assert set(hits) <= {16, 32, 64}                # only power-of-two shapes
    assert bucket_size(17, 16, 64) == 32 and bucket_size(100, 16, 64) == 64


def test_engine_microbatches_large_requests(f64):
    from repro.serving.predict_service import PredictEngine

    f, ker, w = _problem(n=256, levels=3, rank=16)
    plan = oos.prepare(f, w)
    engine = PredictEngine(f, plan, ker, min_bucket=8, max_bucket=32)
    queries = jax.random.normal(jax.random.PRNGKey(0), (70, 5),
                                dtype=jnp.float64)
    got = engine(queries)                            # 70 > max_bucket
    want = oos.apply_plan(f, plan, queries, ker)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-12, atol=1e-12)
    assert engine.stats["queries"] == 70 and engine.stats["calls"] == 3


def test_engine_empty_batch(f64):
    """A serving frontend may forward an empty request batch; it must get
    an empty result back, not a crash."""
    from repro.serving.predict_service import PredictEngine

    f, ker, w = _problem(n=128, levels=2, rank=8)
    plan = oos.prepare(f, w)
    engine = PredictEngine(f, plan, ker)
    out = engine(jnp.zeros((0, 5), jnp.float64))
    assert out.shape == (0, 2)
    assert engine.stats["calls"] == 0


def test_engine_warmup_covers_all_buckets(f64):
    from repro.serving.predict_service import PredictEngine

    f, ker, w = _problem(n=128, levels=2, rank=8)
    plan = oos.prepare(f, w)
    engine = PredictEngine(f, plan, ker, min_bucket=8, max_bucket=32)
    assert engine.warmup() == [8, 16, 32]
    assert set(engine.stats["bucket_hits"]) == {8, 16, 32}


# ---------------------------------------------------------------------------
# Registry coverage for the new stages
# ---------------------------------------------------------------------------

def test_registry_has_oos_stages():
    stages = {s for s, _ in registered()}
    assert {"oos_local", "oos_walk"} <= stages
    for stage in ("oos_local", "oos_walk"):
        assert {b for s, b in registered(stage)} == {"xla", "pallas"}


def test_resolve_backend_covers_oos_stages():
    tpu = SolveConfig(interpret=False)
    for stage in ("oos_local", "oos_walk"):
        # compiled f32 + aligned contraction dim -> pallas
        assert resolve_backend(tpu, stage, dtype=jnp.float32,
                               n0=256, r=256) == "pallas"
        # interpret mode is CPU emulation: auto never picks it
        assert resolve_backend(SolveConfig(), stage, dtype=jnp.float32,
                               n0=256, r=256) == "xla"
        # float64 oracle path stays on xla unless forced
        assert resolve_backend(tpu, stage, dtype=jnp.float64,
                               n0=256, r=256) == "xla"
        # odd contraction dims fall back
        assert resolve_backend(tpu, stage, dtype=jnp.float32,
                               n0=27, r=16) == "xla"
        # explicit override wins
        assert resolve_backend(SolveConfig(backend="pallas"), stage,
                               dtype=jnp.float64, n0=27, r=16) == "pallas"


def test_tile_config_oos_query_blocks():
    t = tile_config("oos_local", n0=64, r=0, k=1, d=8)
    assert t.block_n0 == 128 and t.fits            # default query block
    # VMEM tiles are counted as laid out: a (n0, d=8) point block pads its
    # lanes to 128, so the Table-1 leaf (128, 90) halves the default block
    table1 = tile_config("oos_local", n0=128, r=0, k=1, d=90)
    assert table1.block_n0 == 64 and table1.fits
    big = tile_config("oos_local", n0=512, r=0, k=1, d=8)
    assert big.fits and big.block_n0 < 128         # shrinks to the budget
    huge = tile_config("oos_local", n0=65536, r=0, k=1, d=64)
    assert not huge.fits and huge.block_n0 == 8    # floor block, reported
    forced = tile_config("oos_walk", n0=256, r=0, k=1, d=8, leaf_block=32)
    assert forced.block_n0 == 32
    # a non-power-of-two override shrinking past the budget still floors at
    # the f32 sublane granularity (8), never below
    odd = tile_config("oos_local", n0=65536, r=0, k=1, d=64, leaf_block=12)
    assert odd.block_n0 == 8


# ---------------------------------------------------------------------------
# Consumers: krr squeeze consistency, gp via engine, kpca transform
# ---------------------------------------------------------------------------

def _xy(n=128, d=3):
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d), dtype=jnp.float64)
    y = jnp.sin(x[:, 0]) + 0.1 * x[:, 1]
    return x, y


def test_krr_predict_shape_recorded_at_fit(f64):
    from repro.core import krr

    x, y = _xy()
    ker = BaseKernel("gaussian", sigma=1.5, jitter=1e-8)
    kw = dict(kernel=ker, lam=1e-2, rank=8, leaf_size=32, levels=2,
              key=jax.random.PRNGKey(1))
    m1 = krr.fit(x, y, **kw)                        # 1-D targets
    m2 = krr.fit(x, y[:, None], **kw)               # single-column 2-D
    m3 = krr.fit(x, jnp.stack([y, -y], axis=1), **kw)   # multi-RHS
    assert m1.predict(x[:9]).shape == (9,)
    assert m2.predict(x[:9]).shape == (9, 1)        # 2-D in -> 2-D out
    assert m3.predict(x[:9]).shape == (9, 2)
    np.testing.assert_allclose(np.asarray(m1.predict(x[:9])),
                               np.asarray(m2.predict(x[:9])[:, 0]))


def test_gp_posterior_via_engine(f64):
    from repro.core import gp
    from repro.core.hck import to_dense

    x, y = _xy()
    ker = BaseKernel("gaussian", sigma=1.5, jitter=1e-10)
    g = gp.fit_gp(x, y, kernel=ker, noise=0.1, rank=16, levels=2,
                  key=jax.random.PRNGKey(2))
    q = jax.random.normal(jax.random.PRNGKey(3), (7, 3), dtype=jnp.float64)
    mean = g.posterior_mean(q)
    want = oos.apply_plan(g.factors, g.plan, q, ker)[:, 0]
    np.testing.assert_allclose(np.asarray(mean), np.asarray(want),
                               rtol=1e-12, atol=1e-12)
    # batched posterior variance vs the dense Eq. 4 diagonal
    a = to_dense(g.factors)
    ainv = jnp.linalg.inv(a + 0.1 * jnp.eye(g.factors.n, dtype=jnp.float64))
    var = g.posterior_var(q)
    for i in range(7):
        v = oos.oos_vector_reference(g.factors, q[i], ker)
        want_i = ker.gram(q[i][None])[0, 0] - v @ ainv @ v
        assert float(var[i]) == pytest.approx(float(want_i), rel=1e-4)


def test_kpca_transform_matches_training_embedding(f64):
    from repro.core import kpca

    x, _ = _xy(n=128)
    ker = BaseKernel("gaussian", sigma=1.5, jitter=1e-10)
    f = build_hck(x, levels=2, rank=16, key=jax.random.PRNGKey(4), kernel=ker)
    model = kpca.kpca_fit(f, ker, 3, iters=150, key=jax.random.PRNGKey(5))
    psi = model.transform(f.x_sorted[:16])
    np.testing.assert_allclose(np.asarray(psi),
                               np.asarray(model.embedding[:16]),
                               rtol=1e-5, atol=1e-7)
    # out-of-hull queries stay finite and bounded by the training scale
    far = 10.0 * jnp.ones((3, 3), dtype=jnp.float64)
    out = model.transform(far)
    assert out.shape == (3, 3) and bool(jnp.all(jnp.isfinite(out)))
