"""Mesh-sharded Algorithm-2 build vs the single-host engine.

The key-tree parity design (``dist_build_hck`` splits and folds the PRNG
key EXACTLY like ``build_hck``) means the distributed build is the SAME
randomness, so factors must agree to roundoff — these tests pin 1e-12 in
float64 on an 8-device virtual mesh, including the streaming ingestion
path and an odd-n padded problem.

The mesh tests skip on a 1-device session; the CI ``test-multidevice``
lane runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.  The
``device_level`` / ``owner_device`` property tests need no mesh and run
everywhere.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.partition import auto_levels_ceil, owner_device, pad_points

SETTINGS = dict(max_examples=8, deadline=None)

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


# ---------------------------------------------------------------------------
# device_level / owner_device (no mesh required)
# ---------------------------------------------------------------------------

@given(t=st.integers(0, 16))
@settings(**SETTINGS)
def test_device_level_power_of_two(t):
    """device_level inverts 2**t for every tree-relevant exponent."""
    from repro.launch.dist_hck import device_level

    assert device_level(1 << t) == t


@given(n=st.integers(2, 1 << 12))
@settings(**SETTINGS)
def test_device_level_rejects_non_power_of_two(n):
    """Every non-power-of-two count raises (binary tree level widths)."""
    from repro.launch.dist_hck import device_level

    if n & (n - 1) == 0:
        n += 1          # nudge onto a non-power-of-two
        if n & (n - 1) == 0:
            n += 1
    with pytest.raises(ValueError):
        device_level(n)


@given(t=st.integers(0, 4), extra=st.integers(0, 4))
@settings(**SETTINGS)
def test_owner_device_partitions_leaves_evenly(t, extra):
    """Each device owns a contiguous, equal-size leaf range in order."""
    p = 1 << t
    levels = t + extra if t + extra >= 1 else 1
    leaves = np.arange(1 << levels)
    dev = np.asarray(owner_device(leaves, levels, p))
    counts = np.bincount(dev, minlength=p)
    assert (counts == (1 << levels) // p).all()
    assert (np.diff(dev) >= 0).all()          # contiguous ranges, in order
    assert dev[0] == 0 and dev[-1] == p - 1


def test_owner_device_error_paths():
    """Non-power-of-two device counts and too-shallow trees raise."""
    with pytest.raises(ValueError):
        owner_device(np.arange(8), 3, 3)
    with pytest.raises(ValueError):
        owner_device(np.arange(4), 2, 8)      # levels=2 < log2(8)=3


# ---------------------------------------------------------------------------
# mesh-sharded build parity (8 virtual devices)
# ---------------------------------------------------------------------------

def _max_factor_diff(fa, fb) -> float:
    diffs = [jnp.max(jnp.abs(fa.x_sorted - fb.x_sorted)),
             jnp.max(jnp.abs(fa.u - fb.u)),
             jnp.max(jnp.abs(fa.adiag - fb.adiag))]
    for a, b in zip(fa.sigma, fb.sigma):
        diffs.append(jnp.max(jnp.abs(a - b)))
    for a, b in zip(fa.sigma_cho, fb.sigma_cho):
        diffs.append(jnp.max(jnp.abs(a - b)))
    for a, b in zip(fa.w, fb.w):
        diffs.append(jnp.max(jnp.abs(a - b)))
    return float(jnp.max(jnp.stack(diffs)))


@pytest.fixture(scope="module")
def mesh8():
    from repro.launch.mesh import kernel_mesh

    return kernel_mesh(8)


def test_dist_build_runs_build_hck_partition_program(monkeypatch):
    """The mesh build partitions with build_hck's own compiled program on
    the same points and key: the tree is a discrete function of rounded
    projections, so a re-implementation that agrees on the CPU may still
    move points across a node's median on a TPU."""
    from repro.core import hck, partition
    from repro.core.kernels_fn import BaseKernel
    from repro.launch.dist_hck import dist_build_hck
    from repro.launch.mesh import kernel_mesh

    calls = []
    original = partition.build_partition

    def recording(x, levels, key, method="rp"):
        calls.append((x, levels, np.asarray(key), method))
        return original(x, levels, key, method=method)

    monkeypatch.setattr(hck, "build_partition", recording)
    monkeypatch.setattr(partition, "build_partition", recording)
    ker = BaseKernel("gaussian", sigma=2.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 3), dtype=jnp.float32)
    key = jax.random.PRNGKey(1)
    f_ref = hck.build_hck(x, levels=3, rank=8, key=key, kernel=ker)
    f_dist = dist_build_hck(x, levels=3, rank=8, key=key, kernel=ker,
                            mesh=kernel_mesh(1))
    assert len(calls) == 2
    (x_a, lv_a, k_a, m_a), (x_b, lv_b, k_b, m_b) = calls
    assert x_a is x and x_b is x
    assert (lv_a, m_a) == (lv_b, m_b) and (k_a == k_b).all()
    assert (np.asarray(f_dist.tree.perm) == np.asarray(f_ref.tree.perm)).all()


@needs_mesh
def test_dist_build_matches_single_host(f64, mesh8):
    """dist_build_hck == build_hck at 1e-12 in f64 (same key → same
    landmarks, same tree, same factors; only the placement differs)."""
    from repro.core.hck import build_hck
    from repro.core.kernels_fn import BaseKernel
    from repro.launch.dist_hck import dist_build_hck

    ker = BaseKernel("gaussian", sigma=2.0, jitter=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (1024, 4),
                          dtype=jnp.float64)
    key = jax.random.PRNGKey(1)
    f_ref = build_hck(x, levels=5, rank=8, key=key, kernel=ker)
    f_dist = dist_build_hck(x, levels=5, rank=8, key=key, kernel=ker,
                            mesh=mesh8)
    assert (np.asarray(f_dist.tree.perm) == np.asarray(f_ref.tree.perm)).all()
    assert _max_factor_diff(f_dist, f_ref) < 1e-12


@needs_mesh
def test_dist_build_streaming_matches_single_host(f64, mesh8):
    """The streaming mesh build (chunked host source, odd leaf_batch so
    the unsharded-remainder fallback path runs) == build_hck at 1e-12."""
    from repro.core.hck import build_hck
    from repro.core.kernels_fn import BaseKernel
    from repro.data.pipeline import ArraySource
    from repro.launch.dist_hck import dist_build_hck_streaming

    ker = BaseKernel("gaussian", sigma=2.0, jitter=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (1024, 4),
                          dtype=jnp.float64)
    key = jax.random.PRNGKey(1)
    f_ref = build_hck(x, levels=5, rank=8, key=key, kernel=ker)
    f_str = dist_build_hck_streaming(
        ArraySource(np.asarray(x)), levels=5, rank=8, key=key, kernel=ker,
        mesh=mesh8, leaf_batch=5, chunk_rows=300)
    assert _max_factor_diff(f_str, f_ref) < 1e-12


@needs_mesh
def test_dist_build_odd_n_padded(f64, mesh8):
    """An n that does not fill the tree pads host-side (pad_points) and
    then builds identically on mesh and single host."""
    from repro.core.hck import build_hck
    from repro.core.kernels_fn import BaseKernel
    from repro.launch.dist_hck import dist_build_hck

    # jitter 1e-4: the duplicate-and-jitter padded rows put near-identical
    # points into the landmark grams, and the parity bound is roundoff
    # AMPLIFIED by the Cholesky condition — the larger diagonal keeps the
    # 1e-12 bound honest instead of measuring conditioning
    n, rank = 777, 16
    ker = BaseKernel("gaussian", sigma=1.0, jitter=1e-4)
    x = jax.random.normal(jax.random.PRNGKey(0), (n, 3), dtype=jnp.float64)
    y = jnp.sin(x[:, 0])
    levels = max(3, auto_levels_ceil(n, rank))
    xp, _, _ = pad_points(x, y, rank, levels, jax.random.PRNGKey(2))
    assert xp.shape[0] % 8 == 0 and xp.shape[0] > n
    key = jax.random.PRNGKey(1)
    f_ref = build_hck(xp, levels=levels, rank=rank, key=key, kernel=ker)
    f_dist = dist_build_hck(xp, levels=levels, rank=rank, key=key,
                            kernel=ker, mesh=mesh8)
    assert _max_factor_diff(f_dist, f_ref) < 1e-12


@needs_mesh
def test_dist_build_rejects_shallow_tree(f64, mesh8):
    """levels < log2(P) cannot give every device a subtree."""
    from repro.core.kernels_fn import BaseKernel
    from repro.launch.dist_hck import dist_build_hck

    ker = BaseKernel("gaussian", sigma=2.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 3),
                          dtype=jnp.float64)
    with pytest.raises(ValueError):
        dist_build_hck(x, levels=2, rank=16, key=jax.random.PRNGKey(1),
                       kernel=ker, mesh=mesh8)


@needs_mesh
def test_subtree_sharding_layout(f64, mesh8):
    """The committed factors follow the subtree placement rule: per-leaf
    stacks sharded over the mesh axis, top-of-tree levels replicated."""
    from repro.core.hck import build_hck
    from repro.core.kernels_fn import BaseKernel
    from repro.launch.dist_hck import shard_by_subtree

    ker = BaseKernel("gaussian", sigma=2.0, jitter=1e-8)
    x = jax.random.normal(jax.random.PRNGKey(0), (1024, 4),
                          dtype=jnp.float64)
    f = build_hck(x, levels=5, rank=16, key=jax.random.PRNGKey(1),
                  kernel=ker)
    fs = shard_by_subtree(f, mesh8)

    assert not fs.u.sharding.is_fully_replicated   # (32, n0, r) leaf stack
    assert fs.u.addressable_shards[0].data.shape[0] == fs.u.shape[0] // 8
    assert not fs.adiag.sharding.is_fully_replicated
    assert fs.sigma[0].sharding.is_fully_replicated   # root: replicated
    assert not fs.sigma[4].sharding.is_fully_replicated   # 16 nodes: sharded
    # values untouched by placement
    assert float(jnp.max(jnp.abs(fs.u - f.u))) == 0.0
