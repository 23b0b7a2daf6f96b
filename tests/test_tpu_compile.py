"""Main-path Pallas stages compile for a described TPU v5e at real width.

Interpret mode checks neither Mosaic's lowering rules nor VMEM budgets nor
tiling; the TPU compiler, which is installed even without a chip, does.
Each case compiles one stage's ``ops`` wrapper with ``interpret=False`` at
the width of the YearPredictionMSD smoke (``chip_smoke.py``: 4096 leaves,
n0 = r = 128, d = 90, one right-hand side) for one chip of a described
``v5e:2x2`` topology, and asserts the program holds the Mosaic kernel
(``tpu_custom_call``) and fits the chip's memory.  The factoring stages
(``leaf_factor``, ``build_gram``) also compile at n0 = 256, each with the
tiles per program that :func:`repro.kernels.registry.tile_config` picks
there, and that pick must fit its VMEM budget (Mosaic itself refuses a
kernel over the scoped VMEM limit).  A compile that passes is not a chip
run: nothing executes here.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and a decision taken while
the module is imported would give pytest-xdist workers different tests.
"""
import functools
import os

import pytest

LEAVES, N0, R, D, K, Q = 4096, 128, 128, 90, 1, 1024
HBM_BYTES = 16 * 1024 ** 3          # one v5e chip
# cases that factor several tiles per program -> their registry stage
FACTORING = {"leaf_factor": "leaf_factor", "leaf_factor_256": "leaf_factor",
             "build_gram": "build_gram", "build_gram_256": "build_gram"}


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it.  The chip
    # runs 32-bit: with x64 on (another module's f64 fixture) the kernels'
    # loop counters would trace as int64, which Mosaic cannot lower.
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])
    compilation_cache.reset_cache()


def _case(stage: str):
    """(callable, argument shapes as (shape, ...)) for one stage."""
    from repro.kernels.build_stage import ops as build
    from repro.kernels.hck_leaf import ops as leaf
    from repro.kernels.matvec_stage import ops as matvec
    from repro.kernels.oos_stage import ops as oos
    from repro.kernels.policy_stage import ops as policy
    from repro.kernels.update_stage import ops as update

    gauss = dict(name="gaussian", sigma=3.9, interpret=False)
    pairs = LEAVES // 2
    return {
        # landmark Grams + Cholesky of the deepest level; leaf Grams
        "build_gram": (functools.partial(build.build_gram, jitter=1e-5,
                                         **gauss), [(pairs, R, D)]),
        "build_gram_256": (functools.partial(build.build_gram, jitter=1e-5,
                                             **gauss), [(pairs, 256, D)]),
        "build_gram_leaf": (functools.partial(build.build_gram, jitter=1e-5,
                                              want_chol=False, **gauss),
                            [(LEAVES, N0, D)]),
        # U of a sibling pair against the parent landmarks
        "build_cross": (functools.partial(build.build_cross, **gauss),
                        [(pairs, 2 * N0, D), (pairs, R, D), (pairs, R, R)]),
        "build_gram_dist": (functools.partial(build.build_gram_dist,
                                              jitter=1e-5, **gauss),
                            [(pairs, R, R)]),
        "build_cross_dist": (functools.partial(build.build_cross_dist,
                                               **gauss),
                             [(pairs, 2 * N0, R), (pairs, R, R)]),
        "policy_dist": (functools.partial(policy.policy_dist,
                                          interpret=False),
                        [(pairs, 2 * N0, D), (pairs, R, D)]),
        "leaf_factor": (functools.partial(leaf.leaf_factor, interpret=False),
                        [(LEAVES, N0, N0)]),
        # the same leaf count of twice the width (a 256-point leaf config)
        "leaf_factor_256": (functools.partial(leaf.leaf_factor,
                                              interpret=False),
                            [(LEAVES, 256, 256)]),
        "leaf_solve": (functools.partial(leaf.leaf_solve, interpret=False),
                       [(LEAVES, N0, N0), (LEAVES, N0, R), (LEAVES, R, R),
                        (LEAVES, N0, K)]),
        "leaf_matvec": (functools.partial(leaf.leaf_matvec, interpret=False),
                        [(LEAVES, N0, N0), (LEAVES, N0, R), (LEAVES, N0, K)]),
        "leaf_project": (functools.partial(leaf.leaf_project,
                                           interpret=False),
                         [(LEAVES, N0, R), (LEAVES, N0, K)]),
        # an online insert of a few hundred points: 3 appended rows a leaf
        "leaf_update": (functools.partial(update.leaf_update,
                                          interpret=False),
                        [(LEAVES, N0, N0), (LEAVES, N0, N0), (LEAVES, 3, N0),
                         (LEAVES, 3, 3)]),
        "oos_local": (functools.partial(oos.oos_contract, **gauss),
                      [(Q, N0, D), (Q, N0, K), (Q, D)]),
        "oos_walk": (functools.partial(oos.oos_contract, **gauss),
                     [(Q, R, D), (Q, R, K), (Q, D)]),
        "kernel_matvec": (functools.partial(matvec.kernel_matvec, **gauss),
                          [(4096, D), (8192, D), (8192, K)]),
    }[stage]


@pytest.mark.parametrize("stage", [
    "build_gram", "build_gram_256", "build_gram_leaf", "build_cross",
    "build_gram_dist", "build_cross_dist", "policy_dist", "leaf_factor",
    "leaf_factor_256", "leaf_solve",
    "leaf_matvec", "leaf_project", "leaf_update", "oos_local", "oos_walk",
    "kernel_matvec"])
def test_stage_compiles_for_v5e(one_chip, stage):
    import jax
    import jax.numpy as jnp

    fn, shapes = _case(stage)
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), stage
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, (stage, total)
    if stage in FACTORING:
        from repro.kernels.registry import tile_config

        _, n0, d = shapes[0]
        cfg = tile_config(FACTORING[stage], n0=n0, r=n0, k=1, d=d)
        assert cfg.tiles > 1 and cfg.fits, (stage, cfg)
