"""The program's ``hck.*`` host spans on the fit and serve paths.

A small ``krr.fit`` and two ``KRRServeLoop.serve`` calls run under the
profiler; the ``.xplane.pb`` it writes is read back with ``ProfileData``.
The spans must all be there, nested as the benchmark's readers assume,
carry the loop's batch count, appear for a probe only once its gate has
passed, and leave every compiled program (so every output) unchanged.
"""
import glob
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import krr
from repro.core.kernels_fn import BaseKernel
from repro.kernels.registry import SolveConfig
from repro.serving.predict_service import ModelRegistry
from repro.serving.serve_loop import KRRServeLoop

FIT_SPANS = ("hck.fit.pad", "hck.partition", "hck.build", "hck.invert",
             "hck.solve", "hck.prepare")
SERVE_SPANS = ("hck.engine", "hck.serve.wait", "hck.probe.serve")


def _problem():
    kx, ky, kq = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.uniform(kx, (500, 4), jnp.float32)
    y = jnp.sin(3 * x).sum(axis=1) + 0.05 * jax.random.normal(ky, (500,))
    xq = jax.random.uniform(kq, (40, 4), jnp.float32)
    return x, y.astype(jnp.float32), xq


def _run(config=None):
    """Fit, then serve two batches; the alpha and both answers."""
    x, y, xq = _problem()
    model = krr.fit(x, y, kernel=BaseKernel("gaussian", sigma=0.8,
                                            jitter=1e-5),
                    lam=1e-2, rank=16, key=jax.random.PRNGKey(1),
                    solve_config=config)
    loop = KRRServeLoop(ModelRegistry(model, min_bucket=8, max_bucket=64))
    served = [loop.serve(xq[:10]).z, loop.serve(xq[10:30]).z]
    return [np.asarray(a) for a in (model.alpha, *served)]


def _spans(log_dir):
    """``(name, start_ns, end_ns, thread, stats)`` of every ``hck.*``
    event in the one trace under ``log_dir``."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     line.name, dict(e.stats))
                    for e in line.events if e.name.startswith("hck.")]
    return out


def _traced(log_dir, config=None):
    jax.profiler.start_trace(str(log_dir))
    try:
        outputs = _run(config)
    finally:
        jax.profiler.stop_trace()
    return outputs, _spans(log_dir)


@pytest.fixture(scope="module")
def record(tmp_path_factory):
    """Outputs and spans with the probes' gate closed and open, and the
    outputs of the same run with the profiler off."""
    with mock.patch.dict(os.environ):
        os.environ.pop("REPRO_STRICT_FINITE", None)
        _run()                                   # compile outside the trace
        closed = _traced(tmp_path_factory.mktemp("closed"))
        opened = _traced(tmp_path_factory.mktemp("open"),
                         SolveConfig(checks=True))
        plain = _run()
    return {"closed": closed, "open": opened, "plain": plain}


def _named(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.mark.parametrize("name", ("hck.fit",) + FIT_SPANS + (
    "hck.serve", "hck.serve.attempt") + SERVE_SPANS)
def test_span_recorded(record, name):
    assert _named(record["closed"][1], name)


@pytest.mark.parametrize("child,parent", [
    *[(c, "hck.fit") for c in FIT_SPANS],
    *[(c, "hck.serve.attempt") for c in SERVE_SPANS],
    ("hck.serve.attempt", "hck.serve"),
])
def test_span_nesting(record, child, parent):
    spans = record["closed"][1]
    outer = _named(spans, parent)
    for _, s, e, thread, _ in _named(spans, child):
        assert any(ps <= s and e <= pe and pt == thread
                   for _, ps, pe, pt, _ in outer), (child, parent)


def test_serve_batch_ids(record):
    serves = sorted(_named(record["closed"][1], "hck.serve"),
                    key=lambda sp: sp[1])
    assert [sp[4].get("batch") for sp in serves] == [0, 1]
    assert len(_named(record["closed"][1], "hck.serve.attempt")) == 2


@pytest.mark.parametrize("gate,count", [("closed", 0), ("open", 1)])
def test_probe_span_follows_gate(record, gate, count):
    spans = record[gate][1]
    for stage in ("build", "leaf_factor", "solve"):
        assert len(_named(spans, "hck.probe." + stage)) == count, stage
    # serving validates every batch whatever the gate
    assert len(_named(spans, "hck.probe.serve")) == 2


@pytest.mark.parametrize("gate", ["closed", "open"])
def test_outputs_bitwise_unchanged(record, gate):
    for traced, plain in zip(record[gate][0], record["plain"]):
        np.testing.assert_array_equal(traced, plain)
