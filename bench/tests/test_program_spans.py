"""The program's spans as the benchmark reads them (``progtrace``, the five
span readers, ``tools/gaps.py``).

``data/msd.serve.xplane.pb`` is a chip trace of a program without spans:
on it the clock offset is estimated and the span readers stay silent
while the older readers keep their values.  ``data/msd.fit.xplane.pb.gz``
and ``data/msd.serve.spans.xplane.pb.gz`` are chip traces of short traced
windows of the two cells with the spans: on them every span reader
matches a plain re-computation from ``ProfileData``.  At a tiny size on
the CPU, a traced run reports the span metrics, and a program whose spans
are taken out still runs with the readers silent.
"""
from __future__ import annotations

import contextlib
import gzip
import json
import pathlib
import sys

import pytest

import devtrace
import harness
import progtrace
from conftest import run_json

DATA = pathlib.Path(__file__).resolve().parent / "data"
METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
UNSPANNED = DATA / "msd.serve.xplane.pb"
SPANNED = {"msd.fit": DATA / "msd.fit.xplane.pb.gz",
           "msd.serve": DATA / "msd.serve.spans.xplane.pb.gz"}
READERS = {"msd.fit": ("fit_enqueue_ms",),
           "msd.serve": ("gen_copy_ms", "engine_enqueue_ms", "serve_wait_ms",
                         "serve_probe_ms")}
#: the older readers' values on the unspanned trace, as first read
BEFORE = {"oos_ms": 1.0341514680851063,
          "serve_host_ms": 4.96375070212766,
          "device_idle.serve": 47.94992349506675}


def _reader(name):
    return harness.load_module(METRICS / f"{name}.py")


def _reading(path):
    tr = devtrace.load(str(path)) if path.suffix == ".pb" else None
    pt = progtrace.load(path)
    (_, lo, hi), = [s[:3] for s in pt.spans if s[0] == "bench.window"]
    return harness.Reading(tr, lo, hi, {}, {}, {}, "TPU v5 lite")


def test_offset_of_the_recorded_trace():
    pt = progtrace.load(UNSPANNED)
    assert -1.35e6 <= pt.offset_ns <= -1.24e6
    # every program's end comes before the host hears of it
    assert pt.offset_floor_ns <= pt.offset_ns


def test_offset_by_hand():
    modules = [("a", 100, 110), ("b", 200, 260), ("c", 400, 405)]
    launches = [(50, 103), (150, 204), (300, 390)]       # b starts 4 early
    dones = [(115, 116), (265, 266), (420, 421)]
    assert progtrace._offset(modules, launches, dones) == (-4, -5)
    assert progtrace._offset(modules, launches[:2], dones) == (None, None)


@pytest.mark.parametrize("name", [n for names in READERS.values()
                                  for n in names])
def test_span_readers_silent_without_spans(name):
    assert _reader(name).read(_reading(UNSPANNED), trace=UNSPANNED) is None


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_older_readers_unchanged(name):
    assert _reader(name).read(_reading(UNSPANNED)) == pytest.approx(
        BEFORE[name], rel=1e-12)


def _plain(path):
    """Host events of a trace, read the plain way: ``name -> [(start,
    end)]`` for the ``hck.*`` and ``bench.*`` spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_serialized_xspace(
        gzip.decompress(path.read_bytes()))
    out: dict = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("hck.", "bench.")):
                        out.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    return out


def _expected(path, name):
    ev = _plain(path)
    (lo, hi), = ev["bench.window"]

    def inside(span):
        return [(s, e) for s, e in ev.get(span, []) if lo <= s < hi]

    def mean_ms(span, unit):
        return sum(e - s for s, e in inside(span)) / len(inside(unit)) / 1e6

    if name == "fit_enqueue_ms":
        return mean_ms("hck.fit", "hck.fit")
    if name == "gen_copy_ms":
        outer = inside("bench.serve")
        return sum((e - s) - sum(ie - is_ for is_, ie in inside("hck.serve")
                                 if s <= is_ and ie <= e)
                   for s, e in outer) / len(outer) / 1e6
    span = {"engine_enqueue_ms": "hck.engine",
            "serve_wait_ms": "hck.serve.wait",
            "serve_probe_ms": "hck.probe.serve"}[name]
    return mean_ms(span, "hck.serve")


@pytest.mark.parametrize("cell,name", [(c, n) for c, names in READERS.items()
                                       for n in names])
def test_span_readers_match_plain_sums(cell, name):
    path = SPANNED[cell]
    value = _reader(name).read(_reading(path), trace=path)
    assert value is not None and value > 0
    assert value == pytest.approx(_expected(path, name), rel=1e-9)


def test_serve_spans_cover_the_batch():
    """The four serve readers account for nearly all of a batch's span."""
    path = SPANNED["msd.serve"]
    reading = _reading(path)
    parts = sum(_reader(n).read(reading, trace=path)
                for n in READERS["msd.serve"])
    ev = _plain(path)
    (lo, hi), = ev["bench.window"]
    serve = [e - s for s, e in ev["bench.serve"] if lo <= s < hi]
    assert 0.9 <= parts / (sum(serve) / len(serve) / 1e6) <= 1.0


@pytest.mark.parametrize("cell", sorted(SPANNED))
def test_spanned_trace_offset(cell):
    pt = progtrace.load(SPANNED[cell])
    assert pt.offset_ns is not None and pt.offset_ns < 0
    assert pt.offset_floor_ns <= pt.offset_ns


def _trace(spans, ops):
    return progtrace.ProgramTrace(
        [(n, s, e, {}) for n, s, e in spans], ops, 0, 0)


def test_gaps_by_hand():
    sys.path.insert(0, str(METRICS.parent / "tools"))
    import gaps

    pt = _trace([("bench.window", 0, 100), ("bench.serve", 10, 60),
                 ("hck.serve", 20, 60), ("hck.serve.wait", 30, 40)],
                [("op", 0, 12), ("op", 25, 32), ("op", 38, 45),
                 ("op", 70, 90)])
    assert gaps.gaps(pt, 0, 100, 1) == [
        (12, 13, "bench.serve"), (32, 6, "hck.serve.wait"),
        (45, 25, "hck.serve"), (90, 10, "bench.window")]
    assert [g[1] for g in gaps.gaps(pt, 0, 100, 10)] == [13, 25, 10]
    assert gaps.split(pt, gaps.gaps(pt, 0, 100, 1)) == {
        "bench.serve": 8, "hck.serve": 20, "hck.serve.wait": 6,
        "bench.window": 20}


def test_span_sums_by_hand():
    pt = _trace([("bench.serve", 0, 10), ("hck.serve", 2, 8),
                 ("hck.engine", 3, 4), ("hck.engine", 3, 5),
                 ("bench.serve", 20, 30), ("hck.serve", 21, 30),
                 ("hck.engine", 22, 24)], [])
    assert pt.per_unit_ms("hck.engine", "hck.serve", 0, 40) == 4 / 2 / 1e6
    assert pt.uncovered_ms("bench.serve", "hck.serve", 0, 40) == 5 / 2 / 1e6
    assert pt.per_unit_ms("hck.engine", "hck.fit", 0, 40) is None
    assert pt.uncovered_ms("bench.fit", "hck.serve", 0, 40) is None


def _span_metrics(result):
    return {m for names in READERS.values() for m in names} & set(
        result["metrics"])


def _cpu_readers(tree):
    """Keep the readers a CPU trace can feed: the span readers and the
    pad share (the rest need a TPU's ops or its peaks)."""
    path = tree / "BENCHMARK.json"
    spec = json.loads(path.read_text())
    keep = {n for names in READERS.values() for n in names}
    spec["per_layer"] = [m for m in spec["per_layer"]
                         if m["name"] in keep | {"pad_share.serve"}]
    path.write_text(json.dumps(spec))


@pytest.mark.parametrize("cell", ["msd.fit", "msd.serve"])
def test_traced_run_reports_span_metrics(tree, capsys, cell):
    _cpu_readers(tree)
    traced = run_json(tree, cell, seconds=1.0, trace=True, capsys=capsys)
    assert traced["correct"], traced["checks"]
    assert _span_metrics(traced) == set(READERS[cell])
    assert all(traced["metrics"][m]["value"] > 0 for m in READERS[cell])


def test_program_without_spans_runs_silent(tree, capsys, monkeypatch):
    from repro.core import hck, krr
    from repro.runtime import health
    from repro.serving import predict_service, serve_loop

    for module in (hck, krr, health, predict_service, serve_loop):
        monkeypatch.setattr(module, "span",
                            lambda *a, **k: contextlib.nullcontext())
    _cpu_readers(tree)
    traced = run_json(tree, "msd.serve", seconds=1.0, trace=True,
                      capsys=capsys)
    assert traced["correct"], traced["checks"]
    assert _span_metrics(traced) == set()
    assert "pad_share.serve" in traced["metrics"]
