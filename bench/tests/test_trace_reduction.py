"""The trace -> metrics reduction, on a small trace recorded on the chip.

``data/msd.serve.xplane.pb`` is the profiler's trace of a short traced
``msd.serve`` window on one TPU v5e (``bench/run.py --trace 1``).  The
sums, the idle share and the roofline arithmetic are recomputed here by
simpler means and compared with ``devtrace``/``layers``/``cost``.
"""
from __future__ import annotations

import pathlib

import numpy as np
import pytest

import cost
import devtrace
import layers

DATA = pathlib.Path(__file__).resolve().parent / "data"
TRACE = DATA / "msd.serve.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return devtrace.load(str(TRACE))


@pytest.fixture(scope="module")
def raw():
    """Every device op and module of the trace, read the plain way."""
    from jax.profiler import ProfileData

    ops, modules = [], []
    for plane in ProfileData.from_file(str(TRACE)).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, int(e.start_ns),
                          int(e.start_ns + e.duration_ns))
                    if line.name == "XLA Ops":
                        ops.append(ev)
                    elif line.name == "XLA Modules":
                        modules.append(ev)
    return ops, modules


def _window(tr):
    (_, lo, hi), = [s for s in tr.spans if s[0] == "bench.window"]
    return lo, hi


def test_events_found(tr, raw):
    assert len(tr.device_ops) == 1
    assert len(tr.ops) == len(raw[0]) > 0
    assert len(tr.modules) == len(raw[1]) > 0
    names = {s[0] for s in tr.spans}
    assert {"bench.window", "bench.serve"} <= names


def test_per_layer_sums(tr, raw):
    lo, hi = _window(tr)
    want = sum(e - s for name, s, e in raw[0]
               if name.startswith("%oos_contract_kernel") and lo <= s < hi)
    assert want > 0
    assert devtrace.total_ns(devtrace.within(tr.ops, lo, hi),
                             ["oos_contract_kernel"]) == pytest.approx(want)
    want_mod = sum(e - s for name, s, e in raw[1]
                   if name.startswith("jit_apply_plan(") and lo <= s < hi)
    n = len([s for s in tr.spans if s[0] == "bench.serve" and lo <= s[1] < hi])

    class R:
        trace, window = tr, {}
    R.lo, R.hi = lo, hi
    assert layers.module_ms(R, "jit_apply_plan", "serve") == pytest.approx(
        want_mod / n / 1e6)


def test_host_time_per_span(tr):
    lo, hi = _window(tr)

    class R:
        trace, window = tr, {}
    R.lo, R.hi = lo, hi
    units = [s for s in tr.spans if s[0] == "bench.serve" and lo <= s[1] < hi]
    ops = devtrace.within(tr.ops, lo, hi)
    want = sum((e - s) - devtrace.busy_ns(ops, s, e) for _, s, e in units)
    assert layers.host_ms(R, "serve") == pytest.approx(want / len(units) / 1e6)


def test_idle_share_is_a_union(tr, raw):
    lo, hi = _window(tr)
    # a 1 us grid marks every microsecond some op covers
    grid = np.zeros((hi - lo) // 1000 + 1, bool)
    for _, s, e in raw[0]:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            grid[(s - lo) // 1000:(e - lo + 999) // 1000] = True
    busy = tr.busy_s(lo, hi)
    assert busy == pytest.approx(grid.sum() * 1e-6, rel=0.02)
    assert busy < sum(min(e, hi) - max(s, lo) for _, s, e in raw[0]
                      if e > lo and s < hi) / 1e9 + 1e-12


def test_union_by_hand():
    ev = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 35, 36)]
    assert devtrace.union(ev, 0, 100) == [[0, 20], [30, 40]]
    assert devtrace.busy_ns(ev, 8, 32) == 14
    gaps = devtrace.idle_gaps(ev, [("bench.serve", 18, 35)], 0, 50)
    assert gaps[0] == ["bench.serve", 10e-9] and gaps[1] == ["idle", 10e-9]


def test_roofline_by_hand():
    peak = cost.peaks("TPU v5 lite")
    # 2 GFLOP and 1 GB in 10 ms: bytes bind, 1e9 / 819e9 s = 1.2210 ms
    assert cost.roofline_share(2e9, 1e9, 0.01, peak) == pytest.approx(
        100 * (1e9 / 819e9) / 0.01)
    # compute binds: 197e9 flops take 1 ms at the bf16 peak
    assert cost.roofline_share(197e9, 1e3, 0.002, peak) == pytest.approx(50)
    f, b = cost.stage_cost("oos_local", batch=10, n0=128, d=90, k=1)
    assert f == 10 * (2 * 128 * 90 + 5 * 128 + 2 * 128)
    assert b == 10 * 4 * (128 * 91 + 91)


def test_unknown_device_raises():
    with pytest.raises(KeyError, match="no peaks"):
        cost.peaks("TPU v99")
