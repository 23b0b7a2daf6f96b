"""Helpers that the per-layer metric readers in ``metrics/`` share."""
from __future__ import annotations

import numpy as np

import cost
from devtrace import union, within


def spans(reading, name: str) -> list:
    """The harness's ``bench.<name>`` spans inside the window."""
    return [s for s in within(reading.trace.spans, reading.lo, reading.hi)
            if s[0] == "bench." + name]


def ops(reading) -> list:
    """Device ops that start inside the window."""
    return within(reading.trace.ops, reading.lo, reading.hi)


def per_unit_ms(reading, total_ns: float, unit: str):
    """``total_ns`` over the number of ``unit`` spans, in ms (None when
    the window holds no such span or the total is 0)."""
    n = len(spans(reading, unit))
    return total_ns / n / 1e6 if n and total_ns > 0 else None


def module_ms(reading, module: str, unit: str):
    """Device time of the program ``module`` per ``unit`` span, in ms."""
    total = sum(e - s for name, s, e in
                within(reading.trace.modules, reading.lo, reading.hi)
                if name == module)
    return per_unit_ms(reading, total, unit)


def kernel_ns(reading, kernels) -> int:
    """Summed device time of the named kernels' ops in the window."""
    kernels = set(kernels)
    return sum(e - s for name, s, e in ops(reading) if name in kernels)


def host_ms(reading, unit: str):
    """Mean time per ``unit`` span in which no device op ran, in ms."""
    units = spans(reading, unit)
    if not units:
        return None
    busy = np.asarray(union(ops(reading), reading.lo, reading.hi),
                      dtype=np.int64).reshape(-1, 2)
    # busy time before each instant t: the whole intervals that end by t
    # plus the part of the one that straddles t
    done = np.concatenate([[0], np.cumsum(busy[:, 1] - busy[:, 0])])

    def busy_before(t):
        i = np.searchsorted(busy[:, 0], t, side="right")
        part = np.clip(t - busy[i - 1, 0], 0, busy[i - 1, 1] - busy[i - 1, 0]) \
            if i else 0
        return done[i - 1] + part if i else 0

    idle = [(e - s) - (busy_before(e) - busy_before(s)) for _, s, e in units]
    return float(np.mean(idle)) / 1e6


def idle_pct(reading):
    """Percent of the window in which no device op ran."""
    window_s = (reading.hi - reading.lo) / 1e9
    return 100.0 * (1.0 - reading.trace.busy_s(reading.lo, reading.hi)
                    / window_s)


def roofline_pct(reading, launches: list, kernels):
    """Roofline share of ``launches`` (stage, kwargs) over the device time
    of ``kernels``; None where the window ran none of them."""
    t = kernel_ns(reading, kernels)
    if t <= 0 or not launches:
        return None
    flops = sum(cost.stage_cost(s, **kw)[0] for s, kw in launches)
    nbytes = sum(cost.stage_cost(s, **kw)[1] for s, kw in launches)
    return cost.roofline_share(flops, nbytes, t / 1e9, reading.peak)


def unit_seconds(reading, unit: str) -> float:
    """Summed length of the ``unit`` spans, in seconds."""
    return sum(e - s for _, s, e in spans(reading, unit)) / 1e9


def served_batches(reading) -> list:
    """``(rows, bucket_rows, seconds)`` of every batch served in the
    window (the driver's record)."""
    serve = reading.window.get("serve")
    return serve["batches"] if serve else []


