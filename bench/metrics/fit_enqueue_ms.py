"""Host time of ``krr.fit`` per fit (its ``hck.fit`` span): how long the
host takes to enqueue a fit's programs before the call returns."""
import pathlib

import progtrace

TRACE = pathlib.Path(__file__).resolve().parents[2] / ".bench_trace"


def read(reading, trace=TRACE):
    pt = progtrace.of(trace)
    if pt is None:
        return None
    return pt.per_unit_ms("hck.fit", "hck.fit", reading.lo, reading.hi)
