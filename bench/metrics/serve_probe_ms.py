"""Time per served batch of the serving loop's forced finiteness probe
(its ``hck.probe.serve`` span: three small programs and a read back)."""
import pathlib

import progtrace

TRACE = pathlib.Path(__file__).resolve().parents[2] / ".bench_trace"


def read(reading, trace=TRACE):
    pt = progtrace.of(trace)
    if pt is None:
        return None
    return pt.per_unit_ms("hck.probe.serve", "hck.serve", reading.lo,
                          reading.hi)
