"""Time per served batch the serving loop blocks on the device's result
(its ``hck.serve.wait`` span, around ``block_until_ready``)."""
import pathlib

import progtrace

TRACE = pathlib.Path(__file__).resolve().parents[2] / ".bench_trace"


def read(reading, trace=TRACE):
    pt = progtrace.of(trace)
    if pt is None:
        return None
    return pt.per_unit_ms("hck.serve.wait", "hck.serve", reading.lo,
                          reading.hi)
