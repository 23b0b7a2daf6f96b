"""Time per served batch spent in the harness's ``bench.serve`` span but
outside the program's ``hck.serve``: the load generator's gather of the batch's
rows and their copy to the device."""
import pathlib

import progtrace

TRACE = pathlib.Path(__file__).resolve().parents[2] / ".bench_trace"


def read(reading, trace=TRACE):
    pt = progtrace.of(trace)
    if pt is None:
        return None
    return pt.uncovered_ms("bench.serve", "hck.serve", reading.lo,
                           reading.hi)
