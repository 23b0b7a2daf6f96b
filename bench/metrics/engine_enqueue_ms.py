"""Host time of ``PredictEngine.apply`` per served batch (its ``hck.engine``
span): validation, padding, the ``apply_plan`` dispatch and the slice."""
import pathlib

import progtrace

TRACE = pathlib.Path(__file__).resolve().parents[2] / ".bench_trace"


def read(reading, trace=TRACE):
    pt = progtrace.of(trace)
    if pt is None:
        return None
    return pt.per_unit_ms("hck.engine", "hck.serve", reading.lo,
                          reading.hi)
