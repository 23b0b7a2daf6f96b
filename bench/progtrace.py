"""The program's own spans in a traced run, with the device on the host's
clock.

The program marks its host work with ``hck.*`` spans
(``repro.runtime.trace.span``); the harness marks its own with
``bench.*``.  Both are host events.  The device's ops and programs carry
the device's timestamps, which the profiler puts on the host's timeline
with an offset: on one TPU v5e the device's events sit about 1.3 ms
early.  This module reads the spans, with the ids each carries, and the
device's events, and estimates that offset from the host's runtime
events:

* every program starts after the host's ``TpuLoadedExecutable::
  ExecuteLaunch`` for it ends, so ``offset <= device start - launch end``
  for each program, paired with its launch in launch order; the estimate
  is the smallest such difference;
* the host's ``tpu::System::Execute=>Done`` for a program comes after the
  program ends, so ``offset >= device end - done``; the largest such
  difference is a bound on the estimate, kept as ``offset_floor_ns``.

``offset`` is the device's clock minus the host's (negative: the device's
events sit early).  The estimate is made where the trace holds one device
and as many launches as programs; elsewhere it is None and the device's
events are left as they are.

A trace is loaded once per path and modification time, so the readers of
one run share it.  Readers find the run's trace in ``.bench_trace`` at the
root of the checkout, which the harness removes after every reader ran.
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import os
import pathlib

import devtrace

LAUNCH = "TpuLoadedExecutable::ExecuteLaunch"
DONE = "tpu::System::Execute=>Done"
PREFIXES = ("hck.", "bench.")

_CACHE: dict = {}


@dataclasses.dataclass
class ProgramTrace:
    """Host spans ``(name, start_ns, end_ns, ids)`` and device ops
    ``(name, start_ns, end_ns)`` on the host's clock, sorted by start."""

    spans: list
    ops: list
    offset_ns: int | None
    offset_floor_ns: int | None

    @property
    def has_program_spans(self) -> bool:
        """Whether the program recorded any ``hck.*`` span."""
        return any(s[0].startswith("hck.") for s in self.spans)

    def named(self, name: str, lo: int, hi: int) -> list:
        """``(name, start_ns, end_ns)`` of the spans called ``name`` that
        start inside ``[lo, hi)``."""
        return [s[:3] for s in self.spans if s[0] == name and lo <= s[1] < hi]

    def per_unit_ms(self, name: str, unit: str, lo: int, hi: int):
        """Time inside ``name`` spans (nested ones counted once) per
        ``unit`` span, both starting inside ``[lo, hi)``, in ms; None
        where the window holds no ``unit`` span."""
        units = self.named(unit, lo, hi)
        if not units:
            return None
        inside = self.named(name, lo, hi)
        return devtrace.busy_ns(inside, lo, hi) / len(units) / 1e6

    def uncovered_ms(self, outer: str, inner: str, lo: int, hi: int):
        """Mean time per ``outer`` span that no ``inner`` span covers, in
        ms; None where the window holds no span of either name."""
        outers, inners = self.named(outer, lo, hi), self.named(inner, lo, hi)
        if not outers or not inners:
            return None
        merged = devtrace.union(inners, lo, hi)
        starts = [s for s, _ in merged]
        covered = 0
        for _, s, e in outers:
            i = max(bisect.bisect_right(starts, s) - 1, 0)
            while i < len(merged) and merged[i][0] < e:
                covered += max(0, min(e, merged[i][1]) - max(s, merged[i][0]))
                i += 1
        total = sum(e - s for _, s, e in outers)
        return (total - covered) / len(outers) / 1e6


def _data(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(
                gzip.decompress(f.read()))
    return ProfileData.from_file(path)


def _offset(modules: list, launches: list, dones: list):
    """``(offset, floor)`` from one device's programs and the host's
    launches and completions, each paired in order; None where the counts
    differ."""
    if not modules or len(launches) != len(modules):
        return None, None
    offset = min(m[1] - end for m, (_, end) in zip(modules, launches))
    floor = None
    if len(dones) == len(modules):
        floor = max(m[2] - start for m, (start, _) in zip(modules, dones))
    return offset, floor


def _read(path: str) -> ProgramTrace:
    data = _data(path)
    devices, spans, launches, dones = [], [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(devtrace.op_key(e.name), int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                            for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [(devtrace.module_key(e.name), int(e.start_ns),
                                 int(e.start_ns + e.duration_ns))
                                for e in line.events]
            devices.append((ops, sorted(modules, key=lambda m: m[1])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    s, t = int(e.start_ns), int(e.start_ns + e.duration_ns)
                    if e.name.startswith(PREFIXES):
                        spans.append((e.name, s, t, dict(e.stats)))
                    elif e.name == LAUNCH:
                        launches.append((s, t))
                    elif e.name == DONE:
                        dones.append((s, t))
    offset = floor = None
    if len(devices) == 1:
        offset, floor = _offset(devices[0][1], sorted(launches),
                                sorted(dones))
    shift = offset or 0
    ops = sorted(((n, s - shift, e - shift)
                  for dev_ops, _ in devices for n, s, e in dev_ops),
                 key=lambda ev: ev[1])
    return ProgramTrace(sorted(spans, key=lambda s: s[1]), ops, offset,
                        floor)


def load(path) -> ProgramTrace:
    """The trace in ``path``: an ``.xplane.pb`` file, the same gzipped
    (``.xplane.pb.gz``), or the directory that holds the one trace."""
    path = str(path)
    if os.path.isdir(path):
        path = devtrace.find(path)
    key = (os.path.realpath(path), os.stat(path).st_mtime_ns)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = _read(path)
    return _CACHE[key]


def of(path) -> ProgramTrace | None:
    """The trace in ``path`` where it holds the program's spans, else None
    (no trace, or a program without spans)."""
    try:
        pt = load(pathlib.Path(path))
    except FileNotFoundError:
        return None
    return pt if pt.has_program_spans else None
