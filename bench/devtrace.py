"""Reduction of a JAX profiler trace to what the per-layer metrics read.

A trace (``<dir>/plugins/profile/<time>/*.xplane.pb``) is read with
``jax.profiler.ProfileData``.  Three kinds of event are kept, all on the
profiler's one clock, in nanoseconds:

* device ops: the ``XLA Ops`` line of every ``/device:TPU:<i>`` plane,
  named by the HLO instruction (``%hck_leaf_factor.1 = ...`` is kept as
  ``hck_leaf_factor``);
* device programs: the ``XLA Modules`` line (``jit_invert_with_leaf(..)``
  is kept as ``jit_invert_with_leaf``);
* host spans: events of the host plane whose name starts with ``bench.``,
  the ``TraceAnnotation`` spans the harness puts around its calls.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

_SUFFIX = re.compile(r"\.\d+$")


@dataclasses.dataclass
class Trace:
    """Events as ``(name, start_ns, end_ns)`` tuples, sorted by start;
    ``device_ops`` holds each device's ops apart, ``ops`` all of them."""

    device_ops: list
    modules: list
    spans: list

    @property
    def ops(self) -> list:
        """Every device's ops, sorted by start."""
        return sorted((ev for dev in self.device_ops for ev in dev),
                      key=lambda ev: ev[1])

    def busy_s(self, lo: int, hi: int) -> float:
        """Seconds in ``[lo, hi]`` in which an op ran, averaged over the
        devices."""
        return sum(busy_ns(dev, lo, hi) for dev in self.device_ops) / (
            1e9 * max(len(self.device_ops), 1))


def op_key(name: str) -> str:
    """``%hck_leaf_factor.1 = f32[..] custom-call(..)`` -> ``hck_leaf_factor``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def module_key(name: str) -> str:
    """``jit_invert_with_leaf(1234)`` -> ``jit_invert_with_leaf``."""
    return name.split("(", 1)[0]


def find(log_dir: str) -> str:
    """The one ``.xplane.pb`` file under ``log_dir``."""
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(files)}")
    return files[0]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` file (or the one under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find(path)
    data = ProfileData.from_file(path)
    device_ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            device_ops.append(ops)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(op_key(e.name), int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                            for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [(module_key(e.name), int(e.start_ns),
                                 int(e.start_ns + e.duration_ns))
                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, int(e.start_ns),
                           int(e.start_ns + e.duration_ns))
                          for e in line.events if e.name.startswith("bench.")]
    return Trace([sorted(ops, key=lambda e: e[1]) for ops in device_ops],
                 sorted(modules, key=lambda e: e[1]),
                 sorted(spans, key=lambda e: e[1]))


def union(intervals, lo: int, hi: int) -> list:
    """Merged ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    out = []
    for _, s, e in sorted(intervals, key=lambda ev: ev[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return sum(e - s for s, e in union(intervals, lo, hi))


def within(events, lo: int, hi: int) -> list:
    """Events that start inside ``[lo, hi)``."""
    return [ev for ev in events if lo <= ev[1] < hi]


def total_ns(events, keys) -> int:
    """Summed durations of the events whose name is in ``keys``."""
    keys = set(keys)
    return sum(e - s for name, s, e in events if name in keys)


def top(events, n: int = 10) -> list:
    """The ``n`` names with the most summed duration, in seconds."""
    acc: dict = {}
    for name, s, e in events:
        acc[name] = acc.get(name, 0) + (e - s)
    return [[k, v / 1e9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops, spans, lo: int, hi: int, n: int = 10) -> list:
    """The ``n`` longest device-idle gaps in ``[lo, hi]``, each labelled
    with the innermost host span around its midpoint (``idle`` where the
    harness was in none), in seconds."""
    busy = union(ops, lo, hi)
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if hi > prev:
        gaps.append((prev, hi))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) // 2
        inner = [sp for sp in spans if sp[1] <= mid < sp[2]]
        label = min(inner, key=lambda sp: sp[2] - sp[1])[0] if inner \
            else "idle"
        out.append([label, (e - s) / 1e9])
    return out
