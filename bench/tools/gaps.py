"""Device-idle gaps of a traced run, on the host's clock, by span.

  python3 bench/tools/gaps.py .bench_trace             # a run's trace dir
  python3 bench/tools/gaps.py bench/tests/data/msd.fit.xplane.pb.gz

Reads the trace with ``progtrace``, which moves the device's events onto
the host's clock by the estimated offset, and takes the harness's
``bench.window`` span.  Lists every gap of at least ``--min-ms`` in which
no device op ran, labelled by the innermost ``hck.*`` or ``bench.*`` span
over its midpoint (``idle`` where there is none), then the gaps' totals by
that label and the idle time by the innermost span open at each instant,
per fit or served batch of the window and as a share of it.
"""
from __future__ import annotations

import argparse
import bisect
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import progtrace  # noqa: E402


def innermost(spans: list) -> tuple:
    """``(starts, labels)``: from each start on, the label of the shortest
    span open there, until the next start."""
    edges = sorted([(s, 1, i) for i, (_, s, _, _) in enumerate(spans)]
                   + [(e, 0, i) for i, (_, _, e, _) in enumerate(spans)])
    open_, starts, labels = set(), [], []
    for t, opening, i in edges:
        (open_.add if opening else open_.discard)(i)
        inner = min(open_, key=lambda j: spans[j][2] - spans[j][1],
                    default=None)
        starts.append(t)
        labels.append("idle" if inner is None else spans[inner][0])
    return starts, labels


def gaps(pt, lo: int, hi: int, min_ns: int) -> list:
    """``(start_ns, length_ns, label)`` of the idle gaps in ``[lo, hi]``."""
    busy = devtrace.union(devtrace.within(pt.ops, lo, hi), lo, hi)
    edges = [lo] + [t for s, e in busy for t in (s, e)] + [hi]
    starts, labels = innermost(pt.spans)
    out = []
    for s, e in zip(edges[::2], edges[1::2]):
        if e - s >= min_ns:
            i = bisect.bisect_right(starts, (s + e) // 2) - 1
            out.append((s, e - s, labels[i] if i >= 0 else "idle"))
    return out


def split(pt, found: list) -> dict:
    """Idle time of the gaps ``found``, in ns, by the innermost span open
    at each instant (a gap across several spans is shared among them)."""
    starts, labels = innermost(pt.spans)
    out: dict = {}
    for s, n, _ in found:
        t, e = s, s + n
        i = bisect.bisect_right(starts, t) - 1
        while t < e:
            end = min(starts[i + 1], e) if i + 1 < len(starts) else e
            label = labels[i] if i >= 0 else "idle"
            out[label] = out.get(label, 0) + end - t
            t, i = end, i + 1
    return out


def _table(title: str, totals: dict, counts: dict, n_units: int,
           window_ns: int) -> None:
    print(f"\n{title}\nlabel  gaps  total_ms  per_unit_ms  window_%")
    for label, ns in sorted(totals.items(), key=lambda kv: -kv[1]):
        per = f"{ns / n_units / 1e6:.4f}" if n_units else "-"
        print(f"{label}  {counts.get(label, '-')}  {ns / 1e6:.3f}  {per}  "
              f"{100 * ns / window_ns:.2f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="an .xplane.pb(.gz) file or a trace dir")
    ap.add_argument("--min-ms", type=float, default=0.1)
    args = ap.parse_args(argv)
    pt = progtrace.load(args.trace)
    (_, lo, hi), = [s[:3] for s in pt.spans if s[0] == "bench.window"]
    units = [u for u in ("fit", "serve")
             if pt.named("bench." + u, lo, hi)]
    n_units = len(pt.named("bench." + units[0], lo, hi)) if units else 0
    offset = ("not estimated" if pt.offset_ns is None
              else f"{pt.offset_ns / 1e6:.4f} ms")
    floor = ("none" if pt.offset_floor_ns is None
             else f"{pt.offset_floor_ns / 1e6:.4f} ms")
    print(f"window {(hi - lo) / 1e6:.3f} ms, {n_units} "
          f"{'bench.' + units[0] if units else 'units'}; device clock minus "
          f"host clock {offset} (floor from completions {floor})")
    found = gaps(pt, lo, hi, int(args.min_ms * 1e6))
    print("start_ms  length_ms  label")
    for s, n, label in found:
        print(f"{(s - lo) / 1e6:9.3f} {n / 1e6:10.4f}  {label}")
    totals: dict = {}
    counts: dict = {}
    for _, n, label in found:
        totals[label] = totals.get(label, 0) + n
        counts[label] = counts.get(label, 0) + 1
    _table("gaps by the span over their midpoint", totals, counts, n_units,
           hi - lo)
    _table("idle time by the span open at each instant", split(pt, found),
           {}, n_units, hi - lo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
