"""Sweep the offered rate of an open-loop cell to find its knee.

  python3 bench/tools/knee.py --workload msd.serve --seed 5 \\
      --seconds 6 --rates 8000 14000 22000 26000

One set-up, then one window per rate (the cell's traffic file with
``rate_rps`` replaced).  Prints, per rate, the offered and served points
per second, p50/p95 latency, the time the queue took to drain after the
window closed, and the share of the window the serving loop was busy.
The knee is the highest rate whose drain stays near one batch's service
time and whose busy share stays below one.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()

    import harness

    root = BENCH.parent
    cell = harness.Cell.find(root, args.workload)
    harness.run_env(root, cell)
    import numpy as np

    why = harness.platform_error(cell.workload["chips"])
    if why is not None:
        print(f"knee: {why}", file=sys.stderr)
        return 3
    ctx = harness.Context(cell, args.seed)
    driver = cell.driver()
    state = driver.setup(ctx)
    for rate in args.rates:
        traffic = dict(cell.traffic, rate_rps=rate)
        rec = driver.serve_window(ctx, state, args.seconds, traffic)
        lat = rec["latency"] * 1e3
        done = rec["due"] + rec["latency"]
        rows = sum(b[0] for b in rec["batches"])
        busy = sum(b[2] for b in rec["batches"])
        print(json.dumps({
            "rate_rps": rate, "requests": int(len(lat)),
            "points_per_s": rows / args.seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "drain_ms": float((done.max() - args.seconds) * 1e3),
            "busy_share": busy / float(done.max()),
            "batches": len(rec["batches"]),
            "mean_rows": rows / max(len(rec["batches"]), 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
