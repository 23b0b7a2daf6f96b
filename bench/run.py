"""Run one benchmark cell once.

  python3 bench/run.py --workload msd.fit --seed 7 --seconds 30 --trace 0
  python3 bench/run.py --workload msd.fit --seed 7 --seconds 3 --control

From the root of a checkout.  Prints progress, then as the last line of
standard output one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, ``checks``; with ``--trace 1`` the per-layer
metrics and a ``breakdown``), and as the last lines of standard error
each number compared with its limit.  Exits non-zero, printing no result,
where JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the configuration's lower-precision control "
                         "in the program's place (reads correct: false)")
    args = ap.parse_args(argv)
    bench = pathlib.Path(__file__).resolve().parent
    sys.path.insert(0, str(bench))
    import harness

    return harness.run(bench.parent, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start,
                       control=args.control)


if __name__ == "__main__":
    sys.exit(main())
