"""Benchmark entry point: one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # all, CPU-scaled
  PYTHONPATH=src python -m benchmarks.run fig3       # one
  PYTHONPATH=src python -m benchmarks.run --smoke cg # one CI smoke gate

Prints ``name,us_per_call,derived`` CSV blocks per benchmark plus the
per-figure detail tables.  ``--smoke <name>`` (name one of solve, oos,
build, sweep, cg, dist, update, roofline) is the CI entry point: it runs the
matching ``bench_<name>.py --smoke --out BENCH_<name>.json`` as a
subprocess (several gates flip ``jax_enable_x64`` globally, so isolation
is mandatory) and exits with the gate's status — the ci.yml bench matrix
fans out over exactly these names.  ``roofline`` maps to
``roofline_report.py --smoke`` (the autotune tile-DB cache-hit gate).
"""
from __future__ import annotations

import sys
import time

#: CI smoke gates: --smoke <name> -> bench_<name>.py --smoke
SMOKE_BENCHES = ("solve", "oos", "build", "sweep", "cg", "dist", "update",
                 "landmarks", "roofline")

#: smoke benches whose gate lives outside the bench_<name>.py convention
SMOKE_SCRIPTS = {"roofline": "roofline_report.py"}


#: figure/table sections: name -> (title, ``python -m`` arguments).  Each
#: runs as its own process: the parent never touches JAX (a chip belongs
#: to one process, so a parent holding it would starve every child), and a
#: section that flips ``jax_enable_x64`` for its parity gates cannot
#: re-dtype a later one.
SECTIONS = {
    "fig3": ("fig3_stability (error-vs-sigma variance bands)",
             ["benchmarks.fig3_stability"]),
    "fig4": ("fig4_partitioning (+Table 2 overhead)",
             ["benchmarks.fig4_partitioning"]),
    "fig56": ("fig5/6 performance vs r / time / memory",
              ["benchmarks.fig56_perf_vs_r"]),
    "fig7": ("fig7 n-vs-r trade-off", ["benchmarks.fig7_n_vs_r"]),
    "fig8": ("fig8 kernel-PCA alignment", ["benchmarks.fig8_kpca"]),
    "sweep": ("sweep engine (σ×λ grid amortization, BENCH_sweep.json)",
              ["benchmarks.bench_sweep", "--smoke",
               "--out", "BENCH_sweep.json"]),
    "cg": ("iterative solvers (exact-kernel PCG, BENCH_cg.json)",
           ["benchmarks.bench_cg", "--smoke", "--out", "BENCH_cg.json"]),
    "cost": ("cost scaling of Alg 1/2/3 (paper §4.5)",
             ["benchmarks.cost_scaling"]),
    "roofline": ("roofline table (dry-run artifacts + BENCH_*.json)",
                 ["benchmarks.roofline_report", "--bench-dir", "."]),
}


def _section(name):
    print(f"\n==== {name} " + "=" * max(0, 60 - len(name)), flush=True)


def run_smoke(name: str) -> int:
    """Run one bench_<name>.py CI smoke gate in a subprocess.

    Returns the subprocess exit code (nonzero = a parity/perf gate
    missed; the bench also writes BENCH_<name>.json for the artifact
    upload either way).
    """
    import pathlib
    import subprocess

    if name not in SMOKE_BENCHES:
        print(f"unknown smoke bench {name!r}; pick one of "
              f"{', '.join(SMOKE_BENCHES)}", file=sys.stderr)
        return 2
    script = (pathlib.Path(__file__).parent
              / SMOKE_SCRIPTS.get(name, f"bench_{name}.py"))
    return subprocess.run(
        [sys.executable, str(script), "--smoke",
         "--out", f"BENCH_{name}.json"]).returncode


def main() -> None:
    import pathlib
    import subprocess

    argv = sys.argv[1:]
    if argv and argv[0] == "--smoke":
        if len(argv) != 2:
            print("usage: run.py --smoke <name>", file=sys.stderr)
            raise SystemExit(2)
        raise SystemExit(run_smoke(argv[1]))
    which = set(argv)
    unknown = which - set(SECTIONS)
    if unknown:
        print(f"unknown section(s) {sorted(unknown)}; pick from "
              f"{', '.join(SECTIONS)}", file=sys.stderr)
        raise SystemExit(2)

    root = pathlib.Path(__file__).resolve().parent.parent
    summary = []
    failed = 0
    for name, (title, args) in SECTIONS.items():
        if which and name not in which:
            continue
        _section(title)
        t0 = time.perf_counter()
        rc = subprocess.run([sys.executable, "-m", *args],
                            cwd=root).returncode
        summary.append((args[0].split(".")[-1], time.perf_counter() - t0))
        if rc:
            failed = rc           # a section's gate miss must not exit 0

    _section("summary")
    print("name,us_per_call,derived")
    for name, dt in summary:
        print(f"{name},{dt * 1e6:.0f},wall_s={dt:.2f}")
    if failed:
        raise SystemExit(failed)


if __name__ == "__main__":
    main()
