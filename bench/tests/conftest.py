"""Fixtures of the benchmark's own tests: a copy of the benchmark in a
temporary directory, with tiny configurations, that runs on the CPU.

  python -m pytest bench/tests        # from the root of the repository
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "bench"))
sys.path.insert(0, str(REPO / "src"))

#: tiny shapes of the two configurations; the limits stay the
#: configuration's own
TINY = {
    "yearpredictionmsd": dict(n_train=3000, n_test=1500, d=90, levels=5),
}


def copy_tree(dst: pathlib.Path, tiny: bool = True) -> pathlib.Path:
    """The benchmark's files in ``dst``, ``src`` linked; with ``tiny`` the
    configurations and traffic cut to a CPU's size."""
    shutil.copytree(REPO / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    (dst / "src").symlink_to(REPO / "src")
    if tiny:
        bench = json.loads((dst / "BENCHMARK.json").read_text())
        for c in bench["configs"]:
            path = dst / c["file"]
            cfg = json.loads(path.read_text())
            cfg.update(TINY[cfg["name"]])
            path.write_text(json.dumps(cfg))
        for path in (dst / "bench" / "traffic").glob("open_loop*.json"):
            tr = json.loads(path.read_text())
            tr.update(rate_rps=200, max_bucket=1024, max_batch_points=1024)
            path.write_text(json.dumps(tr))
    return dst


@pytest.fixture
def tree(tmp_path):
    """A tiny copy of the benchmark, and the harness bound to it."""
    import krrcell

    old = krrcell.BLOCK
    krrcell.BLOCK = 256
    yield copy_tree(tmp_path)
    krrcell.BLOCK = old


def run_json(root, workload, seed=3, seconds=1.0, trace=False, capsys=None):
    """Run a cell through the harness on the CPU; the parsed result line."""
    import harness

    rc = harness.run(root, workload, seed, seconds, trace, require_tpu=False)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])
