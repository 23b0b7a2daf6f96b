"""A cell, a configuration, a traffic mix and a per-layer metric are added
as new files plus new entries of ``BENCHMARK.json``, and the harness runs
them, at a tiny size on the CPU, without an edit to a file it had."""
from __future__ import annotations

import hashlib
import json

from conftest import run_json

NEW_CONFIG = {
    "name": "tinyreg", "source": "a tiny regression deployment for the test",
    "n_train": 2000, "n_test": 700, "d": 40, "task": "regression",
    "classes": 0, "rank": 32, "leaf": 32, "levels": 6, "kernel": "gaussian",
    "sigma_rule": "median pairwise distance of the first 1024 points",
    "lam": 0.01, "jitter": 1e-05,
    "precision": {"dtype": "float32", "matmul": "highest"},
    "reference": "hck_krr", "reduced": [],
    "control": {"matmul": "high", "backend": "xla"},
    "limits": {"tree": 0, "factors": 1.0, "alpha": 1.0, "predict": 1.0,
               "predict_worst": 100.0, "centered": 1.0,
               "centered_worst": 100.0},
}

NEW_TRAFFIC = {
    "driver": "open_loop", "about": "small requests at a low rate",
    "rate_rps": 150, "points_min": 1, "points_max": 16,
    "max_batch_points": 256, "min_bucket": 16, "max_bucket": 256,
}

NEW_METRIC = '''"""Served batches per second of the window (the driver's record)."""


def read(reading):
    serve = reading.window.get("serve")
    if not serve or not serve["batches"]:
        return None
    return len(serve["batches"]) / ((reading.hi - reading.lo) / 1e9)
'''


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def test_new_cell_runs_from_new_files(tree, capsys):
    before = _digests(tree)
    bench = tree / "bench"
    (bench / "configs" / "tinyreg.json").write_text(json.dumps(NEW_CONFIG))
    (bench / "traffic" / "small.json").write_text(json.dumps(NEW_TRAFFIC))
    (bench / "metrics" / "batches_per_s.py").write_text(NEW_METRIC)
    spec = json.loads((tree / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tinyreg", "source": "test",
                            "file": "bench/configs/tinyreg.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tinyreg.small", "config": "tinyreg",
                              "traffic": "small", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"].startswith("predict_"):
            m["workloads"].append("tinyreg.small")
    spec["per_layer"].append({
        "name": "batches_per_s", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "serving",
        "moves": "predict_p95_ms", "workloads": ["tinyreg.small"]})
    (tree / "BENCHMARK.json").write_text(json.dumps(spec))

    untraced = run_json(tree, "tinyreg.small", seconds=1.5, capsys=capsys)
    assert untraced["correct"], untraced["checks"]
    assert set(untraced["metrics"]) == {"setup_s", "predict_p95_ms",
                                        "predict_p50_ms"}
    traced = run_json(tree, "tinyreg.small", seconds=1.5, trace=True,
                      capsys=capsys)
    assert traced["correct"], traced["checks"]
    assert set(traced["metrics"]) == {"batches_per_s"}
    assert traced["metrics"]["batches_per_s"]["value"] > 0

    after = _digests(tree)
    assert all(after[p] == d for p, d in before.items())
