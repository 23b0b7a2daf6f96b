"""The control, run through the harness in the program's place, reads
``correct: false`` where the sound run of the same seed passes.

A configuration's ``control`` is the program at the next precision down:
on a TPU its XLA stages with matmuls at ``high`` (three bfloat16 passes;
its Pallas stages refuse ``high``).  The CPU computes ``high`` as
``highest``, so at the test's size on the CPU the control in the tiny
copy's configuration is the program's own bfloat16 policy
(``SolveConfig(precision="bf16")``) instead: the same path through the
harness, with a precision the CPU does lower.  At the test's size its
leaf factors lose definiteness and the served predictions are NaN, which
the program's serving loop refuses with a ``NumericalFailure``: a control
that crashes has failed too.  On a TPU the result line has to read false.
"""
from __future__ import annotations

import json

import jax
import pytest

import harness
from repro.runtime.health import NumericalFailure


def _run(root, workload, capsys, control):
    rc = harness.run(root, workload, 4, 1.0, False, require_tpu=False,
                     control=control)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["msd.fit", "msd.serve"])
def test_control_reads_incorrect(tree, workload, capsys):
    if jax.default_backend() != "tpu":
        path = tree / "bench" / "configs" / "yearpredictionmsd.json"
        cfg = json.loads(path.read_text())
        cfg["control"] = {"matmul": "highest", "policy": "bf16"}
        path.write_text(json.dumps(cfg))
    sound = _run(tree, workload, capsys, control=False)
    assert sound["correct"], sound["checks"]
    try:
        control = _run(tree, workload, capsys, control=True)
    except NumericalFailure:
        assert jax.default_backend() != "tpu"
        return
    assert "control" in control
    assert not control["correct"], control["checks"]
