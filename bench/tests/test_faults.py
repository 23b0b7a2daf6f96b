"""A run with the timed path broken underneath reads ``correct: false``.

The harness's look for a chip is skipped; everything else of a run is the
cell's own, at the tiny CPU size of ``conftest.TINY``.  One case per fault
the cells can have (``bench/faults.py``).
"""
from __future__ import annotations

import pytest

import faults
from conftest import run_json


@pytest.mark.parametrize("workload", ["msd.fit", "msd.serve"])
def test_sound_run_is_correct(tree, workload, capsys):
    result = run_json(tree, workload, capsys=capsys)
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("workload,fault", sorted(faults.FAULTS))
def test_fault_reads_incorrect(tree, workload, fault, capsys, monkeypatch):
    faults.plant(workload, fault, monkeypatch.setattr)
    result = run_json(tree, workload, capsys=capsys)
    assert not result["correct"], result["checks"]
