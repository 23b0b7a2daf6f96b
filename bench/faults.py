"""Faults planted in the program underneath a run, for the fault tests
(``tests/test_faults.py``) and the readings at the cells' own size
(``tools/readings.py``).  Each returns a replacement for a function of the
program, made from the original:

* ``unchanged``: the solve returns its input state (alpha = 0) / the
  prediction step returns zeros;
* ``half``: half of the batch left out and the mean of the rest put in
  its place: the fit's targets, and the answers of a served batch;
* ``altered``: one answer of every prediction call altered where it is
  produced.

The cells run on one chip, so there is no exchange between chips to leave
out.
"""
from __future__ import annotations

import importlib

import jax.numpy as jnp


def _unchanged_solve(orig):
    def solve_with_inverse(f, inv, b, ridge=0.0, config=None):
        return jnp.zeros_like(b)
    return solve_with_inverse


def _half_targets(orig):
    def pad_points(x, y, *args, **kwargs):
        x, y, mask = orig(x, y, *args, **kwargs)
        h = y.shape[0] // 2
        mean = jnp.mean(y[:h].astype(jnp.float32), axis=0).astype(y.dtype)
        return x, y.at[h:].set(mean), mask
    return pad_points


def _zero_rows(orig):
    def apply_plan(f, plan, q, kernel, config=None):
        return jnp.zeros_like(orig(f, plan, q, kernel, config))
    return apply_plan


def _half_rows(orig):
    def apply_plan(f, plan, q, kernel, config=None):
        z = orig(f, plan, q, kernel, config)
        h = z.shape[0] // 2
        return z.at[h:].set(jnp.mean(z[:h], axis=0))
    return apply_plan


def _altered(orig):
    def apply_plan(f, plan, q, kernel, config=None):
        z = orig(f, plan, q, kernel, config)
        return z.at[0].set(-z[0] - 1.0)
    return apply_plan


#: (workload, fault) -> (module, function, replacement maker)
FAULTS = {
    ("msd.fit", "unchanged"): ("repro.core.hmatrix", "solve_with_inverse",
                               _unchanged_solve),
    ("msd.fit", "half"): ("repro.core.krr", "pad_points", _half_targets),
    ("msd.fit", "altered"): ("repro.core.oos", "apply_plan", _altered),
    ("msd.serve", "unchanged"): ("repro.core.oos", "apply_plan", _zero_rows),
    ("msd.serve", "half"): ("repro.core.oos", "apply_plan", _half_rows),
    ("msd.serve", "altered"): ("repro.core.oos", "apply_plan", _altered),
}


def plant(workload: str, fault: str, setattr_=setattr) -> None:
    """Put ``fault`` into the program for ``workload`` (``setattr_`` may be
    a test's ``monkeypatch.setattr``, which undoes it)."""
    module, name, make = FAULTS[(workload, fault)]
    mod = importlib.import_module(module)
    setattr_(mod, name, make(getattr(mod, name)))
