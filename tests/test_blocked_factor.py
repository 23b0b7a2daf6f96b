"""Blocked in-VMEM factorizations: parity and contract, in interpret mode.

``hck_leaf_factor`` (leaf Cholesky + triangular inverse) and
``gram_chol_kernel`` / ``gram_chol_dist_kernel`` (node Gram + Cholesky)
factor by row panels with the off-diagonal work on the MXU, several tiles
per program; tiles of at most one panel take the unblocked one-hot loops.
Inputs are ridged Gaussian Gram tiles, shaped like the leaf Schur
complements of Algorithm 2, with condition number at most 1e4; batches are
not a multiple of the tiles each program holds, so the padded launch runs.

The residual bounds are twice what the unblocked one-hot kernels reached
on these same inputs (``ONEHOT``, float32, interpret mode on the CPU).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.build_stage.build_stage import (gram_chol_dist_kernel,
                                                   gram_chol_kernel)
from repro.kernels.hck_leaf.hck_leaf import hck_leaf_factor
from repro.kernels.hck_leaf.ref import hck_leaf_factor_ref
from repro.kernels.registry import tile_config
from repro.kernels.update_stage.ops import leaf_update
from repro.kernels.update_stage.ref import leaf_update_ref

COND = 1e4
SLACK = 2.0

# What the unblocked one-hot kernels reached on these inputs, per (case, m):
# leaf: (|lo lo^T - A|/|A|, |linv lo - I|, lo vs ref, linv vs ref);
# gram / gram_dist: (|L L^T - G|/|G|, L vs jnp.linalg.cholesky).
ONEHOT = {
    ("leaf", 8): (6.01e-8, 1.24e-6, 5.53e-7, 3.74e-6),
    ("leaf", 16): (4.75e-8, 5.57e-6, 1.60e-6, 3.33e-5),
    ("leaf", 64): (2.78e-8, 1.38e-5, 4.40e-6, 5.41e-5),
    ("leaf", 128): (2.10e-8, 1.83e-5, 9.96e-6, 8.22e-5),
    ("leaf", 256): (2.15e-8, 1.88e-5, 1.22e-5, 7.97e-5),
    ("gram", 8): (7.21e-8, 5.94e-7),
    ("gram", 16): (5.12e-8, 1.23e-6),
    ("gram", 64): (3.15e-8, 4.50e-6),
    ("gram", 128): (2.22e-8, 6.57e-6),
    ("gram", 256): (2.37e-8, 1.11e-5),
    ("gram_dist", 128): (2.23e-8, 6.65e-6),
}


def _points(m: int, seed: int) -> np.ndarray:
    """Five tiles of m points in 4-d, tight enough that the Gram's own
    spectrum falls below the ridge."""
    return np.random.RandomState(seed).randn(5, m, 4) * 0.35


def _gram(x: np.ndarray) -> np.ndarray:
    return np.exp(-0.5 * ((x[:, :, None] - x[:, None]) ** 2).sum(-1))


def _ridge(g: np.ndarray) -> np.ndarray:
    """Per-tile ridge that puts the condition number at ~COND."""
    return np.linalg.eigvalsh(g)[:, -1] / COND


def _rel(a, b) -> np.ndarray:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return (np.linalg.norm(a - b, axis=(1, 2))
            / np.linalg.norm(b, axis=(1, 2)))


def _residuals(kind: str, m: int) -> tuple:
    """The numbers ``ONEHOT`` records, for the kernels as they are."""
    x = _points(m, m)
    g = _gram(x)
    if kind == "leaf":
        a = jnp.asarray(g + _ridge(g)[:, None, None] * np.eye(m),
                        jnp.float32)
        assert len(a) % tile_config("leaf_factor", n0=m, r=m, k=1).tiles
        lo, linv = hck_leaf_factor(a, interpret=True)
        rlo, rlinv = hck_leaf_factor_ref(a)
        lo64, linv64 = np.asarray(lo, np.float64), np.asarray(linv, np.float64)
        assert np.array_equal(lo64, np.tril(lo64))
        assert np.array_equal(linv64, np.tril(linv64))
        eye = np.broadcast_to(np.eye(m), lo64.shape)
        return (_rel(lo64 @ lo64.transpose(0, 2, 1), a).max(),
                np.linalg.norm(linv64 @ lo64 - eye, axis=(1, 2)).max(),
                _rel(lo, rlo).max(), _rel(linv, rlinv).max())
    jitter = float(_ridge(g).max() / m)
    kw = dict(name="gaussian", sigma=1.0, jitter=jitter, interpret=True)
    if kind == "gram":
        gram, chol = gram_chol_kernel(jnp.asarray(x, jnp.float32), **kw)
    else:
        dist = jnp.asarray(((x[:, :, None] - x[:, None]) ** 2).sum(-1),
                           jnp.float32)
        gram, chol = gram_chol_dist_kernel(dist, **kw)
    c64 = np.asarray(chol, np.float64)
    assert np.array_equal(c64, np.tril(c64))
    return (_rel(c64 @ c64.transpose(0, 2, 1), gram).max(),
            _rel(chol, jnp.linalg.cholesky(gram)).max())


def _indefinite(m: int) -> None:
    """An indefinite tile yields NaN from both factorizations (no clamp)."""
    x = _points(m, 7)
    a = _gram(x) + 1e-3 * np.eye(m)
    a[:, m - 1, m - 1] = -1.0
    lo, linv = hck_leaf_factor(jnp.asarray(a, jnp.float32), interpret=True)
    assert np.isnan(np.asarray(lo)).any() and np.isnan(np.asarray(linv)).any()
    # a Gram made indefinite through a negative jitter
    _, chol = gram_chol_kernel(jnp.asarray(x, jnp.float32), sigma=1.0,
                               jitter=-1.0, interpret=True)
    assert np.isnan(np.asarray(chol)).any(axis=(1, 2)).all()


def _update(k: int) -> None:
    """``leaf_update``'s appended k x k block takes the unblocked loop."""
    n0 = 16
    x = _points(n0 + k, 11)
    g = _gram(x) + 1e-2 * np.eye(n0 + k)
    g = jnp.asarray(g, jnp.float32)
    lo, linv = hck_leaf_factor_ref(g[:, :n0, :n0])
    b, c = g[:, n0:, :n0], g[:, n0:, n0:]
    got = leaf_update(lo, linv, b, c, interpret=True)
    want = leaf_update_ref(lo, linv, b, c)
    for gt, wt in zip(got, want):
        np.testing.assert_allclose(np.asarray(gt), np.asarray(wt),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("kind,m", sorted(ONEHOT) + [
    ("indefinite", 3), ("indefinite", 16), ("indefinite", 128),
    ("update", 3)])
def test_blocked_factorization(kind, m):
    if kind == "indefinite":
        return _indefinite(m)
    if kind == "update":
        return _update(m)
    got = _residuals(kind, m)
    for value, onehot in zip(got, ONEHOT[(kind, m)]):
        assert value <= SLACK * onehot, (kind, m, got, ONEHOT[(kind, m)])
