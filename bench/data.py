"""Seeded data of a Table-1 configuration, made on the device.

A copy of ``repro.data.pipeline.regression_dataset`` (the repository's
synthetic Table-1 stand-ins: same n, d and task, a smooth mixture-of-bumps
target) and of the bandwidth rule of ``chip_smoke.problem`` (sigma = the
median pairwise distance of the first 1024 training points).  It is copied
so that a change to the program cannot change what the benchmark feeds it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (``PRNGKey`` keeps 32)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@functools.partial(jax.jit, static_argnames=("n_train", "n_test", "d",
                                             "task", "classes"))
def _draw(key, *, n_train, n_test, d, task, classes):
    kx, kc, kw, kn, kt = jax.random.split(key, 5)
    centers = jax.random.uniform(kc, (32, d))
    weights = jax.random.normal(kw, (32,))
    lengthscale = 0.5 * math.sqrt(d)

    def fstar(x):
        d2 = jnp.sum((x[:, None, :] - centers[None]) ** 2, -1)
        return jnp.exp(-d2 / (2 * lengthscale ** 2)) @ weights

    x = jax.random.uniform(kx, (n_train, d))
    f = fstar(x)
    xt = jax.random.uniform(kt, (n_test, d))
    ft = fstar(xt)
    y = f + 0.05 * jnp.std(f) * jax.random.normal(kn, f.shape)
    sub = x[:1024]
    d2 = jnp.sum((sub[:, None, :] - sub[None, :, :]) ** 2, axis=-1)
    sigma = jnp.sqrt(jnp.median(d2))
    if task == "regression":
        return x, y, xt, ft, sigma
    qs = jnp.quantile(f, jnp.linspace(0, 1, classes + 1)[1:-1])
    return (x, jnp.searchsorted(qs, y).astype(jnp.int32), xt,
            jnp.searchsorted(qs, ft).astype(jnp.int32), sigma)


def dataset(cfg: dict, seed: int):
    """``(x, y, xt, yt, sigma)`` for configuration ``cfg`` and ``seed``.

    Regression targets are the noisy draw for training and the noise-free
    function for the held-out set; multiclass labels are quantile bins of
    the same function (``regression_dataset``'s rule).  ``sigma`` is a
    Python float.
    """
    x, y, xt, yt, sigma = _draw(
        seed_key(seed), n_train=cfg["n_train"], n_test=cfg["n_test"],
        d=cfg["d"], task=cfg["task"], classes=cfg["classes"])
    return x, y, xt, yt, float(sigma)


def targets(y: jax.Array, task: str, classes: int) -> jax.Array:
    """Targets as the fit sees them: (n, 1) for regression, one-vs-all
    +-1 columns (n, classes) for multiclass."""
    if task == "regression":
        return y[:, None]
    return jnp.where(y[:, None] == jnp.arange(classes)[None, :], 1.0, -1.0)
