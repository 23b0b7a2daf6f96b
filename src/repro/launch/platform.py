"""Process-level platform setup: environment BEFORE the first jax import.

jax reads its platform list, XLA flags and config defaults from the
environment at first import / backend init, so every entry point
(``chip_smoke.py``, launch/train.py, launch/serve.py, benchmarks/*) must
route through :func:`setup_platform` before importing jax.  The module
itself imports no jax for the same reason.

What it sets:

  * ``JAX_PLATFORMS`` — from the ``platform`` argument or the
    ``REPRO_PLATFORM`` env var (cpu / tpu).  Unset means jax's own
    auto-detection order.
  * ``JAX_COMPILATION_CACHE_DIR`` — the persistent compile cache.  A value
    already in the environment is kept as it is; otherwise the cache goes
    to :data:`DEFAULT_CACHE_DIR`, one fixed directory inside the checkout
    (the path is part of the cache key, so it must not move between runs).
  * ``JAX_DEFAULT_MATMUL_PRECISION=highest`` unless already set: the TPU's
    default f32 matmul is one bfloat16 pass.  JAX reads this setting when
    it traces an op, so it reaches every f32 matmul of the program: XLA
    einsums, ``jnp.linalg`` internals and the Pallas kernels' own dots
    (Mosaic then contracts in fp32).  Per-call ``precision=`` arguments
    would need one at each of about a hundred call sites and still miss
    the library internals.  On a TPU v5e at the Table-1 shape of
    ``chip_smoke.py``, ``hmatrix.solve`` returns NaN without it, and it
    makes the solve 3-4% slower (``PERF.md``).  CPU matmuls are f32
    either way.  A program that imports ``repro`` without calling this
    function sets the variable itself (README, Install).
  * ``--xla_force_host_platform_device_count`` — from ``host_devices`` or
    ``REPRO_HOST_DEVICES``, for virtual-mesh CPU runs.

Flags already present in ``XLA_FLAGS`` are never duplicated or
overridden, so callers can still pre-set anything by hand.  Idempotent;
returns a record of what was applied for logging.
"""
from __future__ import annotations

import os
import pathlib
import sys
import warnings

#: persistent compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def _merge_xla_flags(new_flags) -> list:
    existing = os.environ.get("XLA_FLAGS", "")
    present = {f.split("=")[0] for f in existing.split() if f}
    added = [f for f in new_flags if f.split("=")[0] not in present]
    if added:
        os.environ["XLA_FLAGS"] = " ".join(
            ([existing] if existing else []) + added)
    return added


def setup_platform(platform: str | None = None,
                   host_devices: int | None = None) -> dict:
    """Configure the jax platform, compile cache and XLA flags.

    Call before the first ``import jax``; warns (but still applies the
    env, for any later-spawned subprocess) when jax is already imported.
    Arguments beat the ``REPRO_PLATFORM`` / ``REPRO_HOST_DEVICES`` env
    vars, which beat auto-detection.
    """
    if "jax" in sys.modules:
        warnings.warn(
            "setup_platform() called after jax import; XLA flags may not "
            "take effect in this process", RuntimeWarning, stacklevel=2)

    platform = platform or os.environ.get("REPRO_PLATFORM") or None
    if host_devices is None:
        hd = os.environ.get("REPRO_HOST_DEVICES")
        host_devices = int(hd) if hd else None

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(DEFAULT_CACHE_DIR))
    os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "highest")
    applied = {"platform": platform, "host_devices": host_devices,
               "compilation_cache_dir": os.environ["JAX_COMPILATION_CACHE_DIR"],
               "flags": []}
    if platform:
        os.environ.setdefault("JAX_PLATFORMS", platform)
        applied["platform"] = os.environ["JAX_PLATFORMS"]
    if host_devices:
        applied["flags"] += _merge_xla_flags(
            (f"--xla_force_host_platform_device_count={host_devices}",))
    return applied
