"""Shared benchmark utilities: timing, synthetic Table-1 stand-ins, CSV,
platform metadata, and per-stage roofline blocks for BENCH_*.json."""
from __future__ import annotations

import sys

from repro.launch.platform import setup_platform

if "jax" not in sys.modules:
    # XLA/platform flags must land before the jax import; benches that
    # need custom flags (bench_dist's virtual mesh) call setup_platform
    # themselves first and import this module afterwards.
    setup_platform()

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.hck_krr import HCKConfig  # noqa: E402
from repro.data.pipeline import regression_dataset  # noqa: E402
from repro.utils import roofline  # noqa: E402


def timeit(fn, *args, repeats: int = 3, **kwargs) -> tuple[float, object]:
    """Median wall time (seconds) + last result (blocked)."""
    out = None
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2], out


def small_dataset(name: str, n: int, d: int, task: str = "regression",
                  n_classes: int = 0, seed: int = 0):
    """CPU-sized synthetic stand-in mirroring a Table-1 dataset's (d, task)."""
    cfg = HCKConfig(name, n_train=n, n_test=max(n // 4, 64), d=d, task=task,
                    n_classes=n_classes)
    return regression_dataset(cfg, jax.random.PRNGKey(seed))


def emit(rows: list[dict], header: list[str]):
    print(",".join(header))
    for r in rows:
        print(",".join(str(r.get(h, "")) for h in header))


def rel_err(pred, truth) -> float:
    return float(jnp.linalg.norm(pred - truth) / jnp.linalg.norm(truth))


def acc(pred, truth) -> float:
    return float(jnp.mean((pred == truth).astype(jnp.float32)))


def platform_record(dtype=None) -> dict:
    """Machine/runtime metadata every BENCH_*.json carries, so perf
    trajectories across machines stay comparable.  A backend that fails
    to initialize raises: a record never names a device that did not run."""
    return {
        "device_kind": str(jax.devices()[0].device_kind),
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "dtype": str(jnp.dtype(dtype).name) if dtype is not None else (
            "float64" if jax.config.jax_enable_x64 else "float32"),
        "jax_version": jax.__version__,
    }


def roofline_block(stage_times: dict[str, tuple[float, dict]]) -> dict:
    """Per-stage roofline records for a BENCH_*.json.

    ``stage_times`` maps stage name -> (measured seconds, shape kwargs for
    :func:`repro.utils.roofline.stage_cost`); returns ``{"hw": <model>,
    "stages": {stage: record}}`` with achieved fractions against the
    (possibly tile-DB-calibrated) device model.
    """
    hw = roofline.hw_model()
    stages = {}
    for stage, (secs, shape) in stage_times.items():
        try:
            stages[stage] = roofline.stage_roofline(stage, secs, hw=hw,
                                                    **shape)
        except ValueError:
            continue    # stage without a cost model: skip, don't kill bench
    return {"hw": hw, "stages": stages}
