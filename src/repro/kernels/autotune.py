"""Measured tile/backend selection for the stage registry (autotuner).

The registry's ``tile_config`` heuristics and ``resolve_backend`` auto
rules were derived on CPU under interpret mode; on a real accelerator the
right tile size and the xla-vs-pallas crossover are empirical.  This
module runs a timed sweep per (stage, shape bucket, device kind, dtype)
over candidate tile sizes and backends, and persists the winners in an
on-disk JSON database (``~/.cache/repro/tile_db.json``, overridable with
``REPRO_TILE_DB``).  ``tile_config``/``resolve_backend`` consult the DB
first and fall back to the existing heuristics on a cold cache, so a
machine without measurements behaves exactly as before.

Keying: shapes are bucketed to powers of two, so one measurement covers a
neighborhood of problem sizes; the device key is the fine-grained
``jax.devices()[0].device_kind`` (distinct GPUs tune separately) while
calibration queries aggregate by coarse platform (cpu/gpu/tpu).

A second ``autotune_stage`` call with the same key is a cache hit: the
stored record is returned with ``"cached": True`` and no kernels run.
Set ``REPRO_AUTOTUNE=0`` to disable DB lookups entirely (heuristics only).
"""
from __future__ import annotations

import functools
import json
import os
import time

from repro.kernels.registry import OOS_STAGES, get_impl, tile_config
from repro.utils import roofline

#: stage -> pallas tile keyword that the autotuner sweeps; stages absent
#: here have no free tile (whole-node programs) and tune backend only.
TUNABLE = {
    "leaf_matvec": "block_n0",
    "build_cross": "block_m",
    "build_cross_dist": "block_m",
    "oos_local": "block_q",
    "oos_walk": "block_q",
    "kernel_matvec": "block_n",
}

#: stages the convenience sweep (autotune_all / roofline smoke) covers.
DEFAULT_STAGES = ("leaf_matvec", "leaf_solve", "leaf_project", "leaf_factor",
                  "build_gram", "build_cross", "build_gram_dist",
                  "build_cross_dist", "oos_local", "oos_walk",
                  "kernel_matvec", "pairwise_kernel")

_ITEMSIZE_DTYPE = {2: "bfloat16", 4: "float32", 8: "float64"}

#: set while a sweep is running so registry consults don't recurse into
#: the half-written DB (candidate timings must use explicit tiles).
_SWEEPING = False


def db_path() -> str:
    """Path of the tile database (``REPRO_TILE_DB`` or the user cache)."""
    return os.environ.get(
        "REPRO_TILE_DB",
        os.path.join(os.path.expanduser("~"), ".cache", "repro",
                     "tile_db.json"))


def lookups_enabled() -> bool:
    """Whether registry-side DB consults are active (``REPRO_AUTOTUNE``)."""
    return os.environ.get("REPRO_AUTOTUNE", "1") != "0" and not _SWEEPING


class TileDB:
    """On-disk JSON map of measured tile/backend choices.

    Corrupt or unreadable files degrade to an empty DB (heuristic
    fallback) instead of raising; the next ``save`` rewrites the file.
    """

    def __init__(self, path: str | None = None):
        """Load the DB at ``path`` (default :func:`db_path`)."""
        self.path = path or db_path()
        self.entries: dict[str, dict] = {}
        self.corrupt = False
        try:
            with open(self.path) as f:
                raw = json.load(f)
            entries = raw.get("entries", {})
            if isinstance(entries, dict):
                self.entries = {k: v for k, v in entries.items()
                                if isinstance(v, dict)}
            else:
                self.corrupt = True
        except FileNotFoundError:
            pass
        except (json.JSONDecodeError, OSError, AttributeError):
            self.corrupt = True

    def get(self, key: str) -> dict | None:
        """Stored record for ``key`` or None."""
        return self.entries.get(key)

    def put(self, key: str, rec: dict) -> None:
        """Insert/replace ``key`` (in memory; call :meth:`save` to persist)."""
        self.entries[key] = rec

    def save(self) -> None:
        """Atomically write the DB back to disk."""
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        import jax

        blob = {"version": 1, "jax": jax.__version__, "entries": self.entries}
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(blob, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)


_DB: TileDB | None = None


def get_db() -> TileDB:
    """Process-wide DB singleton (loaded lazily from :func:`db_path`)."""
    global _DB
    if _DB is None or _DB.path != db_path():
        _DB = TileDB()
    return _DB


def reset_db() -> None:
    """Drop the cached singleton (tests repoint ``REPRO_TILE_DB``)."""
    global _DB
    _DB = None
    device_kind.cache_clear()


@functools.lru_cache(maxsize=None)
def device_kind() -> str:
    """Fine-grained device kind of device 0 (sanitized for DB keys).

    A backend that fails to initialize raises rather than reading as cpu.
    """
    import jax

    kind = jax.devices()[0].device_kind
    return str(kind).strip().replace(" ", "_").replace("|", "_") or "cpu"


def _bucket(v: int) -> int:
    return 0 if v <= 0 else 1 << max(0, int(v) - 1).bit_length()


def bucket_key(stage: str, device: str, dtype: str, *, n0: int, r: int,
               k: int, d: int) -> str:
    """DB key: stage | device kind | dtype | pow2-bucketed shape."""
    return (f"{stage}|{device}|{dtype}|"
            f"n0={_bucket(n0)},r={_bucket(r)},k={_bucket(k)},d={_bucket(d)}")


def candidates(stage: str, *, n0: int, r: int, k: int, d: int,
               itemsize: int = 4) -> list[int]:
    """Candidate tile sizes for a tunable stage at one shape.

    Row-tiled leaf/build stages use power-of-two divisors of ``n0`` (the
    launch snaps to divisors); query/row-padded stages (oos_*,
    kernel_matvec) use free powers of two.  Candidates whose working set
    exceeds the VMEM budget are dropped; the heuristic default is always
    included so the sweep can only improve on it.
    """
    if stage not in TUNABLE:
        return []
    if stage in OOS_STAGES or stage == "kernel_matvec":
        cands = [32, 64, 128, 256]
    else:
        cands = [b for b in (8, 16, 32, 64, 128, 256, 512, 1024)
                 if b <= n0 and n0 % b == 0]
    default = tile_config(stage, n0=n0, r=r, k=k, d=d, itemsize=itemsize,
                          leaf_block=None).block_n0
    out = []
    for b in sorted(set(cands) | {default}):
        cfg = tile_config(stage, n0=n0, r=r, k=k, d=d, itemsize=itemsize,
                          leaf_block=b)
        if cfg.fits and cfg.block_n0 not in out:
            out.append(cfg.block_n0)
    return out or [default]


def _stage_inputs(stage: str, key, *, batch: int, n0: int, r: int, k: int,
                  d: int, dtype):
    """Synthetic (args, kwargs) matching one stage's registry signature."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(key, 4)

    def rnd(i, *shape):
        return jax.random.normal(keys[i], shape, dtype)

    def tril(a):
        return jnp.tril(a) + jnp.eye(a.shape[-1], dtype=dtype)

    kw = {"name": "gaussian", "sigma": 1.0}
    if stage == "leaf_matvec":
        return (rnd(0, batch, n0, n0), rnd(1, batch, n0, r),
                rnd(2, batch, n0, k)), {}
    if stage == "leaf_solve":
        return (tril(rnd(0, batch, n0, n0)), rnd(1, batch, n0, r),
                rnd(2, batch, r, r), rnd(3, batch, n0, k)), {}
    if stage == "leaf_project":
        return (rnd(0, batch, n0, r), rnd(1, batch, n0, k)), {}
    if stage == "leaf_factor":
        a = rnd(0, batch, n0, n0)
        spd = (a @ a.transpose(0, 2, 1)) / n0 + 2.0 * jnp.eye(n0, dtype=dtype)
        return (spd,), {}
    if stage == "build_gram":
        return (rnd(0, batch, n0, d),), {**kw, "jitter": 1e-4,
                                         "want_chol": True}
    if stage == "build_gram_dist":
        return (jnp.abs(rnd(0, batch, n0, n0)),), {**kw, "jitter": 1e-4,
                                                   "want_chol": True}
    if stage == "build_cross":
        return (rnd(0, batch, n0, d), rnd(1, batch, r, d),
                tril(rnd(2, batch, r, r))), kw
    if stage == "build_cross_dist":
        return (jnp.abs(rnd(0, batch, n0, r)),
                tril(rnd(1, batch, r, r))), kw
    if stage in OOS_STAGES:
        return (rnd(0, batch, n0, d), rnd(1, batch, n0, k),
                rnd(2, batch, d)), kw
    if stage == "kernel_matvec":
        return (rnd(0, n0, d), rnd(1, max(r, 8), d),
                rnd(2, max(r, 8), k)), kw
    if stage == "pairwise_kernel":
        return (rnd(0, n0, d), rnd(1, max(r, 8), d)), kw
    raise ValueError(f"no input builder for stage {stage!r}")


def _time_impl(fn, args, kwargs, repeats: int) -> float:
    """Best-of-``repeats`` wall time (s) of the jitted call, post-warmup."""
    import jax

    call = jax.jit(lambda *a: fn(*a, **kwargs))
    jax.block_until_ready(call(*args))     # compile outside the clock
    times = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(call(*args))
        times.append(time.perf_counter() - t0)
    return min(times)


def autotune_stage(stage: str, *, n0: int, r: int = 0, k: int = 1,
                   d: int = 0, batch: int = 8, dtype="float32",
                   backends: tuple[str, ...] = ("xla", "pallas"),
                   interpret: bool | None = None, repeats: int = 3,
                   db: TileDB | None = None, force: bool = False,
                   seed: int = 0) -> dict:
    """Measure (or fetch) the best (backend, tile) for one stage bucket.

    On a cache hit the stored record is returned with ``"cached": True``
    and nothing is timed; pass ``force=True`` to re-sweep.  The sweep
    times every (backend, candidate tile) pair on synthetic inputs at the
    bucketed shape, records the winner plus all candidate timings and the
    achieved GFLOP/s / GB/s of the best run (for roofline calibration),
    and persists the DB.
    """
    global _SWEEPING
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    device = device_kind()
    key = bucket_key(stage, device, dtype.name, n0=n0, r=r, k=k, d=d)
    db = db or get_db()
    hit = db.get(key)
    if hit is not None and not force:
        return {**hit, "cached": True}

    if interpret is None:
        interpret = roofline.default_device_kind() == "cpu"
    bn0, br = max(_bucket(n0), 8), _bucket(r)
    bk, bd = max(_bucket(k), 1), _bucket(d)
    args, kwargs = _stage_inputs(stage, jax.random.PRNGKey(seed), batch=batch,
                                 n0=bn0, r=br, k=bk, d=bd, dtype=dtype)
    tile_kw = TUNABLE.get(stage)
    cands = candidates(stage, n0=bn0, r=br, k=bk, d=bd,
                       itemsize=dtype.itemsize) if tile_kw else [None]

    results = []
    _SWEEPING = True
    try:
        for backend in backends:
            try:
                fn = get_impl(stage, backend)
            except KeyError:
                continue
            blocks = cands if (backend == "pallas" and tile_kw) else [None]
            for block in blocks:
                kw = dict(kwargs, interpret=interpret)
                if backend == "pallas" and tile_kw and block is not None:
                    kw[tile_kw] = block
                try:
                    t = _time_impl(fn, args, kw, repeats)
                except Exception as e:   # noqa: BLE001 — skip bad candidate
                    results.append({"backend": backend, "block": block,
                                    "error": f"{type(e).__name__}: {e}"})
                    continue
                results.append({"backend": backend, "block": block, "s": t})
    finally:
        _SWEEPING = False

    timed = [c for c in results if "s" in c]
    if not timed:
        raise RuntimeError(f"autotune: no candidate ran for {key}")
    best = min(timed, key=lambda c: c["s"])
    qbatch = batch if (stage in OOS_STAGES or "leaf" in stage
                       or stage.startswith("build")) else 1
    flops, nbytes = roofline.stage_cost(stage, batch=qbatch, n0=bn0, r=br,
                                        k=bk, d=bd, itemsize=dtype.itemsize)
    pallas_timed = [c for c in timed
                    if c["backend"] == "pallas" and c["block"] is not None]
    pallas_block = (min(pallas_timed, key=lambda c: c["s"])["block"]
                    if pallas_timed else None)
    rec = {
        "stage": stage, "device_kind": device,
        "platform": roofline.default_device_kind(),
        "dtype": dtype.name,
        "bucket": {"n0": bn0, "r": br, "k": bk, "d": bd, "batch": batch},
        "backend": best["backend"], "block": best["block"],
        "pallas_block": pallas_block,
        "best_s": best["s"], "interpret": bool(interpret),
        "jax": jax.__version__, "candidates": results,
        "rates": {"flops_per_s": flops / best["s"],
                  "bytes_per_s": nbytes / best["s"]},
    }
    db.put(key, rec)
    try:
        db.save()
    except OSError:
        pass    # read-only cache dir: keep the in-memory entry
    return {**rec, "cached": False}


def autotune_all(*, n0: int = 256, r: int = 16, k: int = 2, d: int = 4,
                 batch: int = 8, dtype="float32",
                 stages: tuple[str, ...] = DEFAULT_STAGES,
                 repeats: int = 3, force: bool = False) -> list[dict]:
    """Sweep the standard stage set at one shape; returns the records."""
    out = []
    for stage in stages:
        out.append(autotune_stage(stage, n0=n0, r=r, k=k, d=d, batch=batch,
                                  dtype=dtype, repeats=repeats, force=force))
    return out


def _lookup(stage: str, dtype_name: str, *, n0: int, r: int, k: int,
            d: int) -> dict | None:
    if not lookups_enabled():
        return None
    db = get_db()
    if not db.entries:
        return None
    return db.get(bucket_key(stage, device_kind(), dtype_name,
                             n0=n0, r=r, k=k, d=d))


def lookup_block(stage: str, *, n0: int, r: int, k: int, d: int = 0,
                 itemsize: int = 4) -> int | None:
    """Measured tile size for this bucket, or None (cold cache/untunable).

    Tile sizes only steer the pallas launch, so this prefers the best
    *pallas* candidate even when the xla backend won the sweep overall.
    """
    if stage not in TUNABLE:
        return None
    dtype_name = _ITEMSIZE_DTYPE.get(itemsize, "float32")
    rec = _lookup(stage, dtype_name, n0=n0, r=r, k=k, d=d)
    if rec is None:
        return None
    block = rec.get("pallas_block") or rec.get("block")
    return None if block is None else int(block)


def lookup_backend(stage: str, *, dtype, n0: int, r: int, k: int = 1,
                   d: int = 0) -> str | None:
    """Measured backend winner for this bucket, or None (cold cache)."""
    import jax.numpy as jnp

    rec = _lookup(stage, jnp.dtype(dtype).name, n0=n0, r=r, k=k, d=d)
    return None if rec is None else rec.get("backend")


def calibrated_peaks(platform: str | None = None) -> dict | None:
    """Best measured rates on this platform, for roofline calibration.

    Scans the DB for entries whose coarse platform matches and returns
    ``{"flops_per_s": max, "bytes_per_s": max}`` — the demonstrated
    compute/bandwidth ceilings — or None when no measurements exist.
    """
    if not lookups_enabled():
        return None
    platform = platform or roofline.default_device_kind()
    db = get_db()
    best_f, best_b = 0.0, 0.0
    for rec in db.entries.values():
        if rec.get("platform") != platform:
            continue
        rates = rec.get("rates") or {}
        best_f = max(best_f, float(rates.get("flops_per_s", 0.0)))
        best_b = max(best_b, float(rates.get("bytes_per_s", 0.0)))
    if best_f <= 0.0 and best_b <= 0.0:
        return None
    return {"flops_per_s": best_f, "bytes_per_s": best_b}
