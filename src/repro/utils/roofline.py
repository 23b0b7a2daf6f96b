"""Roofline-term derivation from compiled dry-run artifacts.

  compute term    = HLO_FLOPs / (chips * peak_FLOPs)
  memory term     = HLO_bytes / (chips * HBM_bw)
  collective term = collective_bytes / (chips * link_bw)

cost_analysis() supplies FLOPs and bytes; collective bytes are NOT in
cost_analysis, so we parse the (post-SPMD) HLO text and sum the operand
sizes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute.  Hardware model: TPU v5e — 197 TF/s bf16 per chip,
819 GB/s HBM, ~50 GB/s per ICI link.

This module also carries the *analytic* per-stage cost models of the HCK
solve/build/predict engines (:func:`stage_cost`): closed-form flop/byte
counts at a given ``TileConfig`` shape, used by every benchmark to emit a
``roofline`` block (achieved fraction of the device roofline per stage)
and by the autotuner to convert measured stage times into achieved
GFLOP/s / GB/s rates.  :func:`hw_model` picks the peak-rate constants per
device kind and, when the autotune tile DB holds measurements for this
device, calibrates the peaks to the best measured rates so dry-run
predictions match the measured configs.
"""
from __future__ import annotations

import dataclasses
import re

# --- hardware constants (TPU v5e) -------------------------------------------
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per ICI link

#: nominal peak-rate models per device kind.  The TPU row is the v5e chip
#: the dry-run roofline was calibrated against; the gpu row is an
#: A100-class part (f32 tensor-core peak, HBM2e); the cpu row is a
#: deliberately rough server-class host (AVX2 f32 + dual-channel DDR) —
#: CPU numbers exist so achieved fractions stay finite in CI, not as a
#: precision model.
HW_MODELS = {
    "tpu": {"peak_flops": PEAK_FLOPS, "hbm_bw": HBM_BW, "link_bw": LINK_BW},
    "gpu": {"peak_flops": 78e12, "hbm_bw": 1.6e12, "link_bw": 25e9},
    "cpu": {"peak_flops": 2e11, "hbm_bw": 3e10, "link_bw": 1e10},
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# shapes like  f32[16,128]{1,0}  or  bf16[8,1024,128]
_SHAPE_RE = re.compile(r"(\w+?)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op, by kind.

    Sizes in post-SPMD HLO are PER-PARTICIPANT shapes, so the totals are
    per-device wire bytes (the roofline denominator is per-chip link bw).
    ``collective-permute-start``/``done`` pairs are counted once (start).
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "=" not in stripped:
            continue
        lhs, rhs = stripped.split("=", 1)
        rhs = rhs.strip()
        m = re.match(r"^(?:\(([^)]*)\)|(\w+\[[\d,]*\](?:\{[^}]*\})?))\s+"
                     r"([\w-]+)", rhs)
        if not m:
            continue
        opname = m.group(3)
        kind = next((c for c in _COLLECTIVES if opname == c
                     or opname == c + "-start"), None)
        if kind is None:
            continue
        shapes_src = m.group(1) if m.group(1) is not None else m.group(2)
        nbytes = sum(_shape_bytes(dt, dims)
                     for dt, dims in _SHAPE_RE.findall(shapes_src))
        out[kind] += nbytes
        counts[kind] += 1
    out_counts = {k + "_count": v for k, v in counts.items()}
    return {**out, **out_counts, "total": sum(out[k] for k in _COLLECTIVES)}


@dataclasses.dataclass
class RooflineTerms:
    """All inputs are PER-DEVICE quantities.

    jax's compiled.cost_analysis() reports the post-SPMD per-device program,
    and (calibrated empirically — see EXPERIMENTS.md §Dry-run) counts each
    while/scan body ONCE, so callers must depth-extrapolate scan-over-layers
    programs before constructing these terms.
    """

    flops: float                  # per-device HLO flops
    hbm_bytes: float              # per-device bytes accessed
    coll_bytes_per_dev: float     # per-device wire bytes
    chips: int
    # peak rates; default to the TPU v5e constants, overridable with a
    # calibrated hw_model() so dry-run predictions track measured devices
    peak_flops: float = PEAK_FLOPS
    hbm_bw: float = HBM_BW
    link_bw: float = LINK_BW

    @property
    def compute_s(self) -> float:
        return self.flops / self.peak_flops

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.coll_bytes_per_dev / self.link_bw

    @property
    def bound(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Lower-bound step time = max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def as_dict(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_bytes_per_dev": self.coll_bytes_per_dev,
            "chips": self.chips,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "bound": self.bound,
            "step_s": self.step_s,
        }


def model_flops(param_count: int, tokens: int, kind: str) -> float:
    """MODEL_FLOPS = 6 N D for training, 2 N D for inference forward."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * param_count * tokens


# ---------------------------------------------------------------------------
# Per-stage analytic cost models (HCK engines) + device-kind peak models
# ---------------------------------------------------------------------------

def default_device_kind() -> str:
    """Coarse device kind of the default jax backend: cpu / gpu / tpu.

    A backend that fails to initialize raises rather than reading as cpu.
    """
    import jax

    backend = jax.default_backend()
    if backend in ("gpu", "cuda", "rocm"):
        return "gpu"
    return backend if backend in HW_MODELS else "cpu"


def hw_model(device_kind: str | None = None, *, calibrate: bool = True) -> dict:
    """Peak-rate model for one device kind.

    Starts from the nominal :data:`HW_MODELS` row and — when ``calibrate``
    and the autotune tile DB holds measurements for this device kind —
    raises the peaks to the best *measured* achieved rates, so rooflines
    computed against it compare stages to what this machine demonstrably
    sustains rather than to a datasheet.  The returned dict records which
    source won under ``"calibration"``.
    """
    kind = device_kind or default_device_kind()
    model = dict(HW_MODELS.get(kind, HW_MODELS["cpu"]))
    model["device_kind"] = kind
    model["calibration"] = "nominal"
    if calibrate:
        try:
            from repro.kernels import autotune

            peaks = autotune.calibrated_peaks(kind)
        except Exception:   # noqa: BLE001 — no DB / import issue -> nominal
            peaks = None
        if peaks:
            if peaks.get("flops_per_s"):
                model["peak_flops"] = max(model["peak_flops"] / 1e3,
                                          peaks["flops_per_s"])
            if peaks.get("bytes_per_s"):
                model["hbm_bw"] = max(model["hbm_bw"] / 1e3,
                                      peaks["bytes_per_s"])
            model["calibration"] = "measured (tile_db)"
    return model


def stage_cost(stage: str, *, batch: int = 1, n0: int, r: int = 0,
               k: int = 1, d: int = 0, itemsize: int = 4) -> tuple[float, float]:
    """Closed-form (flops, hbm_bytes) of one stage launch.

    Shapes follow :func:`repro.kernels.registry.tile_config`: ``n0`` is the
    leaf/node/contraction size, ``r`` the rank (or second matrix extent),
    ``k`` the rhs count, ``d`` the ambient dimension, ``batch`` the number
    of leaves/nodes/queries/rows the launch covers.  Kernel-evaluation
    epilogues (exp, scaling) are counted at ~5 flops/element.  These are
    algorithmic minima — recomputation inside a tiled kernel is not
    charged — so achieved fractions derived from them are conservative.
    """
    epi = 5.0   # flops/element for the kernel nonlinearity epilogue
    if stage == "leaf_matvec":
        f = 2.0 * n0 * n0 * k + 2.0 * n0 * r * k
        b = n0 * n0 + n0 * r + n0 * k * 2 + r * k
    elif stage == "leaf_solve":
        f = 4.0 * n0 * n0 * k + 4.0 * n0 * r * k + 2.0 * r * r * k
        b = n0 * n0 + n0 * r + r * r + n0 * k * 2 + r * k
    elif stage == "leaf_project":
        f = 2.0 * n0 * r * k
        b = n0 * r + n0 * k + r * k
    elif stage == "leaf_factor":
        f = (2.0 / 3.0) * n0 ** 3          # Cholesky + triangular inverse
        b = 3.0 * n0 * n0
    elif stage == "build_gram":
        f = 2.0 * n0 * n0 * d + epi * n0 * n0 + n0 ** 3 / 3.0
        b = n0 * d + 2.0 * n0 * n0
    elif stage == "build_gram_dist":
        f = epi * n0 * n0 + n0 ** 3 / 3.0
        b = 3.0 * n0 * n0
    elif stage == "build_cross":
        f = 2.0 * n0 * r * d + epi * n0 * r + 4.0 * n0 * r * r
        b = n0 * d + r * d + r * r + n0 * r
    elif stage == "build_cross_dist":
        f = epi * n0 * r + 4.0 * n0 * r * r
        b = 2.0 * n0 * r + r * r
    elif stage in ("oos_local", "oos_walk"):
        # per query: distance row + epilogue + weight contraction
        f = 2.0 * n0 * d + epi * n0 + 2.0 * n0 * k
        b = n0 * (d + k) + d + k
    elif stage == "kernel_matvec":
        f = 2.0 * n0 * r * d + epi * n0 * r + 2.0 * n0 * r * k
        b = n0 * d + r * d + r * k + n0 * k
    elif stage == "pairwise_kernel":
        f = 2.0 * n0 * r * d + epi * n0 * r
        b = n0 * d + r * d + n0 * r
    else:
        raise ValueError(f"no cost model for stage {stage!r}")
    return batch * f, batch * b * float(itemsize)


def stage_roofline(stage: str, measured_s: float, *, batch: int = 1,
                   n0: int, r: int = 0, k: int = 1, d: int = 0,
                   itemsize: int = 4, hw: dict | None = None) -> dict:
    """Roofline record for one measured stage time.

    Returns flops/bytes (from :func:`stage_cost`), the ideal time under
    ``hw`` (max of compute and memory terms), which term binds, the
    achieved fraction of that roofline, and the achieved GFLOP/s / GB/s.
    """
    hw = hw or hw_model()
    flops, nbytes = stage_cost(stage, batch=batch, n0=n0, r=r, k=k, d=d,
                               itemsize=itemsize)
    compute_s = flops / hw["peak_flops"]
    memory_s = nbytes / hw["hbm_bw"]
    ideal_s = max(compute_s, memory_s)
    measured_s = max(float(measured_s), 1e-12)
    return {
        "stage": stage,
        "flops": flops,
        "bytes": nbytes,
        "intensity": flops / max(nbytes, 1.0),
        "compute_s": compute_s,
        "memory_s": memory_s,
        "ideal_s": ideal_s,
        "measured_s": measured_s,
        "bound": "compute" if compute_s >= memory_s else "memory",
        "achieved_frac": ideal_s / measured_s,
        "achieved_gflops": flops / measured_s / 1e9,
        "achieved_gbps": nbytes / measured_s / 1e9,
    }
