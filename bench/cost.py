"""Operations and bytes of the program's kernels and calls, from shapes.

``stage_cost`` is a copy of ``repro.utils.roofline.stage_cost`` (closed-form
algorithmic minima per registry stage launch), kept here so that a change
to the program cannot change the yardstick.  ``fit_launches`` and
``serve_launches`` list the launches one ``krr.fit`` and one served batch
make at a configuration's shapes; ``fit_flops``/``serve_flops`` add the
XLA parts of each call.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"

_EPI = 5.0   # flops per element of the kernel nonlinearity epilogue


def stage_cost(stage: str, *, batch: int = 1, n0: int, r: int = 0,
               k: int = 1, d: int = 0, itemsize: int = 4,
               chol: bool = True) -> tuple[float, float]:
    """Closed-form ``(flops, hbm_bytes)`` of one stage launch over
    ``batch`` rows (leaves, nodes or queries)."""
    if stage == "leaf_factor":
        f = (2.0 / 3.0) * n0 ** 3          # Cholesky + triangular inverse
        b = 3.0 * n0 * n0
    elif stage == "build_gram":
        f = 2.0 * n0 * n0 * d + _EPI * n0 * n0 + (n0 ** 3 / 3.0 if chol
                                                   else 0.0)
        b = n0 * d + (2.0 if chol else 1.0) * n0 * n0
    elif stage == "build_cross":
        f = 2.0 * n0 * r * d + _EPI * n0 * r + 4.0 * n0 * r * r
        b = n0 * d + r * d + r * r + n0 * r
    elif stage in ("oos_local", "oos_walk"):
        # per query: distance row + epilogue + weight contraction
        f = 2.0 * n0 * d + _EPI * n0 + 2.0 * n0 * k
        b = n0 * (d + k) + d + k
    else:
        raise ValueError(f"no cost model for stage {stage!r}")
    return batch * f, batch * b * float(itemsize)


def _shape(cfg: dict) -> tuple[int, int, int, int, int]:
    levels, leaf, r = cfg["levels"], cfg["leaf"], cfg["rank"]
    k = cfg["classes"] if cfg["task"] == "multiclass" else 1
    return levels, leaf, r, cfg["d"], k


def fit_launches(cfg: dict) -> dict:
    """``{kernel: [(stage, kwargs), ...]}`` of one fit's Pallas launches."""
    levels, n0, r, d, _ = _shape(cfg)
    gram = [("build_gram", dict(batch=1 << lvl, n0=r, d=d))
            for lvl in range(levels)]
    gram.append(("build_gram", dict(batch=1 << levels, n0=n0, d=d,
                                    chol=False)))
    cross = [("build_cross", dict(batch=1 << (levels - 1), n0=2 * n0, r=r,
                                  d=d))]
    cross += [("build_cross", dict(batch=1 << (lvl - 1), n0=2 * r, r=r, d=d))
              for lvl in range(1, levels)]
    return {"gram_chol_kernel": gram, "cross_solve_kernel": cross,
            "hck_leaf_factor": [("leaf_factor",
                                 dict(batch=1 << levels, n0=n0))]}


def fit_flops(cfg: dict, refine_steps: int = 2) -> float:
    """Counted flops of one ``krr.fit``: partition projections, the build
    launches and the per-node inverse Cholesky factors, Algorithm 2
    (about 37 n r^2, ``repro.core.hmatrix``), the refined solve (about
    18 n r per right-hand side per operator application) and the plan."""
    levels, n0, r, d, k = _shape(cfg)
    n = n0 << levels
    total = 2.0 * n * d * levels
    for launches in fit_launches(cfg).values():
        for stage, kw in launches:
            if stage != "leaf_factor":
                total += stage_cost(stage, **kw)[0]
    total += sum((1 << lvl) * r ** 3 / 3.0 for lvl in range(levels))
    total += 37.0 * n * r * r
    total += (1 + 2 * refine_steps) * 18.0 * n * r * k
    total += 2.0 * n * r * k
    return total


def serve_launches(cfg: dict, rows: int) -> list:
    """The two OOS launches of a served batch of ``rows`` (padded) rows."""
    _, n0, r, d, k = _shape(cfg)
    return [("oos_local", dict(batch=rows, n0=n0, d=d, k=k)),
            ("oos_walk", dict(batch=rows, n0=r, d=d, k=k))]


def serve_flops(cfg: dict, rows: int) -> float:
    """Counted flops of one served batch: routing plus both launches."""
    levels, _, _, d, _ = _shape(cfg)
    return 2.0 * rows * d * levels + sum(
        stage_cost(s, **kw)[0] for s, kw in serve_launches(cfg, rows))


def peaks(device_kind: str) -> dict:
    """The peak row of ``device_kind``; an unknown device is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> float:
    """Percent of the roofline: the least time the chip could take, the
    larger of flops over peak and bytes over bandwidth, over ``seconds``."""
    ideal = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * ideal / seconds
