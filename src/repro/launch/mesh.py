"""Production mesh construction + sharding resolution.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: (data=16, model=16) = 256 chips.  Multi-pod:
(pod=2, data=16, model=16) = 512 chips — the leading "pod" axis carries the
cross-pod data parallelism (slowest links carry the least-frequent
collective: the per-step gradient all-reduce, optionally int8-compressed).
Nothing below assumes those numbers; MeshConfig is config.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, MeshConfig, ShapeConfig
from repro.models import transformer as tf


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def kernel_mesh(n_devices: int | None = None, axis: str = "dev") -> Mesh:
    """1-D device mesh for the distributed HCK pipeline.

    The kernel side shards the hierarchy by SUBTREE — device p owns the
    contiguous leaf range whose root-path prefix equals p (see
    ``repro.launch.dist_hck``) — so its mesh is a single axis over the
    first ``n_devices`` devices (default: all).  The device count must be
    a power of two: the top ``log2(P)`` tree levels map 1:1 onto mesh
    coordinates, and a binary tree has no non-power-of-two level widths
    (``dist_hck.device_level`` raises otherwise).
    """
    from repro.launch.dist_hck import device_level

    n = n_devices if n_devices is not None else jax.device_count()
    if n > jax.device_count():
        raise ValueError(
            f"kernel_mesh wants {n} devices but only {jax.device_count()} "
            "are visible (set XLA_FLAGS="
            "--xla_force_host_platform_device_count=N for a virtual mesh)")
    device_level(n)          # validates the power-of-two constraint
    # Auto axes: the pipeline places its arrays with NamedSharding and lets
    # GSPMD partition the rest (jax.make_mesh now defaults to Explicit)
    return jax.make_mesh((n,), (axis,), devices=jax.devices()[:n],
                         axis_types=(AxisType.Auto,))


def make_mesh(cfg: MeshConfig) -> Mesh:
    return jax.make_mesh(cfg.shape, cfg.axis_names,
                         axis_types=(AxisType.Auto,) * len(cfg.axis_names))


def mesh_config_for(mesh: Mesh) -> MeshConfig:
    names = mesh.axis_names
    if "pod" in names:
        return MeshConfig(pods=mesh.shape["pod"], data=mesh.shape["data"],
                          model=mesh.shape["model"])
    return MeshConfig(pods=1, data=mesh.shape["data"],
                      model=mesh.shape["model"])


# ---------------------------------------------------------------------------
# Input shardings per (arch x shape)
# ---------------------------------------------------------------------------

def _dp(mcfg: MeshConfig):
    return mcfg.dp_axes if len(mcfg.dp_axes) > 1 else mcfg.dp_axes[0]


def _div(n: int, ways: int) -> bool:
    return ways > 0 and n % ways == 0


def batch_pspecs(cfg: ArchConfig, shape: ShapeConfig, mcfg: MeshConfig) -> dict:
    """PartitionSpecs for every input in model_zoo.input_specs."""
    dp = _dp(mcfg)
    dp_ways = mcfg.pods * mcfg.data
    b, s = shape.global_batch, shape.seq_len
    batch_spec = dp if _div(b, dp_ways) else None

    if shape.kind in ("train", "prefill"):
        tok = P(batch_spec, None, None) if cfg.family == "audio" \
            else P(batch_spec, None)
        out = {"tokens": tok}
        if cfg.family == "vlm":
            out["patches"] = P(batch_spec, None, None)
        if shape.kind == "train":
            out["labels"] = tok
        return out

    # decode: tokens + caches + pos
    tok = P(batch_spec, None, None) if cfg.family == "audio" \
        else P(batch_spec, None)
    caches = cache_pspecs(cfg, shape, mcfg)
    return {"tokens": tok, "caches": caches, "pos": P()}


def cache_pspecs(cfg: ArchConfig, shape: ShapeConfig, mcfg: MeshConfig) -> dict:
    """Decode-cache shardings.

    Rules: batch over DP when divisible; otherwise (long_500k, B=1) shard
    the SEQUENCE dim of KV caches over the DP axes (sequence parallelism);
    heads over "model" when divisible, else head_dim.
    """
    dp = _dp(mcfg)
    dp_ways = mcfg.pods * mcfg.data
    tp = mcfg.model
    b, s = shape.global_batch, shape.seq_len
    hck = tf.use_hck(cfg, s)
    bspec = dp if _div(b, dp_ways) else None
    seq_dp = None if bspec is not None else dp        # SP fallback (B=1)

    def heads_spec(h):
        return "model" if _div(h, tp) else None

    def seq_shard(seq_len):
        """KV caches shard the SEQUENCE dim over "model" (flash-decode
        layout): scores/output reductions over seq become small psums,
        and the per-token cache write lands on one shard.  Sharding heads
        or head_dim instead makes XLA re-distribute the whole cache per
        layer (measured 2.2e11 B/dev/token on deepseek-67b — §Perf)."""
        if seq_dp is not None and _div(seq_len, dp_ways * tp):
            return (seq_dp, "model") if isinstance(seq_dp, str) else \
                tuple(list((seq_dp if isinstance(seq_dp, tuple) else
                            (seq_dp,))) + ["model"])
        return "model" if _div(seq_len, tp) else seq_dp

    out: dict = {}
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    if cfg.family in ("dense", "vlm", "audio", "moe"):
        if hck:
            # the whole point of the Alg-3 decode state is that it is SMALL
            # (window n0 + rank r, not the 500k cache) — replicating the
            # window makes its per-token ring-buffer shift purely local
            # (seq-sharding it cost 3.6e9 B/dev/token of shift traffic —
            # §Perf iteration 4)
            out["hck"] = {
                "window_k": P(None, bspec, None, None, None),
                "window_v": P(None, bspec, None, None, None),
                "lm_k": P(None, bspec, None, None, None),
                "sigma": P(None, bspec, None, None, None),
                "summary": P(None, bspec, None, None, None),
                "win_len": P(None),
            }
        else:
            out["k"] = P(None, bspec, None, seq_shard(s), None)
            out["v"] = P(None, bspec, None, seq_shard(s), None)
    if cfg.ssm:
        din = cfg.ssm_expand * cfg.d_model
        nh = din // cfg.ssm_head_dim
        out["ssm"] = P(None, bspec, heads_spec(nh), None, None)
        out["conv"] = P(None, bspec, None, None)
        if cfg.family == "hybrid" and cfg.shared_attn_every:
            if hck:
                out["shared_hck"] = {
                    "window_k": P(None, bspec, None, None, None),
                    "window_v": P(None, bspec, None, None, None),
                    "lm_k": P(None, bspec, None, None, None),
                    "sigma": P(None, bspec, None, None, None),
                    "summary": P(None, bspec, None, None, None),
                    "win_len": P(None),
                }
            else:
                out["shared_k"] = P(None, bspec, None, seq_shard(s), None)
                out["shared_v"] = P(None, bspec, None, seq_shard(s), None)
    return out


def to_shardings(mesh: Mesh, pspec_tree):
    return jax.tree.map(
        lambda p: NamedSharding(mesh, p), pspec_tree,
        is_leaf=lambda x: isinstance(x, P))
