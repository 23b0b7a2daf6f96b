"""Serving launcher.

``--task lm`` (default): prefill a batch of prompts, decode with the
arch-appropriate cache (exact KV or the paper's HCK Algorithm-3 state).

  PYTHONPATH=src python -m repro.launch.serve --task lm --arch granite-3-2b \
      --reduced --prompt-len 64 --gen 32 --batch 2

``--task krr``: fit an HCK kernel ridge model and serve a stream of query
micro-batches through the versioned hot-swap registry
(repro.serving.predict_service.ModelRegistry), reporting queries/sec and
latency percentiles.  ``--update-batch N`` absorbs N new points online
mid-stream (krr.fit_incremental) and hot-swaps the new version under the
running stream — zero downtime, swap latency reported; ``--rollback``
additionally rolls back to v1 for the tail of the stream.

  PYTHONPATH=src python -m repro.launch.serve --task krr --n 16384 \
      --rank 64 --queries 4096 --update-batch 256 --rollback
"""
from __future__ import annotations

import argparse
import time

from repro.launch.platform import setup_platform

setup_platform()    # JAX_PLATFORMS/compile cache must land before jax loads

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def run_lm(args):
    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.models.model_zoo import input_specs
    from repro.models.transformer import N_CODEBOOKS, init_params
    from repro.serving.serve_loop import ServeSession

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    max_seq = args.max_seq or (args.prompt_len + args.gen + 16)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)

    shape = ShapeConfig("serve", args.prompt_len, args.batch, "prefill")
    batch = input_specs(cfg, shape, abstract=False, key=key)

    session = ServeSession(cfg, params, max_seq=max_seq)
    t0 = time.perf_counter()
    last_logits = session.prefill(batch)
    jax.block_until_ready(last_logits)
    t_prefill = time.perf_counter() - t0
    if cfg.family == "audio":
        last = jnp.argmax(last_logits.reshape(
            args.batch, N_CODEBOOKS, cfg.vocab), axis=-1)[:, None, :]
    else:
        last = jnp.argmax(last_logits, axis=-1)[:, None]

    t0 = time.perf_counter()
    out = session.decode(last, steps=args.gen, temperature=args.temperature)
    jax.block_until_ready(out)
    t_decode = time.perf_counter() - t0
    print(f"arch={cfg.name} prefill {args.prompt_len} tok: {t_prefill*1e3:.1f} ms; "
          f"decode {args.gen} tok: {t_decode*1e3:.1f} ms "
          f"({t_decode/args.gen*1e3:.2f} ms/tok)")
    print("generated token ids (first row):", out[0, :16].tolist())


def run_krr(args):
    from repro.core import krr
    from repro.core.kernels_fn import BaseKernel
    from repro.kernels.registry import SolveConfig
    from repro.serving.predict_service import ModelRegistry
    from repro.serving.serve_loop import KRRServeLoop

    cfg = SolveConfig(backend=args.solve_backend)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (args.n, args.d))
    y = jnp.sin(x[:, 0]) + 0.25 * jnp.cos(x[:, 1] * 2.0)
    ker = BaseKernel("gaussian", sigma=2.0)

    t0 = time.perf_counter()
    model = krr.fit(x, y, kernel=ker, lam=1e-2, rank=args.rank,
                    key=jax.random.PRNGKey(1), solve_config=cfg)
    jax.block_until_ready(model.alpha)
    t_fit = time.perf_counter() - t0

    t0 = time.perf_counter()
    registry = ModelRegistry(model, tag="fit", warmup=True)
    t_warm = time.perf_counter() - t0
    loop = KRRServeLoop(registry)

    qkey = jax.random.PRNGKey(2)
    queries = jax.random.normal(qkey, (args.queries, args.d))
    batches = [queries[i:i + args.micro_batch]
               for i in range(0, args.queries, args.micro_batch)]
    swap_at = len(batches) // 2 if args.update_batch else None
    rollback_at = (3 * len(batches)) // 4 if args.rollback else None
    t_swap = t_rollback = None
    info = None
    t0 = time.perf_counter()
    for i, batch in enumerate(batches):
        if swap_at is not None and i == swap_at:
            # online update + hot swap, mid-stream: the live version keeps
            # serving while the new one builds and warms; the swap itself
            # is one atomic reference store
            ukey = jax.random.PRNGKey(5)
            xu = jax.random.normal(ukey, (args.update_batch, args.d))
            yu = jnp.sin(xu[:, 0]) + 0.25 * jnp.cos(xu[:, 1] * 2.0)
            t1 = time.perf_counter()
            _, info = registry.update_and_publish(xu, yu, tag="update",
                                                  warmup=True)
            t_swap = time.perf_counter() - t1
        if rollback_at is not None and i == rollback_at:
            t1 = time.perf_counter()
            registry.rollback(1)
            t_rollback = time.perf_counter() - t1
        loop.serve(batch)
    total = time.perf_counter() - t0
    lat = sorted(r.latency_s for r in loop.responses)
    p50 = lat[len(lat) // 2]
    p99 = lat[min(len(lat) - 1, int(len(lat) * 0.99))]
    print(f"krr n={args.n} rank={args.rank} d={args.d}: "
          f"fit {t_fit:.2f} s, publish+warmup {t_warm:.2f} s "
          f"(versions served {loop.versions_served})")
    print(f"served {args.queries} queries in micro-batches of "
          f"{args.micro_batch}: {args.queries / total:,.0f} queries/s, "
          f"latency p50 {p50*1e3:.2f} ms  p99 {p99*1e3:.2f} ms")
    if t_swap is not None:
        print(f"online update of {args.update_batch} points mid-stream: "
              f"build+warm+swap {t_swap*1e3:.1f} ms "
              f"(insert k={info.record.k}/leaf, resid {info.residual:.2e}, "
              f"rebuild={info.needs_rebuild})")
    if t_rollback is not None:
        print(f"rollback to v1 mid-stream: {t_rollback*1e3:.2f} ms "
              f"(stored engine reused — bitwise-identical serving)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", choices=["lm", "krr"], default="lm")
    # lm task
    ap.add_argument("--arch")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--max-seq", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    # krr task
    ap.add_argument("--n", type=int, default=16384)
    ap.add_argument("--rank", type=int, default=64)
    ap.add_argument("--d", type=int, default=8)
    ap.add_argument("--queries", type=int, default=4096)
    ap.add_argument("--micro-batch", type=int, default=256)
    ap.add_argument("--update-batch", type=int, default=0,
                    help="absorb this many new points online mid-stream and "
                    "hot-swap the updated model (0 = off)")
    ap.add_argument("--rollback", action="store_true",
                    help="roll back to the initial version for the stream "
                    "tail (demonstrates the stored-version swap)")
    ap.add_argument("--solve-backend", choices=["auto", "xla", "pallas"],
                    default="auto", help="SolveConfig backend shared by the "
                    "build engine, solve, and prediction stages")
    args = ap.parse_args()

    if args.task == "lm":
        if not args.arch:
            raise SystemExit("--arch is required for --task lm")
        run_lm(args)
    else:
        run_krr(args)


if __name__ == "__main__":
    main()
