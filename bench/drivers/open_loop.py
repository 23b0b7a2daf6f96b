"""Open-loop prediction traffic against the program's serving loop.

The traffic file gives the arrival rate, the request sizes and the
batching; the seed gives the order.  Requests arrive on a Poisson schedule
of ``rate_rps`` and carry ``points_min`` to ``points_max`` query points,
log-uniform, drawn from the held-out set.  The sizes and gaps are drawn
once from a fixed seed and only their order and the points come from
``--seed``, so every seed offers the same work.

One thread serves: each pass takes every due request, up to
``max_batch_points`` points, as one ``KRRServeLoop.serve`` call over a
``ModelRegistry`` with buckets ``min_bucket`` .. ``max_bucket``.  The
batch is padded on the host to its bucket by repeating its last row, as
``PredictEngine`` would pad it, so that only the bucket shapes, all
warmed up in set-up, ever reach the device (``PredictEngine.apply``
compiles its eager padding and slicing per distinct row count).  A
request's latency runs from when it was due to the end of the call that
carried it; requests due in the window and still queued at its close are
served and counted.  ``gen_lag`` is how late the idle loop woke for a due
request.
"""
from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

import krrcell


#: seed of the request sizes and gaps, the same for every ``--seed``
SHAPES_SEED = 0


def schedule(traffic: dict, seconds: float, seed: int):
    """``(due_s, sizes)`` of the requests due in ``[0, seconds)``."""
    base = np.random.default_rng(SHAPES_SEED)
    rate = traffic["rate_rps"]
    n = int(rate * seconds * 1.5) + 64
    gaps = base.exponential(1.0 / rate, n)
    lo, hi = np.log(traffic["points_min"]), np.log(traffic["points_max"] + 1)
    sizes = np.floor(np.exp(base.uniform(lo, hi, n))).astype(np.int64)
    order = np.random.default_rng(seed).permutation(n)
    due = np.cumsum(gaps[order])
    sizes = sizes[order]
    keep = due < seconds
    return due[keep], sizes[keep]


def setup(ctx):
    from repro.serving.predict_service import ModelRegistry, bucket_size
    from repro.serving.serve_loop import KRRServeLoop

    tr = ctx.traffic
    prob = krrcell.problem(ctx)
    model = prob.fit()
    jax.block_until_ready(krrcell.model_arrays(model))
    registry = ModelRegistry(model, warmup=True, min_bucket=tr["min_bucket"],
                             max_bucket=tr["max_bucket"])
    loop = KRRServeLoop(registry)
    xt_host = np.asarray(prob.xt)
    buckets, b = [], tr["min_bucket"]
    while b <= tr["max_bucket"]:
        buckets.append(b)
        b *= 2
    for b in buckets:                    # the serve path at every shape
        jax.block_until_ready(loop.serve(jnp.asarray(xt_host[:b])).z)
    return {"prob": prob, "model": model, "loop": loop, "xt": xt_host,
            "bucket": lambda q: bucket_size(q, tr["min_bucket"],
                                            tr["max_bucket"])}


def serve_window(ctx, state, seconds, traffic):
    """Serve the schedule of ``traffic`` for ``seconds``; the record."""
    loop, xt, bucket = state["loop"], state["xt"], state["bucket"]
    due, sizes = schedule(traffic, seconds, ctx.seed)
    rng = np.random.default_rng([ctx.seed, 1])
    flat = rng.integers(0, xt.shape[0], int(sizes.sum()))
    start = np.concatenate([[0], np.cumsum(sizes)])
    cap = traffic["max_batch_points"]
    engine = loop.registry.live.engine
    stats0 = dict(engine.stats)
    done = np.full(len(due), np.nan)
    lags, batches, outputs = [], [], []
    queue = collections.deque()
    nxt = 0
    t0 = time.perf_counter()
    while nxt < len(due) or queue:
        now = time.perf_counter() - t0
        while nxt < len(due) and due[nxt] <= now:
            queue.append(nxt)
            nxt += 1
        if not queue:
            with ctx.span("wait"):
                time.sleep(max(0.0, due[nxt] - now))
            lags.append(time.perf_counter() - t0 - due[nxt])
            continue
        take, rows = [], 0
        while queue and rows + sizes[queue[0]] <= cap:
            rows += sizes[queue[0]]
            take.append(queue.popleft())
        idx = flat[start[take[0]]:start[take[-1] + 1]]
        idx = np.concatenate([idx, np.full(bucket(rows) - rows, idx[-1])])
        with ctx.span("serve"):
            t_send = time.perf_counter() - t0
            served = loop.serve(jnp.asarray(xt[idx]))
            t_done = time.perf_counter() - t0
        done[take] = t_done
        batches.append((rows, bucket(rows), t_done - t_send))
        outputs.append((served.z, take))
    stats1 = engine.stats
    latency = done - due
    return {
        "due": due, "sizes": sizes, "points": flat, "start": start,
        "latency": latency,
        "lags": np.asarray(lags), "batches": batches, "outputs": outputs,
        "engine": {k: stats1[k] - stats0[k]
                   for k in ("calls", "queries", "padded_queries")},
    }


def window(ctx, state, seconds):
    rec = serve_window(ctx, state, seconds, ctx.traffic)
    state["record"] = rec
    lat = rec["latency"] * 1e3
    return {"attempted": int(len(lat)),
            "failed": int(np.sum(~np.isfinite(lat))),
            "metrics": {"predict_p95_ms": float(np.percentile(lat, 95)),
                        "predict_p50_ms": float(np.percentile(lat, 50))},
            "serve": rec}


def check(ctx, state):
    prob, rec = state["prob"], state.pop("record")
    limits = ctx.cfg["limits"]
    factors = state.pop("model").factors
    state.pop("loop")
    # every answer served: the held-out row and the prediction of each
    idx, pred = [], []
    for z, take in rec["outputs"]:
        pts = rec["points"][rec["start"][take[0]]:rec["start"][take[-1] + 1]]
        idx.append(pts)
        pred.append(np.asarray(z)[:len(pts)])
    idx, pred = np.concatenate(idx), np.concatenate(pred)
    rec["outputs"] = None
    ref = krrcell.reference_fit(ctx, prob, factors.tree.perm,
                                factors.x_sorted)
    e = krrcell.prediction_errors(ctx, prob, ref, idx, pred)
    ctx.say(f"served answers compared: {len(idx) - e['near']} of {len(idx)} "
            f"({e['near']} routed by rounding); offset {e['offset']:.4g}")
    return [
        krrcell.check("tree", ref.misplaced, limits["tree"]),
        krrcell.check("served", e["share"], limits["predict"]),
        krrcell.check("served_worst", e["worst"], limits["predict_worst"]),
        krrcell.check("served_centered", e["centered"], limits["centered"]),
        krrcell.check("served_centered_worst", e["centered_worst"],
                      limits["centered_worst"]),
    ]
