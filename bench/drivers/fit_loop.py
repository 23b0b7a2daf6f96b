"""Closed loop of whole fits: ``krr.fit`` on the same seeded data and key,
back to back, each ending in ``block_until_ready`` on everything it
returns.  ``fit_s`` is the time from the window's start to the end of the
last fit, over the number of fits.

Compared with the plain reference, for the last fit of the window: the
tree (points on the wrong side of a split beyond rounding), the factors,
alpha, and the predictions of the fitted plan on every held-out point.
"""
from __future__ import annotations

import time

import jax
import numpy as np

import krrcell


def setup(ctx):
    prob = krrcell.problem(ctx)
    model = prob.fit()                   # compiles, or loads the cache
    jax.block_until_ready(krrcell.model_arrays(model))
    if model.factors.levels != ctx.cfg["levels"]:
        raise ValueError(f"the fit built {model.factors.levels} levels; the "
                         f"configuration states {ctx.cfg['levels']}")
    return {"prob": prob, "model": None}


def window(ctx, state, seconds):
    fit = state["prob"].fit
    fits, model = 0, None
    t0 = time.perf_counter()
    while True:
        model = None                     # one fit's memory at a time
        with ctx.span("fit"):
            model = fit()
            jax.block_until_ready(krrcell.model_arrays(model))
        fits += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    state["model"] = model
    return {"attempted": fits, "failed": 0,
            "metrics": {"fit_s": elapsed / fits}}


def check(ctx, state):
    prob, model = state["prob"], state.pop("model")
    limits = ctx.cfg["limits"]
    idx = np.arange(prob.xt.shape[0])
    pred = np.asarray(model.engine(prob.xt))
    factors, alpha = model.factors, model.alpha
    model.inverse = model.leaf_lo = model.plan = None
    del model
    ref = krrcell.reference_fit(ctx, prob, factors.tree.perm,
                                factors.x_sorted)
    e = krrcell.prediction_errors(ctx, prob, ref, idx, pred)
    ctx.say(f"held-out queries compared: {len(idx) - e['near']} of "
            f"{len(idx)} ({e['near']} routed by rounding); offset "
            f"{e['offset']:.4g}, centered {e['centered']:.4g}, centered "
            f"worst {e['centered_worst']:.4g}; program alpha's residual "
            f"under the reference operator "
            f"{krrcell.alpha_residual(ctx, ref, alpha):.3e}")
    return [
        krrcell.check("tree", ref.misplaced, limits["tree"]),
        krrcell.check("factors", krrcell.factor_error(factors, ref),
                      limits["factors"]),
        krrcell.check("alpha", krrcell.rel(alpha, ref.alpha),
                      limits["alpha"]),
        krrcell.check("predict", e["share"], limits["predict"]),
        krrcell.check("predict_worst", e["worst"], limits["predict_worst"]),
    ]
