"""Readings for the limits: the numbers both cells compare, per seed.

  python3 bench/tools/readings.py --seeds 601 602 603 [--window 1] \\
      [--fault half] [--control]

In one process, so that every program compiles once.  Per seed: the
``msd.serve`` set-up (the program's fit, its registry and warm-up) and a
window of ``--window`` seconds of its traffic; the held-out predictions of
the fitted model; then the reference fit on the program's tree.  Against
the reference, the numbers of ``msd.fit`` (tree, factors, alpha, the
held-out predictions) and of ``msd.serve`` (every answer served in the
window), each prediction number with its centered pair and the offset;
the reference's residual by refinement step, the program's alpha's
residual under the reference operator, and the held-out predictions of
the reference's own float32 solve with two float32 refinement steps (the
comparison's earlier reference, ``f32_ref``) against the converged one.

With ``--fault`` the fault (``bench/faults.py``) is planted in the
program; with ``--control`` the configuration's control runs in the
program's place.  Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))


def seed_row(ctx, window: float) -> dict:
    """The readings of one seed (``ctx.seed``)."""
    import jax
    import numpy as np

    import krrcell

    driver = ctx.cell.driver()
    t0 = time.perf_counter()
    state = driver.setup(ctx)
    rec = driver.serve_window(ctx, state, window, ctx.traffic)
    prob, model = state["prob"], state.pop("model")
    state.clear()
    idx, served = [], []
    for z, take in rec["outputs"]:
        pts = rec["points"][rec["start"][take[0]]:rec["start"][take[-1] + 1]]
        idx.append(pts)
        served.append(np.asarray(z)[:len(pts)])
    idx, served = np.concatenate(idx), np.concatenate(served)
    del rec
    held = np.asarray(model.engine(prob.xt))
    factors, alpha = model.factors, model.alpha
    del model
    ref = krrcell.reference_fit(ctx, prob, factors.tree.perm,
                                factors.x_sorted)
    all_idx = np.arange(prob.xt.shape[0])
    row = {
        "seed": ctx.seed, "tree": ref.misplaced,
        "factors": krrcell.factor_error(factors, ref),
        "alpha": krrcell.rel(alpha, ref.alpha),
        "fit": krrcell.prediction_errors(ctx, prob, ref, all_idx, held),
        "serve": krrcell.prediction_errors(ctx, prob, ref, idx, served),
        "served_answers": int(len(idx)),
        "reference_residuals": ref.residuals,
        "program_alpha_residual": krrcell.alpha_residual(ctx, ref, alpha),
    }
    del factors, alpha
    # the earlier reference: two float32 refinement steps on the device
    mod = ctx.cell.reference()
    with jax.default_matmul_precision(krrcell.REFERENCE_MATMUL):
        fac = mod.factorize(ref.factors, ctx.cfg["lam"])
        x = mod._inverse_apply(ref.factors, fac, ref.y_sorted)
        for _ in range(2):
            x = x + mod._inverse_apply(
                ref.factors, fac,
                ref.y_sorted - (mod.matvec(ref.factors, x)[0]
                                + ctx.cfg["lam"] * x))
        del fac
        d_leaf = mod.matvec(ref.factors, x)[1]
    old = krrcell.Reference(ref.misplaced, ref.directions, ref.thresholds,
                            ref.x_sorted, ref.factors, x, d_leaf,
                            ref.y_sorted, [])
    old_pred, _ = krrcell.reference_predict(ctx, prob, old, all_idx)
    row["f32_ref"] = krrcell.prediction_errors(ctx, prob, ref, all_idx,
                                               old_pred)
    row["f32_ref"]["alpha"] = krrcell.rel(x, ref.alpha)
    row["f32_ref"]["residual"] = mod.residual(mod.host64(ref.factors), x,
                                              ref.y_sorted, ctx.cfg["lam"])
    row["seconds"] = time.perf_counter() - t0
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="msd.serve")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--window", type=float, default=1.0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()

    import harness

    root = BENCH.parent
    cell = harness.Cell.find(root, args.workload)
    harness.run_env(root, cell, args.control)
    why = harness.platform_error(cell.workload["chips"])
    if why is not None:
        print(f"readings: {why}", file=sys.stderr)
        return 3
    if args.fault:
        import faults

        faults.plant(args.workload, args.fault)
    control = cell.config["control"] if args.control else None

    def say(msg):
        print(msg, file=sys.stderr, flush=True)

    for seed in args.seeds:
        ctx = harness.Context(cell, seed, say=say, control=control)
        row = seed_row(ctx, args.window)
        row.update(fault=args.fault, control=bool(args.control))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
