"""Gradient-based capacity planning vs grid search, on the same budget.

    PYTHONPATH=src python examples/capacity_plan.py [--devices 64]
        [--periods 6] [--slo-margin 1.02] [--budget 49] [--seed 0]

The operator question: how much edge-server capacity (and how aggressive
a model-ladder mix) does this fleet need to hit an accuracy SLO?  Two
knobs reparameterize the engine's continuous leaves:

  * ``log_cap``  — server-capacity scale: ``p_es * exp(-log_cap)``
    (bigger knob = faster ES = more admitted offloads);
  * ``mix``      — ladder-mix logit: ``acc * 2 * sigmoid(mix)`` rescales
    the accuracy ladder (a stand-in for shifting load toward larger
    server-side models).

Both planners search the SAME 2-D knob space for the cheapest point
meeting the SLO (mean served accuracy per device-period):

  * *grid search* — the classic operator move: a budget-bounded lattice
    scan, one full rollout per point (the only option when the serving
    stack is a black box);
  * *gradient descent* — Adam on a penalized SLO loss, fed by
    `rollout_value_and_grad` (`EngineParams.with_differentiable`): the
    whole epoch — implicit-gradient simplex, smoothed rounding,
    sigmoid-relaxed admission — differentiates in ONE backward sweep
    that costs ~1.3x a forward rollout, so every step is one "eval" on
    the shared budget.  Straight-through mode reports the HARD rollout's
    value, so SLO attainment is measured on the real metric, not the
    relaxation.

The script prints both trajectories and exits 1 unless the gradient
planner reaches the SLO in FEWER rollout evals than the grid scan.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))


def sigmoid(x):
    import numpy as np
    return 1.0 / (1.0 + np.exp(-x))


def main() -> int:
    from repro.core.types import enable_compile_cache
    enable_compile_cache()
    import numpy as np

    import optax

    from repro.api import engine as E
    from repro.serving import FleetConfig

    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=64)
    ap.add_argument("--periods", type=int, default=6)
    ap.add_argument("--slo-margin", type=float, default=1.02,
                    help="SLO = margin * base mean accuracy")
    ap.add_argument("--budget", type=int, default=49,
                    help="rollout-eval budget (grid points)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = FleetConfig(n_devices=args.devices, T=1.2,
                      n_servers=max(1, args.devices // 16), policy="amr2",
                      backend="jax", rate=9.0, batch_max=8,
                      horizon=args.periods + 2, seed=args.seed,
                      straggler_frac=0.25, outage_frac=0.1)
    base = E.EngineParams.from_config(cfg, horizon=args.periods + 2)
    armed = base.with_differentiable(smooth_mode="st")
    base_es = np.asarray(base.p_es, np.float64)
    base_acc = np.asarray(base.acc, np.float64)
    N = args.devices * args.periods

    def at_knobs(log_cap, mix, p=None):
        return dataclasses.replace(
            p if p is not None else base,
            p_es=base_es * np.exp(-log_cap),
            acc=base_acc * 2.0 * sigmoid(mix))

    def mean_acc(log_cap, mix):
        p = at_knobs(log_cap, mix)
        _, m = E.rollout(E.init_state(p), p, args.periods)
        return float(np.sum(np.asarray(m.total_accuracy))) / N

    base_acc_mean = mean_acc(0.0, 0.0)
    slo = args.slo_margin * base_acc_mean
    # capacity is not free: the penalty keeps both planners looking for
    # the CHEAPEST feasible point instead of maxing the knob
    lam = 0.02 * slo

    def objective(log_cap, mix, acc_mean):
        short = max(0.0, slo - acc_mean)
        return short * short / (slo * slo) + lam * max(0.0, log_cap) / slo

    print(f"fleet: {args.devices} devices x {args.periods} periods, "
          f"base mean acc {base_acc_mean:.4f}, SLO {slo:.4f} "
          f"({args.slo_margin:.2f}x)")

    # ---- grid search ----------------------------------------------------
    side = max(2, int(round(args.budget ** 0.5)))
    caps = np.linspace(0.0, 0.5, side)
    mixes = np.linspace(-1.0, 1.0, side)
    grid_evals, grid_hit, grid_best = 0, None, (np.inf, None)
    for lc in caps:                       # cheapest capacity first
        for mx in mixes:
            acc = mean_acc(float(lc), float(mx))
            grid_evals += 1
            obj = objective(float(lc), float(mx), acc)
            if obj < grid_best[0]:
                grid_best = (obj, (float(lc), float(mx), acc))
            if acc >= slo and grid_hit is None:
                grid_hit = grid_evals
                print(f"[grid] SLO met at eval {grid_evals}: "
                      f"log_cap={lc:.3f} mix={mx:.3f} acc={acc:.4f}")
        if grid_hit is not None:
            break
    if grid_hit is None:
        grid_hit = grid_evals + 1         # never met within budget
        print(f"[grid] SLO not met in {grid_evals} evals; "
              f"best acc {grid_best[1][2]:.4f}")

    # ---- gradient descent -----------------------------------------------
    knobs = {"log_cap": np.float64(0.0), "mix": np.float64(0.0)}
    opt = optax.adam(0.12)
    opt_state = opt.init(knobs)
    gd_evals, gd_hit = 0, None
    for it in range(args.budget):
        p = at_knobs(knobs["log_cap"], knobs["mix"], armed)
        val, g = E.rollout_value_and_grad(
            E.init_state(p), p, args.periods, wrt=("p_es", "acc"))
        gd_evals += 1
        acc = float(val) / N
        # knob-space chain rule through the two reparameterizations
        d_cap = float(np.sum(np.asarray(g["p_es"], np.float64)
                             * base_es * -np.exp(-knobs["log_cap"])))
        s = sigmoid(knobs["mix"])
        d_mix = float(np.sum(np.asarray(g["acc"], np.float64)
                             * base_acc * 2.0 * s * (1.0 - s)))
        short = max(0.0, slo - acc)
        dv = -2.0 * short / (slo * slo * N)       # d(objective)/d(value)
        grads = {"log_cap": dv * d_cap
                 + (lam / slo if knobs["log_cap"] > 0 else 0.0),
                 "mix": dv * d_mix}
        print(f"[grad] eval {gd_evals}: log_cap={knobs['log_cap']:.3f} "
              f"mix={knobs['mix']:.3f} acc={acc:.4f}"
              + (" (SLO met)" if acc >= slo else ""))
        if acc >= slo:
            gd_hit = gd_evals
            break
        updates, opt_state = opt.update(grads, opt_state, knobs)
        knobs = {k: np.float64(knobs[k] + updates[k]) for k in knobs}

    # ---- verdict --------------------------------------------------------
    print(f"\ngrid search:      SLO at eval {grid_hit} "
          f"(budget {args.budget})")
    print(f"gradient descent: SLO at eval {gd_hit if gd_hit else '-'}")
    if gd_hit is None:
        print("FAIL: gradient planner did not reach the SLO")
        return 1
    if gd_hit >= grid_hit:
        print("FAIL: gradient planner needed no fewer evals than grid")
        return 1
    print(f"OK: gradient planner reached the SLO in {gd_hit} rollout "
          f"evals vs {grid_hit} for grid search "
          f"({grid_hit / gd_hit:.1f}x fewer)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
