"""Online hierarchical inference: threshold learners vs the clairvoyant.

    PYTHONPATH=src python examples/hi_sim.py [--devices 64]
        [--periods 64] [--offload-cost 0.15] [--hi-seed 11] [--seed 0]

The paper's AMR^2 plans offloading from a KNOWN accuracy table; the
online twin (Moothedath & Champati, arXiv 2304.00891) must learn WHEN to
consult the edge server per sample, from calibrated local-model
confidences alone.  This script rolls the same fleet — heterogeneous
per-device ES accuracies, one shared confidence stream — under every
decision rule the engine implements:

  * ``fixed``     — a shared constant threshold (theta0 = 0.5);
  * ``threshold`` — the OGD online threshold learner;
  * ``ucb`` / ``exp3`` — bandits over a discretized threshold grid;
  * the *clairvoyant* — rule "fixed" armed with the per-device optimum
    ``theta* = clip(acc_es - beta, 0, 1)``, which accrues exactly zero
    pseudo-regret (the online problem's AMR^2-with-the-answer-key).

Because ``HIModel`` is an all-leaf pytree, all five sweeps reuse ONE
compiled `rollout` (two trace shapes: scalar vs per-device ``theta0``).
The script prints a cumulative-regret table over the horizon and exits 1
unless (a) the clairvoyant's regret is exactly 0, (b) the learner beats
the fixed baseline it starts from, and (c) the learner's regret growth
is sublinear (second-half increment < first-half increment).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))


def main() -> int:
    from repro.core.types import enable_compile_cache
    enable_compile_cache()
    import numpy as np

    from repro.api import engine as E
    from repro.core.hi import HIModel
    from repro.serving import FleetConfig

    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=64)
    ap.add_argument("--periods", type=int, default=64)
    ap.add_argument("--offload-cost", type=float, default=0.15)
    ap.add_argument("--hi-seed", type=int, default=11)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    beta = args.offload_cost

    cfg = FleetConfig(n_devices=args.devices, T=1.2,
                      n_servers=max(1, args.devices // 16), policy="amr2",
                      backend="jax", rate=9.0, batch_max=8,
                      horizon=args.periods + 2, seed=args.seed,
                      straggler_frac=0.25, outage_frac=0.1)
    base = E.EngineParams.from_config(cfg, horizon=args.periods + 2)
    acc = np.asarray(base.acc, np.float64).copy()
    acc[:, base.m] = np.random.default_rng(7).uniform(
        0.65, 0.92, args.devices)
    het = dataclasses.replace(base, acc=acc)
    theta_star = np.clip(acc[:, base.m] - beta, 0.0, 1.0)

    def roll(rule, theta0=0.5):
        hm = HIModel.make(theta0=theta0, offload_cost=beta)
        p = het.with_hi(hm, rule=rule, hi_seed=args.hi_seed)
        state, m = E.rollout(E.init_state(p), p, args.periods)
        jobs = int(np.asarray(m.n_jobs).sum())
        return {"regret": np.asarray(m.hi_regret, np.float64),
                "acc": float(np.asarray(m.total_accuracy).sum())
                / max(jobs, 1),
                "off": int(np.asarray(m.n_hi_offloaded).sum())
                / max(jobs, 1),
                "theta": np.asarray(state.hi.theta, np.float64)}

    runs = {
        "fixed(0.5)": roll("fixed"),
        "threshold": roll("threshold"),
        "ucb": roll("ucb"),
        "exp3": roll("exp3"),
        "clairvoyant": roll("fixed", theta0=theta_star),
    }

    marks = sorted({p for p in (8, 16, 32, args.periods)
                    if p <= args.periods})
    print(f"fleet: {args.devices} devices x {args.periods} periods, "
          f"beta={beta}, acc_es in "
          f"[{acc[:, base.m].min():.2f}, {acc[:, base.m].max():.2f}], "
          f"stream seed {args.hi_seed} (shared by every rule)\n")
    head = "cumulative regret".ljust(14) + "".join(
        f"@{p}".rjust(11) for p in marks) + "  acc/job  offload%"
    print(head)
    for name, r in runs.items():
        row = name.ljust(14) + "".join(
            f"{r['regret'][p - 1]:11.1f}" for p in marks)
        print(f"{row}  {r['acc']:.4f}   {100 * r['off']:5.1f}%")
    err = np.abs(runs["threshold"]["theta"] - theta_star)
    print(f"\nlearner |theta - theta*|: mean {err.mean():.3f}, "
          f"max {err.max():.3f}")

    failures = []
    if runs["clairvoyant"]["regret"][-1] != 0.0:
        failures.append(
            f"clairvoyant regret {runs['clairvoyant']['regret'][-1]} != 0")
    reg_l = runs["threshold"]["regret"]
    if not reg_l[-1] < runs["fixed(0.5)"]["regret"][-1]:
        failures.append("learner did not beat the fixed(0.5) baseline")
    half = args.periods // 2 - 1
    if not reg_l[-1] - reg_l[half] < reg_l[half] - reg_l[0]:
        failures.append("learner regret growth is not sublinear")
    if failures:
        print("\nFAIL:", "; ".join(failures))
        return 1
    print("\nOK: clairvoyant floor exact, learner beat the fixed "
          "baseline with sublinear regret")
    return 0


if __name__ == "__main__":
    sys.exit(main())
