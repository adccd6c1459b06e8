"""Multi-cell mobility demo: a fleet roaming a 4-cell grid, planned by the
pure-functional engine with traced routing, per-cell segmented admission,
and handover (warm-basis + ES-belief migration).

Three runs over the same replayed trace:

  * single-pool baseline — mobility off (today's one-ES engine);
  * nearest-cell routing — devices attach to the closest covered cell;
  * min-response-time routing — cells are load- and link-aware, so a
    congested or slow-linked cell sheds devices to its neighbours.

Also shows the `routed` registry policy: the host-level one-shot planner
that routes a FleetProblem's lanes by position before delegating to amr2.

    PYTHONPATH=src python examples/mobility_sim.py
"""
import numpy as np

from repro.api import engine as E
from repro.core.mobility import MobilityModel
from repro.core.types import enable_compile_cache
from repro.serving import FleetConfig


def main():
    enable_compile_cache()
    D, periods = 64, 16
    cfg = FleetConfig(n_devices=D, T=1.2, n_servers=8, policy="amr2",
                      rate=9.0, batch_max=8, horizon=periods + 2, seed=0)
    params = E.EngineParams.from_config(cfg, horizon=periods + 2)

    # a 2x2 grid of cells, 30 apart; devices random-walk around homes
    # drawn near cell centres, so coverage edges and handovers both occur
    rng = np.random.default_rng(7)
    cxy = 30.0 * np.array([[0., 0.], [1., 0.], [0., 1.], [1., 1.]])
    home = cxy[rng.integers(0, 4, D)]
    steps = rng.normal(scale=5.0, size=(periods + 2, D, 2)).cumsum(axis=0)
    trace = home + steps - steps[:1]                    # start at home
    mob = MobilityModel.make(cell_xy=cxy, trace=trace,
                             cell_rate=np.array([1.0, 0.7, 1.3, 1.0]),
                             radius=28.0, link_alpha=0.6)

    def run(tag, p):
        _, m = E.rollout(E.init_state(p), p, periods)
        acc = float(np.asarray(m.total_accuracy).sum())
        jobs = int(np.asarray(m.n_jobs).sum())
        print(f"  {tag:<22} acc/job {acc / max(jobs, 1):.4f}   "
              f"offloading {int(np.asarray(m.n_offloading).sum()):4d}   "
              f"handovers {int(np.asarray(m.n_handover).sum()):4d}   "
              f"outage-periods {int(np.asarray(m.n_outage).sum()):4d}")
        return acc / max(jobs, 1)

    print(f"{D} devices x {periods} periods, 4 cells "
          f"(rates {np.asarray(mob.cell_rate).tolist()}, radius 28):")
    run("single-pool (off)", params)
    run("nearest cell", params.with_mobility(mob, routing="nearest"))
    run("min response time",
        params.with_mobility(mob, routing="min_time"))

    # ---- the `routed` registry policy: one-shot host-level planning ----
    from repro import api
    from repro.core import InstanceBatch, paper_instance

    fp = api.FleetProblem.from_batch(InstanceBatch.stack(
        [paper_instance(8, T=1.2, seed=s) for s in range(D)]))
    sol = api.get_solver("routed").solve_fleet(
        fp, positions=trace[0], mobility=mob, routing="nearest")
    att = np.bincount(sol.cell[sol.cell >= 0], minlength=4)
    print(f"\nrouted policy (one-shot): cells {att.tolist()} attached, "
          f"{int((sol.cell < 0).sum())} uncovered (local-only); "
          f"accuracy {float(sol.accuracy.sum()):.2f}")


if __name__ == "__main__":
    main()
