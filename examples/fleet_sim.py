"""Fleet serving demo: N edge devices, a small ES pool, Poisson traffic.

    PYTHONPATH=src python examples/fleet_sim.py --devices 64 --periods 20 \
        [--servers 2] [--rate 10] [--batch-max 12] [--t 1.2] [--seed 0] \
        [--rollout] [--chaos [LOSS_RATE]] [--fault-seed 0]

The whole run is described by ONE declarative `FleetConfig`
(`FleetEngine.from_config`): every period the fleet is planned by a
handful of batched registry solves (`repro.api.solve` on per-shape-group
`FleetProblem`s); devices that lose the ES-capacity admission race replan
onto their local model ladder in one batched ES-disabled solve, drifting
devices trigger the EMA straggler audit, and per-device ES-link outages
are planned around.

``--rollout`` runs the same epoch through the pure-functional engine
instead (`repro.serving.engine_v2`): the whole multi-period simulation is
ONE `lax.scan` over the jitted period step, zero per-period host
round-trips.  With ``--policy amr2`` or ``--policy dual`` the
trajectories are bit-identical to the loop above on the replayed arrival
trace; the default ``auto`` resolves to amr2 in the rollout engine (the
loop's auto additionally gives identical-job devices the exact DP, so
those per-period numbers may differ slightly).

``--chaos [LOSS_RATE]`` arms the fault-injection subsystem (requires the
delegated/rollout engine): mid-period ES crashes, link degradation,
injected stragglers, and per-sample offload loss, resolved by the traced
degradation ladder (retry with capped backoff -> largest local model
fitting the residual 2T deadline -> drop).  The per-period lines grow
retry/fallback/drop/miss counters and the realized makespan; the fault
trace is replayed from ``--fault-seed``, so runs are reproducible.
"""
from __future__ import annotations

import argparse


def _fault_model(args):
    """The demo fault mix: the requested offload-loss rate plus moderate
    crash / link-degradation / straggler probabilities."""
    from repro.serving import FaultModel
    if args.chaos is None:
        return None
    return FaultModel.make(loss_rate=args.chaos, es_crash_prob=0.05,
                           link_degrade_prob=0.2, link_degrade_mag=0.5,
                           straggler_prob=0.15, straggler_mult=2.0)


def _chaos_cols(retries, fallback, dropped, miss, makespan, T):
    return (f"retry={retries:>3} fb={fallback:>2} drop={dropped:>2} "
            f"miss={miss:>2} realized={makespan / T:4.2f}T ")


def _main_rollout(args) -> None:
    import numpy as np

    from repro.serving import FleetConfig, engine_v2

    config = FleetConfig(
        n_devices=args.devices, T=args.t, n_servers=args.servers,
        policy=args.policy, rate=args.rate, batch_max=args.batch_max,
        horizon=max(args.periods, 2), seed=args.seed,
        faults=_fault_model(args), fault_seed=args.fault_seed)
    params = engine_v2.EngineParams.from_config(config,
                                                horizon=args.periods)
    state, m = engine_v2.rollout(engine_v2.init_state(params), params,
                                 args.periods)
    chaos_tag = (f", chaos armed: loss={args.chaos:g} "
                 f"fault_seed={args.fault_seed}" if params.chaos else "")
    print(f"[fleet] engine-v2 rollout: {args.periods} periods as one "
          f"lax.scan over {args.devices} devices (policy "
          f"{params.policy}{chaos_tag})")
    for i in range(args.periods):
        jobs = int(np.asarray(m.n_jobs)[i])
        chaos_cols = "" if not params.chaos else _chaos_cols(
            int(np.asarray(m.n_retries)[i]),
            int(np.asarray(m.n_fallback_local)[i]),
            int(np.asarray(m.n_dropped)[i]),
            int(np.asarray(m.n_deadline_miss)[i]),
            float(np.asarray(m.realized_makespan)[i]), args.t)
        print(f"[fleet] t={i:>3} jobs={jobs:>4} "
              f"acc/job={float(np.asarray(m.mean_job_accuracy)[i]):.3f} "
              f"offload={int(np.asarray(m.n_offloading)[i]):>3} "
              f"bumped={int(np.asarray(m.n_backpressured)[i]):>3} "
              f"outage={int(np.asarray(m.n_outage)[i]):>2} "
              f"straggler_upd={int(np.asarray(m.n_straggler_updates)[i])} "
              f"es_util={float(np.asarray(m.es_utilization)[i]):4.0%} "
              f"viol={int(np.asarray(m.n_violations)[i]):>2} "
              f"{chaos_cols}"
              f"backlog={int(np.asarray(m.backlog)[i])}")
    jobs = int(np.asarray(m.n_jobs).sum())
    acc = float(np.asarray(m.total_accuracy).sum())
    chaos_sum = "" if not params.chaos else (
        f"retries={int(np.asarray(m.n_retries).sum())}, "
        f"fallback_local={int(np.asarray(m.n_fallback_local).sum())}, "
        f"dropped={int(np.asarray(m.n_dropped).sum())}, "
        f"deadline_miss={int(np.asarray(m.n_deadline_miss).sum())}, "
        f"worst_makespan="
        f"{float(np.asarray(m.realized_makespan).max()) / args.t:.2f}T, ")
    print(f"[fleet] done: {jobs} jobs, "
          f"acc/job={acc / max(jobs, 1):.3f}, "
          f"violation_rate="
          f"{np.asarray(m.n_violations).sum() / (args.periods * args.devices):.1%}, "
          f"{chaos_sum}"
          f"final_backlog={int(np.asarray(m.backlog)[-1])}")


def main(argv=None):
    from repro.core.types import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=64)
    ap.add_argument("--periods", type=int, default=20)
    ap.add_argument("--servers", type=int, default=2)
    ap.add_argument("--rate", type=float, default=10.0)
    ap.add_argument("--batch-max", type=int, default=12)
    ap.add_argument("--t", type=float, default=1.2, help="period budget T")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--policy", default="auto")
    ap.add_argument("--rollout", action="store_true",
                    help="run the epoch as one engine-v2 lax.scan rollout")
    ap.add_argument("--chaos", type=float, nargs="?", const=0.1,
                    default=None, metavar="LOSS_RATE",
                    help="arm fault injection at this offload-loss rate "
                    "(default 0.1 when the flag is given bare)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="replayed fault-trace seed (chaos runs are "
                    "reproducible under a fixed seed)")
    args = ap.parse_args(argv)

    if args.chaos is not None and args.policy == "auto":
        # fault injection needs the traced engine core; "auto" in the
        # loop engine routes identical-job devices to the host DP path
        args.policy = "amr2"

    if args.rollout:
        return _main_rollout(args)

    from repro.serving import FleetConfig, FleetEngine

    config = FleetConfig(
        n_devices=args.devices, T=args.t, n_servers=args.servers,
        policy=args.policy, rate=args.rate, batch_max=args.batch_max,
        horizon=max(args.periods, 2), seed=args.seed,
        faults=_fault_model(args), fault_seed=args.fault_seed)
    engine = FleetEngine.from_config(config)

    specs = [st.spec for st in engine.devices]
    print(f"[fleet] {args.devices} devices ({sum(1 for s in specs if s.drift is not None)}"
          f" stragglers, {sum(1 for s in specs if s.outage is not None)} flaky links)"
          f" | {args.servers} ES servers | T={args.t}s")
    chaos = args.chaos is not None
    for _ in range(args.periods):
        s = engine.run_period()
        chaos_cols = "" if not chaos else _chaos_cols(
            s.n_retries, s.n_fallback_local, s.n_dropped,
            s.n_deadline_miss, s.realized_makespan, args.t)
        print(f"[fleet] t={s.period:>3} jobs={s.n_jobs:>4} "
              f"acc/job={s.mean_job_accuracy:.3f} "
              f"offload={s.n_offloading:>3} bumped={s.n_backpressured:>3} "
              f"outage={s.n_outage:>2} straggler_upd={s.n_straggler_updates} "
              f"es_util={s.es_utilization:4.0%} viol={s.n_violations:>2} "
              f"{chaos_cols}"
              f"plan={s.plan_seconds * 1e3:6.1f}ms backlog={s.backlog}")
    summ = engine.summary()
    print(f"[fleet] done: {summ['jobs']} jobs, "
          f"acc/job={summ['mean_job_accuracy']:.3f}, "
          f"violation_rate={summ['violation_rate']:.1%}, "
          f"backpressure_rate={summ['backpressure_rate']:.1%}, "
          f"planning throughput={summ['devices_per_second']:.0f} devices/s")


if __name__ == "__main__":
    main()
