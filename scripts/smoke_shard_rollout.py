"""Sharded engine-v2 smoke: `step`/`rollout` under `shard_map` must match
the unsharded pure-functional engine.

Launch with host-platform devices spawned BEFORE jax initialises:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python scripts/smoke_shard_rollout.py

Environment knobs: ``SHARD_SMOKE_DEVICES`` (fleet size, default 64),
``SHARD_SMOKE_SHARDS`` (mesh size, default all jax devices),
``SHARD_SMOKE_PERIODS`` (default 8), ``SHARD_SMOKE_CHAOS=1`` (arm the
fault-injection subsystem with a replayed fault trace AND flip a quarter
of the fleet's outage schedule mid-horizon — the stale-warm-basis guard
and the per-device folded fault draws must both hold under sharding).
Exits 1 on any parity failure — integer metrics (including the ladder
and LP pivot counters) and the final pytree state must match exactly, float metrics
to 1e-9 (per-shard partial sums + psum reassociate the float64
reductions).
"""
from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))


def main() -> int:
    import jax
    import numpy as np

    n_shards = int(os.environ.get("SHARD_SMOKE_SHARDS",
                                  len(jax.devices())))
    if len(jax.devices()) < max(n_shards, 2):
        print(f"FAIL: {len(jax.devices())} jax device(s); launch with "
              f"XLA_FLAGS=--xla_force_host_platform_device_count="
              f"{max(n_shards, 8)}", file=sys.stderr)
        return 1
    n_devices = int(os.environ.get("SHARD_SMOKE_DEVICES", 64))
    periods = int(os.environ.get("SHARD_SMOKE_PERIODS", 8))

    from repro.api import engine as E
    from repro.serving import FleetConfig

    cfg = FleetConfig(n_devices=n_devices, T=1.2,
                      n_servers=max(1, n_devices // 16), policy="amr2",
                      rate=8.0, batch_max=8, horizon=periods + 2, seed=0)
    params = E.EngineParams.from_config(cfg, horizon=periods + 2)
    chaos = os.environ.get("SHARD_SMOKE_CHAOS", "0") == "1"
    if chaos:
        import dataclasses

        from repro.serving import FaultModel

        # mid-horizon outage flip on every 4th device: the stale-warm-
        # basis cold-start (PR 6) must agree across shards with the
        # fault path armed
        outage = np.array(params.outage)
        outage[::4, max(1, periods // 2):] = \
            ~outage[::4, max(1, periods // 2):]
        params = dataclasses.replace(params, outage=outage)
        params = params.with_faults(
            FaultModel.make(loss_rate=0.1, straggler_prob=0.15,
                            straggler_mult=2.0, link_degrade_prob=0.2,
                            link_degrade_mag=0.5, es_crash_prob=0.05),
            fault_seed=3)
    state = E.init_state(params)
    mesh = E.fleet_mesh(n_shards)
    sstate, sparams = E.shard(state, params, mesh)

    failures = []

    def check(tag, got, want, exact):
        got, want = np.asarray(got), np.asarray(want)
        ok = (np.array_equal(got, want) if exact
              else np.allclose(got, want, rtol=1e-9, atol=1e-12))
        if not ok:
            failures.append(f"{tag}: sharded {got} != unsharded {want}")

    ladder_ints = ("n_offload_samples", "n_offload_ok", "n_deadline_miss",
                   "n_retries", "n_fallback_local", "n_dropped")
    # the LP's pivot counters: each lane batch waits for its slowest lane
    # on every shard, so the psum-ed counts equal the unsharded ones
    lp_counts = ("lp_pivots", "lp_pivot_slots")

    # one sharded step vs unsharded
    u1, mu = E.step(state, params)
    s1, ms = E.step_sharded(sstate, sparams, mesh)
    for f in ("n_jobs", "n_violations", "n_offloading", "n_backpressured",
              "n_outage", "n_straggler_updates", "backlog") + ladder_ints \
            + lp_counts:
        check(f"step/{f}", getattr(ms, f), getattr(mu, f), exact=True)
    for f in ("total_accuracy", "worst_violation", "es_utilization",
              "realized_makespan"):
        check(f"step/{f}", getattr(ms, f), getattr(mu, f), exact=False)

    # whole sharded rollout vs unsharded rollout
    uf, MU = E.rollout(state, params, periods)
    sf, MS = E.rollout_sharded(sstate, sparams, periods, mesh)
    for f in ("n_jobs", "n_violations", "n_offloading", "n_backpressured",
              "n_outage", "backlog") + ladder_ints + lp_counts:
        check(f"rollout/{f}", getattr(MS, f), getattr(MU, f), exact=True)
    for f in ("total_accuracy", "realized_makespan"):
        check(f"rollout/{f}", getattr(MS, f), getattr(MU, f), exact=False)
    if chaos and int(np.asarray(MU.n_retries).sum()) \
            + int(np.asarray(MU.n_fallback_local).sum()) \
            + int(np.asarray(MU.n_dropped).sum()) == 0:
        failures.append("chaos armed but the ladder never fired "
                        "(vacuous parity)")
    check("final/warm_basis", sf.warm_basis, uf.warm_basis, exact=True)
    check("final/pending", sf.pending, uf.pending, exact=True)
    check("final/p_ed", sf.p_ed, uf.p_ed, exact=False)

    if failures:
        print("FAIL: sharded engine diverged from unsharded:",
              file=sys.stderr)
        for f in failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    acc = float(np.asarray(MS.total_accuracy).sum())
    print(f"[shard-smoke] ok: {n_devices} devices x {periods} periods on "
          f"a {n_shards}-shard mesh match the unsharded engine "
          f"(total accuracy {acc:.3f})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
