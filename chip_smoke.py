"""Chip smoke test: run the fleet planner's main path once on a TPU.

    python chip_smoke.py               # one chip: main path + NumPy oracle
    python chip_smoke.py --four-chips  # sharded rollout vs unsharded only

The default run drives the public engine API the way an operator would:
`EngineParams.from_config` -> `init_state` -> `rollout` over a
16384-device amr2 fleet (reduced-tableau simplex, donated state), prints
what it saw, and then checks a 256-device fleet of the same configuration
against the per-device NumPy pipeline (`FleetEngine(backend="numpy")`
`.run_period_reference`) on the same replayed arrival trace.  Last, the
fleet Pallas kernels run compiled on the chip against their jnp
references.  The timings it prints are smoke readings, not benchmark
numbers.

``--four-chips`` runs only the sharded path: `shard` + `rollout_sharded`
of the same 16384-device fleet over a 4-device ``"fleet"`` mesh, compared
with the unsharded `rollout` on one chip in the same process.

Everything runs in this one process (a chip belongs to one process at a
time).  The script exits non-zero, printing no result line, when JAX finds
no TPU or any phase fails; on success its last line is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from collections import Counter

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

# the scale benchmark's 16384-device point (benchmarks/fleet_bench.py)
BIG = dict(n_devices=16384, T=1.2, n_servers=1024, policy="amr2",
           rate=10.0, batch_max=12, seed=7)
PERIODS = 8
ORACLE_DEVICES = 256
ORACLE_PERIODS = 4
ORACLE_RTOL = 1e-6
SHARD_RTOL = 1e-9
INT_METRICS = ("n_jobs", "n_violations", "n_offloading", "n_backpressured",
               "n_outage", "n_straggler_updates", "n_unsolved", "backlog")


def log(**kv) -> None:
    """One ``key=value`` line per reading (never JSON: the last line is
    reserved for the result object)."""
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


class CompileMonitor:
    """Counts JAX's own compile events: seconds of backend compilation
    (or persistent-cache retrieval) and of lowering to MLIR, and the
    persistent cache's hits and misses."""

    def __init__(self):
        import jax.monitoring as mon
        self.counts = Counter()
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts["backend_compile_s"] += secs
        elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.counts["lowering_s"] += secs

    def _on_event(self, event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            self.counts[event.rsplit("/", 1)[1]] += 1

    def snapshot(self) -> Counter:
        return Counter(self.counts)


def fleet_config(n_devices: int, n_servers: int):
    from repro.serving import FleetConfig
    return FleetConfig(**{**BIG, "n_devices": n_devices,
                          "n_servers": n_servers})


def build(n_devices: int, n_servers: int, periods: int):
    from repro.api import engine as E
    params = E.EngineParams.from_config(
        fleet_config(n_devices, n_servers), horizon=periods + 2,
        lp_method="revised")
    return params, E.init_state(params)


def host(metrics) -> dict:
    import jax
    return {k: np.asarray(v) for k, v in
            jax.device_get(metrics).__dict__.items()}


def main_path(mon: CompileMonitor, dev) -> None:
    """The 16384-device amr2 rollout: cold (compiles), then warm."""
    import jax
    from repro.api import engine as E

    t0 = time.perf_counter()
    params, state = build(BIG["n_devices"], BIG["n_servers"], PERIODS)
    log(phase="main", setup_s=time.perf_counter() - t0)

    c0 = mon.snapshot()
    t0 = time.perf_counter()
    state, metrics = E.rollout(state, params, PERIODS, donate=True)
    jax.block_until_ready((state, metrics))
    first_s = time.perf_counter() - t0
    c1 = mon.snapshot()
    c1.subtract(c0)
    log(phase="main", first_call_s=first_s,
        backend_compile_s=c1["backend_compile_s"],
        lowering_s=c1["lowering_s"], cache_hits=c1["cache_hits"],
        cache_misses=c1["cache_misses"],
        compile_cache="warm" if c1["cache_hits"] and not c1["cache_misses"]
        else "cold")
    c1 = mon.snapshot()

    t0 = time.perf_counter()
    state, metrics = E.rollout(E.init_state(params), params, PERIODS,
                               donate=True)
    jax.block_until_ready((state, metrics))
    warm_s = time.perf_counter() - t0
    c2 = mon.snapshot()
    c2.subtract(c1)

    m = host(metrics)
    n_unsolved = int(m["n_unsolved"].sum())
    worst_make = float(m["realized_makespan"].max())
    stats = dev.memory_stats() or {}
    log(phase="main", devices=BIG["n_devices"], periods=PERIODS,
        warm_rollout_s=warm_s, recompile_s=c2["backend_compile_s"],
        n_unsolved=n_unsolved, n_jobs=int(m["n_jobs"].sum()),
        total_accuracy=repr(float(m["total_accuracy"].sum())),
        worst_violation=repr(float(m["worst_violation"].max())),
        worst_makespan_s=repr(worst_make),
        n_backpressured=int(m["n_backpressured"].sum()),
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"))
    if not np.isfinite(m["total_accuracy"]).all():
        raise SystemExit("main path: non-finite total_accuracy")
    if n_unsolved:
        raise SystemExit(f"main path: {n_unsolved} LPs left unsolved")
    if int(m["n_jobs"].sum()) <= 0:
        raise SystemExit("main path: the rollout planned no jobs")


def oracle_check() -> None:
    """256 devices, same configuration: the chip rollout vs the host
    NumPy pipeline on the same replayed arrival trace."""
    from repro.api import engine as E
    from repro.serving import FleetEngine

    n_servers = ORACLE_DEVICES // 16
    params, state = build(ORACLE_DEVICES, n_servers, ORACLE_PERIODS)
    _, metrics = E.rollout(state, params, ORACLE_PERIODS)
    m = host(metrics)

    ref = FleetEngine.from_config(dataclasses.replace(
        fleet_config(ORACLE_DEVICES, n_servers), backend="numpy"))
    stats = [ref.run_period_reference() for _ in range(ORACLE_PERIODS)]

    want = np.array([s.total_accuracy for s in stats])
    gap = np.abs(m["total_accuracy"] - want) / np.maximum(np.abs(want), 1e-12)
    agree = {f: bool(np.array_equal(m[f], [getattr(s, f) for s in stats]))
             for f in ("n_offloading", "n_backpressured", "n_violations")}
    worst_make = float(m["realized_makespan"].max())
    T = BIG["T"]
    log(phase="oracle", devices=ORACLE_DEVICES, periods=ORACLE_PERIODS,
        max_rel_accuracy_gap=repr(float(gap.max())),
        chip_total_accuracy=repr(float(m["total_accuracy"].sum())),
        oracle_total_accuracy=repr(float(want.sum())),
        n_unsolved=int(m["n_unsolved"].sum()),
        worst_makespan_s=repr(worst_make), bound_2T_s=2 * T,
        **{f"{k}_agree": v for k, v in agree.items()})
    if not gap.max() <= ORACLE_RTOL:
        raise SystemExit(f"oracle: accuracy gap {gap.max()} > {ORACLE_RTOL}")
    if int(m["n_unsolved"].sum()):
        raise SystemExit("oracle: unsolved LPs in the 256-device rollout")
    if not worst_make <= 2 * T:
        raise SystemExit(f"oracle: makespan {worst_make} s > 2T")


def kernels() -> None:
    """The fleet Pallas kernels, through the wrappers the solvers call,
    compiled on the chip at fleet widths in float32 and checked against
    their jnp references; float64 operands must be refused."""
    import jax.numpy as jnp
    from repro.core.types import x64_scope
    from repro.kernels.cckp_dp import ops as cckp_ops
    from repro.kernels.cckp_dp.ref import NEG, cckp_model_dp_ref
    from repro.kernels.simplex_pivot import ops as pivot_ops
    from repro.kernels.simplex_pivot.ref import (pivot_update_ref,
                                                 reduced_pivot_ref)

    rng = np.random.default_rng(0)
    lanes, n = 1024, BIG["batch_max"]
    R, C0 = n + 2, 3 * n + 2
    checks = {}

    y = np.full((1201, n + 1), NEG, np.float32)
    y[:, 0] = 0.0
    y[500:, 1] = 0.3
    for p in (0, 40):
        got = cckp_ops.model_dp(jnp.asarray(y), p, 0.37, n + 1)
        want = cckp_model_dp_ref(jnp.asarray(y), 0.37, p=p, n_steps=n + 1)
        checks[f"cckp_dp_p{p}"] = (
            np.allclose(got[0], want[0], rtol=1e-6)
            and np.array_equal(got[1], want[1]))

    tabs = rng.normal(size=(lanes, R + 1, C0 + R + 1)).astype(np.float32)
    r = rng.integers(0, R, lanes)
    j = rng.integers(0, C0 + R, lanes)
    tabs[np.arange(lanes), r, j] += 2.0 * np.sign(tabs[np.arange(lanes), r, j])
    mask = rng.uniform(size=lanes) < 0.7
    args = [jnp.asarray(x) for x in (tabs, r, j, mask)]
    checks["simplex_pivot"] = np.allclose(
        pivot_ops.pivot_update(*args), pivot_update_ref(*args),
        rtol=1e-5, atol=1e-5)

    A = rng.normal(size=(lanes, R, C0)).astype(np.float32)
    Binv = np.broadcast_to(np.eye(R, dtype=np.float32), (lanes, R, R))
    basis = np.broadcast_to(C0 + np.arange(R, dtype=np.int32), (lanes, R))
    args = [jnp.asarray(x) for x in (
        A, np.zeros((lanes, C0), np.float32), Binv,
        rng.uniform(0.5, 2.0, (lanes, R)).astype(np.float32), basis,
        rng.uniform(size=lanes) < 0.3, np.ones(lanes, bool),
        rng.uniform(size=lanes) < 0.8)]
    got = pivot_ops.reduced_pivot(*args, art_cost=1.0, tol=1e-5)
    want = reduced_pivot_ref(*args, art_cost=1.0, tol=1e-5)
    checks["reduced_pivot"] = (
        all(np.allclose(g, w, rtol=1e-5, atol=1e-6)
            for g, w in zip(got[:2], want[:2]))
        and all(np.array_equal(g, w) for g, w in zip(got[2:], want[2:])))

    with x64_scope():
        wide = jnp.zeros((4, 3, 5), jnp.float64)
    try:
        pivot_ops.pivot_update(wide, jnp.zeros(4, jnp.int32),
                               jnp.zeros(4, jnp.int32), jnp.ones(4, bool))
        checks["float64_refused"] = False
    except ValueError as e:
        checks["float64_refused"] = "64-bit floats" in str(e)
    log(phase="kernels", **checks)
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        raise SystemExit(f"kernels: {bad} disagree with their references")


def four_chips() -> None:
    """`rollout_sharded` over a 4-device fleet mesh vs `rollout` on one
    chip, same fleet, same process."""
    import jax
    from repro.api import engine as E

    if len(jax.devices()) < 4:
        raise SystemExit(f"--four-chips needs 4 devices, found "
                         f"{len(jax.devices())}")
    params, state = build(BIG["n_devices"], BIG["n_servers"], PERIODS)
    t0 = time.perf_counter()
    _, ref = E.rollout(state, params, PERIODS)
    ref = host(ref)
    log(phase="four_chips", unsharded_s=time.perf_counter() - t0)

    mesh = E.fleet_mesh(4)
    state_s, params_s = E.shard(E.init_state(params), params, mesh)
    t0 = time.perf_counter()
    final, got = E.rollout_sharded(state_s, params_s, PERIODS, mesh)
    jax.block_until_ready((final, got))
    log(phase="four_chips", sharded_s=time.perf_counter() - t0)
    got = host(got)
    per_dev = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
               for d in mesh.devices.flat}
    # where the fleet axis landed: one (D/4, ...) block per device
    p_ed_blocks = {s.device.id: s.data.shape
                   for s in final.p_ed.addressable_shards}

    unequal = [f for f in INT_METRICS if not np.array_equal(ref[f], got[f])]
    a, b = ref["total_accuracy"], got["total_accuracy"]
    rel = float((np.abs(a - b) / np.maximum(np.abs(a), 1e-12)).max())
    log(phase="four_chips", devices=BIG["n_devices"], periods=PERIODS,
        int_metrics_equal=not unequal, unequal=",".join(unequal) or "none",
        max_rel_accuracy_gap=repr(rel), peak_bytes_per_device=per_dev,
        p_ed_blocks=p_ed_blocks)
    if unequal:
        raise SystemExit(f"four chips: integer metrics differ: {unequal}")
    if not rel <= SHARD_RTOL:
        raise SystemExit(f"four chips: accuracy gap {rel} > {SHARD_RTOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-unsharded 4-chip phase")
    args = ap.parse_args(argv)

    import jax

    from repro.core.types import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing to "
              f"run on it", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    mon = CompileMonitor()
    log(phase="device", kind=repr(dev.device_kind),
        count=len(jax.devices()), compile_cache_dir=cache)

    if args.four_chips:
        four_chips()
    else:
        main_path(mon, dev)
        oracle_check()
        kernels()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
