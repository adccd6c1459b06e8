"""Fleet-scale planning benchmark — the repo's end-to-end scaling story.

Sections:

  * ``fleet/parity/B`` — plans the SAME fleet twice per solver at the 64-
    AND 256-device points (``FLEET_BENCH_PARITY_SIZES``), batched vs the
    per-device NumPy oracle —
      - vmapped AMR^2 vs the sequential simplex (accuracy gap <= 1e-6 and
        the paper's 2T makespan guarantee per device),
      - vmapped `dual_schedule_batch` vs the NumPy `dual_schedule`
        (bit-identical assignments),
      - vmapped `amdp_batch` vs the scalar CCKP DP on identical-job
        devices (bit-identical assignments),
    and reports batched-vs-sequential planning throughput.  Results merge
    into ``BENCH_fleet.json`` keyed by device count, so the documented
    256-device baseline is reproduced by the benchmark itself.
  * ``fleet/warm_cold/B`` — consecutive-period LP re-solves at 64/256/1024
    devices (``FLEET_BENCH_WARM_SIZES``): period t's optimal bases warm-
    start period t+1's batched AMR^2 solve (`solve(..., warm_start=)`),
    asserting bit-tight warm/cold LP-objective parity plus a bounded
    rounded-accuracy gap vs the per-device NumPy oracle, and reporting
    warm-vs-cold throughput plus warm-basis acceptance rates.
  * ``fleet/scale/B``  — engine-v2 `rollout()` (ONE lax.scan per point,
    state buffers donated, amr2 on the reduced-tableau
    ``method="revised"`` simplex) at increasing fleet sizes through the
    CI-feasible 16k point, with the 100k point opt-in via
    ``FLEET_BENCH_SCALE_SIZES=102400``; reports devices/sec plus
    aggregate accuracy / violation numbers and gates >= 16k points on
    not scaling worse than the smallest amr2 point (plus the absolute
    ``FLEET_BENCH_MIN_DEVICES_PER_S`` floor when set).
  * ``fleet/speedup``  — the scanned `engine.rollout` hot path (amr2 and
    dual policies) against the PR-1 per-device `run_period_reference`
    loop at the 256-device point.
  * ``fleet/chaos/*`` — the fault-injection subsystem under load
    (``FLEET_BENCH_CHAOS_DEVICES`` / ``FLEET_BENCH_CHAOS_PERIODS``):
    pins the armed-null rollout bitwise against the fault-free engine,
    sweeps the offload loss rate through 40% on ONE compiled rollout
    (fault rates are pytree leaves), and asserts graceful degradation —
    per-period offload accounting closes exactly, realized makespans
    respect the 2T + retry-budget bound, and the 10%-loss point keeps
    >= 90% of the fault-free accuracy (no cliff) — plus a harsh
    crash+degrade+straggler entry for the documented worst case.
  * ``fleet/grad/B`` — the differentiable serving stack
    (``FLEET_BENCH_GRAD_DEVICES``, default 256): ONE
    `rollout_value_and_grad` backward sweep (implicit-gradient simplex +
    smoothed rounding/admission, soft mode) vs 2-point finite
    differences over every continuous knob, gated >= 5x and FD
    spot-checked to rtol 1e-4; also records the reverse-mode overhead
    vs the plain forward rollout.
  * ``fleet/hi/B`` — online hierarchical inference
    (``FLEET_BENCH_HI_DEVICES`` / ``FLEET_BENCH_HI_PERIODS``): every
    decision rule rolls the IDENTICAL replayed confidence stream over a
    fleet with heterogeneous per-device ES accuracies — a 9-point
    fixed-threshold sweep on ONE compiled rollout (``theta0`` is a
    leaf), the OGD threshold learner, UCB/EXP3 — and records cumulative
    pseudo-regret trajectories against the offline clairvoyant (gated
    exactly 0.0); at horizons >= 32 periods the learner must beat the
    best fixed grid point.

Every section also folds its numbers into ``BENCH_fleet.json`` (repo root;
override with ``BENCH_FLEET_JSON``).  Sections merge dict-into-dict (one
level per nesting), so a partial run — e.g. the CI smoke job, which only
runs the small device counts — updates its keys and leaves every
previously-recorded key intact (`scripts/check_bench_keys.py` enforces
this in CI).  ``FLEET_BENCH_SCALE_SIZES`` (or the legacy
``FLEET_BENCH_SIZES``) / ``FLEET_BENCH_PERIODS`` /
``FLEET_BENCH_SPEEDUP_DEVICES`` / ``FLEET_BENCH_PARITY_SIZES`` /
``FLEET_BENCH_WARM_SIZES`` shrink (or, for the 100k scale point, grow)
the run for CI smoke jobs.

Standalone:  PYTHONPATH=src python benchmarks/fleet_bench.py
CSV via the harness:  python benchmarks/run.py fleet
"""
from __future__ import annotations

import json
import os
import platform
import time

import numpy as np

PARITY_DEVICES = 64
PARITY_JOBS = 12
SCALE_PERIODS = 20
_BIG = 256            # scale points from here down run fewer periods

_JSON_PATH = os.environ.get(
    "BENCH_FLEET_JSON",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "BENCH_fleet.json"))
_RESULTS: dict = {}


def _merge(old, new):
    """Dict-into-dict merge, recursing so a partial run (one device count,
    one policy) never drops previously-recorded sibling keys."""
    if isinstance(old, dict) and isinstance(new, dict):
        out = dict(old)
        for k, v in new.items():
            out[k] = _merge(old.get(k), v) if k in old else v
        return out
    return new


def _record(section: str, payload) -> None:
    """Fold one section's numbers into BENCH_fleet.json.

    Merges into the existing document — recursively for dict payloads, so
    e.g. a 64-device-only smoke run updates ``parity["64"]`` and leaves
    ``parity["256"]`` intact — and rewrites after every section so an
    interrupted run still leaves a valid file.  The in-process accumulator
    merges too (not assigns): a section recorded in several calls — e.g.
    ``scaling()`` re-run for extra sizes in one process — keeps its
    earlier keys even when the on-disk document is unreadable at rewrite
    time (the case where merge-on-write alone cannot recover them)."""
    if isinstance(payload, dict):
        _RESULTS[section] = _merge(_RESULTS.get(section, {}), payload)
    else:
        _RESULTS[section] = payload
    doc = {}
    try:
        with open(_JSON_PATH) as fh:
            doc = json.load(fh)
    except (OSError, ValueError):
        pass
    doc = _merge(doc, {"host": platform.node(),
                       "platform": platform.platform(),
                       "unix_time": time.time(), **_RESULTS})
    with open(_JSON_PATH, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
        fh.write("\n")


def _scale_sizes():
    """Scale-section fleet sizes.  ``FLEET_BENCH_SCALE_SIZES`` wins (the
    opt-in 100k+ knob), then the legacy ``FLEET_BENCH_SIZES`` (the CI
    smoke job's), then the default through the 16k point."""
    for var in ("FLEET_BENCH_SCALE_SIZES", "FLEET_BENCH_SIZES"):
        env = os.environ.get(var)
        if env:
            return tuple(int(x) for x in env.split(","))
    return (256, 1024, 4096, 16384)


def _periods(n_devices: int) -> int:
    cap = int(os.environ.get("FLEET_BENCH_PERIODS", SCALE_PERIODS))
    return min(cap, 5 if n_devices >= _BIG else SCALE_PERIODS)


def _parity_instances(n_devices=PARITY_DEVICES, n_jobs=PARITY_JOBS, seed=0,
                      periods=1):
    """One fleet, `periods` consecutive arrival draws: a list of
    per-period instance lists sharing the same device profiles (the
    warm-start scenario: only the job classes change period to period)."""
    from repro.serving.fleet import make_fleet
    rng = np.random.default_rng(seed)
    specs = make_fleet(n_devices, seed=seed, straggler_frac=0.0,
                       outage_frac=0.0)
    T = 1.2
    rounds = []
    for _ in range(periods):
        insts = []
        for spec in specs:
            classes = rng.choice(spec.profile.classes, size=n_jobs)
            insts.append(spec.profile.instance(classes, T))
        rounds.append(insts)
    if periods == 1:
        return rounds[0], T
    return rounds, T


def _parity_sizes():
    env = os.environ.get("FLEET_BENCH_PARITY_SIZES")
    if env:
        return tuple(int(x) for x in env.split(","))
    return (64, 256)


def _warm_sizes():
    env = os.environ.get("FLEET_BENCH_WARM_SIZES")
    if env:
        return tuple(int(x) for x in env.split(","))
    return (64, 256, 1024)


def _parity_at(n_devices: int):
    """One parity round at a given device count.  Returns (entry, rows)."""
    from repro import api
    from repro.core import InstanceBatch, identical_instance

    insts, T = _parity_instances(n_devices)
    fp = api.FleetProblem.from_batch(InstanceBatch.stack(insts))
    api.solve(fp, policy="amr2")                        # compile once
    t0 = time.perf_counter()
    sol = api.solve(fp, policy="amr2")                  # ONE jit call
    batched_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    oracle = api.solve(fp, policy="amr2", backend="numpy")  # seq. simplex
    oracle_s = time.perf_counter() - t0

    gaps = np.abs(sol.accuracy - oracle.accuracy)
    max_gap = float(gaps.max())
    assert max_gap <= 1e-6, \
        f"batched/oracle accuracy mismatch: {max_gap:.2e}"
    assert float(np.max(sol.makespan)) <= 2 * T + 1e-9, \
        f"2T guarantee violated: {float(np.max(sol.makespan)):.3f} > {2 * T}"

    # --- dual: batched jitted bisection vs NumPy oracle, bit-identical ---
    api.solve(fp, policy="dual")                        # compile once
    t0 = time.perf_counter()
    dual_sol = api.solve(fp, policy="dual")
    dual_batched_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    dual_oracle = api.solve(fp, policy="dual", backend="numpy")
    dual_oracle_s = time.perf_counter() - t0
    np.testing.assert_array_equal(dual_sol.assignment,
                                  dual_oracle.assignment)

    # --- amdp: vmapped CCKP DP vs scalar DP, bit-identical ---------------
    n_ident = min(n_devices, PARITY_DEVICES)  # scalar DP oracle is slow
    ident = [identical_instance(PARITY_JOBS, 2, T=1.0 + 0.05 * (s % 8),
                                seed=s) for s in range(n_ident)]
    ident_fp = api.FleetProblem.from_batch(InstanceBatch.stack(ident))
    api.solve(ident_fp, policy="amdp")                  # compile once
    t0 = time.perf_counter()
    amdp_sol = api.solve(ident_fp, policy="amdp")
    amdp_batched_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    amdp_oracle = api.solve(ident_fp, policy="amdp", backend="numpy")
    amdp_oracle_s = time.perf_counter() - t0
    assert (np.atleast_1d(amdp_sol.solver) == "amdp").all()
    np.testing.assert_array_equal(np.asarray(amdp_sol.status),
                                  np.asarray(amdp_oracle.status))
    np.testing.assert_array_equal(amdp_sol.assignment,
                                  amdp_oracle.assignment)

    n = len(insts)
    entry = {
        "devices": n, "jobs_per_device": PARITY_JOBS,
        "amr2_max_acc_gap": max_gap,
        "amr2_batched_devices_per_s": n / batched_s,
        "amr2_oracle_devices_per_s": n / oracle_s,
        "dual_batched_devices_per_s": n / dual_batched_s,
        "dual_oracle_devices_per_s": n / dual_oracle_s,
        "amdp_batched_devices_per_s": len(ident) / amdp_batched_s,
        "amdp_oracle_devices_per_s": len(ident) / amdp_oracle_s,
        "assertions": "passed",
    }
    rows = [
        (f"fleet/parity/{n}/batched", batched_s / n * 1e6,
         f"devices={n};devices_per_s={n / batched_s:.0f};"
         f"max_acc_gap={max_gap:.1e};single_jit_call=1"),
        (f"fleet/parity/{n}/numpy_oracle", oracle_s / n * 1e6,
         f"devices={n};devices_per_s={n / oracle_s:.0f};"
         f"speedup={oracle_s / batched_s:.1f}x"),
        (f"fleet/parity/{n}/dual_batched", dual_batched_s / n * 1e6,
         f"devices={n};devices_per_s={n / dual_batched_s:.0f};"
         f"speedup_vs_numpy={dual_oracle_s / dual_batched_s:.1f}x;"
         f"assignments=bit_identical"),
        (f"fleet/parity/{n}/amdp_batched", amdp_batched_s / len(ident) * 1e6,
         f"devices={len(ident)};"
         f"devices_per_s={len(ident) / amdp_batched_s:.0f};"
         f"speedup_vs_scalar={amdp_oracle_s / amdp_batched_s:.1f}x;"
         f"assignments=bit_identical"),
    ]
    return entry, rows


def parity():
    """Batched registry solves vs per-device NumPy/scalar oracles — every
    path goes through `repro.api.solve`, the single front door.  Runs at
    BOTH the 64- and 256-device points (the device count is part of the
    BENCH_fleet.json merge key) so the documented 256-device baseline is
    actually reproduced here, not extrapolated from the 64-device run."""
    entries = {}
    out = []
    for n_devices in _parity_sizes():
        entry, rows = _parity_at(n_devices)
        entries[str(n_devices)] = entry
        out.extend(rows)
    _record("parity", entries)
    return out


def warm_cold():
    """Warm-started vs cold batched LP across consecutive fleet periods.

    Period t is solved cold; its per-device optimal bases
    (`Solution.basis`) warm-start period t+1, whose profiles are identical
    but whose arrival classes are freshly drawn — exactly the fleet
    engine's period-to-period situation.  Asserts (a) bit-tight warm/cold
    parity on the LP OBJECTIVE (vertex-invariant), (b) the rounded
    accuracy within AMR^2's own rounding bound of the per-device NumPy
    oracle (warm and cold may land on different optimal vertices of a
    degenerate LP, so exact assignment parity is not guaranteed — the
    observed gap is recorded), and (c) the 2T makespan guarantee; then
    reports warm-vs-cold throughput and the warm-basis acceptance rate."""
    from repro import api
    from repro.core import InstanceBatch
    from repro.core.amr2 import build_lp_arrays_batch
    from repro.core.lp import solve_lp_batch

    entries = {}
    out = []
    reps = 5                    # min-of-reps: the CPU dev hosts time-share
    for n_devices in _warm_sizes():
        (prev, cur), T = _parity_instances(n_devices, periods=2)
        fp_prev = api.FleetProblem.from_batch(InstanceBatch.stack(prev))
        fp = api.FleetProblem.from_batch(InstanceBatch.stack(cur))
        sol_prev = api.solve(fp_prev, policy="amr2")    # period t (cold)
        basis = sol_prev.basis

        api.solve(fp, policy="amr2")                    # compile cold
        api.solve(fp, policy="amr2", warm_start=basis)  # compile warm
        cold_s = min(_timed(lambda: api.solve(fp, policy="amr2"))
                     for _ in range(reps))
        warm_s = min(_timed(lambda: api.solve(
            fp, policy="amr2", warm_start=basis)) for _ in range(reps))
        warm_sol = api.solve(fp, policy="amr2", warm_start=basis)

        oracle = api.solve(fp, policy="amr2", backend="numpy")
        gap = float(np.abs(warm_sol.accuracy - oracle.accuracy).max())
        # rounded accuracies from two optimal vertices of a degenerate LP
        # can legitimately differ (different fractional-job sets), but
        # never by more than AMR^2's own rounding slack per device
        acc = np.asarray(fp.acc)
        round_bound = float((2 * (acc.max(axis=1) - acc.min(axis=1))).max())
        assert gap <= round_bound + 1e-9, \
            f"warm/oracle accuracy gap {gap:.3e} exceeds the AMR2 " \
            f"rounding bound {round_bound:.3e}"
        assert float(np.max(warm_sol.makespan)) <= 2 * T + 1e-9

        # warm acceptance, pivot counts, and timing straight from the LP
        # layer (isolates the simplex gain from the fixed api-side costs:
        # LP-array assembly, canonicalization, rounding)
        c, A_ub, b_ub, A_eq, b_eq = build_lp_arrays_batch(
            InstanceBatch.stack(cur))
        res_w = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq, warm_basis=basis)
        res_c = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq)
        # the vertex-invariant check: warm and cold must agree on the LP
        # OBJECTIVE bit-tight even when they sit on different optimal
        # vertices of a degenerate instance
        obj_gap = float(np.abs(res_w.fun - res_c.fun).max())
        assert obj_gap <= 1e-6, \
            f"warm/cold LP objective mismatch: {obj_gap:.3e}"
        lp_warm_s = min(_timed(lambda: solve_lp_batch(
            c, A_ub, b_ub, A_eq, b_eq, warm_basis=basis))
            for _ in range(reps))
        lp_cold_s = min(_timed(lambda: solve_lp_batch(
            c, A_ub, b_ub, A_eq, b_eq)) for _ in range(reps))
        warm_rate = float(np.asarray(res_w.warm).mean())
        n = n_devices
        entry = {
            "devices": n, "jobs_per_device": PARITY_JOBS,
            "warm_max_acc_gap": gap,
            "warm_cold_obj_gap": obj_gap,
            "amr2_cold_devices_per_s": n / cold_s,
            "amr2_warm_devices_per_s": n / warm_s,
            "warm_speedup": cold_s / warm_s,
            "lp_cold_devices_per_s": n / lp_cold_s,
            "lp_warm_devices_per_s": n / lp_warm_s,
            "lp_warm_speedup": lp_cold_s / lp_warm_s,
            "warm_accept_rate": warm_rate,
            "warm_mean_pivots": float(np.asarray(res_w.niter).mean()),
            "cold_mean_pivots": float(np.asarray(res_c.niter).mean()),
            "assertions": "passed",
        }
        entries[str(n)] = entry
        out.append((
            f"fleet/warm_cold/{n}", warm_s / n * 1e6,
            f"devices={n};warm_devices_per_s={n / warm_s:.0f};"
            f"cold_devices_per_s={n / cold_s:.0f};"
            f"speedup={cold_s / warm_s:.1f}x;"
            f"warm_accept_rate={warm_rate:.2f};"
            f"pivots_warm={entry['warm_mean_pivots']:.1f};"
            f"pivots_cold={entry['cold_mean_pivots']:.1f};"
            f"max_acc_gap={gap:.1e}"))
    _record("warm_cold", entries)
    return out


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _engine(n_devices: int, *, policy: str = "auto", seed: int = 7):
    from repro.serving import FleetConfig, FleetEngine
    return FleetEngine.from_config(FleetConfig(
        n_devices=n_devices, T=1.2, n_servers=max(1, n_devices // 16),
        policy=policy, rate=10.0, batch_max=PARITY_JOBS,
        horizon=SCALE_PERIODS, seed=seed))


def _scale_params(n_devices: int, policy: str, periods: int):
    """Engine-v2 params for one scale point: Poisson arrivals (no D x S
    replay trace to materialize at 100k devices) and the reduced-tableau
    LP path for amr2 (the memory shape that admits 100k lanes)."""
    from repro.api import engine as E
    from repro.serving import RequestQueue
    from repro.serving.fleet import make_fleet

    specs = make_fleet(n_devices, seed=7, horizon=max(4, periods))
    queue = RequestQueue(n_devices, (128, 512, 1024), rate=10.0,
                         batch_max=PARITY_JOBS, seed=7)
    params = E.EngineParams.from_fleet(
        specs, queue, T=1.2, n_servers=max(1, n_devices // 16),
        policy=policy, horizon=max(4, periods), arrivals="poisson",
        lp_method="revised" if policy == "amr2" else "tableau")
    return params


def scaling():
    """Engine-v2 `rollout()` throughput + accuracy/violation vs fleet
    size: each point is ONE `lax.scan` over the jitted period step with
    the input state's buffers DONATED (`rollout(..., donate=True)`), amr2
    on the reduced-tableau (``method="revised"``) simplex — the
    100k-lane shape.  Default sizes run through the 16k point (CI-feasible
    on a shared runner); the 100k point is opt-in via
    ``FLEET_BENCH_SCALE_SIZES=102400``.

    Gates: every amr2 point must clear the absolute
    ``FLEET_BENCH_MIN_DEVICES_PER_S`` floor when set (the CI 16k smoke
    pins one), and the 16384-device amr2 point must additionally clear
    ``FLEET_BENCH_SCALE_ANCHOR`` devices/s — default 9900, the
    256-device amr2 rollout anchor the dense-tableau engine measured on
    the 1-core dev host: per-device LP work is constant across fleet
    sizes, so a 64x-larger fleet that can't sustain the small-fleet
    throughput means the planner stopped scaling.  Set it to 0 on
    slower hosts (shared CI runners use the absolute floor instead).
    The opt-in 100k point is recorded but NOT anchored: its admission
    scan is O(n_devices * n_servers) sequential first-fit work (the
    server pool grows with the fleet), which dominates past ~50k
    devices and is outside what the anchor measures.  Each point is
    recorded into BENCH_fleet.json as soon as it is measured, so a
    tripped gate never discards earlier points."""
    import jax

    from repro.api import engine as E

    out = []
    entries: dict = {}  # per-size slices, mirrors what _record has seen
    floor = float(os.environ.get("FLEET_BENCH_MIN_DEVICES_PER_S", 0))
    anchor = float(os.environ.get("FLEET_BENCH_SCALE_ANCHOR", 9900)) or None
    for n_devices in _scale_sizes():
        periods = _periods(n_devices)
        for policy in ("amr2", "dual"):
            params = _scale_params(n_devices, policy, periods)
            # compile the DONATED jit variant (its own cache entry)
            _, M = E.rollout(E.init_state(params), params, periods,
                             donate=True)
            jax.block_until_ready(np.asarray(M.total_accuracy))
            t0 = time.perf_counter()
            # donate a fresh state's buffers: the steady-state rollout
            # shape (the old and new fleet state never coexist)
            _, M = E.rollout(E.init_state(params), params, periods,
                             donate=True)
            acc = np.asarray(M.total_accuracy)
            jax.block_until_ready(acc)
            wall = time.perf_counter() - t0
            n_jobs = int(np.asarray(M.n_jobs).sum())
            dps = n_devices * periods / wall
            entry = {
                "devices": n_devices, "policy": policy, "periods": periods,
                "path": "rollout_scan_donated",
                "lp_method": params.lp_method,
                "jobs": n_jobs,
                "devices_per_s_plan": dps,
                "devices_per_s_wall": dps,
                "mean_job_accuracy": float(acc.sum()) / max(n_jobs, 1),
                "violation_rate": float(np.asarray(M.n_violations).sum())
                / (n_devices * periods),
                "backpressure_rate":
                float(np.asarray(M.n_backpressured).sum())
                / (n_devices * periods),
            }
            # record BEFORE the gates so a tripped assert still leaves
            # the measured point in BENCH_fleet.json
            entries.setdefault(str(n_devices), {})[policy] = entry
            _record("scale", {str(n_devices): {policy: entry}})
            if policy == "amr2" and n_devices == max(_scale_sizes()):
                out.extend(_scale_chaos_point(params, n_devices, periods,
                                              M, wall))
            if policy == "amr2":
                assert int(np.asarray(M.n_unsolved).sum()) == 0, \
                    f"{n_devices}-device rollout left LPs unsolved"
                if floor:
                    assert dps >= floor, \
                        f"{n_devices}-device rollout at {dps:.0f} " \
                        f"devices/s is under the {floor:.0f} floor"
                if anchor is not None and n_devices == 16384:
                    assert dps >= anchor, \
                        f"{n_devices}-device rollout at {dps:.0f} " \
                        f"devices/s is under the 256-device scale " \
                        f"anchor ({anchor:.0f}; FLEET_BENCH_SCALE_ANCHOR)"
            tag = f"fleet/scale/{n_devices}" + (
                "" if policy == "amr2" else f"/{policy}")
            out.append((
                tag, wall / (n_devices * periods) * 1e6,
                f"periods={periods};jobs={n_jobs};"
                f"devices_per_s={dps:.0f};"
                f"lp_method={params.lp_method};donate=1;"
                f"acc_per_job={entry['mean_job_accuracy']:.4f};"
                f"violation_rate={entry['violation_rate']:.4f};"
                f"backpressure_rate={entry['backpressure_rate']:.4f};"
                f"sim_wall_s={wall:.2f}"))
    return out


def _scale_chaos_point(params, n_devices: int, periods: int, M_free,
                       free_wall: float):
    """Armed-chaos companion to the largest scale point: prices the fault
    trace AT SCALE instead of extrapolating from the 64-device chaos
    section.  Armed-null is GATED bitwise-free (same trajectory as the
    fault-free rollout — arming buys only the traced fault block, whose
    overhead is recorded); armed-hot records the full ladder's cost."""
    import dataclasses

    import jax

    from repro.api import engine as E
    from repro.serving import FaultModel

    out = []
    entry: dict = {"devices": n_devices, "periods": periods}
    for tag, fm in (("armed_null", FaultModel.none()),
                    ("armed_hot", FaultModel.make(
                        link_degrade_prob=0.2, link_degrade_mag=0.6,
                        straggler_prob=0.15, straggler_mult=1.8,
                        loss_rate=0.05))):
        p = dataclasses.replace(params, faults=fm, chaos=True,
                                fault_seed=11)
        _, M = E.rollout(E.init_state(p), p, periods,
                         donate=True)                      # compile
        jax.block_until_ready(np.asarray(M.total_accuracy))
        t0 = time.perf_counter()
        _, M = E.rollout(E.init_state(p), p, periods, donate=True)
        jax.block_until_ready(np.asarray(M.total_accuracy))
        wall = time.perf_counter() - t0
        dps = n_devices * periods / wall
        if tag == "armed_null":
            for f in ("total_accuracy", "n_jobs", "n_violations",
                      "n_offloading", "n_backpressured", "backlog",
                      "es_utilization"):
                assert np.array_equal(np.asarray(getattr(M, f)),
                                      np.asarray(getattr(M_free, f))), \
                    f"armed-null chaos at {n_devices} devices diverged " \
                    f"from the fault-free rollout on {f}"
            entry[tag] = {
                "devices_per_s_wall": dps,
                "overhead_vs_fault_free": free_wall / wall,
                "parity": "bitwise_vs_fault_free",
            }
        else:
            entry[tag] = {
                "devices_per_s_wall": dps,
                "overhead_vs_fault_free": free_wall / wall,
                "n_retries": int(np.asarray(M.n_retries).sum()),
                "n_fallback_local":
                    int(np.asarray(M.n_fallback_local).sum()),
                "n_dropped": int(np.asarray(M.n_dropped).sum()),
                "n_deadline_miss":
                    int(np.asarray(M.n_deadline_miss).sum()),
                "n_es_audit_updates":
                    int(np.asarray(M.n_es_audit_updates).sum()),
                "worst_realized_makespan":
                    float(np.asarray(M.realized_makespan).max()),
            }
        out.append((
            f"fleet/scale/{n_devices}/chaos_{tag.split('_')[1]}",
            wall / (n_devices * periods) * 1e6,
            f"devices={n_devices};devices_per_s={dps:.0f};"
            f"free_ratio={free_wall / wall:.2f}" + (
                ";parity=bitwise" if tag == "armed_null" else
                f";es_audit_updates={entry[tag]['n_es_audit_updates']}")))
    _record("scale", {str(n_devices): {"chaos": entry}})
    return out


def speedup():
    """Vectorized engine vs the PR-1 per-device reference loop at the
    256-device scale point (or FLEET_BENCH_SPEEDUP_DEVICES).

    Two kinds of comparison, kept separate so the loop gain is not
    conflated with a solver/policy change:

      * *loop speedup* — the scanned `engine.rollout` vs
        `run_period_reference` under the SAME policy (amr2/amr2 and
        dual/dual), isolating the array-resident single-scan path against
        the per-device Python loop;
      * *path speedup* — the new hot path (`engine.rollout`, ONE lax.scan
        with donated state buffers; amr2 on the reduced-tableau simplex)
        against the PR-1 serving configuration (`run_period_reference`,
        policy "auto"), the number the ROADMAP tracks.  The reference
        loop's `solve_many` itself already benefits from the batched
        solvers, so this UNDERSTATES the gain over the literal PR-1 code.

    The scan path has no separate per-period planning phase, so its
    ``devices_per_s_plan`` equals its wall number.
    """
    import jax

    from repro.api import engine as E

    n = int(os.environ.get("FLEET_BENCH_SPEEDUP_DEVICES", _BIG))
    periods = _periods(n)

    def _run(policy: str, reference: bool):
        engine = _engine(n, policy=policy)
        step = (engine.run_period_reference if reference
                else engine.run_period)
        step()                                          # compile once
        engine.history.clear()
        t0 = time.perf_counter()
        for _ in range(periods):
            step()
        wall = time.perf_counter() - t0
        s = engine.summary()
        return {
            "devices_per_s_plan": s["devices_per_second"],
            "devices_per_s_wall": n * periods / wall,
            "mean_job_accuracy": s["mean_job_accuracy"],
            "violation_rate": s["violation_rate"],
        }

    def _run_scan(policy: str):
        params = _scale_params(n, policy, periods)
        _, M = E.rollout(E.init_state(params), params, periods,
                         donate=True)              # compile (donated jit)
        jax.block_until_ready(np.asarray(M.total_accuracy))
        t0 = time.perf_counter()
        _, M = E.rollout(E.init_state(params), params, periods,
                         donate=True)
        acc = np.asarray(M.total_accuracy)
        jax.block_until_ready(acc)
        wall = time.perf_counter() - t0
        n_jobs = int(np.asarray(M.n_jobs).sum())
        dps = n * periods / wall
        return {
            "devices_per_s_plan": dps,      # scan: plan == wall (one call)
            "devices_per_s_wall": dps,
            "mean_job_accuracy": float(acc.sum()) / max(n_jobs, 1),
            "violation_rate": float(np.asarray(M.n_violations).sum())
            / (n * periods),
        }

    pr1 = _run("auto", reference=True)        # the PR-1 serving config
    ref_amr2 = _run("amr2", reference=True)
    ref_dual = _run("dual", reference=True)
    new_amr2 = _run_scan("amr2")
    new_dual = _run_scan("dual")

    def _ratio(a, b, key):
        return a[key] / max(b[key], 1e-12)

    entry = {
        "devices": n, "periods": periods,
        "pr1_reference_auto": pr1,
        "reference_amr2": ref_amr2,
        "reference_dual": ref_dual,
        "vectorized_amr2": new_amr2,
        "vectorized_dual": new_dual,
        # same-policy pairs: the array-resident loop in isolation
        "amr2_loop_speedup_wall": _ratio(new_amr2, ref_amr2,
                                         "devices_per_s_wall"),
        "dual_loop_speedup_wall": _ratio(new_dual, ref_dual,
                                         "devices_per_s_wall"),
        # hot path vs the PR-1 serving configuration
        "amr2_speedup_plan": _ratio(new_amr2, pr1, "devices_per_s_plan"),
        "amr2_speedup_wall": _ratio(new_amr2, pr1, "devices_per_s_wall"),
        "dual_speedup_plan": _ratio(new_dual, pr1, "devices_per_s_plan"),
        "dual_speedup_wall": _ratio(new_dual, pr1, "devices_per_s_wall"),
        "dual_accuracy_delta": (new_dual["mean_job_accuracy"]
                                - pr1["mean_job_accuracy"]),
    }
    _record("speedup", {str(n): entry})
    return [
        ("fleet/speedup/pr1_reference", 1e6
         / max(pr1["devices_per_s_wall"], 1e-9),
         f"devices={n};devices_per_s={pr1['devices_per_s_wall']:.0f};"
         f"policy=auto;path=per_device"),
        ("fleet/speedup/vectorized_amr2", 1e6
         / max(new_amr2["devices_per_s_wall"], 1e-9),
         f"devices={n};devices_per_s={new_amr2['devices_per_s_wall']:.0f};"
         f"loop_speedup={entry['amr2_loop_speedup_wall']:.1f}x;"
         f"vs_pr1={entry['amr2_speedup_wall']:.1f}x"),
        ("fleet/speedup/vectorized_dual", 1e6
         / max(new_dual["devices_per_s_wall"], 1e-9),
         f"devices={n};devices_per_s={new_dual['devices_per_s_wall']:.0f};"
         f"loop_speedup={entry['dual_loop_speedup_wall']:.1f}x;"
         f"vs_pr1={entry['dual_speedup_wall']:.1f}x;"
         f"acc_delta={entry['dual_accuracy_delta']:+.4f}"),
    ]


def rollout():
    """Engine-v2 rollout (ONE lax.scan over the jitted period step) vs the
    per-period `run()` loop at the 256-device point
    (``FLEET_BENCH_ROLLOUT_DEVICES`` / ``FLEET_BENCH_ROLLOUT_PERIODS``),
    for both traceable policies.

    Three timed paths per policy over the same replayed arrival trace:

      * *host_loop* — `run()` with engine-v2 delegation disabled: the
        pre-v2 per-period pipeline (batched api solves + host
        admission/replan/audit), the baseline the >= 2x acceptance gate
        is against;
      * *delegated* — `run()` as shipped: per-period calls into the same
        jitted core the scan uses (host queue + stats bookkeeping per
        period);
      * *scan* — `engine.rollout`: the whole epoch in one traced call,
        zero per-period host sync.

    The scan and the delegated loop are first pinned BIT-IDENTICAL on
    every trajectory (the engine-v2 parity contract), then timed (min
    over ``reps``).  The >= 2x gate binds on the dual policy, where the
    planner is cheap and the loop's per-period host work dominates; for
    amr2 the step is LP-compute-bound on CPU, so removing the host loop
    buys ~1.3-1.7x steady-state — both numbers are recorded."""
    import jax
    import numpy as np

    from repro.api import engine as E
    from repro.serving import FleetConfig, FleetEngine

    n = int(os.environ.get("FLEET_BENCH_ROLLOUT_DEVICES", _BIG))
    periods = int(os.environ.get("FLEET_BENCH_ROLLOUT_PERIODS", 32))
    reps = 3
    entries = {}
    out = []

    for policy in ("amr2", "dual"):
        def mkcfg():
            return FleetConfig(
                n_devices=n, T=1.2, n_servers=max(1, n // 16),
                policy=policy, rate=10.0, batch_max=PARITY_JOBS,
                horizon=periods + 2, seed=7)

        params = E.EngineParams.from_config(mkcfg(), horizon=periods + 2)
        state = E.init_state(params)

        # --- parity pin: scan == per-period delegated loop, bit for bit -
        _, metrics = E.rollout(state, params, periods)    # also compiles
        eng = FleetEngine.from_config(mkcfg())
        stats = eng.run(periods)
        for f in ("n_jobs", "n_violations", "n_offloading",
                  "n_backpressured", "n_outage", "n_straggler_updates",
                  "backlog"):
            got = np.asarray(getattr(metrics, f))
            want = np.array([getattr(s, f) for s in stats])
            assert np.array_equal(got, want), \
                f"rollout/run() {policy} trajectory mismatch on {f}"
        acc_gap = float(np.abs(
            np.asarray(metrics.total_accuracy)
            - np.array([s.total_accuracy for s in stats])).max())
        assert acc_gap == 0.0, \
            f"rollout/run() {policy} accuracy gap {acc_gap}"

        def _time_scan():
            t0 = time.perf_counter()
            _, M = E.rollout(state, params, periods)
            jax.block_until_ready(np.asarray(M.total_accuracy))
            return time.perf_counter() - t0

        def _time_run(disable_delegation):
            best = np.inf
            for _ in range(reps):
                import dataclasses
                e = FleetEngine.from_config(dataclasses.replace(
                    mkcfg(), delegate=not disable_delegation))
                e.run_period()              # compile / warm caches
                e.history.clear()
                t0 = time.perf_counter()
                e.run(periods)
                best = min(best, time.perf_counter() - t0)
            return best

        scan_s = min(_time_scan() for _ in range(reps))
        delegated_s = _time_run(False)
        host_loop_s = _time_run(True)

        dps = lambda s: n * periods / s
        entry = {
            "devices": n, "periods": periods, "policy": policy,
            "parity": "bit_identical_vs_delegated_run",
            "scan_devices_per_s_wall": dps(scan_s),
            "delegated_loop_devices_per_s_wall": dps(delegated_s),
            "host_loop_devices_per_s_wall": dps(host_loop_s),
            "scan_speedup_vs_host_loop": host_loop_s / scan_s,
            "scan_speedup_vs_delegated_loop": delegated_s / scan_s,
        }
        if policy == "dual":
            assert entry["scan_speedup_vs_host_loop"] >= 2.0, \
                f"dual rollout scan only " \
                f"{entry['scan_speedup_vs_host_loop']:.2f}x over the " \
                f"per-period host run() loop (acceptance floor: 2x)"
        entries[policy] = entry
        out.extend([
            (f"fleet/rollout/{n}/{policy}/scan",
             scan_s / (n * periods) * 1e6,
             f"devices={n};periods={periods};"
             f"devices_per_s={dps(scan_s):.0f};"
             f"single_lax_scan=1;parity=bit_identical"),
            (f"fleet/rollout/{n}/{policy}/delegated_loop",
             delegated_s / (n * periods) * 1e6,
             f"devices={n};devices_per_s={dps(delegated_s):.0f};"
             f"scan_speedup="
             f"{entry['scan_speedup_vs_delegated_loop']:.2f}x"),
            (f"fleet/rollout/{n}/{policy}/host_loop",
             host_loop_s / (n * periods) * 1e6,
             f"devices={n};devices_per_s={dps(host_loop_s):.0f};"
             f"scan_speedup={entry['scan_speedup_vs_host_loop']:.2f}x"),
        ])
    _record("rollout", {str(n): entries})
    return out


def sharded():
    """`rollout_sharded` (shard_map over the fleet axis) vs the unsharded
    scan, keyed by shard x device count.  Needs > 1 jax device — spawn
    host-platform devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
    sharded smoke job does); on a single-device host the section reports
    a skip and records nothing (merge-on-write keeps any previously
    recorded keys)."""
    import jax
    import numpy as np

    from repro.api import engine as E
    from repro.serving import FleetConfig

    n_shards = len(jax.devices())
    if n_shards < 2:
        return [("fleet/sharded/skipped", 0.0,
                 "reason=single_jax_device;hint=XLA_FLAGS="
                 "--xla_force_host_platform_device_count=8")]
    n = int(os.environ.get("FLEET_BENCH_SHARD_DEVICES", 64))
    periods = int(os.environ.get("FLEET_BENCH_ROLLOUT_PERIODS", 32))
    reps = 3

    cfg = FleetConfig(
        n_devices=n, T=1.2, n_servers=max(1, n // 16), policy="amr2",
        rate=10.0, batch_max=PARITY_JOBS, horizon=periods + 2, seed=7)
    params = E.EngineParams.from_config(cfg, horizon=periods + 2)
    state = E.init_state(params)
    mesh = E.fleet_mesh(n_shards)
    sstate, sparams = E.shard(state, params, mesh)

    _, MU = E.rollout(state, params, periods)             # compile
    _, MS = E.rollout_sharded(sstate, sparams, periods, mesh)
    for f in ("n_jobs", "n_violations", "n_offloading", "n_backpressured",
              "backlog"):
        assert np.array_equal(np.asarray(getattr(MS, f)),
                              np.asarray(getattr(MU, f))), \
            f"sharded/unsharded mismatch on {f}"
    acc_gap = float(np.abs(np.asarray(MS.total_accuracy)
                           - np.asarray(MU.total_accuracy)).max())
    assert acc_gap <= 1e-9 * max(
        1.0, float(np.abs(np.asarray(MU.total_accuracy)).max())), \
        f"sharded accuracy gap {acc_gap:.2e}"

    def _timed_roll(fn):
        best = np.inf
        for _ in range(reps):
            t0 = time.perf_counter()
            _, M = fn()
            jax.block_until_ready(np.asarray(M.total_accuracy))
            best = min(best, time.perf_counter() - t0)
        return best

    unsharded_s = _timed_roll(lambda: E.rollout(state, params, periods))
    sharded_s = _timed_roll(
        lambda: E.rollout_sharded(sstate, sparams, periods, mesh))
    dps = lambda s: n * periods / s
    entry = {
        "devices": n, "periods": periods, "n_shards": n_shards,
        "parity": "matches_unsharded",
        "max_accuracy_gap": acc_gap,
        "unsharded_devices_per_s_wall": dps(unsharded_s),
        "sharded_devices_per_s_wall": dps(sharded_s),
        "shard_speedup": unsharded_s / sharded_s,
    }
    _record("sharded", {f"{n_shards}x{n}": entry})
    return [
        (f"fleet/sharded/{n_shards}x{n}", sharded_s / (n * periods) * 1e6,
         f"devices={n};shards={n_shards};"
         f"devices_per_s={dps(sharded_s):.0f};"
         f"speedup_vs_unsharded={unsharded_s / sharded_s:.2f}x;"
         f"max_acc_gap={acc_gap:.1e}"),
    ]


def chaos():
    """Graceful degradation under injected faults, at the 64-device point
    (``FLEET_BENCH_CHAOS_DEVICES`` / ``FLEET_BENCH_CHAOS_PERIODS``).

    Three pieces, all on the scanned `engine.rollout` path:

      * *armed-null parity* — chaos=True with the all-zero FaultModel
        must reproduce the fault-free rollout BIT for BIT (identity
        factors and zero losses are exact in float64), so arming the
        subsystem costs nothing but the traced fault block;
      * *loss sweep* — offload loss 0% -> 40% on ONE compiled rollout
        (rates are leaves, only the armed trace compiles once).  Gates:
        the per-period accounting identity ``admitted == completed +
        fallback + dropped`` closes exactly at every point, realized
        makespans stay under ``2T + backoff_cap + one retransmission of
        the worst admitted demand``, and the 10%-loss point retains
        >= 90% of the fault-free accuracy — the retry + local-fallback
        ladder flattens the loss cliff instead of dropping work;
      * *harsh* — crash + link-degrade + straggler + loss all armed at
        once: the worst-case regime the README documents (deadline
        misses are EXPECTED here — the point is they are counted, not
        hidden)."""
    import dataclasses

    import jax

    from repro.api import engine as E
    from repro.serving import FaultModel, FleetConfig

    n = int(os.environ.get("FLEET_BENCH_CHAOS_DEVICES", 64))
    periods = int(os.environ.get("FLEET_BENCH_CHAOS_PERIODS", 12))
    T = 1.2
    cfg = FleetConfig(
        n_devices=n, T=T, n_servers=max(1, n // 16), policy="amr2",
        rate=10.0, batch_max=PARITY_JOBS, horizon=periods + 2, seed=7,
        fault_seed=11)
    base = E.EngineParams.from_config(cfg, horizon=periods + 2)
    assert not base.chaos
    out = []

    # --- armed-null bitwise parity -------------------------------------
    _, m0 = E.rollout(E.init_state(base), base, periods)
    armed = dataclasses.replace(base, faults=FaultModel.none(), chaos=True)
    t0 = time.perf_counter()
    _, m1 = E.rollout(E.init_state(armed), armed, periods)
    jax.block_until_ready(np.asarray(m1.total_accuracy))
    armed_s = time.perf_counter() - t0
    for f in ("total_accuracy", "n_jobs", "n_violations", "n_offloading",
              "backlog", "realized_makespan"):
        assert np.array_equal(np.asarray(getattr(m0, f)),
                              np.asarray(getattr(m1, f))), \
            f"armed-null chaos rollout diverged from fault-free on {f}"
    acc0 = float(np.asarray(m0.total_accuracy).sum())
    jobs0 = int(np.asarray(m0.n_jobs).sum())

    # realized-makespan bound for loss-only models: no link degradation,
    # so one retry round retransmits at most the worst admitted demand
    demand_cap = float(np.asarray(base.p_es).max()) * base.batch_max

    def _gated_run(params, worst_link):
        _, M = E.rollout(E.init_state(params), params, periods)
        n_off = np.asarray(M.n_offload_samples)
        closed = (n_off == np.asarray(M.n_offload_ok)
                  + np.asarray(M.n_fallback_local)
                  + np.asarray(M.n_dropped))
        assert closed.all(), "per-period offload accounting did not close"
        cap = float(params.faults.backoff_cap)
        bound = 2.0 * T + cap + demand_cap * worst_link
        worst = float(np.asarray(M.realized_makespan).max())
        assert worst <= bound + 1e-9, \
            f"realized makespan {worst:.3f} exceeds the ladder bound " \
            f"{bound:.3f} (2T + backoff cap + one retransmission)"
        return M, worst

    # --- offload-loss sweep on the one armed trace ---------------------
    sweep = {}
    for loss in (0.0, 0.05, 0.1, 0.2, 0.4):
        p = dataclasses.replace(armed,
                                faults=FaultModel.make(loss_rate=loss))
        M, worst = _gated_run(p, worst_link=1.0)
        acc = float(np.asarray(M.total_accuracy).sum())
        entry = {
            "loss_rate": loss,
            "accuracy_vs_fault_free": acc / max(acc0, 1e-12),
            "total_accuracy": acc,
            "n_retries": int(np.asarray(M.n_retries).sum()),
            "n_fallback_local": int(np.asarray(M.n_fallback_local).sum()),
            "n_dropped": int(np.asarray(M.n_dropped).sum()),
            "n_deadline_miss": int(np.asarray(M.n_deadline_miss).sum()),
            "worst_realized_makespan": worst,
        }
        sweep[f"{loss:g}"] = entry
        out.append((
            f"fleet/chaos/loss_{loss:g}", 0.0,
            f"devices={n};acc_ratio={entry['accuracy_vs_fault_free']:.4f};"
            f"retries={entry['n_retries']};"
            f"fallback={entry['n_fallback_local']};"
            f"dropped={entry['n_dropped']};"
            f"worst_makespan={worst:.3f}"))
    assert sweep["0"]["accuracy_vs_fault_free"] == 1.0, \
        "zero-rate sweep point must reproduce the fault-free accuracy"
    assert sweep["0.1"]["accuracy_vs_fault_free"] >= 0.90, \
        f"10% offload loss dropped accuracy to " \
        f"{sweep['0.1']['accuracy_vs_fault_free']:.3f}x fault-free — " \
        f"the degradation ladder should hold >= 0.90x (no cliff)"

    # --- harsh regime: everything armed at once ------------------------
    harsh_fm = FaultModel.make(es_crash_prob=0.08, link_degrade_prob=0.25,
                               link_degrade_mag=0.6, straggler_prob=0.2,
                               straggler_mult=1.8, loss_rate=0.15)
    M, worst = _gated_run(
        dataclasses.replace(armed, faults=harsh_fm),
        worst_link=1.0 + float(harsh_fm.link_degrade_mag))
    acc = float(np.asarray(M.total_accuracy).sum())
    harsh = {
        "accuracy_vs_fault_free": acc / max(acc0, 1e-12),
        "n_retries": int(np.asarray(M.n_retries).sum()),
        "n_fallback_local": int(np.asarray(M.n_fallback_local).sum()),
        "n_dropped": int(np.asarray(M.n_dropped).sum()),
        "n_deadline_miss": int(np.asarray(M.n_deadline_miss).sum()),
        "deadline_miss_rate": int(np.asarray(M.n_deadline_miss).sum())
        / max(jobs0, 1),
        "worst_realized_makespan": worst,
    }
    assert harsh["n_retries"] + harsh["n_fallback_local"] \
        + harsh["n_dropped"] > 0, "harsh fault model never fired"

    _record("chaos", {
        "devices": n, "periods": periods, "jobs": jobs0,
        "armed_null_parity": "bitwise",
        "armed_null_devices_per_s": n * periods / armed_s,
        "loss_sweep": sweep, "harsh": harsh,
        "assertions": "passed",
    })
    out.append((
        f"fleet/chaos/harsh", 0.0,
        f"devices={n};acc_ratio={harsh['accuracy_vs_fault_free']:.4f};"
        f"miss_rate={harsh['deadline_miss_rate']:.4f};"
        f"dropped={harsh['n_dropped']};worst_makespan={worst:.3f}"))
    return out


def _mobility_sizes():
    env = os.environ.get("FLEET_BENCH_MOBILITY_SIZES")
    if env:
        return tuple(int(x) for x in env.split(","))
    return (4096, 16384)


def mobility():
    """The multi-cell mobility subsystem at scale (`core.mobility`).

    Two pieces per device count (``FLEET_BENCH_MOBILITY_SIZES``; the
    102400 point is opt-in, like the scale section's):

      * *admission microbench* — the OLD global sequential first-fit scan
        (`admit_mask_jnp`: one `lax.scan` step per device, each step an
        argmin over `n_servers` — the O(D x S) wall the ROADMAP names as
        the entire 100k gap) against the NEW segmented per-cell
        formulation (`admit_mask_segmented`: sorts + cumsums, no
        sequential pass) on the same demand vector.  Both jitted, both
        admitting into ``D // 16`` servers.  Gated: at >= 16384 devices
        the segmented scan must beat the global scan.
      * *mobility-armed rollout* — the full engine with a replayed
        3-cell-per-128-device trace (routing + handover + segmented
        admission + ES-belief plumbing) at the LARGEST size, reported as
        devices/s alongside the scale section's single-pool number.  The
        opt-in 102400 point is gated on beating the recorded single-pool
        scan there (``FLEET_BENCH_MOBILITY_ANCHOR`` devices/s, default
        8100 — the ~8.1k devices/s the global-admission engine measured),
        closing the ROADMAP's "segmented/hierarchical admission scan"
        rung."""
    import jax
    import jax.numpy as jnp
    from repro.core.types import x64_scope

    from repro.api import engine as E
    from repro.core.mobility import MobilityModel, admit_mask_segmented

    out = []
    entries: dict = {}
    sizes = _mobility_sizes()
    anchor = float(os.environ.get("FLEET_BENCH_MOBILITY_ANCHOR", 8100))
    reps = 3
    rng = np.random.default_rng(0)
    T = 1.2
    with x64_scope():
        for n in sizes:
            n_servers = max(1, n // 16)
            S = 16 if n_servers % 16 == 0 else 1
            k = n_servers // S
            demands = jnp.asarray(np.where(
                rng.random(n) < 0.3, 0.0, rng.uniform(0.0, 1.5, n)))
            cell = jnp.asarray(rng.integers(0, S, n).astype(np.int32))
            glob = jax.jit(lambda d: E.admit_mask_jnp(d, T, n_servers))
            seg = jax.jit(lambda d, c: admit_mask_segmented(
                d, c, T, S, k))
            jax.block_until_ready(glob(demands))           # compile
            jax.block_until_ready(seg(demands, cell))
            glob_s = min(_timed(lambda: jax.block_until_ready(
                glob(demands))) for _ in range(reps))
            seg_s = min(_timed(lambda: jax.block_until_ready(
                seg(demands, cell))) for _ in range(reps))
            speedup_x = glob_s / seg_s
            entry = {
                "devices": n, "n_servers": n_servers, "n_cells": S,
                "global_scan_s": glob_s, "segmented_s": seg_s,
                "segmented_speedup": speedup_x,
            }
            if n >= 16384:
                assert speedup_x > 1.0, \
                    f"segmented admission ({seg_s * 1e3:.1f} ms) did not " \
                    f"beat the global sequential scan " \
                    f"({glob_s * 1e3:.1f} ms) at {n} devices"
            entries[str(n)] = {"admission": entry}
            _record("mobility", {str(n): {"admission": entry}})
            out.append((
                f"fleet/mobility/admission/{n}", seg_s / n * 1e6,
                f"devices={n};cells={S};servers={n_servers};"
                f"segmented_ms={seg_s * 1e3:.2f};"
                f"global_scan_ms={glob_s * 1e3:.2f};"
                f"speedup={speedup_x:.1f}x"))

    # --- mobility-armed rollout at the largest point ---------------------
    n = max(sizes)
    periods = _periods(n)
    params = _scale_params(n, "amr2", periods)
    n_servers = params.n_servers
    S = 16 if n_servers % 16 == 0 else 1
    cxy = np.stack([20.0 * np.array([i % 4, i // 4]) for i in range(S)])
    dev_home = cxy[rng.integers(0, S, n)]
    trace = (rng.normal(scale=6.0, size=(max(4, periods), n, 2))
             + dev_home)
    mob = MobilityModel.make(cell_xy=cxy, trace=trace, radius=30.0,
                             link_alpha=0.2)
    armed = params.with_mobility(mob, routing="nearest")
    _, M = E.rollout(E.init_state(armed), armed, periods,
                     donate=True)                          # compile
    jax.block_until_ready(np.asarray(M.total_accuracy))
    t0 = time.perf_counter()
    _, M = E.rollout(E.init_state(armed), armed, periods, donate=True)
    jax.block_until_ready(np.asarray(M.total_accuracy))
    wall = time.perf_counter() - t0
    dps = n * periods / wall
    n_jobs = int(np.asarray(M.n_jobs).sum())
    entry = {
        "devices": n, "periods": periods, "n_cells": S,
        "policy": "amr2", "routing": "nearest", "path":
        "rollout_scan_donated_segmented_admission",
        "devices_per_s_wall": dps,
        "n_handover": int(np.asarray(M.n_handover).sum()),
        "mean_job_accuracy": float(np.asarray(M.total_accuracy).sum())
        / max(n_jobs, 1),
        "violation_rate": float(np.asarray(M.n_violations).sum())
        / (n * periods),
    }
    _record("mobility", {str(n): {"rollout": entry}})
    if n >= 102400:
        assert dps > anchor, \
            f"102400-device mobility rollout at {dps:.0f} devices/s did " \
            f"not improve on the recorded global-admission engine " \
            f"(~{anchor:.0f} devices/s; FLEET_BENCH_MOBILITY_ANCHOR)"
    out.append((
        f"fleet/mobility/rollout/{n}", wall / (n * periods) * 1e6,
        f"devices={n};cells={S};periods={periods};"
        f"devices_per_s={dps:.0f};"
        f"handovers={entry['n_handover']};"
        f"acc_per_job={entry['mean_job_accuracy']:.4f};"
        f"violation_rate={entry['violation_rate']:.4f}"))
    return out


def grad():
    """The differentiable serving stack at the 256-device point
    (``FLEET_BENCH_GRAD_DEVICES`` / ``FLEET_BENCH_GRAD_PERIODS``).

    One `rollout_value_and_grad` pass (soft mode, implicit-gradient
    simplex + smoothed rounding/admission) returns d(total accuracy)/d
    for EVERY continuous knob — all of ``p_es``, ``T``, and ``acc`` — in
    a single backward sweep.  The honest baseline is central (2-point)
    finite differences, which needs TWO rollouts per scalar knob; the
    recorded ``speedup_vs_fd`` is ``2 * n_knobs * forward_wall /
    grad_wall`` and is gated >= 5x (it lands orders of magnitude higher
    — the gate just keeps the mechanism honest if the knob set ever
    shrinks to a handful).  Also records the reverse-mode overhead
    (``grad_wall / forward_wall``, the classic 2-5x band for a scanned
    epoch) and a 3-coordinate FD spot-check at rtol 1e-4 so the recorded
    gradient is demonstrably the right one, not just a fast one."""
    import dataclasses

    import jax

    from repro.api import engine as E
    from repro.serving import FleetConfig

    n = int(os.environ.get("FLEET_BENCH_GRAD_DEVICES", _BIG))
    periods = int(os.environ.get("FLEET_BENCH_GRAD_PERIODS", 5))
    reps = 3
    cfg = FleetConfig(
        n_devices=n, T=1.2, n_servers=max(1, n // 16), policy="amr2",
        rate=10.0, batch_max=PARITY_JOBS, horizon=periods + 2, seed=7)
    base = E.EngineParams.from_config(cfg, horizon=periods + 2)
    # jitter p_es off the LP vertex kinks (see tests/test_grad.py): FD
    # and the implicit gradient must measure the same linearity region
    rng = np.random.default_rng(7)
    arr = np.asarray(base.p_es, np.float64)
    nudge = (rng.uniform(1e-3, 3e-3, size=arr.shape)
             * rng.choice([-1.0, 1.0], size=arr.shape))
    params = dataclasses.replace(base, p_es=arr + nudge
                                 ).with_differentiable(smooth_mode="soft")
    wrt = ("p_es", "T", "acc")
    n_knobs = int(np.asarray(params.p_es).size
                  + np.asarray(params.acc).size + 1)

    def fwd():
        _, M = E.rollout(E.init_state(params), params, periods)
        jax.block_until_ready(np.asarray(M.total_accuracy))
        return float(np.asarray(M.total_accuracy).sum())

    def vag():
        val, g = E.rollout_value_and_grad(
            E.init_state(params), params, periods, wrt=wrt)
        jax.block_until_ready(np.asarray(g["p_es"]))
        return val, g

    fwd()                                                  # compile
    val, grads = vag()                                     # compile
    fwd_s = min(_timed(fwd) for _ in range(reps))
    grad_s = min(_timed(vag) for _ in range(reps))
    speedup_x = 2 * n_knobs * fwd_s / grad_s
    assert speedup_x >= 5.0, \
        f"value_and_grad at {grad_s * 1e3:.0f} ms is only {speedup_x:.1f}x " \
        f"over 2-point FD of all {n_knobs} knobs (acceptance floor: 5x)"

    # FD spot-check: the recorded gradient is correct, not just fast
    def _value_at(leaf, idx, eps):
        a = np.asarray(getattr(params, leaf), np.float64)
        flat = np.atleast_1d(a).ravel().copy()
        flat[idx] += eps
        rep = flat.reshape(np.shape(a)) if np.shape(a) else float(flat[0])
        p = dataclasses.replace(params, **{leaf: rep})
        _, M = E.rollout(E.init_state(p), p, periods)
        return float(np.asarray(M.total_accuracy).sum())

    checked = 0
    for leaf, idx in (("p_es", int(rng.integers(arr.size))), ("T", 0),
                      ("acc", int(rng.integers(
                          np.asarray(params.acc).size)))):
        an = float(np.atleast_1d(
            np.asarray(grads[leaf], np.float64)).ravel()[idx])
        eps = 1e-5
        fd_v = (_value_at(leaf, idx, eps)
                - _value_at(leaf, idx, -eps)) / (2 * eps)
        err = abs(fd_v - an)
        assert err < 1e-6 or err / max(abs(fd_v), abs(an)) < 1e-4, \
            f"grad({leaf}[{idx}]) = {an} but central FD = {fd_v}"
        checked += 1

    entry = {
        "devices": n, "periods": periods, "n_knobs": n_knobs,
        "smooth_mode": "soft", "wrt": list(wrt),
        "value": float(val),
        "grad_norm_p_es": float(np.linalg.norm(
            np.asarray(grads["p_es"], np.float64))),
        "forward_wall_s": fwd_s,
        "grad_wall_s": grad_s,
        "grad_overhead_vs_forward": grad_s / fwd_s,
        "speedup_vs_fd": speedup_x,
        "fd_spot_checks_passed": checked,
        "assertions": "passed",
    }
    _record("grad", {str(n): entry})
    return [(
        f"fleet/grad/{n}", grad_s / (n * periods) * 1e6,
        f"devices={n};periods={periods};knobs={n_knobs};"
        f"grad_ms={grad_s * 1e3:.0f};fwd_ms={fwd_s * 1e3:.0f};"
        f"overhead={grad_s / fwd_s:.2f}x;"
        f"speedup_vs_fd={speedup_x:.0f}x;fd_checks={checked}")]


def hi():
    """Online hierarchical inference vs the offline clairvoyant
    (``FLEET_BENCH_HI_DEVICES`` / ``FLEET_BENCH_HI_PERIODS``, default
    256 x 64).

    The fleet gets HETEROGENEOUS per-device ES accuracies (drawn in
    [0.65, 0.92] — the regime of the online problem, where no shared
    threshold can be right for every device), and every rule replays the
    IDENTICAL confidence stream (one ``hi_seed``; the stream folds its
    own key, so rules differ only in their decisions):

      * a fixed-threshold sweep over the 9-point bandit grid — scalar
        ``theta0`` is a pytree leaf, so all 9 points reuse ONE compiled
        rollout;
      * the OGD threshold learner, UCB, and EXP3;
      * the clairvoyant (rule="fixed" with per-device ``theta0 =
        clip(acc_es - beta, 0, 1)``), whose cumulative pseudo-regret is
        gated EXACTLY 0.0 — the regret metric's floor is the offline
        per-sample optimum, the role AMR^2 plays for the planned path.

    Gates: the clairvoyant floor, the per-period serving identity
    (n_hi_offloaded + n_hi_local_final == n_jobs), and — at any horizon
    >= 32 periods — the threshold learner's cumulative regret beating
    the BEST fixed grid point's (sublinear vs linear growth; the learner
    converges per device, a shared threshold cannot)."""
    import dataclasses

    from repro.api import engine as E
    from repro.core.hi import HIModel
    from repro.serving import FleetConfig

    n = int(os.environ.get("FLEET_BENCH_HI_DEVICES", _BIG))
    periods = int(os.environ.get("FLEET_BENCH_HI_PERIODS", 64))
    beta, hi_seed = 0.15, 7
    cfg = FleetConfig(
        n_devices=n, T=1.2, n_servers=max(1, n // 16), policy="amr2",
        rate=10.0, batch_max=PARITY_JOBS, horizon=periods + 2, seed=7)
    base = E.EngineParams.from_config(cfg, horizon=periods + 2)
    acc = np.asarray(base.acc, np.float64).copy()
    rng = np.random.default_rng(7)
    acc[:, base.m] = rng.uniform(0.65, 0.92, n)
    het = dataclasses.replace(base, acc=acc)
    theta_star = np.clip(acc[:, base.m] - beta, 0.0, 1.0)
    ck = sorted({max(0, p - 1) for p in (8, 16, 32, periods)
                 if p <= periods})

    def _roll(params):
        t0 = time.perf_counter()
        state, M = E.rollout(E.init_state(params), params, periods)
        reg = np.asarray(M.hi_regret, np.float64)
        off = np.asarray(M.n_hi_offloaded, np.int64)
        loc = np.asarray(M.n_hi_local_final, np.int64)
        jobs = np.asarray(M.n_jobs, np.int64)
        assert np.array_equal(off + loc, jobs), \
            "per-period HI serving identity broke"
        return {
            "regret": float(reg[-1]),
            "regret_trajectory": {str(t + 1): float(reg[t]) for t in ck},
            "offload_rate": float(off.sum() / max(jobs.sum(), 1)),
            "acc_per_job": float(
                np.asarray(M.total_accuracy).sum() / max(jobs.sum(), 1)),
            "wall_s": time.perf_counter() - t0,
        }, state

    grid = np.linspace(0.1, 0.9, 9)
    sweep = {}
    for th in grid:                       # one compiled rollout, 9 leaves
        p = het.with_hi(HIModel.make(theta0=float(th),
                                     offload_cost=beta),
                        rule="fixed", hi_seed=hi_seed)
        sweep[f"{th:.1f}"], _ = _roll(p)
    best_th, best_fixed = min(((k, v["regret"]) for k, v in sweep.items()),
                              key=lambda kv: kv[1])

    rules = {}
    theta_err = None
    for rule in ("threshold", "ucb", "exp3"):
        p = het.with_hi(HIModel.make(offload_cost=beta), rule=rule,
                        hi_seed=hi_seed)
        rules[rule], state = _roll(p)
        if rule == "threshold":
            theta_err = float(np.abs(
                np.asarray(state.hi.theta) - theta_star).mean())

    clair = het.with_hi(HIModel.make(theta0=theta_star,
                                     offload_cost=beta),
                        rule="fixed", hi_seed=hi_seed)
    rules["clairvoyant"], _ = _roll(clair)
    assert rules["clairvoyant"]["regret"] == 0.0, \
        f"the clairvoyant fixed rule accrued nonzero pseudo-regret " \
        f"{rules['clairvoyant']['regret']} (floor broken)"

    learner = rules["threshold"]["regret"]
    if periods >= 32:
        assert learner < best_fixed, \
            f"threshold learner regret {learner:.1f} did not beat the " \
            f"best fixed grid point (theta={best_th}: {best_fixed:.1f}) " \
            f"at a {periods}-period horizon"

    wall = rules["threshold"]["wall_s"]
    entry = {
        "devices": n, "periods": periods, "hi_seed": hi_seed,
        "offload_cost": beta,
        "acc_es_range": [float(acc[:, base.m].min()),
                         float(acc[:, base.m].max())],
        "fixed_sweep": sweep,
        "best_fixed_theta": float(best_th),
        "best_fixed_regret": best_fixed,
        "rules": rules,
        "learner_theta_abs_err": theta_err,
        "learner_beats_best_fixed": bool(learner < best_fixed),
        "assertions": "passed",
    }
    _record("hi", {str(n): entry})
    return [(
        f"fleet/hi/{n}", wall / (n * periods) * 1e6,
        f"devices={n};periods={periods};"
        f"learner_regret={learner:.1f};best_fixed={best_fixed:.1f}"
        f"@{best_th};ucb={rules['ucb']['regret']:.1f};"
        f"exp3={rules['exp3']['regret']:.1f};clairvoyant=0;"
        f"theta_err={theta_err:.3f}")]


ALL = [parity, warm_cold, scaling, speedup, rollout, sharded, chaos,
       mobility, grad, hi]


def main():
    from repro.core.types import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    for fn in ALL:
        for name, us, derived in fn():
            print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
