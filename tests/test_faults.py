"""Chaos subsystem: `FaultModel` pytree, the traced degradation ladder
(retry -> local fallback -> drop), engine/fleet wiring, strict-mode
unsolved-period semantics, and the executor's per-sample status audit."""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.api import engine as E
from repro.serving import (EXEC_DROPPED, EXEC_FALLBACK_LOCAL, EXEC_OK_ED,
                           EXEC_OK_ES, FaultModel, FleetConfig, FleetEngine,
                           TierProfile, UnsolvedPeriodError, execute, plan,
                           greedy_local_fill, realize_execution,
                           sample_realization)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LADDER_INTS = ("n_offload_samples", "n_offload_ok", "n_deadline_miss",
               "n_retries", "n_fallback_local", "n_dropped")


def _config(n_devices=8, *, policy="amr2", seed=5, horizon=40, rate=9.0,
            n_servers=2, straggler_frac=0.25, outage_frac=0.1,
            batch_max=8, **extra):
    return FleetConfig(n_devices=n_devices, T=1.2, n_servers=n_servers,
                       policy=policy, backend="jax", rate=rate,
                       batch_max=batch_max, horizon=horizon, seed=seed,
                       straggler_frac=straggler_frac,
                       outage_frac=outage_frac, **extra)


_HARSH = dict(es_crash_prob=0.08, link_degrade_prob=0.25,
              link_degrade_mag=0.6, straggler_prob=0.2,
              straggler_mult=1.8, loss_rate=0.15)


# ---------------------------------------------------------------------------
# FaultModel: construction, validation, pytree plumbing
# ---------------------------------------------------------------------------
def test_fault_model_none_is_null_and_make_validates():
    assert FaultModel.none().is_null()
    assert not FaultModel.make(loss_rate=0.1).is_null()
    # backoff-only models are still null: no fault can ever fire
    assert FaultModel.make(backoff_base=0.1, backoff_cap=0.5).is_null()
    with pytest.raises(ValueError, match="loss_rate"):
        FaultModel.make(loss_rate=1.5)
    with pytest.raises(ValueError, match="es_crash_prob"):
        FaultModel.make(es_crash_prob=-0.1)
    with pytest.raises(ValueError, match="straggler_mult"):
        FaultModel.make(straggler_prob=0.5, straggler_mult=0.5)
    with pytest.raises(ValueError, match="link_degrade_mag"):
        FaultModel.make(link_degrade_mag=-1.0)
    with pytest.raises(ValueError, match="backoff"):
        FaultModel.make(backoff_base=-0.01)


def test_fault_model_pytree_roundtrip_all_leaves():
    import jax
    fm = FaultModel.make(**_HARSH)
    leaves, treedef = jax.tree_util.tree_flatten(fm)
    assert len(leaves) == len(dataclasses.fields(FaultModel))
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    for f in dataclasses.fields(FaultModel):
        assert float(getattr(rebuilt, f.name)) == \
            float(getattr(fm, f.name)), f.name


# ---------------------------------------------------------------------------
# greedy_local_fill vs a NumPy oracle
# ---------------------------------------------------------------------------
def _fill_oracle(lat, accl, budget, elig):
    D, n, m = lat.shape
    choice = np.full((D, n), m, np.int32)
    fit = np.zeros((D, n), bool)
    used = np.zeros(D)
    for d in range(D):
        res = float(budget[d])
        for j in range(n):
            if not elig[d, j]:
                continue
            fits = lat[d, j] <= res + 1e-12
            if not fits.any():
                continue
            pick = int(np.argmax(np.where(fits, accl[d], -np.inf)))
            choice[d, j] = pick
            fit[d, j] = True
            res -= lat[d, j, pick]
            used[d] += lat[d, j, pick]
    return choice, fit, used


@given(seed=st.integers(0, 2**16))
@settings(max_examples=10, deadline=None)
def test_greedy_local_fill_matches_numpy_oracle(seed):
    from repro.core.types import x64_scope
    rng = np.random.default_rng(seed)
    D, n, m = rng.integers(1, 5), rng.integers(1, 7), rng.integers(1, 4)
    lat = rng.uniform(0.05, 0.8, size=(D, n, m))
    accl = rng.uniform(0.2, 0.9, size=(D, m))
    budget = rng.uniform(0.0, 1.5, size=D)
    elig = rng.uniform(size=(D, n)) < 0.6
    with x64_scope():
        choice, fit, used = greedy_local_fill(lat, accl, budget, elig)
    c0, f0, u0 = _fill_oracle(lat, accl, budget, elig)
    np.testing.assert_array_equal(np.asarray(choice), c0)
    np.testing.assert_array_equal(np.asarray(fit), f0)
    np.testing.assert_allclose(np.asarray(used), u0, atol=1e-12)
    # spend never exceeds the budget
    assert (np.asarray(used) <= budget + 1e-9).all()


# ---------------------------------------------------------------------------
# realize_execution: the ladder's documented invariants (hypothesis)
# ---------------------------------------------------------------------------
def _random_period(rng, fm, seed, *, max_retries):
    """A random planned period + its fault realization (x64 required)."""
    import jax
    import jax.numpy as jnp
    D, n, m = 3, 5, 2
    mask = rng.uniform(size=(D, n)) < 0.8
    es_samp = mask & (rng.uniform(size=(D, n)) < 0.5)
    acc = np.concatenate(
        [np.sort(rng.uniform(0.3, 0.8, size=(D, m)), axis=1),
         rng.uniform(0.8, 0.95, size=(D, 1))], axis=1)
    acc_jobs = np.where(es_samp, acc[:, [m]],
                        acc[:, 0][:, None]) * mask
    p_es_jobs = rng.uniform(0.05, 0.4, size=(D, n))
    lat_local = rng.uniform(0.02, 0.5, size=(D, n, m))
    ed_wall = rng.uniform(0.0, 1.0, size=D)
    real = sample_realization(jax.random.PRNGKey(seed), fm, D, n,
                              max_retries + 1)
    rx = realize_execution(
        fm, real, mask=jnp.asarray(mask), es_samp=jnp.asarray(es_samp),
        acc_jobs=jnp.asarray(acc_jobs), p_es_jobs=jnp.asarray(p_es_jobs),
        ed_wall=jnp.asarray(ed_wall), lat_local=jnp.asarray(lat_local),
        acc=jnp.asarray(acc), T=jnp.float64(1.0), max_retries=max_retries)
    demand = (p_es_jobs * es_samp).sum(axis=1)
    return rx, real, demand, es_samp


@given(seed=st.integers(0, 2**16), loss=st.floats(0.0, 1.0),
       crash=st.floats(0.0, 1.0), max_retries=st.integers(0, 3))
@settings(max_examples=10, deadline=None)
def test_ladder_invariants_hypothesis(seed, loss, crash, max_retries):
    """For random plans and fault draws: (a) retry attempts are bounded
    by max_retries per sample, (b) the realized ES time respects the
    documented 2T + backoff_cap + demand*link bound, (c) the local
    fallback fits the residual deadline (ed_wall <= max(ed_audit, 2T)),
    (d) every admitted offload is accounted for exactly once, and (e)
    the pass is deterministic under a fixed key."""
    from repro.core.types import x64_scope
    rng = np.random.default_rng(seed)
    fm = FaultModel.make(loss_rate=loss, es_crash_prob=crash,
                         link_degrade_prob=0.3, link_degrade_mag=0.5,
                         straggler_prob=0.3, straggler_mult=2.0)
    with x64_scope():
        rx, real, demand, es_samp = _random_period(
            rng, fm, seed, max_retries=max_retries)
        rx2, *_ = _random_period(np.random.default_rng(seed), fm, seed,
                                 max_retries=max_retries)
    n_off = np.asarray(rx.n_offload)
    # (a) bounded retries
    assert (np.asarray(rx.n_retries) <= max_retries * n_off).all()
    # (b) realized ES wall bound (deadline = 2T, T = 1.0)
    cap = float(fm.backoff_cap)
    bound = 2.0 + cap + demand * np.asarray(real.link_factor)
    assert (np.asarray(rx.es_wall) <= bound + 1e-9).all()
    # (c) fallback fits the residual deadline
    assert (np.asarray(rx.ed_wall)
            <= np.maximum(np.asarray(rx.ed_audit), 2.0) + 1e-9).all()
    # (d) accounting identity, per device
    np.testing.assert_array_equal(
        n_off, np.asarray(rx.n_offload_ok) + np.asarray(rx.n_fallback_local)
        + np.asarray(rx.n_dropped))
    # (e) deterministic under a fixed key
    for f, a in zip(rx._fields, rx):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(getattr(rx2, f)), f)


def test_null_realization_reproduces_priced_execution():
    """All-identity factors + no losses: the realized pass must equal the
    priced plan bit for bit (the armed-null engine pin relies on it)."""
    from repro.core.types import x64_scope
    rng = np.random.default_rng(3)
    with x64_scope():
        rx, real, demand, es_samp = _random_period(
            rng, FaultModel.none(), 3, max_retries=2)
    assert not bool(np.asarray(real.es_crash))
    assert (np.asarray(real.link_factor) == 1.0).all()
    np.testing.assert_array_equal(np.asarray(rx.es_wall), demand)
    assert int(np.asarray(rx.n_retries).sum()) == 0
    assert int(np.asarray(rx.n_dropped).sum()) == 0
    np.testing.assert_array_equal(np.asarray(rx.n_offload),
                                  np.asarray(rx.n_offload_ok))


def test_es_crash_skips_retries_and_walks_the_ladder():
    """A certain pool crash: no retry can help — zero retries, every
    offloaded sample lands on rung 2 or rung 3."""
    from repro.core.types import x64_scope
    fm = FaultModel.make(es_crash_prob=1.0, loss_rate=0.0)
    with x64_scope():
        rx, real, _, es_samp = _random_period(
            np.random.default_rng(0), fm, 0, max_retries=3)
    assert bool(np.asarray(real.es_crash))
    assert int(np.asarray(rx.n_retries).sum()) == 0
    assert int(np.asarray(rx.n_offload_ok).sum()) == 0
    np.testing.assert_array_equal(
        np.asarray(rx.n_offload),
        np.asarray(rx.n_fallback_local) + np.asarray(rx.n_dropped))


# ---------------------------------------------------------------------------
# engine wiring: the armed-null bitwise pin + chaos accounting
# ---------------------------------------------------------------------------
def test_armed_null_fault_model_is_bitwise_invisible():
    """chaos=True with the all-zero FaultModel must trace the realized-
    execution pass and still reproduce the fault-free rollout BIT for
    BIT — identity factors and zero losses are exact in float64."""
    periods = 6
    cfg = _config(6, horizon=periods + 2)
    base = E.EngineParams.from_config(cfg, horizon=periods + 2)
    assert not base.chaos
    armed = dataclasses.replace(base, faults=FaultModel.none(), chaos=True)
    s0, m0 = E.rollout(E.init_state(base), base, periods)
    s1, m1 = E.rollout(E.init_state(armed), armed, periods)
    for f in [x.name for x in dataclasses.fields(type(m0))]:
        np.testing.assert_array_equal(np.asarray(getattr(m0, f)),
                                      np.asarray(getattr(m1, f)), f)
    for f in ("period", "key", "p_ed", "pending", "head", "warm_basis",
              "n_updates"):
        np.testing.assert_array_equal(np.asarray(getattr(s0, f)),
                                      np.asarray(getattr(s1, f)), f)


def test_chaos_rollout_accounting_and_makespan_bound():
    periods = 8
    cfg = _config(8, horizon=periods + 2)
    base = E.EngineParams.from_config(cfg, horizon=periods + 2)
    params = base.with_faults(FaultModel.make(**_HARSH), fault_seed=11)
    assert params.chaos
    _, m = E.rollout(E.init_state(params), params, periods)
    n_off = np.asarray(m.n_offload_samples)
    # admitted == completed + fallback + dropped, every period
    np.testing.assert_array_equal(
        n_off, np.asarray(m.n_offload_ok) + np.asarray(m.n_fallback_local)
        + np.asarray(m.n_dropped))
    # the ladder actually fired under a harsh model
    assert int(np.asarray(m.n_retries).sum()) \
        + int(np.asarray(m.n_fallback_local).sum()) \
        + int(np.asarray(m.n_dropped).sum()) > 0
    # realized makespan respects 2T + backoff cap + one retransmission
    # of the worst admitted per-device demand at the worst link factor
    T = float(np.asarray(base.T))
    demand_cap = float(np.asarray(params.p_es).max()) * base.batch_max
    worst_link = 1.0 + float(params.faults.link_degrade_mag)
    bound = 2.0 * T + float(params.faults.backoff_cap) \
        + demand_cap * worst_link
    assert (np.asarray(m.realized_makespan) <= bound + 1e-9).all()
    # arming chaos must not perturb the arrival trajectory
    _, m0 = E.rollout(E.init_state(base), base, periods)
    for f in ("n_jobs", "backlog", "n_outage"):
        np.testing.assert_array_equal(np.asarray(getattr(m, f)),
                                      np.asarray(getattr(m0, f)), f)


def test_chaos_deterministic_and_seed_sensitive():
    periods = 5
    cfg = _config(6, horizon=periods + 2)
    fm = FaultModel.make(**_HARSH)
    p1 = E.EngineParams.from_config(cfg, horizon=periods + 2) \
        .with_faults(fm, fault_seed=1)
    _, a = E.rollout(E.init_state(p1), p1, periods)
    _, b = E.rollout(E.init_state(p1), p1, periods)
    for f in LADDER_INTS + ("total_accuracy", "realized_makespan"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), f)
    p2 = p1.with_faults(fm, fault_seed=2)
    _, c = E.rollout(E.init_state(p2), p2, periods)
    assert any(not np.array_equal(np.asarray(getattr(a, f)),
                                  np.asarray(getattr(c, f)))
               for f in LADDER_INTS)


def test_fleet_run_matches_rollout_under_chaos():
    """The delegated Python-loop FleetEngine replays the same folded
    fault stream as the scanned rollout — ladder counters bit-equal."""
    periods = 6
    cfg = _config(6, horizon=periods + 2,
                  faults=FaultModel.make(**_HARSH), fault_seed=4)
    eng = FleetEngine.from_config(cfg)
    assert eng._v2_params is not None and eng._v2_params.chaos
    params = E.EngineParams.from_config(cfg, horizon=periods + 2)
    _, metrics = E.rollout(E.init_state(params), params, periods)
    stats = eng.run(periods)
    assert int(np.asarray(metrics.n_dropped).sum()) \
        + int(np.asarray(metrics.n_fallback_local).sum()) > 0
    for i, s in enumerate(stats):
        for f in LADDER_INTS + ("n_jobs", "n_violations", "backlog"):
            assert int(np.asarray(getattr(metrics, f))[i]) == \
                getattr(s, f), (i, f)
        for f in ("total_accuracy", "realized_makespan"):
            assert float(np.asarray(getattr(metrics, f))[i]) == \
                getattr(s, f), (i, f)


def test_fleet_faults_require_delegation():
    cfg = _config(4, horizon=4, faults=FaultModel.make(loss_rate=0.1))
    with pytest.raises(ValueError, match="delegation"):
        FleetEngine.from_config(
            FleetConfig(**{**cfg.__dict__, "backend": "numpy"}))
    # a null model on a host-path engine is fine (chaos disarmed)
    host = FleetEngine.from_config(
        FleetConfig(**{**cfg.__dict__, "backend": "numpy",
                       "faults": FaultModel.none()}))
    assert host._v2_params is None
    host.run_period()


def test_from_fleet_rejects_negative_max_retries():
    cfg = _config(4, horizon=4, max_retries=-1)
    with pytest.raises(ValueError, match="max_retries"):
        E.EngineParams.from_config(cfg, horizon=4)


# ---------------------------------------------------------------------------
# strict-mode unsolved periods: partial stats + warn path (satellite)
# ---------------------------------------------------------------------------
def test_unsolved_period_error_carries_partial_stats():
    cfg = _config(4, horizon=6, straggler_frac=0.0, outage_frac=0.0)
    eng = FleetEngine.from_config(cfg)
    assert eng._v2_params is not None
    eng.run_period()                       # period 0 solves fine
    eng._v2_params = dataclasses.replace(eng._v2_params, maxiter=1)
    with pytest.raises(UnsolvedPeriodError,
                       match="not solved to optimality") as ei:
        eng.run_period()
    err = ei.value
    assert err.period == 1
    assert err.n_unsolved > 0
    assert len(err.partial_stats) == 1     # the solved period survives
    assert err.partial_stats[0].period == 0


def test_unsolved_strict_warn_serves_greedy_fallback():
    cfg = _config(4, horizon=6, straggler_frac=0.0, outage_frac=0.0,
                  strict="warn")
    eng = FleetEngine.from_config(cfg)
    eng._v2_params = dataclasses.replace(eng._v2_params, maxiter=1)
    with pytest.warns(RuntimeWarning, match="greedy local-only fallback"):
        stats = eng.run(3)
    assert len(stats) == 3                 # the run completes
    assert sum(s.n_jobs for s in stats) > 0
    with pytest.raises(ValueError, match="strict"):
        FleetEngine.from_config(
            FleetConfig(**{**cfg.__dict__, "strict": "loose"}))


def test_unsolved_lanes_recovered_not_garbage():
    """Under maxiter=1 every lane goes unsolved; the greedy local-only
    recovery must still produce sane metrics: nonnegative accuracy, no
    offloading from unsolved lanes beyond the LP's said-so, and the
    accounting identity intact."""
    periods = 3
    cfg = _config(4, horizon=periods + 2, straggler_frac=0.0,
                  outage_frac=0.0)
    params = dataclasses.replace(
        E.EngineParams.from_config(cfg, horizon=periods + 2), maxiter=1)
    _, m = E.rollout(E.init_state(params), params, periods)
    assert int(np.asarray(m.n_unsolved).sum()) > 0
    assert (np.asarray(m.total_accuracy) >= 0).all()
    np.testing.assert_array_equal(
        np.asarray(m.n_offload_samples),
        np.asarray(m.n_offload_ok) + np.asarray(m.n_fallback_local)
        + np.asarray(m.n_dropped))


# ---------------------------------------------------------------------------
# sharded chaos parity (subprocess — XLA flag must precede jax init)
# ---------------------------------------------------------------------------
def test_sharded_chaos_rollout_matches_unsharded_subprocess():
    env = dict(os.environ)
    env.update({
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "SHARD_SMOKE_DEVICES": "16", "SHARD_SMOKE_SHARDS": "8",
        "SHARD_SMOKE_PERIODS": "4", "SHARD_SMOKE_CHAOS": "1",
        "PYTHONPATH": os.path.join(REPO, "src") + os.pathsep
        + env.get("PYTHONPATH", ""),
    })
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "smoke_shard_rollout.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "[shard-smoke] ok" in proc.stdout


# ---------------------------------------------------------------------------
# executor: per-sample status audit (satellite bugfix)
# ---------------------------------------------------------------------------
def _profile():
    return TierProfile(
        name="t", p_ed=np.array([[0.01, 0.04]]), p_es=np.array([0.35]),
        acc=np.array([0.4, 0.56, 0.77]), classes=[64])


def _applies(m=2, short_on=None):
    def make_ed(i):
        def f(jobs):
            out = [0.5] * len(jobs)
            return out[:-1] if i == short_on and len(out) else out
        return f
    return [make_ed(i) for i in range(m)], lambda jobs: [0.9] * len(jobs)


def test_executor_status_codes_cover_every_sample():
    prof = _profile()
    inst = prof.instance(np.full(12, 64), T=1.0)
    p = plan(inst)
    assert len(p.per_model[2]) > 0          # some jobs offloaded
    apply_ed, apply_es = _applies()
    rep = execute(p, apply_ed, apply_es, list(range(12)))
    assert rep.status is not None and len(rep.status) == 12
    assert rep.n_dropped == 0
    on_es = set(p.per_model[2].tolist())
    for j in range(12):
        want = EXEC_OK_ES if j in on_es else EXEC_OK_ED
        assert rep.status[j] == want, j
    # es_fail: bounced jobs land as FALLBACK_LOCAL, never dropped
    rep2 = execute(p, apply_ed, apply_es, list(range(12)), es_fail=True)
    assert rep2.replanned and rep2.n_dropped == 0
    assert (rep2.status[sorted(on_es)] == EXEC_FALLBACK_LOCAL).all()


def test_executor_short_output_is_audited_not_silently_lost():
    """Regression: an apply fn returning fewer results than jobs used to
    leave the tail samples silently missing from `results`; they now
    surface as EXEC_DROPPED with a nonzero audit count."""
    from repro.serving import replan_without_es
    prof = _profile()
    inst = prof.instance(np.full(8, 64), T=10.0)
    p = replan_without_es(inst)         # ED-only: the victim model runs
    victim = max((i for i, ids in p.per_model.items()
                  if i < 2 and len(ids)),
                 key=lambda i: len(p.per_model[i]))
    apply_ed, apply_es = _applies(short_on=victim)
    rep = execute(p, apply_ed, apply_es, list(range(8)))
    assert rep.n_dropped == 1
    assert len(rep.results) == 8 - 1
    assert (rep.status == EXEC_DROPPED).sum() == 1
