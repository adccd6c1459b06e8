"""Online hierarchical inference: `HIModel`/`HILearnerState` pytrees, the
calibrated confidence stream, the traced decision rules, engine/fleet
wiring (armed-null pin, replay == fold, run == rollout parity), the
regret accounting, and the registry's online solvers."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax

from repro import api
from repro.api import engine as E
from repro.core.hi import (HILearnerState, HIModel, _draw_uniforms,
                           presample_stream, sample_confidence,
                           validate_hi)
from repro.serving import FleetConfig, FleetEngine


def _config(n_devices=8, *, policy="amr2", seed=5, horizon=40, rate=9.0,
            n_servers=2, batch_max=8, **extra):
    return FleetConfig(n_devices=n_devices, T=1.2, n_servers=n_servers,
                       policy=policy, backend="jax", rate=rate,
                       batch_max=batch_max, horizon=horizon, seed=seed,
                       straggler_frac=0.25, outage_frac=0.1, **extra)


def _armed(params, rule="threshold", *, hm=None, **kw):
    hm = HIModel.make() if hm is None else hm
    return params.with_hi(hm, rule=rule, **kw)


def _theta_star(params):
    """(D,) clairvoyant threshold: clip(acc_es - beta, 0, 1)."""
    beta = float(np.asarray(params.hi.offload_cost))
    return np.clip(np.asarray(params.acc)[:, params.m] - beta, 0.0, 1.0)


# ---------------------------------------------------------------------------
# HIModel: construction, validation, pytree plumbing
# ---------------------------------------------------------------------------
def test_hi_model_none_is_null_and_make_validates():
    assert HIModel.none().is_null()
    assert not HIModel.make().is_null()
    with pytest.raises(ValueError, match="spread"):
        HIModel.make(spread=1.5)
    with pytest.raises(ValueError, match="offload_cost"):
        HIModel.make(offload_cost=1.0)
    with pytest.raises(ValueError, match="lr and tau"):
        HIModel.make(lr=0.0)
    with pytest.raises(ValueError, match="theta0"):
        HIModel.make(theta0=-0.1)
    with pytest.raises(ValueError, match="conf_trace"):
        HIModel.make(conf_trace=np.zeros((2, 4, 8)))
    # pytree round-trip keeps leaves bit-for-bit
    hm = HIModel.make(spread=[0.2, 0.9], theta0=0.4)
    leaves, tree = jax.tree_util.tree_flatten(hm)
    back = jax.tree_util.tree_unflatten(tree, leaves)
    for a, b in zip(leaves, jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_from_profiles_ranks_spread_by_latency():
    """Slower (higher mean-latency) classes must get the larger spreads,
    and the (D, c, m) stacked table reduces like the (c, m) one."""
    p_ed = np.array([[0.3, 0.2], [0.1, 0.05], [0.6, 0.5]])
    hm = HIModel.from_profiles(p_ed, spread_range=(0.2, 0.8))
    assert hm.spread.shape == (3,)
    order = np.argsort(p_ed.mean(axis=1))
    assert np.all(np.diff(hm.spread[order]) > 0)
    assert hm.spread.min() == 0.2 and hm.spread.max() == 0.8
    stacked = np.broadcast_to(p_ed, (5, 3, 2))
    np.testing.assert_array_equal(
        HIModel.from_profiles(stacked, spread_range=(0.2, 0.8)).spread,
        hm.spread)
    with pytest.raises(ValueError, match="spread_range"):
        HIModel.from_profiles(p_ed, spread_range=(0.9, 0.2))


def test_validate_hi_errors():
    hm = HIModel.make()
    kw = dict(n_devices=4, n_classes=3, n_models=2, stream="fold",
              n_arms=9, local_model=0)
    with pytest.raises(ValueError, match="unknown HI rule"):
        validate_hi(hm, rule="softmax", **kw)
    with pytest.raises(ValueError, match="unknown HI stream"):
        validate_hi(hm, rule="fixed", **{**kw, "stream": "mmap"})
    with pytest.raises(ValueError, match="n_arms"):
        validate_hi(hm, rule="ucb", **{**kw, "n_arms": 1})
    with pytest.raises(ValueError, match="local model"):
        validate_hi(hm, rule="fixed", **{**kw, "local_model": 2})
    with pytest.raises(ValueError, match="spread"):
        validate_hi(HIModel.make(spread=[0.5, 0.5]), rule="fixed", **kw)
    with pytest.raises(ValueError, match="theta0"):
        validate_hi(HIModel.make(theta0=[0.5, 0.5]), rule="fixed", **kw)
    with pytest.raises(ValueError, match="conf_trace"):
        validate_hi(hm, rule="fixed", **{**kw, "stream": "replay"})
    with pytest.raises(ValueError, match="batch_max"):
        validate_hi(HIModel.make(conf_trace=np.zeros((2, 4, 6, 3))),
                    rule="fixed", **{**kw, "stream": "replay"},
                    batch_max=8)


# ---------------------------------------------------------------------------
# the calibrated confidence stream
# ---------------------------------------------------------------------------
def test_confidence_is_mean_preserving_and_calibrated():
    """E[conf] == acc_local and P(correct | conf) == conf (binned), for
    both tight and wide spreads; ES outcomes are Bernoulli(acc_es)."""
    from repro.core.types import x64_scope
    D, n = 4, 20_000
    acc_local = np.array([0.55, 0.7, 0.8, 0.92])
    acc_es = np.array([0.9, 0.85, 0.95, 0.97])
    hm = HIModel.make(spread=0.8)
    ci = np.zeros((D, n), np.int32)
    with x64_scope():
        conf, cl, ces = sample_confidence(
            jax.random.PRNGKey(3), hm, acc_local, acc_es, ci)
    conf, cl, ces = (np.asarray(x) for x in (conf, cl, ces))
    np.testing.assert_allclose(conf.mean(axis=1), acc_local, atol=0.01)
    np.testing.assert_allclose(cl.mean(axis=1), acc_local, atol=0.02)
    np.testing.assert_allclose(ces.mean(axis=1), acc_es, atol=0.02)
    # calibration: within a confidence bin, the local hit-rate is the bin
    for d in range(D):
        for lo in (0.3, 0.5, 0.7):
            sel = (conf[d] >= lo) & (conf[d] < lo + 0.2)
            if sel.sum() > 500:
                assert abs(cl[d, sel].mean() - conf[d, sel].mean()) < 0.05
    # spread really spreads: wider spread -> wider confidence swings
    with x64_scope():
        conf0, _, _ = sample_confidence(
            jax.random.PRNGKey(3), HIModel.make(spread=0.1), acc_local,
            acc_es, ci)
    assert np.std(np.asarray(conf0)) < np.std(conf)


def test_draw_uniforms_gid_offset_matches_global_slice():
    """A shard drawing with its global-id offset reproduces exactly its
    rows of the full-fleet draw — the 8-shard-safe fold contract."""
    from repro.core.types import x64_scope
    D, n, S = 4, 6, 3
    with x64_scope():
        key = jax.random.PRNGKey(11)
        full = np.asarray(_draw_uniforms(key, S * D, n))
        for s in range(S):
            shard = np.asarray(
                _draw_uniforms(key, D, n, gid_offset=s * D))
            np.testing.assert_array_equal(shard, full[s * D:(s + 1) * D])


def test_presample_stream_replays_the_fold_keyed_draws():
    """`presample_stream` must reproduce the armed engine's per-period
    uniforms bit for bit (fold seed by t, split off the confidence key,
    fold global device ids)."""
    from repro.core.types import x64_scope
    tr = presample_stream(7, 3, 5, periods=4)
    assert tr.shape == (4, 3, 5, 3)
    with x64_scope():
        base = jax.random.PRNGKey(7)
        for t in range(4):
            kc, _ = jax.random.split(jax.random.fold_in(base, t))
            np.testing.assert_array_equal(
                tr[t], np.asarray(_draw_uniforms(kc, 3, 5)))


# ---------------------------------------------------------------------------
# arming / interplay validators
# ---------------------------------------------------------------------------
def test_with_hi_validates_and_disarms():
    params = E.EngineParams.from_config(_config(), horizon=6)
    assert not params.hi_armed
    armed = _armed(params)
    assert armed.hi_armed and armed.hi_rule == "threshold"
    off = armed.with_hi(None)
    assert not off.hi_armed and off.hi.is_null()
    with pytest.raises(ValueError, match="unknown HI rule"):
        _armed(params, rule="softmax")
    with pytest.raises(ValueError, match="local model"):
        _armed(params, local_model=params.m)


def test_hi_and_other_subsystems_are_mutually_exclusive():
    from repro.core.faults import FaultModel
    from repro.core.mobility import MobilityModel
    params = E.EngineParams.from_config(_config(), horizon=8)
    armed = _armed(params)
    fm = FaultModel.make(es_crash_prob=0.1)
    trace = np.zeros((8, params.n_devices, 2))
    mob = MobilityModel.make(cell_xy=np.zeros((1, 2)), trace=trace)
    # arming HI second
    with pytest.raises(ValueError, match="chaos disarmed"):
        _armed(params.with_faults(fm, fault_seed=1))
    with pytest.raises(ValueError, match="mobility off"):
        _armed(params.with_mobility(mob))
    with pytest.raises(ValueError, match="differentiable"):
        _armed(params.with_differentiable())
    # arming HI first
    with pytest.raises(ValueError, match="HI disarmed"):
        armed.with_faults(fm, fault_seed=1)
    with pytest.raises(ValueError, match="HI disarmed"):
        armed.with_mobility(mob)
    with pytest.raises(ValueError, match="HI disarmed"):
        armed.with_differentiable()


def test_sharded_entry_points_reject_armed_hi():
    params = E.EngineParams.from_config(_config(), horizon=6)
    armed = _armed(params)
    state = E.init_state(armed)
    for call in (lambda: E.shard(state, armed, None),
                 lambda: E.step_sharded(state, armed, None),
                 lambda: E.rollout_sharded(state, armed, 2, None)):
        with pytest.raises(ValueError, match="sharded entry points"):
            call()


# ---------------------------------------------------------------------------
# engine wiring: the armed-null pin and the arrival-stream invariant
# ---------------------------------------------------------------------------
def test_hi_off_rollout_is_bitwise_pinned():
    """Disarming via `with_hi(None)` (a round-trip through arming) must
    reproduce the default rollout BIT for BIT on every metric and state
    leaf: the subsystem is invisible while ``hi_rule == "off"``."""
    periods = 10
    params = E.EngineParams.from_config(_config(), horizon=periods + 2)
    round_trip = _armed(params).with_hi(None)
    s0, m0 = E.rollout(E.init_state(params), params, periods)
    s1, m1 = E.rollout(E.init_state(round_trip), round_trip, periods)
    for f in E._METRIC_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(m0, f)),
                                      np.asarray(getattr(m1, f)), f)
    for f in E._STATE_FIELDS:
        for a, b in zip(jax.tree.leaves(getattr(s0, f)),
                        jax.tree.leaves(getattr(s1, f))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), f)
    # the HI counters are exact zeros while disarmed
    for f in ("n_hi_offloaded", "n_hi_local_final", "hi_regret"):
        assert np.asarray(getattr(m0, f)).sum() == 0, f


def test_arming_hi_leaves_arrivals_untouched():
    """The confidence stream folds its own seed: arming must not perturb
    the arrival PRNG, backlog, or per-period job counts."""
    periods = 10
    params = E.EngineParams.from_config(_config(), horizon=periods + 2)
    armed = _armed(params)
    s0, m0 = E.rollout(E.init_state(params), params, periods)
    s1, m1 = E.rollout(E.init_state(armed), armed, periods)
    np.testing.assert_array_equal(np.asarray(s0.key), np.asarray(s1.key))
    np.testing.assert_array_equal(np.asarray(s0.head),
                                  np.asarray(s1.head))
    np.testing.assert_array_equal(np.asarray(m0.n_jobs),
                                  np.asarray(m1.n_jobs))


def test_armed_rollout_is_deterministic_and_seed_sensitive():
    periods = 8
    params = E.EngineParams.from_config(_config(), horizon=periods + 2)
    armed = _armed(params, hi_seed=3)
    _, m0 = E.rollout(E.init_state(armed), armed, periods)
    _, m1 = E.rollout(E.init_state(armed), armed, periods)
    for f in ("total_accuracy", "n_hi_offloaded", "hi_regret"):
        np.testing.assert_array_equal(np.asarray(getattr(m0, f)),
                                      np.asarray(getattr(m1, f)), f)
    other = _armed(params, hi_seed=4)
    _, m2 = E.rollout(E.init_state(other), other, periods)
    assert not np.array_equal(np.asarray(m0.hi_regret),
                              np.asarray(m2.hi_regret))


def test_replay_stream_equals_fold_stream():
    """`presample_stream` fed back via ``stream="replay"`` pins the
    replayed rollout bitwise to the fold-keyed one."""
    periods = 8
    cfg = _config()
    params = E.EngineParams.from_config(cfg, horizon=periods + 2)
    fold = _armed(params, hi_seed=5)
    tr = presample_stream(5, params.n_devices, params.batch_max,
                          periods + 2)
    replay = params.with_hi(HIModel.make(conf_trace=tr), rule="threshold",
                            stream="replay", hi_seed=5)
    sf, mf = E.rollout(E.init_state(fold), fold, periods)
    sr, mr = E.rollout(E.init_state(replay), replay, periods)
    for f in E._METRIC_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(mf, f)),
                                      np.asarray(getattr(mr, f)), f)
    np.testing.assert_array_equal(np.asarray(sf.hi.theta),
                                  np.asarray(sr.hi.theta))


@pytest.mark.parametrize("rule", ["fixed", "threshold", "ucb", "exp3"])
def test_accounting_identity_every_period(rule):
    """Every admitted sample is served exactly once: n_hi_offloaded +
    n_hi_local_final == n_jobs, per period, for every rule."""
    periods = 10
    params = E.EngineParams.from_config(_config(), horizon=periods + 2)
    armed = _armed(params, rule=rule)
    _, m = E.rollout(E.init_state(armed), armed, periods)
    off = np.asarray(m.n_hi_offloaded)
    loc = np.asarray(m.n_hi_local_final)
    np.testing.assert_array_equal(off + loc, np.asarray(m.n_jobs))
    assert np.asarray(m.hi_regret).min() >= 0.0
    # cumulative regret is nondecreasing over the horizon
    assert np.all(np.diff(np.asarray(m.hi_regret)) >= -1e-12)


def test_run_matches_rollout_bitwise_with_hi():
    """The Python-loop `FleetEngine.run` and the scanned `rollout` follow
    the same armed trajectory bit for bit — counters, accuracy, regret,
    and the learner state."""
    periods = 12
    hm = HIModel.make()
    cfg = _config(hi=hm, hi_rule="threshold", hi_seed=2)
    eng = FleetEngine.from_config(cfg)
    assert eng._v2_params is not None
    params = E.EngineParams.from_config(cfg, horizon=40).with_hi(
        hm, rule="threshold", hi_seed=2)
    state, metrics = E.rollout(E.init_state(params), params, periods)
    stats = eng.run(periods)
    for i, s in enumerate(stats):
        assert int(np.asarray(metrics.n_hi_offloaded)[i]) == \
            s.n_hi_offloaded, i
        assert int(np.asarray(metrics.n_hi_local_final)[i]) == \
            s.n_hi_local_final, i
        assert float(np.asarray(metrics.hi_regret)[i]) == s.hi_regret, i
        assert float(np.asarray(metrics.total_accuracy)[i]) == \
            s.total_accuracy, i
    np.testing.assert_array_equal(np.asarray(state.hi.theta),
                                  np.asarray(eng._v2_hi_state.theta))


# ---------------------------------------------------------------------------
# learning: the clairvoyant floor, convergence, and the bandit baselines
# ---------------------------------------------------------------------------
def test_clairvoyant_fixed_threshold_has_zero_regret():
    """rule="fixed" with per-device theta0 = clip(acc_es - beta, 0, 1)
    IS the clairvoyant: its pseudo-regret is exactly 0.0."""
    periods = 12
    params = E.EngineParams.from_config(_config(), horizon=periods + 2)
    beta = 0.15
    theta_star = np.clip(
        np.asarray(params.acc)[:, params.m] - beta, 0.0, 1.0)
    armed = params.with_hi(HIModel.make(theta0=theta_star,
                                        offload_cost=beta), rule="fixed")
    _, m = E.rollout(E.init_state(armed), armed, periods)
    assert float(np.asarray(m.hi_regret)[-1]) == 0.0


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2**16))
def test_threshold_learner_converges_sublinearly(hi_seed):
    """The OGD learner on a replayed stream: the final threshold lands
    near theta* = acc_es - beta and the cumulative regret is sublinear
    (second-half increment < first-half increment)."""
    periods = 48
    params = E.EngineParams.from_config(_config(), horizon=periods + 2)
    armed = _armed(params, hi_seed=hi_seed)
    state, m = E.rollout(E.init_state(armed), armed, periods)
    theta_star = _theta_star(armed)
    err = np.abs(np.asarray(state.hi.theta) - theta_star)
    assert err.mean() < 0.1, (np.asarray(state.hi.theta), theta_star)
    reg = np.asarray(m.hi_regret)
    first = reg[periods // 2 - 1] - reg[0]
    second = reg[-1] - reg[periods // 2 - 1]
    assert second < first, (first, second)


def test_threshold_learner_beats_miscalibrated_fixed():
    """At a 32-period horizon the learner's cumulative regret undercuts a
    fixed rule whose threshold starts equally wrong (theta0 = 0.5 shared;
    theta* sits near 0.6 for these fleets)."""
    periods = 32
    params = E.EngineParams.from_config(_config(), horizon=periods + 2)
    fixed = _armed(params, rule="fixed")
    learn = _armed(params, rule="threshold")
    _, mf = E.rollout(E.init_state(fixed), fixed, periods)
    _, ml = E.rollout(E.init_state(learn), learn, periods)
    assert float(np.asarray(ml.hi_regret)[-1]) < \
        float(np.asarray(mf.hi_regret)[-1])


@pytest.mark.parametrize("rule", ["ucb", "exp3"])
def test_bandit_rules_learn_and_stay_on_the_grid(rule):
    """Bandits pull arms from `arm_grid`, book one pull per device per
    period, and accrue regret no worse than linear-in-periods times the
    worst single-period regret."""
    periods = 16
    params = E.EngineParams.from_config(_config(), horizon=periods + 2)
    armed = _armed(params, rule=rule, n_arms=5)
    state, m = E.rollout(E.init_state(armed), armed, periods)
    cnt = np.asarray(state.hi.arms_cnt)
    assert cnt.shape == (params.n_devices, 5)
    np.testing.assert_allclose(cnt.sum(axis=1), periods)
    grid = np.linspace(1.0 / 6.0, 5.0 / 6.0, 5)
    on_grid = np.isclose(np.asarray(state.hi.theta)[:, None],
                         np.concatenate([grid, [0.5]])[None, :])
    assert on_grid.any(axis=1).all()
    assert float(np.asarray(m.hi_regret)[-1]) > 0.0


# ---------------------------------------------------------------------------
# the registry's online solvers (the host mirror of `hi_period`)
# ---------------------------------------------------------------------------
def _host_fleet(rng, D=4, n=8, M=3):
    p_ed = rng.uniform(0.05, 0.2, (D, n, M)).cumsum(axis=2)[:, :, ::-1]
    return api.FleetProblem(
        p_ed=p_ed.copy(), p_es=rng.uniform(0.01, 0.05, (D, n)),
        acc=np.sort(rng.uniform(0.5, 0.95, (D, M + 1)), axis=1),
        T=np.ones(D), real_mask=np.ones((D, n), bool))


def test_online_solvers_registered_with_capability():
    infos = api.solvers()
    for name in ("hi_threshold", "hi_bandit"):
        assert infos[name].online and infos[name].batched
    assert not infos["amr2"].online


def test_hi_threshold_solver_decides_and_learns():
    rng = np.random.default_rng(0)
    fleet = _host_fleet(rng)
    conf = rng.uniform(0.3, 0.95, (4, 8))
    hm = HIModel.make()
    sol = api.solve(fleet, policy="hi_threshold", confidence=conf, hi=hm)
    assign = np.asarray(sol.assignment)
    # decide-only: threshold rule at theta0 gates on conf < 0.5
    np.testing.assert_array_equal(assign == fleet.m, conf < 0.5)
    np.testing.assert_array_equal(np.asarray(sol.hi_theta), 0.5)
    # feeding back observations advances the learner state
    st0 = HILearnerState.init(4, 9, hm.theta0)
    sol2 = api.solve(fleet, policy="hi_threshold", confidence=conf, hi=hm,
                     state=st0,
                     observed_local=(rng.random((4, 8)) < 0.7),
                     observed_es=(rng.random((4, 8)) < 0.9))
    assert not np.allclose(np.asarray(sol2.hi_state.theta),
                           np.asarray(st0.theta))


def test_hi_bandit_solver_rules_and_validation():
    rng = np.random.default_rng(1)
    fleet = _host_fleet(rng)
    conf = rng.uniform(0.3, 0.95, (4, 8))
    hm = HIModel.make()
    for rule in ("ucb", "exp3"):
        sol = api.solve(fleet, policy="hi_bandit", confidence=conf,
                        hi=hm, rule=rule)
        theta = np.asarray(sol.hi_theta)
        grid = np.linspace(0.1, 0.9, 9)
        assert np.isclose(theta[:, None], grid[None, :]).any(axis=1).all(), \
            rule
        assign = np.asarray(sol.assignment)
        np.testing.assert_array_equal(assign == fleet.m,
                                      conf < theta[:, None])
    with pytest.raises(ValueError, match="ucb.*exp3"):
        api.solve(fleet, policy="hi_bandit", confidence=conf, hi=hm,
                  rule="thompson")
