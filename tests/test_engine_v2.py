"""Pure-functional engine (`repro.api.engine`): EngineState pytree,
`step`/`rollout` scan semantics, sharding, admission determinism, and the
queue replay/edge-case regressions."""
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.api import engine as E
from repro.serving import (DeviceSpec, EdgeServerPool, FleetConfig,
                           FleetEngine, RequestQueue, TierProfile)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(n_devices=8, *, policy="amr2", seed=5, horizon=40, rate=9.0,
            n_servers=2, straggler_frac=0.25, outage_frac=0.1,
            batch_max=8):
    return FleetConfig(n_devices=n_devices, T=1.2, n_servers=n_servers,
                       policy=policy, backend="jax", rate=rate,
                       batch_max=batch_max, horizon=horizon, seed=seed,
                       straggler_frac=straggler_frac,
                       outage_frac=outage_frac)


INT_FIELDS = ("n_jobs", "n_violations", "n_offloading", "n_backpressured",
              "n_outage", "n_straggler_updates", "backlog")
FLOAT_FIELDS = ("total_accuracy", "mean_job_accuracy", "worst_violation",
                "es_utilization")


def _assert_matches_stats(metrics, stats, *, exact_floats=True):
    """Stacked `PeriodMetrics` vs a list of `FleetPeriodStats`."""
    assert int(np.asarray(metrics.period)[-1]) == stats[-1].period
    for i, s in enumerate(stats):
        for f in INT_FIELDS:
            assert int(np.asarray(getattr(metrics, f))[i]) == \
                getattr(s, f), (i, f)
        for f in FLOAT_FIELDS:
            a = float(np.asarray(getattr(metrics, f))[i])
            b = getattr(s, f)
            if exact_floats:
                assert a == b, (i, f, a, b)
            else:
                assert a == pytest.approx(b, rel=1e-9, abs=1e-9), (i, f)


# ---------------------------------------------------------------------------
# rollout (scan) vs the Python-loop engine: the acceptance-criteria pin
# ---------------------------------------------------------------------------
def test_rollout_bitwise_matches_python_loop_engine_32_periods():
    """`rollout` (one lax.scan) over >= 32 periods must be BIT-identical
    to `FleetEngine.run(periods)` — the per-period Python loop — on the
    replayed arrival trace, including drift/outage schedules, straggler
    audits, and the warm-basis trajectory."""
    periods = 36
    cfg = _config(8, seed=0, horizon=periods + 2)
    eng = FleetEngine.from_config(cfg)
    assert eng._v2_params is not None      # jax/amr2: delegation active
    params = E.EngineParams.from_config(cfg, horizon=periods + 2)
    state, metrics = E.rollout(E.init_state(params), params, periods)
    stats = eng.run(periods)
    _assert_matches_stats(metrics, stats, exact_floats=True)
    # warm-basis and belief trajectories landed in the same place
    np.testing.assert_array_equal(np.asarray(state.warm_basis),
                                  np.asarray(eng._groups[0].warm_basis))
    beliefs = np.stack([d.profile.p_ed for d in eng.devices])
    np.testing.assert_array_equal(np.asarray(state.p_ed),
                                  beliefs[:, eng._v2_lut, :])
    assert int(np.asarray(metrics.n_backpressured).sum()) > 0
    assert int(np.asarray(metrics.n_straggler_updates).sum()) > 0


def test_step_sequence_equals_rollout_scan():
    """Scanning `step` and looping jitted `step` is the same computation:
    the final EngineState pytrees must be exactly equal leaf-for-leaf."""
    cfg = _config(6, horizon=12)
    params = E.EngineParams.from_config(cfg, horizon=12)
    s_loop = E.init_state(params)
    for _ in range(8):
        s_loop, _ = E.step(s_loop, params)
    s_scan, _ = E.rollout(E.init_state(params), params, 8)
    for f in ("period", "key", "p_ed", "pending", "head", "warm_basis",
              "n_updates"):
        np.testing.assert_array_equal(np.asarray(getattr(s_loop, f)),
                                      np.asarray(getattr(s_scan, f)), f)


def test_rollout_matches_reference_loop():
    """rollout vs the PR-1 per-device `run_period_reference` oracle
    (numpy scalar solvers).  Drift-free fleet: the EMA audit's feedback
    loop converges exactly onto its own threshold, where numpy-vs-XLA
    summation-order ulps can flip the update decision — everything else
    (queue, admission, planning, outage, backpressure, backlog) is
    covered."""
    periods = 5
    cfg = _config(6, horizon=periods + 2, straggler_frac=0.0)
    params = E.EngineParams.from_config(cfg, horizon=periods + 2)
    _, metrics = E.rollout(E.init_state(params), params, periods)
    ref = FleetEngine.from_config(
        FleetConfig(**{**cfg.__dict__, "backend": "numpy",
                       "policy": "amr2"}))
    stats = [ref.run_period_reference() for _ in range(periods)]
    _assert_matches_stats(metrics, stats, exact_floats=False)


@given(seed=st.integers(0, 2**16), n_devices=st.integers(2, 6),
       rate=st.floats(2.0, 14.0), n_servers=st.integers(1, 3))
@settings(max_examples=5, deadline=None)
def test_rollout_trajectory_parity_hypothesis(seed, n_devices, rate,
                                              n_servers):
    """Property pin: for random fleets/traffic, `rollout` (scan) ==
    `FleetEngine.run` (Python loop, delegated core) bit-for-bit AND ==
    `run_period_reference` (sequential numpy oracle) to float tolerance
    on accuracy / makespan-violation / backlog / warm-basis
    trajectories."""
    periods = 4
    cfg = _config(n_devices, seed=seed, horizon=periods + 2, rate=rate,
                  n_servers=n_servers, straggler_frac=0.0)
    params = E.EngineParams.from_config(cfg, horizon=periods + 2)
    state, metrics = E.rollout(E.init_state(params), params, periods)

    eng = FleetEngine.from_config(cfg)
    stats = eng.run(periods)
    _assert_matches_stats(metrics, stats, exact_floats=True)
    np.testing.assert_array_equal(np.asarray(state.warm_basis),
                                  np.asarray(eng._groups[0].warm_basis))

    ref = FleetEngine.from_config(
        FleetConfig(**{**cfg.__dict__, "backend": "numpy"}))
    ref_stats = [ref.run_period_reference() for _ in range(periods)]
    _assert_matches_stats(metrics, ref_stats, exact_floats=False)


def test_dual_policy_rollout_runs_and_delegates():
    cfg = _config(6, policy="dual", horizon=8, straggler_frac=0.0)
    eng = FleetEngine.from_config(cfg)
    assert eng._v2_params is not None
    params = E.EngineParams.from_config(cfg, horizon=8)
    state, metrics = E.rollout(E.init_state(params), params, 6)
    stats = eng.run(6)
    _assert_matches_stats(metrics, stats, exact_floats=True)
    # dual carries no basis: the warm state stays cold
    assert (np.asarray(state.warm_basis) == -1).all()


# ---------------------------------------------------------------------------
# array-native Poisson arrivals (jax.random)
# ---------------------------------------------------------------------------
def test_poisson_mode_conserves_jobs():
    cfg = _config(5, horizon=4, straggler_frac=0.0, rate=6.0)
    params = E.EngineParams.from_config(cfg, horizon=4, arrivals="poisson")
    state, metrics = E.rollout(E.init_state(params), params, 10)
    jobs = np.asarray(metrics.n_jobs)
    backlog = np.asarray(metrics.backlog)
    assert (jobs >= 0).all() and (backlog >= 0).all()
    assert jobs.sum() > 0
    # released jobs never exceed the per-device planning window
    assert jobs.max() <= params.n_devices * params.batch_max
    # different seeds draw different traffic
    s2, m2 = E.rollout(E.init_state(params, seed=1), params, 10)
    assert not np.array_equal(np.asarray(m2.n_jobs), jobs)


def test_poisson_zero_rate_means_zero_jobs():
    cfg = _config(4, horizon=4, rate=0.0, straggler_frac=0.0)
    params = E.EngineParams.from_config(cfg, horizon=4, arrivals="poisson")
    _, metrics = E.rollout(E.init_state(params), params, 6)
    assert int(np.asarray(metrics.n_jobs).sum()) == 0
    assert int(np.asarray(metrics.backlog)[-1]) == 0


def test_unsorted_queue_classes_price_correctly():
    """Regression: the delegated run_period maps arrival values to class
    indices via an argsort-indirected searchsorted, so an UNSORTED queue
    class table prices identically to the host pipeline (a raw
    searchsorted on the unsorted table silently mis-priced every job)."""
    prof = TierProfile(name="t", p_ed=np.array([[0.02, 0.08],
                                                [0.01, 0.04]]),
                       p_es=np.array([0.5, 0.35]),
                       acc=np.array([0.4, 0.56, 0.77]), classes=[64, 512])

    def build(delegate):
        specs = [DeviceSpec(profile=prof) for _ in range(3)]
        q = RequestQueue(3, (512, 64), rate=6.0, batch_max=5, seed=2)
        return FleetEngine(specs, q, n_servers=1, T=0.5, backend="jax",
                           policy="amr2", delegate=delegate)

    v2, host = build(True), build(False)
    assert v2._v2_params is not None and host._v2_params is None
    for period in range(3):
        sv, sh = v2.run_period(), host.run_period()
        assert sv.n_jobs == sh.n_jobs
        assert sv.total_accuracy == pytest.approx(sh.total_accuracy,
                                                  abs=1e-9), period


def test_unsolved_plans_are_surfaced_not_silently_rounded():
    """PR-4 strict semantics survive the delegation: an LP that hits its
    iteration cap raises from run_period, and rollout reports it in
    PeriodMetrics.n_unsolved instead of serving best-effort roundings
    silently."""
    import dataclasses

    cfg = _config(4, horizon=4, straggler_frac=0.0, outage_frac=0.0)
    eng = FleetEngine.from_config(cfg)
    assert eng._v2_params is not None
    eng._v2_params = dataclasses.replace(eng._v2_params, maxiter=1)
    with pytest.raises(RuntimeError, match="not solved to optimality"):
        eng.run_period()

    params = dataclasses.replace(
        E.EngineParams.from_config(cfg, horizon=4), maxiter=1)
    _, metrics = E.rollout(E.init_state(params), params, 3)
    assert int(np.asarray(metrics.n_unsolved).sum()) > 0
    # generous default cap: a normal config reports zero unsolved
    ok = E.EngineParams.from_config(cfg, horizon=4)
    _, m2 = E.rollout(E.init_state(ok), ok, 3)
    assert int(np.asarray(m2.n_unsolved).sum()) == 0


# ---------------------------------------------------------------------------
# params validation + replay-horizon guard
# ---------------------------------------------------------------------------
def test_replay_horizon_guard():
    cfg = _config(4, horizon=6)
    params = E.EngineParams.from_config(cfg, horizon=6)
    state = E.init_state(params)
    with pytest.raises(ValueError, match="presample a longer horizon"):
        E.rollout(state, params, 7)
    state, _ = E.rollout(state, params, 6)      # exactly the horizon: fine
    with pytest.raises(ValueError, match="presample a longer horizon"):
        E.step(state, params)


def test_params_reject_untraceable_policy_and_mixed_shapes():
    cfg = _config(4)
    with pytest.raises(ValueError, match="no traceable batched path"):
        E.EngineParams.from_config(cfg, horizon=4, policy="amdp")
    # "auto" resolves to the LP path instead of raising
    assert E.EngineParams.from_config(cfg, horizon=4,
                                      policy="auto").policy == "amr2"
    prof_a = TierProfile(name="a", p_ed=np.array([[0.01, 0.04]]),
                         p_es=np.array([0.3]),
                         acc=np.array([0.4, 0.5, 0.7]), classes=[64])
    prof_b = TierProfile(name="b", p_ed=np.array([[0.01, 0.04],
                                                  [0.02, 0.05]]),
                         p_es=np.array([0.3, 0.4]),
                         acc=np.array([0.4, 0.5, 0.7]), classes=[64, 128])
    queue = RequestQueue(2, (64,), rate=4.0, batch_max=4, seed=0)
    with pytest.raises(ValueError, match="single shape group"):
        E.EngineParams.from_fleet(
            [DeviceSpec(profile=prof_a), DeviceSpec(profile=prof_b)],
            queue, T=0.5)
    # unsorted profile class tables would silently mis-price via the
    # searchsorted re-indexing: rejected up front (FleetEngine's guard)
    unsorted = TierProfile(name="u", p_ed=np.array([[0.01, 0.04],
                                                    [0.02, 0.05]]),
                           p_es=np.array([0.3, 0.4]),
                           acc=np.array([0.4, 0.5, 0.7]),
                           classes=[128, 64])
    q2 = RequestQueue(1, (64,), rate=4.0, batch_max=4, seed=0)
    with pytest.raises(ValueError, match="strictly ascending"):
        E.EngineParams.from_fleet([DeviceSpec(profile=unsorted)], q2,
                                  T=0.5)


# ---------------------------------------------------------------------------
# queue replay + trace edge cases (satellite regressions)
# ---------------------------------------------------------------------------
def test_presample_replays_poll_exactly():
    def build():
        return RequestQueue(3, (128, 512), rate=7.0, batch_max=5, seed=9)
    counts, stream = build().presample(6)
    q = build()
    heads = np.zeros(3, dtype=int)
    classes = np.asarray(q.classes)
    for t in range(6):
        released = q.poll(t)
        for d, r in enumerate(released):
            got = classes[stream[d, heads[d]:heads[d] + len(r)]]
            np.testing.assert_array_equal(got, r, f"period {t} device {d}")
            heads[d] += len(r)
    assert counts.sum() == q.total_arrived


def test_empty_trace_yields_empty_rows_not_skipped_devices():
    """Regression: an EMPTY trace (0 periods) or all-zero arrival rows
    must produce empty per-device arrays / empty `real_mask` rows — every
    engine path runs, nothing crashes, nothing is skipped."""
    empty = RequestQueue(3, (64,), trace=np.zeros((0, 3), dtype=int),
                         batch_max=4, seed=0)
    released = empty.poll(0)
    assert len(released) == 3 and all(len(r) == 0 for r in released)
    counts, stream = empty.presample(4)
    assert counts.shape == (4, 3) and counts.sum() == 0

    prof = TierProfile(name="t", p_ed=np.array([[0.01, 0.04]]),
                       p_es=np.array([0.35]),
                       acc=np.array([0.4, 0.56, 0.77]), classes=[64])
    specs = [DeviceSpec(profile=prof) for _ in range(3)]
    for backend in ("jax", "numpy"):
        q = RequestQueue(3, (64,), trace=np.zeros((0, 3), dtype=int),
                         batch_max=4, seed=0)
        eng = FleetEngine(specs, q, n_servers=1, T=0.5, backend=backend,
                          policy="amr2")
        s = eng.run_period()
        assert s.n_jobs == 0 and s.n_offloading == 0 and s.backlog == 0
    # the pure engine's B=0-arrivals periods: zero-count trace rows
    cfg = FleetConfig(n_devices=3, T=0.5, n_servers=1, policy="amr2",
                      batch_max=4, horizon=4, seed=0, devices=specs,
                      classes=(64,), trace=np.zeros((2, 3), dtype=int),
                      straggler_frac=0.0, outage_frac=0.0)
    params = E.EngineParams.from_config(cfg, horizon=4)
    _, metrics = E.rollout(E.init_state(params), params, 4)
    assert int(np.asarray(metrics.n_jobs).sum()) == 0
    assert (np.asarray(metrics.total_accuracy) == 0).all()


# ---------------------------------------------------------------------------
# ES-pool admission: determinism + vectorized parity (satellite)
# ---------------------------------------------------------------------------
def test_admit_is_insertion_order_invariant():
    """Regression: admission must depend only on (demand, device id) —
    never on how the caller's dict was assembled."""
    rng = np.random.default_rng(0)
    demands = {int(d): float(v) for d, v in
               enumerate(rng.uniform(0.1, 0.9, size=12))}
    demands[3] = demands[7] = 0.4          # an exact tie, id-broken
    pool = EdgeServerPool(2)
    ref_admitted, ref_loads = pool.admit(demands, T=1.0)
    for seed in range(5):
        keys = list(demands)
        np.random.default_rng(seed).shuffle(keys)
        shuffled = {k: demands[k] for k in keys}
        admitted, loads = pool.admit(shuffled, T=1.0)
        assert admitted == ref_admitted
        np.testing.assert_array_equal(loads, ref_loads)


def test_admit_mask_matches_admit_and_traced_scan():
    rng = np.random.default_rng(1)
    dense = rng.uniform(0.0, 0.9, size=16)
    dense[rng.uniform(size=16) < 0.4] = 0.0      # non-offloaders
    pool = EdgeServerPool(3)
    demands = {d: float(v) for d, v in enumerate(dense) if v > 0}
    admitted, loads = pool.admit(demands, T=1.0)
    mask, mloads = pool.admit_mask(dense, T=1.0)
    assert sorted(np.nonzero(mask)[0].tolist()) == sorted(admitted)
    np.testing.assert_allclose(mloads, loads, rtol=0, atol=0)

    import jax.numpy as jnp
    from repro.core.types import x64_scope
    with x64_scope():
        jmask, jloads = E.admit_mask_jnp(jnp.asarray(dense, jnp.float64),
                                         jnp.float64(1.0), 3)
    np.testing.assert_array_equal(np.asarray(jmask), mask)
    np.testing.assert_array_equal(np.asarray(jloads), mloads)


# ---------------------------------------------------------------------------
# sharding: shard_map step parity on host-platform devices (subprocess —
# the flag must be set before jax initialises)
# ---------------------------------------------------------------------------
def test_sharded_step_matches_unsharded_subprocess():
    env = dict(os.environ)
    env.update({
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "SHARD_SMOKE_DEVICES": "16", "SHARD_SMOKE_SHARDS": "8",
        "SHARD_SMOKE_PERIODS": "4",
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
# pytree plumbing
# ---------------------------------------------------------------------------
def test_engine_pytrees_roundtrip():
    import jax
    cfg = _config(3, horizon=4)
    params = E.EngineParams.from_config(cfg, horizon=4)
    state = E.init_state(params)
    for tree in (params, state):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
        for a, b in zip(leaves, jax.tree_util.tree_leaves(rebuilt)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # static solver config rides the treedef, not the leaves
    assert params.policy == "amr2" and params.arrivals == "replay"
    rebuilt = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params),
        jax.tree_util.tree_leaves(params))
    assert rebuilt.policy == "amr2"
    assert rebuilt.batch_max == params.batch_max


# ---------------------------------------------------------------------------
# reduced-tableau LP method, buffer donation, dtype guard, plan chunking
# ---------------------------------------------------------------------------
def _dense_priced(monkeypatch):
    """Run the engine's revised simplex with dense pricing (``col_rows``
    withheld, as before the column pricer) for the rest of the test: a
    rollout jitted from a new function, so that no cached trace of the
    column path is reused."""
    import jax
    monkeypatch.setattr(E, "lp_column_rows", lambda n, m: None)
    return jax.jit(lambda s, p, periods: E._rollout_impl(s, p, periods),
                   static_argnames="periods")


@pytest.mark.parametrize("n_devices,bases_match_tableau",
                         [(8, True), (256, False)])
def test_rollout_lp_method_revised_matches_tableau(n_devices,
                                                   bases_match_tableau,
                                                   monkeypatch):
    """The engine on `lp_method="revised"` must replay the tableau
    engine's trajectory: same integer metrics, same warm-basis carry,
    accuracies to fp noise.  The carried basis is compared as a label
    SET per device: the two representations reach the same optimal
    vertex but may order its rows differently (the leaving-row slot
    depends on the pivot sequence, which differs between the dense
    tableau and the reduced factor on degenerate ties).

    At 256 devices (Poisson arrivals; every period replans and masks
    job slots) a few devices end on another optimal basis of a
    degenerate vertex under the two representations — with dense
    pricing too — so there the label sets are pinned against the
    revised engine priced densely: the column pricer keeps its basis
    carry, integer metrics and pivot slots."""
    from repro.core.types import x64_scope
    cfg = _config(n_devices, horizon=10)
    pt = E.EngineParams.from_config(cfg, horizon=10)
    pr = E.EngineParams.from_config(cfg, horizon=10, lp_method="revised")
    assert pt.lp_method == "tableau" and pr.lp_method == "revised"
    st, mt = E.rollout(E.init_state(pt), pt, 6)
    sr, mr = E.rollout(E.init_state(pr), pr, 6)
    assert np.asarray(mr.n_backpressured).all()     # replans every period
    for f in INT_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(mr, f)),
                                      np.asarray(getattr(mt, f)), f)
    np.testing.assert_allclose(np.asarray(mr.total_accuracy),
                               np.asarray(mt.total_accuracy), atol=1e-12)
    with x64_scope():
        sd, md = _dense_priced(monkeypatch)(E.init_state(pr), pr,
                                            periods=6)
    for f in INT_FIELDS + ("lp_pivot_slots",):
        np.testing.assert_array_equal(np.asarray(getattr(mr, f)),
                                      np.asarray(getattr(md, f)), f)
    np.testing.assert_allclose(np.asarray(mr.total_accuracy),
                               np.asarray(md.total_accuracy), atol=1e-12)
    np.testing.assert_array_equal(np.sort(np.asarray(sr.warm_basis), -1),
                                  np.sort(np.asarray(sd.warm_basis), -1))
    if bases_match_tableau:
        np.testing.assert_array_equal(
            np.sort(np.asarray(sr.warm_basis), -1),
            np.sort(np.asarray(st.warm_basis), -1))


def test_revised_pivot_loops_hold_one_dot(monkeypatch):
    """On the engine's revised path every pivot loop (phase 1 and 2 of the
    plan and of the replan) holds one float64 `dot_general`, BTRAN's
    ``cB·Binv``, and none against A; priced densely, three."""
    import jax
    from repro.core.types import x64_scope
    cfg = _config(8, horizon=10)
    params = E.EngineParams.from_config(cfg, horizon=10, lp_method="revised")
    state = E.init_state(params)
    D, R = params.n_devices, params.n_basis_rows

    def loop_dots():
        with x64_scope():
            # a new function each time: no cached trace is reused
            jaxpr = jax.make_jaxpr(
                lambda s, p: E._step_impl(s, p))(state, params).jaxpr
        return [[(e2.invars[0].aval.shape, e2.invars[1].aval.shape,
                  e2.invars[0].aval.dtype)
                 for e2 in _eqns(e.params["body_jaxpr"].jaxpr)
                 if e2.primitive.name == "dot_general"]
                for e in _eqns(jaxpr) if e.primitive.name == "while"]

    loops = [d for d in loop_dots() if d]
    assert len(loops) == 4
    for d in loops:
        assert d == [((D, R), (D, R, R), np.float64)]
    monkeypatch.setattr(E, "lp_column_rows", lambda n, m: None)
    dense = [d for d in loop_dots() if d]
    assert len(dense) == 4 and all(len(d) == 3 for d in dense)


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, those of its sub-jaxprs (loop and
    branch bodies) included."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_from_fleet_rejects_unknown_lp_method():
    cfg = _config(4, horizon=6)
    with pytest.raises(ValueError, match="lp_method"):
        E.EngineParams.from_config(cfg, horizon=6, lp_method="dense")


def test_rollout_donate_is_bitwise_invisible():
    """`donate=True` consumes the input state's buffers in place (its own
    jit cache entry) — the results must be BIT-identical to the
    non-donated rollout."""
    cfg = _config(6, horizon=8)
    params = E.EngineParams.from_config(cfg, horizon=8)
    s0, m0 = E.rollout(E.init_state(params), params, 5)
    s1, m1 = E.rollout(E.init_state(params), params, 5, donate=True)
    for f in _STATE_FIELDS_TEST:
        np.testing.assert_array_equal(np.asarray(getattr(s0, f)),
                                      np.asarray(getattr(s1, f)), f)
    for f in INT_FIELDS + FLOAT_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(m0, f)),
                                      np.asarray(getattr(m1, f)), f)


_STATE_FIELDS_TEST = ("period", "key", "p_ed", "pending", "head",
                      "warm_basis", "n_updates", "pos", "cell",
                      "cell_load", "p_es_belief")


def test_engine_rejects_float32_state_and_params():
    """The f64 guard: a float32 leaf (e.g. a `device_put` outside any
    enable_x64 scope with global x64 off) must raise, naming the leaf,
    instead of silently running the rollout at single precision."""
    import dataclasses

    cfg = _config(4, horizon=6)
    params = E.EngineParams.from_config(cfg, horizon=6)
    state = E.init_state(params)
    bad_state = dataclasses.replace(
        state, p_ed=np.asarray(state.p_ed, np.float32))
    with pytest.raises(TypeError, match=r"state\.p_ed.*float32"):
        E.step(bad_state, params)
    bad_params = dataclasses.replace(
        params, acc=np.asarray(params.acc, np.float32))
    with pytest.raises(TypeError, match=r"params\.acc.*float32"):
        E.rollout(state, bad_params, 2)


def test_plan_lane_chunking_is_bitwise_invisible(monkeypatch):
    """`_plan` over lane chunks (`_PLAN_LANE_CHUNK`) must return exactly
    what the flat plan returns — warm, cold, and non-divisible (flat
    fallback) alike.  The chunking is purely a cache-blocking transform;
    any numerical difference is a bug."""
    import dataclasses

    import jax.numpy as jnp
    from repro.core.types import x64_scope

    from repro.core.problem import FleetProblem

    cfg = _config(16, horizon=6)
    params = E.EngineParams.from_config(cfg, horizon=6)
    state = E.init_state(params)
    with x64_scope():
        ci, take, *_ = E._arrivals(state, params)
        D, n = 16, params.batch_max
        mask = jnp.arange(n)[None, :] < take[:, None]
        rows = jnp.arange(D)[:, None]
        cic = jnp.clip(ci, 0, params.p_es.shape[1] - 1)
        fp = FleetProblem.from_arrays_unchecked(
            jnp.where(mask[..., None], jnp.asarray(state.p_ed)[rows, cic],
                      0.0),
            jnp.where(mask, jnp.asarray(params.p_es)[rows, cic], 0.0),
            jnp.asarray(params.acc), jnp.broadcast_to(params.T, (D,)),
            mask)
        wb = jnp.asarray(state.warm_basis)
        monkeypatch.setattr(E, "_PLAN_LANE_CHUNK", 0)
        flat = E._plan(params, fp, wb)
        flat_cold = E._plan(params, fp, None)
        for chunk in (4, 8, 5):          # 5 does not divide 16: flat path
            monkeypatch.setattr(E, "_PLAN_LANE_CHUNK", chunk)
            for ref, got in ((flat, E._plan(params, fp, wb)),
                             (flat_cold, E._plan(params, fp, None))):
                for r, g in zip(ref, got):
                    np.testing.assert_array_equal(np.asarray(r),
                                                  np.asarray(g))


# ---------------------------------------------------------------------------
# stale warm-basis invalidation (outage flip) — regression
# ---------------------------------------------------------------------------
class _Captured(Exception):
    pass


def test_step_cold_starts_warm_basis_on_outage_flip(monkeypatch):
    """An outage edge swaps a device's ES columns for the disabled
    sentinel, so last period's optimal basis labels a DIFFERENT LP.
    `step` must mask exactly the flipped devices' warm rows to -1 before
    handing them to the period core (regression: they used to be
    warm-factored against the wrong problem)."""
    import dataclasses

    cfg = _config(6, horizon=4, outage_frac=0.0)
    params = E.EngineParams.from_config(cfg, horizon=4)
    outage = np.zeros((6, params.outage.shape[1]), bool)
    outage[0, 1] = True            # device 0 flips ON at t=1
    outage[1, :] = True            # device 1 always out: no edge
    outage[2, 0] = True            # device 2 flips OFF at t=1
    params = dataclasses.replace(params, outage=outage)
    wb = np.tile(np.arange(params.n_basis_rows, dtype=np.int32), (6, 1))
    state = dataclasses.replace(E.init_state(params),
                                period=np.int32(1), warm_basis=wb)
    captured = {}

    def spy(belief, warm, *a, **k):
        captured["warm"] = np.asarray(warm)
        raise _Captured

    monkeypatch.setattr(E, "_period_impl", spy)
    with pytest.raises(_Captured):
        E._step_impl(state, params)
    got = captured["warm"]
    assert (got[0] == -1).all() and (got[2] == -1).all()
    np.testing.assert_array_equal(got[[1, 3, 4, 5]], wb[[1, 3, 4, 5]])


def test_step_keeps_warm_basis_at_period_zero(monkeypatch):
    """t=0 has no previous period: the (t-1) % H wraparound row must not
    fabricate a flip and throw away a caller-provided basis."""
    import dataclasses

    cfg = _config(4, horizon=4, outage_frac=0.0)
    params = E.EngineParams.from_config(cfg, horizon=4)
    outage = np.zeros((4, params.outage.shape[1]), bool)
    outage[1, -1] = True           # differs from t=0 only via wraparound
    params = dataclasses.replace(params, outage=outage)
    wb = np.tile(np.arange(params.n_basis_rows, dtype=np.int32), (4, 1))
    state = dataclasses.replace(E.init_state(params), warm_basis=wb)
    captured = {}

    def spy(belief, warm, *a, **k):
        captured["warm"] = np.asarray(warm)
        raise _Captured

    monkeypatch.setattr(E, "_period_impl", spy)
    with pytest.raises(_Captured):
        E._step_impl(state, params)
    np.testing.assert_array_equal(captured["warm"], wb)
