"""Multi-cell mobility subsystem (`repro.core.mobility` + engine v2):
segmented per-cell admission vs the sequential oracles, routing geometry,
handover warm-basis/belief migration, the S=1 / infinite-radius bitwise
reduction pin, and the chaos ES-audit satellite."""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.api import engine as E
from repro.core.faults import FaultModel
from repro.core.mobility import (MobilityModel, admit_mask_cells_np,
                                 admit_mask_segmented, route_cells,
                                 validate_mobility)
from repro.serving import FleetConfig, FleetEngine


def _config(n_devices=8, *, n_servers=6, horizon=14, seed=0, rate=9.0):
    return FleetConfig(n_devices=n_devices, T=1.2, n_servers=n_servers,
                       policy="amr2", backend="jax", rate=rate,
                       batch_max=8, horizon=horizon, seed=seed,
                       straggler_frac=0.25, outage_frac=0.1)


def _three_cells(D, horizon, seed=3, radius=9.0):
    rng = np.random.default_rng(seed)
    cxy = np.array([[0.0, 0.0], [10.0, 0.0], [0.0, 10.0]])
    trace = (rng.normal(scale=4.0, size=(horizon, D, 2))
             + cxy[rng.integers(0, 3, D)])
    return MobilityModel.make(
        cell_xy=cxy, trace=trace, cell_rate=np.array([1.0, 0.8, 1.2]),
        radius=radius, link_alpha=0.5)


# ---------------------------------------------------------------------------
# the acceptance pin: S=1 + infinite radius reduces to today's engine BITWISE
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["replay", "walk"])
def test_s1_infinite_radius_reduces_bitwise(mode):
    """One cell at the origin with an infinite coverage radius and unit
    link rate is geometrically inert: every device is always covered,
    the link factor is exactly 1.0, and admission stays on the S=1
    sequential scan — so arming mobility must not move a single bit of
    the trajectory (metrics AND state leaves)."""
    periods = 12
    cfg = _config(8, horizon=periods + 2)
    params = E.EngineParams.from_config(cfg, horizon=periods + 2)
    s_off, m_off = E.rollout(E.init_state(params), params, periods)
    trace = np.zeros((periods + 2, 8, 2))
    mob = MobilityModel.make(cell_xy=np.zeros((1, 2)), trace=trace)
    armed = params.with_mobility(mob, mode=mode, mobility_seed=7)
    s_on, m_on = E.rollout(E.init_state(armed), armed, periods)
    for f in E._METRIC_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(m_off, f)),
                                      np.asarray(getattr(m_on, f)), f)
    for f in ("key", "p_ed", "pending", "head", "warm_basis", "n_updates",
              "p_es_belief"):
        np.testing.assert_array_equal(np.asarray(getattr(s_off, f)),
                                      np.asarray(getattr(s_on, f)), f)
    assert int(np.asarray(m_on.n_handover).sum()) == 0


def test_step_sequence_equals_rollout_with_mobility():
    periods = 8
    cfg = _config(8, horizon=periods + 2)
    params = E.EngineParams.from_config(cfg, horizon=periods + 2)
    p3 = params.with_mobility(_three_cells(8, periods + 2),
                              routing="min_time")
    s_roll, m = E.rollout(E.init_state(p3), p3, periods)
    s = E.init_state(p3)
    for _ in range(periods):
        s, _ = E.step(s, p3)
    for f in E._STATE_FIELDS:
        for a, b in zip(jax.tree.leaves(getattr(s, f)),
                        jax.tree.leaves(getattr(s_roll, f))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), f)
    assert int(np.asarray(m.n_handover).sum()) > 0


# ---------------------------------------------------------------------------
# segmented per-cell admission vs the sequential oracles
# ---------------------------------------------------------------------------
@settings(max_examples=60, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 4), st.integers(1, 3))
def test_segmented_admission_matches_per_cell_oracle(seed, n_cells, k):
    """`admit_mask_segmented` (sort/cumsum, no sequential pass) admits
    exactly the set the per-cell sequential first-fit oracle admits, and
    books the same per-cell load totals."""
    rng = np.random.default_rng(seed)
    D = int(rng.integers(1, 40))
    demands = np.where(rng.random(D) < 0.3, 0.0,
                       rng.uniform(0.0, 1.5, D)).astype(np.float64)
    cell = rng.integers(-1, n_cells, D).astype(np.int32)
    T = 1.2
    adm, loads = admit_mask_segmented(
        jnp.asarray(demands), jnp.asarray(cell), T, n_cells, k)
    adm_np, loads_np = admit_mask_cells_np(demands, cell, T, n_cells, k)
    np.testing.assert_array_equal(np.asarray(adm), adm_np)
    # per-server placement may permute on equal-demand ties; the admitted
    # LOAD multiset per cell is the invariant
    np.testing.assert_allclose(np.sort(np.asarray(loads), axis=1),
                               np.sort(loads_np, axis=1), atol=1e-12)


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 3))
def test_segmented_equals_global_scan_at_one_cell(seed, k):
    """With a single cell the segmented formulation must reproduce the
    global sequential scan (`admit_mask_jnp`, the bitwise-pinned S=1
    oracle) exactly."""
    rng = np.random.default_rng(seed)
    D = int(rng.integers(1, 48))
    demands = np.where(rng.random(D) < 0.3, 0.0,
                       rng.uniform(0.0, 1.5, D)).astype(np.float64)
    T = 1.2
    adm_seg, loads_seg = admit_mask_segmented(
        jnp.asarray(demands), jnp.zeros(D, jnp.int32), T, 1, k)
    adm_glob, loads_glob = E.admit_mask_jnp(jnp.asarray(demands), T, k)
    np.testing.assert_array_equal(np.asarray(adm_seg),
                                  np.asarray(adm_glob))
    np.testing.assert_allclose(np.sort(np.asarray(loads_seg).ravel()),
                               np.sort(np.asarray(loads_glob)), atol=1e-12)


# ---------------------------------------------------------------------------
# routing geometry
# ---------------------------------------------------------------------------
@settings(max_examples=60, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from(["nearest", "min_time"]))
def test_routing_respects_coverage_radius(seed, routing):
    """A device is assigned a cell iff SOME cell is within the coverage
    radius, and the assigned cell is always one of the covering cells."""
    rng = np.random.default_rng(seed)
    D, S = int(rng.integers(1, 24)), int(rng.integers(1, 5))
    cxy = rng.uniform(-10, 10, (S, 2))
    pos = rng.uniform(-15, 15, (D, 2))
    radius = float(rng.uniform(1.0, 12.0))
    mob = MobilityModel.make(cell_xy=cxy, trace=pos[None],
                             cell_rate=rng.uniform(0.5, 2.0, S),
                             radius=radius, link_alpha=0.5)
    cell, covered, lf = (np.asarray(a) for a in route_cells(
        jnp.asarray(pos), mob, jnp.asarray(rng.uniform(0, 1, S)), routing))
    dist = np.linalg.norm(pos[:, None] - cxy[None], axis=2)
    in_range = dist <= radius
    np.testing.assert_array_equal(covered, in_range.any(axis=1))
    assert ((cell >= 0) == covered).all()
    ok = covered.nonzero()[0]
    assert in_range[ok, cell[ok]].all()       # never routed out of range
    np.testing.assert_array_equal(lf[~covered], 1.0)
    if routing == "nearest":
        np.testing.assert_allclose(
            dist[ok, cell[ok]],
            np.where(in_range[ok], dist[ok], np.inf).min(axis=1))


# ---------------------------------------------------------------------------
# handover: warm-basis + belief migration
# ---------------------------------------------------------------------------
class _Captured(Exception):
    pass


def _capture_step_inputs(monkeypatch, state, params):
    captured = {}

    def spy(belief, warm, *a, **k):
        captured["warm"] = np.asarray(warm)
        captured["es_belief"] = np.asarray(k["es_belief"])
        raise _Captured

    monkeypatch.setattr(E, "_period_impl", spy)
    from repro.core.types import x64_scope
    with x64_scope(), pytest.raises(_Captured):
        E._step_impl(state, params)
    return captured


def test_handover_masks_warm_basis_both_directions(monkeypatch):
    """A mid-horizon cell switch (either direction) cold-starts exactly
    the switching devices' warm rows and migrates their ES beliefs back
    to the nominal table — composing with, not replacing, the outage-flip
    staleness rule."""
    D, periods = 6, 4
    cfg = _config(D, n_servers=2, horizon=periods)
    params = E.EngineParams.from_config(cfg, horizon=periods)
    outage = np.zeros((D, params.outage.shape[1]), bool)
    outage[3, 1] = True                      # device 3: outage flip at t=1
    params = dataclasses.replace(params, outage=outage)
    # 2 cells; place devices so their t=1 routing is known
    cxy = np.array([[0.0, 0.0], [10.0, 0.0]])
    trace = np.zeros((periods, D, 2))
    trace[:, 1] = [10.0, 0.0]                # device 1 lives at cell 1
    trace[1, 0] = [10.0, 0.0]                # device 0: cell 0 -> cell 1
    trace[0, 1] = [10.0, 0.0]
    trace[1, 1] = [0.0, 0.0]                 # device 1: cell 1 -> cell 0
    mob = MobilityModel.make(cell_xy=cxy, trace=trace, radius=50.0)
    params = params.with_mobility(mob)
    wb = np.tile(np.arange(params.n_basis_rows, dtype=np.int32), (D, 1))
    belief = np.asarray(params.p_es) * 3.0   # inflated everywhere
    state = dataclasses.replace(
        E.init_state(params), period=np.int32(1), warm_basis=wb,
        cell=np.where(np.arange(D) == 1, 1, 0).astype(np.int32),
        p_es_belief=belief)
    got = _capture_step_inputs(monkeypatch, state, params)
    # devices 0 (0->1), 1 (1->0) switched; device 3 had an outage flip
    assert (got["warm"][0] == -1).all() and (got["warm"][1] == -1).all()
    assert (got["warm"][3] == -1).all()
    np.testing.assert_array_equal(got["warm"][[2, 4, 5]], wb[[2, 4, 5]])
    # belief migration: switched rows reset to nominal, others keep EMA
    np.testing.assert_array_equal(got["es_belief"][[0, 1]],
                                  np.asarray(params.p_es)[[0, 1]])
    np.testing.assert_array_equal(got["es_belief"][[2, 3, 4, 5]],
                                  belief[[2, 3, 4, 5]])


def test_no_handover_mask_at_period_zero(monkeypatch):
    """t=0 'switches' from the init sentinel are not handovers: the warm
    basis (all cold anyway at start, but pinned here with a live one)
    must pass through untouched."""
    D = 4
    cfg = _config(D, n_servers=2, horizon=4)
    params = E.EngineParams.from_config(cfg, horizon=4)
    params = dataclasses.replace(
        params, outage=np.zeros((D, params.outage.shape[1]), bool))
    mob = MobilityModel.make(cell_xy=np.array([[0.0, 0.0], [10.0, 0.0]]),
                             trace=np.zeros((4, D, 2)), radius=50.0)
    params = params.with_mobility(mob)
    wb = np.tile(np.arange(params.n_basis_rows, dtype=np.int32), (D, 1))
    state = dataclasses.replace(E.init_state(params), warm_basis=wb)
    got = _capture_step_inputs(monkeypatch, state, params)
    np.testing.assert_array_equal(got["warm"], wb)


# ---------------------------------------------------------------------------
# geometry validation (satellite: clear errors, not downstream NaNs)
# ---------------------------------------------------------------------------
def test_validation_rejects_bad_geometry():
    D, S = 4, 2
    good = dict(cell_xy=np.zeros((S, 2)), trace=np.zeros((3, D, 2)),
                cell_rate=np.ones(S), radius=5.0)

    def check(msg, **overrides):
        kw = {**good, **overrides}
        mob = MobilityModel(
            cell_xy=np.asarray(kw["cell_xy"]),
            cell_rate=np.asarray(kw["cell_rate"]),
            radius=np.asarray(kw["radius"]),
            link_alpha=np.float64(kw.get("link_alpha", 0.0)),
            walk_sigma=np.float64(kw.get("walk_sigma", 0.0)),
            trace=np.asarray(kw["trace"]))
        with pytest.raises(ValueError, match=msg):
            validate_mobility(mob, n_devices=D, n_servers=S,
                              mode=kw.get("mode", "replay"),
                              routing=kw.get("routing", "nearest"))

    check("float64", cell_xy=np.zeros((S, 2), np.float32))
    check("float64", trace=np.zeros((3, D, 2), np.float32))
    check("strictly positive", cell_rate=np.array([1.0, 0.0]))
    check("strictly positive", cell_rate=np.array([1.0, -2.0]))
    check("cell_rate", cell_rate=np.ones(S + 1))
    check("trace", trace=np.zeros((3, D + 1, 2)))
    check("cell_xy", cell_xy=np.zeros((S, 3)))
    check("radius", radius=0.0)
    check("divisible", cell_xy=np.zeros((3, 2)), cell_rate=np.ones(3))
    with pytest.raises(ValueError, match="mode"):
        validate_mobility(MobilityModel.none(), n_devices=D, n_servers=S,
                          mode="teleport", routing="nearest")
    with pytest.raises(ValueError, match="routing"):
        validate_mobility(MobilityModel.none(), n_devices=D, n_servers=S,
                          mode="replay", routing="random")


def test_from_fleet_and_with_mobility_validate():
    cfg = _config(4, n_servers=2, horizon=4)
    params = E.EngineParams.from_config(cfg, horizon=4)
    bad = MobilityModel(cell_xy=np.zeros((2, 2), np.float32),
                        cell_rate=np.ones(2), radius=np.float64(5.0),
                        link_alpha=np.float64(0.0),
                        walk_sigma=np.float64(0.0),
                        trace=np.zeros((3, 4, 2)))
    with pytest.raises(ValueError, match="float64"):
        params.with_mobility(bad)
    with pytest.raises(ValueError, match="divisible"):
        params.with_mobility(_three_cells(4, 4))   # 2 servers, 3 cells


def test_fleet_engine_rejects_armed_mobility():
    cfg = dataclasses.replace(
        _config(4, n_servers=2, horizon=4),
        mobility=MobilityModel.make(cell_xy=np.zeros((1, 2)),
                                    trace=np.zeros((4, 4, 2))))
    with pytest.raises(ValueError, match="pure-functional engine"):
        FleetEngine.from_config(cfg)


# ---------------------------------------------------------------------------
# satellite 1: chaos ladder -> ES-latency EMA audit
# ---------------------------------------------------------------------------
def test_chaos_off_and_armed_null_keep_es_belief_inert():
    """Chaos off (and armed with a null FaultModel) the ES audit never
    fires: p_es_belief stays == params.p_es and the shared metric fields
    are bitwise-identical to the pre-audit engine."""
    periods = 10
    cfg = _config(8, n_servers=2, horizon=periods + 2)
    params = E.EngineParams.from_config(cfg, horizon=periods + 2)
    s_off, m_off = E.rollout(E.init_state(params), params, periods)
    armed = dataclasses.replace(params, chaos=True)   # null model, armed
    s_null, m_null = E.rollout(E.init_state(armed), armed, periods)
    np.testing.assert_array_equal(np.asarray(s_off.p_es_belief),
                                  np.asarray(params.p_es))
    for f in E._METRIC_FIELDS:
        if f == "realized_makespan":
            continue            # priced == realized under null faults
        np.testing.assert_array_equal(np.asarray(getattr(m_off, f)),
                                      np.asarray(getattr(m_null, f)), f)
    assert int(np.asarray(m_null.n_es_audit_updates).sum()) == 0
    np.testing.assert_array_equal(np.asarray(s_null.p_es_belief),
                                  np.asarray(params.p_es))


def test_chaos_hot_inflates_es_belief_and_host_parity():
    """Link-degrade faults blow realized ES walls past the priced demand:
    the audit must fire, inflate beliefs monotonically, and the host
    `FleetEngine` delegation must thread the SAME belief trajectory
    (stats bitwise-equal to the rollout)."""
    periods = 10
    fm = FaultModel.make(link_degrade_prob=0.6, link_degrade_mag=3.0,
                         loss_rate=0.1)
    cfg = dataclasses.replace(_config(8, n_servers=2, horizon=periods + 2),
                              faults=fm, fault_seed=3)
    params = E.EngineParams.from_config(cfg, horizon=periods + 2)
    state, m = E.rollout(E.init_state(params), params, periods)
    n_upd = int(np.asarray(m.n_es_audit_updates).sum())
    assert n_upd > 0
    belief = np.asarray(state.p_es_belief)
    assert (belief >= np.asarray(params.p_es) - 1e-15).all()
    assert (belief > np.asarray(params.p_es)).any()
    # host delegation parity (threads _v2_es_belief through _period_jit)
    eng = FleetEngine.from_config(cfg)
    stats = eng.run(periods)
    for i, s in enumerate(stats):
        assert s.n_es_audit_updates == \
            int(np.asarray(m.n_es_audit_updates)[i]), i
        assert s.total_accuracy == \
            float(np.asarray(m.total_accuracy)[i]), i
        assert s.realized_makespan == \
            float(np.asarray(m.realized_makespan)[i]), i
    np.testing.assert_array_equal(eng._v2_es_belief, belief)
