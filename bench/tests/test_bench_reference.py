"""The plain reference's parts against independent answers."""
import numpy as np
import pytest

import reference

scipy_optimize = pytest.importorskip("scipy.optimize")


def _lp_value(p_ed, p_es, acc, T):
    """Optimal summed accuracy of one device's LP relaxation (HiGHS)."""
    n, m = p_ed.shape
    c = -np.tile(acc, n)
    A_ub = np.zeros((2, n * (m + 1)))
    for j in range(n):
        A_ub[0, j * (m + 1): j * (m + 1) + m] = p_ed[j]
        A_ub[1, j * (m + 1) + m] = p_es[j]
    A_eq = np.kron(np.eye(n), np.ones(m + 1))
    r = scipy_optimize.linprog(c, A_ub=A_ub, b_ub=[T, T], A_eq=A_eq,
                               b_eq=np.ones(n), method="highs")
    return -r.fun if r.status == 0 else None


@pytest.mark.parametrize("scale", [0.05, 0.2, 0.6])
def test_lp_matches_an_independent_solver(scale):
    """Random devices, including ones whose fastest local plan does not
    fit T (the two-phase path) and infeasible ones."""
    rng = np.random.default_rng(int(scale * 100))
    L, n, m, T = 64, 12, 2, 1.2
    p_ed = np.sort(rng.uniform(0.2, 1.0, (L, n, m)) * scale * 4, axis=2)
    p_es = rng.uniform(0.05, 1.5, (L, n))
    p_es[::7, :] = reference.ES_DISABLED
    acc = np.sort(rng.uniform(0.3, 0.9, (L, m + 1)), axis=1)
    xbar, feasible = reference.solve_lp(p_ed, p_es, acc, T, np.float64)
    for d in range(L):
        want = _lp_value(p_ed[d], p_es[d], acc[d], T)
        assert feasible[d] == (want is not None)
        if want is None:
            continue
        got = (xbar[d] * acc[d]).sum()
        assert got == pytest.approx(want, rel=1e-9, abs=1e-9)
        np.testing.assert_allclose(xbar[d].sum(axis=1), 1.0, atol=1e-9)
        assert (xbar[d][:, :m] * p_ed[d]).sum() <= T + 1e-9
        # a basic solution: at most two jobs fractional (Lemma 1)
        frac = ((xbar[d] > 1e-6) & (xbar[d] < 1 - 1e-6)).any(axis=1)
        assert frac.sum() <= 2


def test_rounding_keeps_the_2T_bound():
    rng = np.random.default_rng(3)
    L, n, m, T = 256, 12, 2, 1.2
    p_ed = np.sort(rng.uniform(0.02, 0.2, (L, n, m)), axis=2)
    p_es = rng.uniform(0.1, 1.1, (L, n))
    acc = np.tile([0.4, 0.56, 0.77], (L, 1))
    assign = reference.plan(p_ed, p_es, acc, T, np.float64)
    on_ed = assign < m
    ed = np.where(on_ed, np.take_along_axis(
        p_ed, np.minimum(assign, m - 1)[..., None], 2)[..., 0], 0).sum(1)
    es = np.where(~on_ed, p_es, 0).sum(1)
    assert (ed <= 2 * T + 1e-9).all() and (es <= 2 * T + 1e-9).all()


def _first_fit(demand, T, k):
    """The literal admission: sorted demands, least-loaded server first."""
    loads = [0.0] * k
    admitted = np.zeros(len(demand), bool)
    for d in sorted(range(len(demand)), key=lambda i: (demand[i], i)):
        if demand[d] <= 0:
            continue
        s = int(np.argmin(loads))
        if loads[s] + demand[d] <= T + 1e-12:
            loads[s] += demand[d]
            admitted[d] = True
    return admitted, np.array(loads)


def test_admission_is_first_fit():
    rng = np.random.default_rng(4)
    demand = np.where(rng.uniform(size=500) < 0.8,
                      rng.uniform(0.01, 1.1, 500), 0.0)
    demand[10:20] = demand[5]          # exact ties break by device id
    got, loads, order, cut = reference.admit(demand, 1.2, 40)
    want, want_loads = _first_fit(demand, 1.2, 40)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(np.sort(loads), np.sort(want_loads))
    assert got[order[:cut]].all() and not got[order[cut:]].any()


def test_split_tie_finds_the_devices_tied_at_the_cut():
    demand = np.array([0.5, 0.3, 0.3 * (1 + 1e-15), 0.3, 0.9, 0.0])
    order = np.array([1, 2, 3, 0, 4])
    assert sorted(reference.split_tie(demand, order, 2).tolist()) == [1, 2, 3]
    assert reference.split_tie(demand, order, 3).tolist() == []   # 0.5 alone
    assert reference.split_tie(demand, order, 5).tolist() == []   # no cut


@pytest.mark.parametrize("chaos", [False, True])
def test_a_sample_of_periods_reads_as_the_whole_run(chaos):
    """Periods left out of a sample are moved through without planning
    where no plan can change the state (no drift), and planned where one
    can; either way the sampled periods read as in the whole run."""
    import json
    import os

    import fleetgen
    from conftest import BENCH
    with open(os.path.join(BENCH, "configs", "campus1k.json")) as f:
        config = {**json.load(f), "n_devices": 64, "n_servers": 4}
    with open(os.path.join(BENCH, "traffic", "frames.json")) as f:
        traffic = json.load(f)
    if chaos:
        config.update(straggler_frac=0.5, drift_mag=3.0, schedule_periods=8)
    fleet = fleetgen.make_fleet(config, traffic, 12, 9)
    whole = reference.run(fleet, config, 10)
    sample = {2, 5, 9}
    part = reference.run(fleet, config, 10, sample=sample)
    assert [t for t, r in enumerate(part) if r is not None] == sorted(sample)
    for t in sample:
        assert part[t] == whole[t]
    ref = reference.FleetReference(fleet, config)
    moved = [ref.advance() for _ in range(8)]
    calm = [bool((fleet.drift[:, t % fleet.drift.shape[1]] == 1).all())
            for t in range(8)]
    # moved on while calm; once a device drifts, only a plan moves it
    assert moved == [all(calm[:t + 1]) for t in range(8)]
    assert all(moved) is not chaos
