"""JAX/NumPy simplex vs scipy.linprog (oracle) — unit + property tests."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from scipy.optimize import linprog

from repro.core import solve_lp, OPTIMAL, INFEASIBLE


def _random_lp(seed, n=10, mc=5, feasible=True):
    rng = np.random.default_rng(seed)
    c = rng.normal(size=n)
    A_ub = rng.uniform(0, 1, size=(mc, n))
    b_ub = rng.uniform(1, 3, size=mc)
    A_eq = np.ones((1, n))
    b_eq = np.array([1.0 if feasible else 100.0])  # sum x = 100 with x<=~3 cap
    return c, A_ub, b_ub, A_eq, b_eq


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("seed", range(8))
def test_matches_scipy_on_random_feasible(backend, seed):
    c, A_ub, b_ub, A_eq, b_eq = _random_lp(seed)
    ours = solve_lp(c, A_ub, b_ub, A_eq, b_eq, backend=backend)
    ref = linprog(c, A_ub=A_ub, b_ub=b_ub, A_eq=A_eq, b_eq=b_eq,
                  bounds=(0, None))
    assert ours.status == OPTIMAL and ref.status == 0
    assert ours.fun == pytest.approx(ref.fun, abs=1e-4)
    # solution feasibility
    x = ours.x
    assert np.all(x >= -1e-6)
    assert np.all(A_ub @ x <= b_ub + 1e-5)
    assert np.allclose(A_eq @ x, b_eq, atol=1e-5)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_detects_infeasible(backend):
    # sum x = 100 while every x bounded by b_ub/Aub rows ~ 3
    n = 6
    c = np.ones(n)
    A_ub = np.eye(n)
    b_ub = np.full(n, 3.0)
    A_eq = np.ones((1, n))
    b_eq = np.array([100.0])
    ours = solve_lp(c, A_ub, b_ub, A_eq, b_eq, backend=backend)
    assert ours.status == INFEASIBLE


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_equality_only(backend):
    # min x0 + 2 x1 s.t. x0 + x1 = 1
    res = solve_lp(np.array([1.0, 2.0]), A_eq=np.array([[1.0, 1.0]]),
                   b_eq=np.array([1.0]), backend=backend)
    assert res.status == OPTIMAL
    assert res.fun == pytest.approx(1.0, abs=1e-6)
    assert res.x[0] == pytest.approx(1.0, abs=1e-5)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_inequality_only(backend):
    # max x (min -x) s.t. x <= 5
    res = solve_lp(np.array([-1.0]), A_ub=np.array([[1.0]]),
                   b_ub=np.array([5.0]), backend=backend)
    assert res.status == OPTIMAL
    assert res.fun == pytest.approx(-5.0, abs=1e-6)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(3, 14),
       mc=st.integers(1, 6))
def test_property_matches_scipy(seed, n, mc):
    rng = np.random.default_rng(seed)
    c = rng.normal(size=n)
    A_ub = rng.uniform(0, 1, size=(mc, n))
    b_ub = rng.uniform(0.5, 3, size=mc)
    A_eq = np.ones((1, n))
    b_eq = np.array([1.0])
    ours = solve_lp(c, A_ub, b_ub, A_eq, b_eq, backend="numpy")
    ref = linprog(c, A_ub=A_ub, b_ub=b_ub, A_eq=A_eq, b_eq=b_eq,
                  bounds=(0, None))
    if ref.status == 0:
        assert ours.status == OPTIMAL
        assert ours.fun == pytest.approx(ref.fun, abs=1e-6)
    elif ref.status == 2:
        assert ours.status == INFEASIBLE


def test_basic_solution_has_basis():
    c, A_ub, b_ub, A_eq, b_eq = _random_lp(0)
    res = solve_lp(c, A_ub, b_ub, A_eq, b_eq, backend="numpy")
    # basis has one entry per row: mc + n_eq rows
    assert len(res.basis) == A_ub.shape[0] + A_eq.shape[0]


# ---------------------------------------------------------------------------
# degenerate pivoting: Bland's-rule fallback (anti-cycling)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_degenerate_beale_reaches_optimum(backend):
    """Beale's classic cycling LP: fully degenerate at the origin — the
    Dantzig rule with naive tie-breaks cycles forever on it.  The solver
    (index tie-break + Bland fallback after K degenerate pivots) must reach
    the optimum -0.05 within the iteration budget."""
    c = np.array([-0.75, 150.0, -0.02, 6.0])
    A_ub = np.array([[0.25, -60.0, -0.04, 9.0],
                     [0.5, -90.0, -0.02, 3.0],
                     [0.0, 0.0, 1.0, 0.0]])
    b_ub = np.array([0.0, 0.0, 1.0])
    res = solve_lp(c, A_ub, b_ub, backend=backend, maxiter=100)
    assert res.status == OPTIMAL
    assert res.fun == pytest.approx(-0.05, abs=1e-6)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("seed", range(4))
def test_pure_bland_rule_matches_scipy(backend, seed):
    """bland_after=0 runs the whole solve under Bland's entering rule — it
    must find the same optimum (slower, but guaranteed cycle-free)."""
    c, A_ub, b_ub, A_eq, b_eq = _random_lp(seed)
    res = solve_lp(c, A_ub, b_ub, A_eq, b_eq, backend=backend,
                   bland_after=0)
    ref = linprog(c, A_ub=A_ub, b_ub=b_ub, A_eq=A_eq, b_eq=b_eq,
                  bounds=(0, None))
    assert res.status == OPTIMAL and ref.status == 0
    assert res.fun == pytest.approx(ref.fun, abs=1e-4)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_degenerate_origin_lp(backend):
    """Every pivot from the all-slack basis is degenerate (b = 0 rows):
    the degeneracy counter must engage Bland and still terminate at the
    (unique, origin) optimum."""
    rng = np.random.default_rng(7)
    n, mc = 5, 4
    c = np.abs(rng.normal(size=n))          # minimize over x >= 0: opt = 0
    A_ub = rng.normal(size=(mc, n))
    b_ub = np.zeros(mc)
    res = solve_lp(c, A_ub, b_ub, backend=backend, maxiter=200)
    assert res.status == OPTIMAL
    assert res.fun == pytest.approx(0.0, abs=1e-8)


# ---------------------------------------------------------------------------
# status propagation: iteration limit / unbounded must never be silent
# ---------------------------------------------------------------------------
from repro.core import UNBOUNDED                      # noqa: E402
from repro.core.lp import ITERATION_LIMIT             # noqa: E402


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_tiny_maxiter_reports_iteration_limit(backend):
    """A maxiter-capped solve must say so — including when phase 1 is the
    phase that got capped (its status used to be discarded and the capped
    tableau could be reported as 'optimal' or 'infeasible')."""
    c, A_ub, b_ub, A_eq, b_eq = _random_lp(3)
    res = solve_lp(c, A_ub, b_ub, A_eq, b_eq, backend=backend, maxiter=1)
    assert res.status == ITERATION_LIMIT
    assert not res.success


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_unbounded_reported(backend):
    # min -x s.t. -x <= 0 (x >= 0): unbounded below
    res = solve_lp(np.array([-1.0]), A_ub=np.array([[-1.0]]),
                   b_ub=np.array([0.0]), backend=backend)
    assert res.status == UNBOUNDED


# ---------------------------------------------------------------------------
# warm starts: revised-simplex start from a previous basis
# ---------------------------------------------------------------------------
def _batch_lp(seed=0, nb=5, n=8, mc=3):
    rng = np.random.default_rng(seed)
    c = rng.normal(size=(nb, n))
    A_ub = rng.uniform(0, 1, size=(nb, mc, n))
    b_ub = rng.uniform(1, 3, size=(nb, mc))
    A_eq = np.ones((nb, 1, n))
    b_eq = np.ones((nb, 1))
    return c, A_ub, b_ub, A_eq, b_eq


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_warm_start_identical_resolve_is_zero_pivots(backend):
    c, A_ub, b_ub, A_eq, b_eq = _random_lp(1)
    cold = solve_lp(c, A_ub, b_ub, A_eq, b_eq, backend=backend)
    warm = solve_lp(c, A_ub, b_ub, A_eq, b_eq, backend=backend,
                    warm_basis=cold.basis)
    assert warm.warm and warm.status == OPTIMAL and warm.niter == 0
    assert warm.fun == pytest.approx(cold.fun, abs=1e-6)
    np.testing.assert_array_equal(np.sort(warm.basis), np.sort(cold.basis))


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_warm_start_perturbed_instance(backend):
    """The fleet scenario: next period's instance differs slightly; the old
    basis remains (near-)optimal and the warm solve matches a cold one."""
    c, A_ub, b_ub, A_eq, b_eq = _random_lp(2)
    cold0 = solve_lp(c, A_ub, b_ub, A_eq, b_eq, backend=backend)
    rng = np.random.default_rng(5)
    A2 = A_ub * (1.0 + 0.05 * rng.normal(size=A_ub.shape))
    warm = solve_lp(c, A2, b_ub, A_eq, b_eq, backend=backend,
                    warm_basis=cold0.basis)
    cold = solve_lp(c, A2, b_ub, A_eq, b_eq, backend=backend)
    assert warm.status == OPTIMAL
    assert warm.fun == pytest.approx(cold.fun, abs=1e-6)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_warm_start_rejected_basis_falls_back_cold(backend):
    c, A_ub, b_ub, A_eq, b_eq = _random_lp(4)
    cold = solve_lp(c, A_ub, b_ub, A_eq, b_eq, backend=backend)
    bad = np.full_like(cold.basis, -1)
    warm = solve_lp(c, A_ub, b_ub, A_eq, b_eq, backend=backend,
                    warm_basis=bad)
    assert not warm.warm                   # rejected -> cold path ran
    assert warm.status == OPTIMAL
    assert warm.fun == pytest.approx(cold.fun, abs=1e-9)


def test_solve_lp_batch_warm_matches_cold():
    from repro.core import solve_lp_batch
    c, A_ub, b_ub, A_eq, b_eq = _batch_lp(0)
    cold = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq)
    assert not cold.warm.any()
    warm = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq,
                          warm_basis=cold.basis)
    assert warm.warm.all() and (warm.niter == 0).all()
    np.testing.assert_allclose(warm.fun, cold.fun, atol=1e-9)
    np.testing.assert_allclose(warm.x, cold.x, atol=1e-9)


def test_solve_lp_batch_warm_mixed_rejections():
    """Lanes with stale (-1) bases are re-solved cold and still correct;
    accepted lanes keep the warm fast path."""
    from repro.core import solve_lp_batch
    c, A_ub, b_ub, A_eq, b_eq = _batch_lp(1)
    cold = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq)
    wb = cold.basis.copy()
    wb[::2] = -1                           # every other lane: no basis
    warm = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq, warm_basis=wb)
    assert (~warm.warm[::2]).all() and warm.warm[1::2].all()
    np.testing.assert_allclose(warm.fun, cold.fun, atol=1e-9)
    assert (warm.status == OPTIMAL).all()


def test_solve_lp_batch_warm_shape_guard():
    from repro.core import solve_lp_batch
    c, A_ub, b_ub, A_eq, b_eq = _batch_lp(2)
    with pytest.raises(ValueError, match="warm_basis"):
        solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq,
                       warm_basis=np.zeros((2, 2), dtype=np.int64))


def test_solve_lp_batch_warm_pallas_impl_matches_jnp():
    """impl='pallas' routes the batched pivot through the simplex_pivot
    kernel (interpret mode on CPU) — bit-identical trajectory to jnp."""
    from repro.core import solve_lp_batch
    c, A_ub, b_ub, A_eq, b_eq = _batch_lp(3)
    cold = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq)
    rng = np.random.default_rng(9)
    A2 = A_ub * (1.0 + 0.1 * rng.normal(size=A_ub.shape))
    ref = solve_lp_batch(c, A2, b_ub, A_eq, b_eq, warm_basis=cold.basis,
                         impl="jnp")
    got = solve_lp_batch(c, A2, b_ub, A_eq, b_eq, warm_basis=cold.basis,
                         impl="pallas")
    np.testing.assert_array_equal(got.status, ref.status)
    np.testing.assert_array_equal(got.niter, ref.niter)
    np.testing.assert_array_equal(got.basis, ref.basis)
    np.testing.assert_allclose(got.x, ref.x, atol=1e-12)


# ---------------------------------------------------------------------------
# simplex_batch_core: the traced warm-or-cold engine path vs the host
# solve_lp_batch dispatch (accepted-warm + cold-fallback), lane for lane
# ---------------------------------------------------------------------------
def _run_core(c, A_ub, b_ub, A_eq, b_eq, basis0, lane_mask=None):
    import jax
    import jax.numpy as jnp
    from repro.core.types import x64_scope

    from repro.core.lp import (_bucket_maxiter, _canonicalize_batch,
                               simplex_batch_core)
    A, b, cf, nv, _ = _canonicalize_batch(c, A_ub, b_ub, A_eq, b_eq)
    maxiter = _bucket_maxiter(50 * (A.shape[1] + 2))
    with x64_scope():
        out = jax.jit(
            lambda A_, b_, c_: simplex_batch_core(
                A_, b_, c_,
                None if basis0 is None else jnp.asarray(basis0),
                nv=nv, maxiter=maxiter,
                lane_mask=None if lane_mask is None
                else jnp.asarray(lane_mask)))(
            jnp.asarray(A), jnp.asarray(b), jnp.asarray(cf))
    return [np.asarray(o) for o in out]      # x, fun, status, niter, basis, ok


@pytest.mark.parametrize("seed", range(3))
def test_simplex_batch_core_cold_bitwise_matches_solve_lp_batch(seed):
    from repro.core import solve_lp_batch
    c, A_ub, b_ub, A_eq, b_eq = _batch_lp(seed, nb=6)
    ref = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq)
    for basis0 in (None, np.full_like(ref.basis, -1)):
        x, fun, status, niter, basis, ok = _run_core(
            c, A_ub, b_ub, A_eq, b_eq, basis0)
        assert not ok.any()
        np.testing.assert_array_equal(status, ref.status)
        np.testing.assert_array_equal(niter, ref.niter)
        np.testing.assert_array_equal(basis, ref.basis)
        np.testing.assert_array_equal(x, ref.x)          # bitwise
        np.testing.assert_array_equal(fun, ref.fun)


def test_simplex_batch_core_warm_and_rejected_match_host_dispatch():
    """Accepted lanes follow `_warm_batch_jit` (shared `_warm_init` /
    `_two_phase_virtual`); rejected/-1 lanes run cold IN the same call and
    must still match the host's subset re-solve bitwise."""
    from repro.core import solve_lp_batch
    c, A_ub, b_ub, A_eq, b_eq = _batch_lp(7, nb=6)
    cold = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq)
    rng = np.random.default_rng(3)
    c2 = c + 0.05 * rng.normal(size=c.shape)   # perturbed next period
    wb = cold.basis.copy()
    wb[::2] = -1                               # stale every other lane
    ref = solve_lp_batch(c2, A_ub, b_ub, A_eq, b_eq, warm_basis=wb)
    x, fun, status, niter, basis, ok = _run_core(
        c2, A_ub, b_ub, A_eq, b_eq, wb)
    np.testing.assert_array_equal(ok, np.asarray(ref.warm))
    np.testing.assert_array_equal(status, ref.status)
    np.testing.assert_array_equal(niter, ref.niter)
    np.testing.assert_array_equal(basis, ref.basis)
    np.testing.assert_array_equal(x, ref.x)
    np.testing.assert_array_equal(fun, ref.fun)


def test_simplex_batch_core_infeasible_lane_status():
    from repro.core import solve_lp_batch
    c, A_ub, b_ub, A_eq, b_eq = _batch_lp(5, nb=4)
    b_eq = b_eq.copy()
    b_eq[1] = 100.0                            # sum x = 100 with x <= ~3 cap
    ref = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq)
    assert ref.status[1] == INFEASIBLE
    x, fun, status, niter, basis, ok = _run_core(
        c, A_ub, b_ub, A_eq, b_eq, None)
    np.testing.assert_array_equal(status, ref.status)
    np.testing.assert_array_equal(x, ref.x)


def test_simplex_batch_core_lane_mask_zeroes_masked_lanes():
    """Masked-out lanes spend zero pivots and active lanes are untouched
    by their presence (the engine's backpressure masking)."""
    from repro.core import solve_lp_batch
    c, A_ub, b_ub, A_eq, b_eq = _batch_lp(2, nb=6)
    ref = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq)
    lane_mask = np.array([True, False, True, False, True, False])
    x, fun, status, niter, basis, ok = _run_core(
        c, A_ub, b_ub, A_eq, b_eq, None, lane_mask=lane_mask)
    np.testing.assert_array_equal(x[lane_mask], ref.x[lane_mask])
    np.testing.assert_array_equal(niter[lane_mask], ref.niter[lane_mask])
    assert (niter[~lane_mask] == 0).all()


# ---------------------------------------------------------------------------
# method="revised": the reduced-tableau revised simplex vs the dense tableau
# ---------------------------------------------------------------------------
def _run_core_m(c, A_ub, b_ub, A_eq, b_eq, basis0, method, impl="jnp",
                lane_mask=None, maxiter=None):
    import jax.numpy as jnp
    from repro.core.types import x64_scope

    from repro.core.lp import (_bucket_maxiter, _canonicalize_batch,
                               simplex_batch_core)
    A, b, cf, nv, _ = _canonicalize_batch(c, A_ub, b_ub, A_eq, b_eq)
    if maxiter is None:
        maxiter = _bucket_maxiter(50 * (A.shape[1] + 2))
    with x64_scope():
        out = simplex_batch_core(
            jnp.asarray(A), jnp.asarray(b), jnp.asarray(cf),
            None if basis0 is None else jnp.asarray(basis0),
            nv=nv, maxiter=maxiter, method=method, impl=impl,
            lane_mask=None if lane_mask is None else jnp.asarray(lane_mask))
    return [np.asarray(o) for o in out]


def _fleet_lp(B, seed=0):
    from repro.core import InstanceBatch, random_instance
    from repro.core.amr2 import build_lp_arrays_batch
    batch = InstanceBatch.stack(
        [random_instance(8, 2, T=1.2, seed=seed + s) for s in range(B)])
    return build_lp_arrays_batch(batch)


@pytest.mark.parametrize("seed", range(3))
def test_revised_cold_matches_tableau_small(seed):
    """Cold parity contract: statuses exact; OPTIMAL lanes agree on x and
    objective to fp noise.  (Pivot SEQUENCES can differ between the two
    representations on degenerate floating-point Dantzig ties — observed
    only on INFEASIBLE lanes of random batches, whose x/fun are
    meaningless — so niter/basis are deliberately not pinned cold.)"""
    c, A_ub, b_ub, A_eq, b_eq = _batch_lp(seed, nb=6)
    t = _run_core_m(c, A_ub, b_ub, A_eq, b_eq, None, "tableau")
    r = _run_core_m(c, A_ub, b_ub, A_eq, b_eq, None, "revised")
    np.testing.assert_array_equal(r[2], t[2])          # status, every lane
    opt = t[2] == OPTIMAL
    np.testing.assert_allclose(r[0][opt], t[0][opt], atol=1e-12)
    np.testing.assert_allclose(r[1][opt], t[1][opt], atol=1e-12)


@pytest.mark.parametrize("B", [64, 256])
def test_revised_fleet_parity(B):
    """The ISSUE's 64/256-device pins on real fleet LPs: cold statuses
    exact + OPTIMAL-lane optima to <= 1e-12; warm restart from the
    tableau's own optimal bases accepts/rejects identically, and every
    ACCEPTED lane is pivot-for-pivot exact (0 iterations, same basis,
    bit-identical x)."""
    c, A_ub, b_ub, A_eq, b_eq = _fleet_lp(B)
    t = _run_core_m(c, A_ub, b_ub, A_eq, b_eq, None, "tableau")
    r = _run_core_m(c, A_ub, b_ub, A_eq, b_eq, None, "revised")
    np.testing.assert_array_equal(r[2], t[2])
    opt = t[2] == OPTIMAL
    assert opt.sum() > B // 2                    # the pin is not vacuous
    np.testing.assert_allclose(r[0][opt], t[0][opt], atol=1e-12)
    np.testing.assert_allclose(r[1][opt], t[1][opt], atol=1e-12)

    tw = _run_core_m(c, A_ub, b_ub, A_eq, b_eq, t[4], "tableau")
    rw = _run_core_m(c, A_ub, b_ub, A_eq, b_eq, t[4], "revised")
    np.testing.assert_array_equal(rw[5], tw[5])  # same accept/reject set
    ok = tw[5]
    assert ok.sum() > B // 2
    assert (rw[3][ok] == 0).all()                # optimal basis: 0 pivots
    np.testing.assert_array_equal(rw[4][ok], tw[4][ok])
    np.testing.assert_array_equal(rw[0][ok], tw[0][ok])   # bitwise
    np.testing.assert_allclose(rw[1][ok], tw[1][ok], atol=1e-12)


def test_revised_pallas_impl_bit_identical():
    """The fused reduced-pivot kernel (interpret mode on CPU) replays the
    jnp reference trajectory bit for bit across a whole two-phase solve."""
    for seed in (0, 7):
        c, A_ub, b_ub, A_eq, b_eq = _batch_lp(seed, nb=6)
        ref = _run_core_m(c, A_ub, b_ub, A_eq, b_eq, None, "revised",
                          impl="jnp")
        got = _run_core_m(c, A_ub, b_ub, A_eq, b_eq, None, "revised",
                          impl="pallas")
        np.testing.assert_array_equal(got[2], ref[2])
        np.testing.assert_array_equal(got[3], ref[3])
        np.testing.assert_array_equal(got[4], ref[4])
        np.testing.assert_array_equal(got[0], ref[0])
        np.testing.assert_array_equal(got[1], ref[1])


def test_revised_infeasible_lane_status():
    c, A_ub, b_ub, A_eq, b_eq = _batch_lp(5, nb=4)
    b_eq = b_eq.copy()
    b_eq[1] = 100.0                            # sum x = 100 with x <= ~3 cap
    t = _run_core_m(c, A_ub, b_ub, A_eq, b_eq, None, "tableau")
    r = _run_core_m(c, A_ub, b_ub, A_eq, b_eq, None, "revised")
    assert r[2][1] == INFEASIBLE
    np.testing.assert_array_equal(r[2], t[2])


def test_revised_lane_mask_zeroes_masked_lanes():
    c, A_ub, b_ub, A_eq, b_eq = _batch_lp(2, nb=6)
    full = _run_core_m(c, A_ub, b_ub, A_eq, b_eq, None, "revised")
    lane_mask = np.array([True, False, True, False, True, False])
    x, fun, status, niter, basis, ok = _run_core_m(
        c, A_ub, b_ub, A_eq, b_eq, None, "revised", lane_mask=lane_mask)
    np.testing.assert_array_equal(x[lane_mask], full[0][lane_mask])
    np.testing.assert_array_equal(niter[lane_mask], full[3][lane_mask])
    assert (niter[~lane_mask] == 0).all()


# ---------------------------------------------------------------------------
# the column pricer: the AMR² LP's two-nonzero columns (`amr2.lp_column_rows`)
# ---------------------------------------------------------------------------
def _amr2_fleet_lps(case, B=48, n=12, m=2):
    """AMR² LPs as the engine builds them (`build_lp_arrays_jnp`): half the
    lanes on the paper's latencies (many identical columns, so exact
    Dantzig ties), half random.  ``case``: "cold"; "masked" (each lane
    takes a random number of its n job slots, the rest zeroed as the
    engine pads them); "es_disabled" (the backpressure replan's sentinel
    ES row, with masked slots too); "warm" (warm bases from the optimal
    bases of the previous period's perturbed LPs).  Returns ``(A, b, c,
    basis0, col_rows)``, float64 jnp arrays."""
    import jax.numpy as jnp
    from repro.core import InstanceBatch, paper_instance, random_instance
    from repro.core.amr2 import build_lp_arrays_jnp, lp_column_rows
    from repro.core.problem import ES_DISABLED_SENTINEL
    from repro.core.types import x64_scope

    insts = [paper_instance(n, T=1.2, seed=s) if s % 2 else
             random_instance(n, m, T=1.2, seed=s, p_hi=0.2)
             for s in range(B)]
    batch = InstanceBatch.stack(insts)
    p_ed, p_es = batch.p_ed, batch.p_es
    rng = np.random.default_rng(17)
    mask = np.ones((B, n), bool)
    if case in ("masked", "es_disabled"):
        mask = np.arange(n)[None, :] < rng.integers(0, n + 1, B)[:, None]
        p_ed = np.where(mask[..., None], p_ed, 0.0)
        p_es = np.where(mask, p_es, 0.0)
    if case == "es_disabled":
        p_es = np.where(mask, ES_DISABLED_SENTINEL, 0.0)
    rows = lp_column_rows(n, m)
    with x64_scope():
        A, b, c = build_lp_arrays_jnp(jnp.asarray(p_ed), jnp.asarray(p_es),
                                      jnp.asarray(batch.acc),
                                      jnp.asarray(batch.T))
        basis0 = None
        if case == "warm":
            A0, b0, c0 = build_lp_arrays_jnp(
                jnp.asarray(p_ed * rng.uniform(0.5, 2.0, p_ed.shape)),
                jnp.asarray(p_es * rng.uniform(0.5, 2.0, p_es.shape)),
                jnp.asarray(batch.acc), jnp.asarray(batch.T))
            basis0 = _solve_amr2(A0, b0, c0, None, None)[4]
    return A, b, c, basis0, rows


def _solve_amr2(A, b, c, basis0, col_rows):
    from repro.core.lp import _bucket_maxiter, simplex_batch_core
    from repro.core.types import x64_scope
    with x64_scope():
        return [np.asarray(o) for o in simplex_batch_core(
            A, b, c, basis0, nv=A.shape[2] - 2, method="revised",
            maxiter=_bucket_maxiter(50 * (A.shape[1] + 2)),
            col_rows=col_rows)]


_AMR2_CASES = ["cold", "masked", "es_disabled", "warm"]


@pytest.mark.parametrize("case", _AMR2_CASES)
def test_column_pricer_matches_dense(case):
    """On the engine's LPs the column pricer and column FTRAN match the
    dense `price_reduced_ref` / ``Binv @ A_j`` to 1 ulp, at the cold and
    the optimal factors of every lane, and the whole revised solve keeps
    its statuses, pivot counts and bases, with x / fun to 1e-12."""
    import jax.numpy as jnp
    from repro.core.lp import _column_form, _warm_init_reduced
    from repro.core.types import x64_scope
    from repro.kernels.simplex_pivot.ref import (ftran_columns_ref,
                                                price_columns_ref,
                                                price_reduced_ref)
    A, b, c, basis0, rows = _amr2_fleet_lps(case)
    dense = _solve_amr2(A, b, c, basis0, None)
    cols_ = _solve_amr2(A, b, c, basis0, rows)
    x, fun, status, niter, basis, ok = dense
    assert (status == OPTIMAL).all()
    assert case != "warm" or ok.all()
    np.testing.assert_array_equal(cols_[2], status)
    np.testing.assert_array_equal(cols_[3], niter)
    np.testing.assert_array_equal(cols_[5], ok)
    np.testing.assert_array_equal(np.sort(cols_[4], 1), np.sort(basis, 1))
    np.testing.assert_allclose(cols_[0], x, rtol=0, atol=1e-12)
    np.testing.assert_allclose(cols_[1], fun, rtol=0, atol=1e-12)

    B, R, C0 = A.shape
    with x64_scope():
        cols = _column_form(A, rows)
        eye = jnp.broadcast_to(jnp.eye(R), (B, R, R))
        cold_bas = jnp.broadcast_to(C0 + jnp.arange(R), (B, R))
        Binv_opt, _rhs, bas_opt, _ok = _warm_init_reduced(
            A, b, jnp.asarray(basis))
        zero = jnp.zeros_like(c)
        for Binv, bas in ((eye, cold_bas), (Binv_opt, bas_opt)):
            # phase 1 prices real columns at 0, so rc = -y·A exactly
            got = price_columns_ref(cols, zero, Binv, bas, 1.0)
            want = price_reduced_ref(A, zero, Binv, bas, 1.0)
            np.testing.assert_array_max_ulp(np.asarray(got),
                                            np.asarray(want), maxulp=1)
            for j in range(C0):
                jj = jnp.full(B, j, jnp.int32)
                got = ftran_columns_ref(cols, Binv, jj)
                want = jnp.einsum("brk,bk->br", Binv, A[:, :, j])
                np.testing.assert_array_max_ulp(np.asarray(got),
                                                np.asarray(want), maxulp=1)


def test_lp_column_rows_scatter_back_to_A():
    """The static column form of `amr2.lp_column_rows`, with its values
    gathered from `build_lp_arrays_jnp`'s A, scatters back to A exactly:
    two distinct rows per column, every other entry of A zero."""
    import jax.numpy as jnp
    from repro.core.amr2 import build_lp_arrays_jnp, lp_column_rows
    from repro.core.lp import _column_form
    from repro.core.types import x64_scope
    rng = np.random.default_rng(5)
    for n, m in [(1, 1), (3, 2), (12, 2), (5, 4), (7, 1)]:
        B = 3
        rows = lp_column_rows(n, m)
        C0 = n * (m + 1) + 2
        assert rows.shape == (2, C0) and (rows[0] != rows[1]).all()
        with x64_scope():
            A, _b, _c = build_lp_arrays_jnp(
                jnp.asarray(rng.uniform(0.01, 1, (B, n, m))),
                jnp.asarray(rng.uniform(0.01, 1, (B, n))),
                jnp.asarray(np.sort(rng.uniform(0.3, 0.9, (B, m + 1)), 1)),
                jnp.full(B, 1.2))
            ra, rb, va, vb = (np.asarray(v) for v in _column_form(A, rows))
        back = np.zeros(A.shape)
        cidx = np.arange(C0)
        back[:, ra, cidx] += va
        back[:, rb, cidx] += vb
        np.testing.assert_array_equal(back, np.asarray(A))
        with pytest.raises(ValueError, match="col_rows"):
            _column_form(A, rows[:, :-1])


def test_col_rows_needs_revised_jnp():
    from repro.core.lp import simplex_batch_core
    A, b, c, _basis0, rows = _amr2_fleet_lps("cold", B=2, n=2)
    for kw in (dict(method="tableau"), dict(method="revised",
                                            impl="pallas")):
        with pytest.raises(ValueError, match="col_rows"):
            simplex_batch_core(A, b, c, None, nv=A.shape[2] - 2, maxiter=8,
                               col_rows=rows, **kw)


def test_simplex_batch_core_unknown_method_raises():
    from repro.core.lp import simplex_batch_core
    c, A_ub, b_ub, A_eq, b_eq = _batch_lp(0, nb=2)
    from repro.core.lp import _canonicalize_batch
    A, b, cf, nv, _ = _canonicalize_batch(c, A_ub, b_ub, A_eq, b_eq)
    with pytest.raises(ValueError, match="method"):
        simplex_batch_core(A, b, cf, None, nv=nv, maxiter=8,
                           method="dense")


def test_solve_lp_batch_method_revised_host_dispatch():
    """`solve_lp_batch(method="revised")` resolves warm AND rejected lanes
    in one jitted call (no pow2-padded subset re-solve) and agrees with
    the tableau dispatch on status, acceptance, and optima."""
    from repro.core import solve_lp_batch
    c, A_ub, b_ub, A_eq, b_eq = _batch_lp(1)
    ref = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq)
    got = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq, method="revised")
    np.testing.assert_array_equal(got.status, ref.status)
    opt = np.asarray(ref.status) == OPTIMAL
    np.testing.assert_allclose(got.x[opt], ref.x[opt], atol=1e-12)
    np.testing.assert_allclose(got.fun[opt], ref.fun[opt], atol=1e-12)

    wb = np.asarray(ref.basis).copy()
    wb[::2] = -1                               # stale every other lane
    wref = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq, warm_basis=wb)
    wgot = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq, warm_basis=wb,
                          method="revised")
    np.testing.assert_array_equal(wgot.warm, wref.warm)
    np.testing.assert_array_equal(wgot.status, wref.status)
    accepted = np.asarray(wref.warm)
    np.testing.assert_array_equal(wgot.basis[accepted], wref.basis[accepted])
    np.testing.assert_allclose(wgot.x[accepted], wref.x[accepted],
                               atol=1e-12)

    with pytest.raises(ValueError, match="method"):
        solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq, method="etas")


# ---------------------------------------------------------------------------
# explicit maxiter= caps the TWO-PHASE TOTAL (regression: each phase used
# to spend the full budget, so niter could reach 2x the requested cap)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_explicit_maxiter_caps_two_phase_total(backend):
    c, A_ub, b_ub, A_eq, b_eq = _random_lp(3)
    full = solve_lp(c, A_ub, b_ub, A_eq, b_eq, backend=backend)
    assert full.status == OPTIMAL and full.niter > 4
    for cap in (1, 3, full.niter - 1):
        res = solve_lp(c, A_ub, b_ub, A_eq, b_eq, backend=backend,
                       maxiter=cap)
        assert res.niter <= cap, \
            f"maxiter={cap} but {res.niter} iterations ran"
    # a budget of exactly the cold pivot count still certifies optimality
    # (the cap check runs AFTER the optimality check on both backends)
    exact = solve_lp(c, A_ub, b_ub, A_eq, b_eq, backend=backend,
                     maxiter=full.niter)
    assert exact.status == OPTIMAL and exact.niter == full.niter


@pytest.mark.parametrize("method", ["tableau", "revised"])
def test_batched_explicit_maxiter_caps_two_phase_total(method):
    from repro.core import solve_lp_batch
    c, A_ub, b_ub, A_eq, b_eq = _batch_lp(4)
    res = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq, maxiter=3,
                         method=method)
    assert (np.asarray(res.niter) <= 3).all()
    assert (np.asarray(res.status) == 1).any()   # ITERATION_LIMIT surfaced
