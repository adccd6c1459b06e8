"""AMR^2 — validates the paper's Lemma 1, Theorems 1 & 2, Corollary 1,
plus optimality of the sub-ILP solver against the literal Algorithm 2."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (amr2, algorithm2_case_tree, brute_force,
                        fractional_jobs, greedy_rra, paper_instance,
                        random_instance, solve_lp_relaxation, solve_sub_ilp,
                        OffloadInstance)


def _small_instances():
    out = []
    for seed in range(12):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(3, 8))
        m = int(rng.integers(1, 4))
        T = float(rng.uniform(0.2, 2.0))
        out.append(random_instance(n, m, T, seed=seed))
    for seed, T in [(0, 0.5), (1, 1.0), (2, 2.0), (3, 4.0)]:
        out.append(paper_instance(6, T=T, seed=seed))
    return out


SMALL = _small_instances()


# -------------------------------------------------------------- Lemma 1 ---
@pytest.mark.parametrize("seed", range(10))
def test_lemma1_at_most_two_fractional(seed):
    inst = random_instance(20, 3, T=1.0, seed=seed)
    xbar, _, status, _ = solve_lp_relaxation(inst)
    if status != 0:
        pytest.skip("infeasible relaxation")
    assert len(fractional_jobs(xbar)) <= 2


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 30),
       m=st.integers(1, 5))
def test_lemma1_property(seed, n, m):
    rng = np.random.default_rng(seed)
    inst = random_instance(n, m, T=float(rng.uniform(0.1, 4.0)), seed=seed)
    xbar, _, status, _ = solve_lp_relaxation(inst)
    if status != 0:
        return
    assert len(fractional_jobs(xbar)) <= 2
    # and the relaxation respects its own constraints
    assert np.allclose(xbar.sum(axis=1), 1.0, atol=1e-5)


# ---------------------------------------------------------- Theorem 1/2 ---
@pytest.mark.parametrize("idx", range(len(SMALL)))
def test_theorems_vs_oracle(idx):
    inst = SMALL[idx]
    opt = brute_force(inst)
    sched = amr2(inst)
    if opt is None:
        return  # P infeasible; theorems are conditioned on feasibility
    # Theorem 1: makespan <= 2T
    assert sched.ed_makespan <= 2 * inst.T + 1e-9
    assert sched.es_makespan <= 2 * inst.T + 1e-9
    # Theorem 2: A* <= A† + 2(a_{m+1} - a_1)
    gap = 2 * (inst.acc[-1] - inst.acc[0])
    assert opt.total_accuracy <= sched.total_accuracy + gap + 1e-6
    # LP upper bound dominates the optimum
    assert sched.lp_accuracy is not None
    assert sched.lp_accuracy >= opt.total_accuracy - 1e-6


@pytest.mark.parametrize("idx", range(len(SMALL)))
def test_corollary1(idx):
    inst = SMALL[idx]
    if not np.all(inst.p_es <= inst.T):
        pytest.skip("corollary precondition: all ES times within T")
    opt = brute_force(inst)
    if opt is None:
        return
    sched = amr2(inst)
    gap = inst.acc[-1] - inst.acc[0]
    assert opt.total_accuracy <= sched.total_accuracy + gap + 1e-6


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_theorem1_property(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 7))
    m = int(rng.integers(1, 3))
    inst = random_instance(n, m, T=float(rng.uniform(0.2, 3.0)), seed=seed)
    opt = brute_force(inst)
    if opt is None:
        return
    sched = amr2(inst)
    assert max(sched.ed_makespan, sched.es_makespan) <= 2 * inst.T + 1e-9
    assert (opt.total_accuracy
            <= sched.total_accuracy + 2 * (inst.acc[-1] - inst.acc[0]) + 1e-6)


# -------------------------------------------------------------- sub-ILP ---
@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000), m=st.integers(1, 5))
def test_sub_ilp_enumeration_is_optimal_vs_case_tree(seed, m):
    """Where the paper's Algorithm-2 case tree yields an assignment, the
    enumerated sub-ILP must achieve at least the same accuracy; both must be
    feasible under the fresh per-tier budgets."""
    inst = random_instance(2, m, T=float(np.random.default_rng(seed).uniform(0.05, 2.0)),
                           seed=seed)
    enum = solve_sub_ilp(inst, 0, 1)
    tree = algorithm2_case_tree(inst, 0, 1)
    if enum is None:
        assert tree is None
        return

    def check(pair):
        i1, i2 = pair
        ed = (inst.p_ed[0, i1] if i1 < inst.m else 0.0) + \
             (inst.p_ed[1, i2] if i2 < inst.m else 0.0)
        es = (inst.p_es[0] if i1 == inst.m else 0.0) + \
             (inst.p_es[1] if i2 == inst.m else 0.0)
        assert ed <= inst.T + 1e-9 and es <= inst.T + 1e-9
        return inst.acc[i1] + inst.acc[i2]

    v_enum = check(enum)
    if tree is not None:
        v_tree = check(tree)
        assert v_enum >= v_tree - 1e-9


# ------------------------------------------------------------ greedy cmp --
def test_amr2_beats_greedy_on_paper_instances():
    """Paper §VII: AMR^2's total accuracy exceeds Greedy-RRA (on average by
    ~40%); we assert it is never materially worse across the paper grid."""
    wins, total = 0, 0
    for T in (0.5, 1.0, 2.0, 4.0):
        for seed in range(5):
            inst = paper_instance(30, T=T, seed=seed)
            a = amr2(inst).total_accuracy
            g = greedy_rra(inst).total_accuracy
            total += 1
            wins += a >= g - 1e-9
    assert wins == total


def test_infeasible_instance_flagged():
    inst = OffloadInstance(p_ed=np.full((3, 2), 10.0), p_es=np.full(3, 10.0),
                           acc=np.array([0.3, 0.5, 0.9]), T=1.0)
    sched = amr2(inst)
    assert sched.status in ("infeasible", "fallback")


# ---------------------------------------------------------------------------
# round_relaxation_jnp: the traced rounding vs the NumPy batched rounding
# ---------------------------------------------------------------------------
def test_round_relaxation_jnp_matches_numpy_batched():
    """The traced rounding must reproduce `round_relaxation_batch` case
    for case — zero/one/two fractional rows, infeasible and unsolved
    status codes — on real LP outputs across many instances."""
    import jax
    import jax.numpy as jnp
    from repro.core.types import x64_scope

    from repro.core.amr2 import (round_relaxation_batch,
                                 round_relaxation_jnp)
    from repro.core.lp import INFEASIBLE as LP_INFEASIBLE
    from repro.core.lp import ITERATION_LIMIT
    from repro.core.types import InstanceBatch
    from repro.core import solve_lp_relaxation

    insts = [random_instance(6, 2, T=float(0.3 + 0.2 * s), seed=100 + s)
             for s in range(10)]
    batch = InstanceBatch.stack(insts)
    xbar = np.zeros((len(insts), 6, 3))
    status = np.zeros(len(insts), dtype=np.int64)
    for i, inst in enumerate(insts):
        xb, _, st, _ = solve_lp_relaxation(inst, backend="numpy")
        xbar[i], status[i] = xb, st
    # exercise the non-OPTIMAL paths too
    status[3] = LP_INFEASIBLE
    status[7] = ITERATION_LIMIT
    ref_assign, ref_status, ref_nf = round_relaxation_batch(
        batch, xbar, status, on_error="mark")
    with x64_scope():
        got = jax.jit(round_relaxation_jnp)(
            jnp.asarray(batch.p_ed), jnp.asarray(batch.p_es),
            jnp.asarray(batch.acc), jnp.asarray(batch.T),
            jnp.asarray(xbar), jnp.asarray(status))
    assign, sched_status, nf = [np.asarray(o) for o in got]
    np.testing.assert_array_equal(assign, ref_assign)
    np.testing.assert_array_equal(sched_status, ref_status)
    np.testing.assert_array_equal(nf, ref_nf)
    # the suite exercised at least one fractional lane
    assert (ref_nf > 0).any()


def test_round_relaxation_jnp_forced_fractional_rows():
    """Hand-built xbar rows force the one- and two-fractional branches
    (including the infeasible-pair fallback)."""
    import jax
    import jax.numpy as jnp
    from repro.core.types import x64_scope

    from repro.core.amr2 import (round_relaxation_batch,
                                 round_relaxation_jnp)
    from repro.core.types import InstanceBatch

    insts = [random_instance(4, 2, T=0.8, seed=s) for s in range(4)]
    # lane 3: nothing fits -> rounding falls back to argmin p_ed
    tiny = insts[3]
    insts[3] = OffloadInstance(p_ed=tiny.p_ed + 10.0, p_es=tiny.p_es + 10.0,
                               acc=tiny.acc, T=tiny.T)
    batch = InstanceBatch.stack(insts)
    xbar = np.zeros((4, 4, 3))
    xbar[:, :, 0] = 1.0                     # integral base
    xbar[1, 2] = [0.5, 0.5, 0.0]           # one fractional row
    xbar[2, 0] = [0.4, 0.6, 0.0]           # two fractional rows
    xbar[2, 3] = [0.0, 0.3, 0.7]
    xbar[3, 1] = [0.5, 0.5, 0.0]           # fractional AND infeasible fit
    xbar[3, 2] = [0.9, 0.0, 0.1]
    status = np.zeros(4, dtype=np.int64)
    ref_assign, ref_status, ref_nf = round_relaxation_batch(
        batch, xbar, status)
    with x64_scope():
        got = jax.jit(round_relaxation_jnp)(
            jnp.asarray(batch.p_ed), jnp.asarray(batch.p_es),
            jnp.asarray(batch.acc), jnp.asarray(batch.T),
            jnp.asarray(xbar), jnp.asarray(status))
    assign, sched_status, nf = [np.asarray(o) for o in got]
    np.testing.assert_array_equal(assign, ref_assign)
    np.testing.assert_array_equal(sched_status, ref_status)
    np.testing.assert_array_equal(nf, ref_nf)
    assert ref_nf.tolist() == [0, 1, 2, 2]
