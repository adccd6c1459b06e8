"""The registry entries: the paper's algorithms (and the beyond-paper
extras) wrapped behind the uniform `Solver` protocol.

Each entry reuses the existing core implementation unchanged — the scalar
NumPy oracles for ``solve_one``, the vmapped/jitted batched paths for
``solve_fleet`` — and declares its capabilities so `repro.api.solve` can
dispatch without policy-specific ``elif`` chains.  Batched entries
bucket-pad the fleet axis to a power of two internally (repeating the last
row) so fluctuating fleet sizes reuse O(log B) compiled programs.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from ..core.amdp import amdp, amdp_batch
from ..core.amr2 import (ST_INFEASIBLE, ST_UNSOLVED, amr2_batch_arrays,
                         build_lp_arrays_batch, round_relaxation,
                         solve_lp_relaxation)
from ..core.dual import dual_schedule, dual_schedule_batch_arrays
from ..core.greedy import greedy_rra
from ..core.lp import INFEASIBLE, OPTIMAL, solve_lp_batch
from ..core.problem import (ST_BOUND, SOLUTION_STATUS_NAMES, FleetProblem,
                            Problem, Solution)
from ..core.types import next_pow2, x64_scope
from .registry import register_solver

_STATUS_CODE = {name: code for code, name in enumerate(SOLUTION_STATUS_NAMES)}


def _pow2_rows(B: int) -> np.ndarray:
    """Row index vector padding a B-row batch to the next power of two by
    repeating the last row (the shared jit-trace-reuse bucketing)."""
    return np.concatenate(
        [np.arange(B), np.full(next_pow2(B) - B, B - 1, dtype=np.int64)])


@register_solver(
    "amr2", batched=True, exact_on_identical=False,
    supports_es_disabled=True, warm_start=True,
    description="LP-relax + round (paper Alg. 1–2): ≤2T makespan, "
                "≤2(a_max−a_min) accuracy gap")
class AMR2Solver:
    def solve_one(self, problem: Problem, *, backend: str = "numpy",
                  frac_tol: float = 1e-4, maxiter: Optional[int] = None,
                  warm_start: Optional[np.ndarray] = None,
                  on_error: str = "raise") -> Solution:
        inst = problem.to_instance()
        xbar, a_lp, status, basis = solve_lp_relaxation(
            inst, backend=backend, maxiter=maxiter, warm_basis=warm_start)
        sched = round_relaxation(inst, xbar, a_lp, status,
                                 frac_tol=frac_tol, on_error=on_error)
        sol = Solution.from_schedule(sched, solver="amr2", problem=problem)
        sol.basis = np.asarray(basis, np.int64)
        return sol

    def solve_fleet(self, fleet: FleetProblem, *, frac_tol: float = 1e-4,
                    maxiter: Optional[int] = None,
                    warm_start: Optional[np.ndarray] = None,
                    impl: str = "jnp", on_error: str = "raise") -> Solution:
        B = len(fleet)
        rows = _pow2_rows(B)
        sub = fleet.take(rows).to_batch()
        wb = None if warm_start is None else np.asarray(warm_start)[rows]
        assign, status, n_frac, lp_acc, basis = amr2_batch_arrays(
            sub, frac_tol=frac_tol, maxiter=maxiter, warm_basis=wb,
            impl=impl, on_error=on_error)
        lp_acc = lp_acc[:B].copy()
        lp_acc[(status[:B] == ST_INFEASIBLE)
               | (status[:B] == ST_UNSOLVED)] = np.nan   # no bound
        return Solution(problem=fleet, assignment=assign[:B],
                        status=status[:B],
                        solver=np.full(B, "amr2", dtype=object),
                        lp_accuracy=lp_acc, n_fractional=n_frac[:B],
                        basis=np.asarray(basis[:B], np.int64))


@register_solver(
    "routed", batched=True, exact_on_identical=False,
    supports_es_disabled=True, warm_start=True,
    description="geometry-aware amr2: route each lane to its best covered "
                "cell, price ES by the link factor, then delegate "
                "(core.mobility; uncovered lanes plan local-only)")
class RoutedSolver:
    """Multi-cell front-end over `AMR2Solver`: the host-level twin of the
    engine's traced routing pass.  Each fleet lane is assigned a serving
    cell from its position (`core.mobility.route_cells` semantics —
    nearest / min-response-time under the coverage radius), its ES column
    is scaled by the per-(device, cell) link factor, and uncovered lanes
    get the ES-disabled sentinel (local-only plans).  The LP itself is
    amr2 unchanged, so every paper guarantee (≤2T makespan, accuracy gap)
    holds per lane under the routed prices."""

    def solve_fleet(self, fleet: FleetProblem, *, positions: np.ndarray,
                    mobility, routing: str = "nearest",
                    frac_tol: float = 1e-4,
                    maxiter: Optional[int] = None,
                    warm_start: Optional[np.ndarray] = None,
                    impl: str = "jnp", on_error: str = "raise") -> Solution:
        from ..core.mobility import route_cells, validate_mobility
        from ..core.problem import ES_DISABLED_SENTINEL
        B = len(fleet)
        pos = np.asarray(positions, np.float64)
        if pos.shape != (B, 2):
            raise ValueError(
                f"positions must be ({B}, 2) to match the fleet; got "
                f"{pos.shape}")
        validate_mobility(mobility, n_devices=B,
                          n_servers=mobility.n_cells,    # 1 server / cell
                          mode="replay", routing=routing)
        cell, covered, link_factor = (
            np.asarray(a) for a in route_cells(
                pos, mobility, np.zeros(mobility.n_cells), routing))
        p_es = fleet.p_es * link_factor[:, None]
        p_es = np.where((~covered[:, None]) & fleet.real_mask,
                        ES_DISABLED_SENTINEL, p_es)
        routed = FleetProblem(p_ed=fleet.p_ed, p_es=p_es, acc=fleet.acc,
                              T=fleet.T, real_mask=fleet.real_mask)
        sol = AMR2Solver().solve_fleet(
            routed, frac_tol=frac_tol, maxiter=maxiter,
            warm_start=warm_start, impl=impl, on_error=on_error)
        # report against the CALLER's (unrouted) problem, tagged with the
        # routing outcome so serving layers can book per-cell admission
        sol.problem = fleet
        sol.solver = np.full(B, "routed", dtype=object)
        sol.cell = cell.astype(np.int64)
        sol.link_factor = link_factor
        return sol


class _HISolverBase:
    """Shared host front-end for the online hierarchical-inference rules
    (`core.hi`): one period of per-sample decisions from an observed
    confidence matrix, with the learner advanced IN-STREAM when the
    caller feeds back the realized outcomes.

    Unlike every offline entry, the decision needs no accuracy table —
    ``fleet.acc`` is consulted only for the regret metric the engine
    books, never by the rule itself.  The traced twin lives inside the
    engine's scan (`EngineParams.with_hi` + `rollout`); this entry is
    the single-period host mirror, `RoutedSolver`-style (solve_fleet
    only)."""

    rule = "fixed"

    def solve_fleet(self, fleet: FleetProblem, *, confidence: np.ndarray,
                    hi=None, state=None, observed_local=None,
                    observed_es=None, t: int = 0, seed: int = 0,
                    n_arms: int = 9, local_model: int = 0) -> Solution:
        """Decide this period's assignments from ``confidence`` (B, n).

        ``hi`` is a `core.hi.HIModel` (default: `HIModel.make()`),
        ``state`` the incoming `HILearnerState` (default: fresh at the
        model's ``theta0``).  Passing BOTH ``observed_local`` and
        ``observed_es`` (B, n) bool outcome matrices advances the
        learner; without them the period is decide-only and the state is
        returned unchanged.  The updated state and the served threshold
        ride on the returned Solution as ``sol.hi_state`` /
        ``sol.hi_theta``."""
        import jax as _jax

        from ..core.hi import (HILearnerState, HIModel, hi_period,
                               validate_hi)
        B, n = fleet.p_es.shape
        m = fleet.p_ed.shape[2]
        hm = hi if hi is not None else HIModel.make()
        # the host mirror receives confidences directly (it never samples
        # the calibration curves), so spread's class count is its own
        validate_hi(hm, n_devices=B,
                    n_classes=np.asarray(hm.spread).shape[0], n_models=m,
                    rule=self.rule, stream="fold", n_arms=n_arms,
                    local_model=local_model)
        conf = np.asarray(confidence, np.float64)
        if conf.shape != (B, n):
            raise ValueError(
                f"confidence must be ({B}, {n}) to match the fleet; got "
                f"{conf.shape}")
        hst = state if state is not None else HILearnerState.init(
            B, n_arms, hm.theta0)
        have_obs = observed_local is not None and observed_es is not None
        cl = (np.asarray(observed_local, bool) if have_obs
              else np.zeros((B, n), bool))
        ces = (np.asarray(observed_es, bool) if have_obs
               else np.zeros((B, n), bool))
        acc_es = np.asarray(fleet.acc, np.float64)[:, m]
        with x64_scope():
            key = _jax.random.fold_in(_jax.random.PRNGKey(seed),
                                      np.int32(t))
            offload, theta_t, new_hst, _reg = hi_period(
                self.rule, hm, hst, conf, cl, ces, fleet.real_mask,
                acc_es, np.int32(t), key, n_arms)
        offload = np.asarray(offload)
        # phantoms follow the fleet convention: free ES columns
        assignment = np.where(offload | ~fleet.real_mask, m, local_model
                              ).astype(np.int64)
        sol = Solution(problem=fleet, assignment=assignment,
                       status=np.full(B, _STATUS_CODE["ok"], np.int64),
                       solver=np.full(B, self.info.name, dtype=object))
        # decide-only calls keep the incoming state: the update above ran
        # on all-False placeholder outcomes and must not be persisted
        sol.hi_state = (_jax.tree.map(np.asarray, new_hst) if have_obs
                        else hst)
        sol.hi_theta = np.asarray(theta_t)
        return sol


@register_solver(
    "hi_threshold", batched=True, exact_on_identical=False,
    supports_es_disabled=False, online=True,
    description="online hierarchical inference: offload sample j iff "
                "conf_j < theta, theta learned in-stream by OGD "
                "(arXiv 2304.00891); engine twin: "
                "EngineParams.with_hi(rule='threshold')")
class HIThresholdSolver(_HISolverBase):
    rule = "threshold"


@register_solver(
    "hi_bandit", batched=True, exact_on_identical=False,
    supports_es_disabled=False, online=True,
    description="online hierarchical inference: UCB over discretized "
                "thresholds (rule='ucb'; EXP3 via rule='exp3'); engine "
                "twin: EngineParams.with_hi(rule='ucb')")
class HIBanditSolver(_HISolverBase):
    rule = "ucb"

    def solve_fleet(self, fleet: FleetProblem, *,
                    confidence: np.ndarray, rule: str = "ucb", hi=None,
                    state=None, observed_local=None, observed_es=None,
                    t: int = 0, seed: int = 0, n_arms: int = 9,
                    local_model: int = 0) -> Solution:
        if rule not in ("ucb", "exp3"):
            raise ValueError(f"hi_bandit rule must be 'ucb' or 'exp3'; "
                             f"got {rule!r}")
        self.rule = rule
        return super().solve_fleet(
            fleet, confidence=confidence, hi=hi, state=state,
            observed_local=observed_local, observed_es=observed_es, t=t,
            seed=seed, n_arms=n_arms, local_model=local_model)


@register_solver(
    "amdp", batched=True, exact_on_identical=True,
    supports_es_disabled=True,
    description="exact pseudo-polynomial DP for identical jobs (paper §VI)")
class AMDPSolver:
    def solve_one(self, problem: Problem, *, backend: str = "numpy",
                  resolution: float = 1e-3, impl: str = "jnp") -> Solution:
        del backend                       # DP runs the same on every backend
        sched = amdp(problem.to_instance(), resolution=resolution,
                          impl=impl)
        return Solution.from_schedule(sched, solver="amdp", problem=problem)

    def solve_fleet(self, fleet: FleetProblem, *, resolution: float = 1e-3,
                    impl: str = "jnp") -> Solution:
        B = len(fleet)
        batch = fleet.to_batch()
        scheds = amdp_batch([batch[b] for b in range(B)],
                                 resolution=resolution, impl=impl)
        assignment = np.stack([s.assignment for s in scheds]) if B else \
            np.zeros((0, fleet.n), dtype=np.int64)
        status = np.array([_STATUS_CODE[s.status] for s in scheds],
                          dtype=np.int64)
        return Solution(problem=fleet, assignment=assignment, status=status,
                        solver=np.full(B, "amdp", dtype=object))


@register_solver(
    "dual", batched=True, exact_on_identical=False,
    supports_es_disabled=True,
    description="beyond-paper Lagrangian-dual bisection + density-greedy "
                "knapsack (no 2T guarantee; ~1% gap, near-free)")
class DualSolver:
    def solve_one(self, problem: Problem, *, backend: str = "numpy",
                  iters: int = 40) -> Solution:
        del backend                       # scalar path is NumPy-only
        sched = dual_schedule(problem.to_instance(), iters=iters)
        return Solution.from_schedule(sched, solver="dual", problem=problem)

    def solve_fleet(self, fleet: FleetProblem, *, iters: int = 40
                    ) -> Solution:
        B = len(fleet)
        sub = fleet.take(_pow2_rows(B)).to_batch()
        assign, status = dual_schedule_batch_arrays(sub, iters=iters)
        return Solution(problem=fleet, assignment=assign[:B],
                        status=status[:B],
                        solver=np.full(B, "dual", dtype=object))


@register_solver(
    "greedy", batched=False, exact_on_identical=False,
    supports_es_disabled=True,
    description="Greedy-RRA baseline (paper §VII): O(n), may violate T")
class GreedySolver:
    def solve_one(self, problem: Problem, *, backend: str = "numpy"
                  ) -> Solution:
        del backend                       # sequential-only (batched=False)
        sched = greedy_rra(problem.to_instance())
        return Solution.from_schedule(sched, solver="greedy", problem=problem)


@register_solver(
    "lp", batched=True, exact_on_identical=False,
    supports_es_disabled=False, bound_only=True, warm_start=True,
    description="LP relaxation A*_LP upper bound; assignment is the argmax "
                "of a possibly fractional optimum")
class LPBoundSolver:
    """Bound-only entry: `accuracy`'s integral counterpart is bounded above
    by ``lp_accuracy``; the argmax assignment need not satisfy the budgets."""

    def solve_one(self, problem: Problem, *, backend: str = "numpy",
                  maxiter: Optional[int] = None,
                  warm_start: Optional[np.ndarray] = None,
                  on_error: str = "raise") -> Solution:
        xbar, a_lp, status, basis = solve_lp_relaxation(
            problem.to_instance(), backend=backend, maxiter=maxiter,
            warm_basis=warm_start)
        if status == INFEASIBLE:
            return Solution(problem=problem,
                            assignment=np.argmin(problem.p_ed, axis=1),
                            status=np.int64(_STATUS_CODE["infeasible"]),
                            solver="lp")
        if status != OPTIMAL:
            if on_error != "mark":
                raise RuntimeError(f"LP relaxation failed (status={status})")
            return Solution(
                problem=problem,
                assignment=np.argmax(xbar, axis=1).astype(np.int64),
                status=np.int64(ST_UNSOLVED), solver="lp")
        return Solution(problem=problem,
                        assignment=np.argmax(xbar, axis=1).astype(np.int64),
                        status=np.int64(ST_BOUND), solver="lp",
                        lp_accuracy=np.float64(a_lp),
                        basis=np.asarray(basis, np.int64))

    def solve_fleet(self, fleet: FleetProblem, *,
                    maxiter: Optional[int] = None,
                    warm_start: Optional[np.ndarray] = None,
                    impl: str = "jnp", method: str = "tableau",
                    on_error: str = "raise") -> Solution:
        B = len(fleet)
        rows = _pow2_rows(B)
        sub = fleet.take(rows).to_batch()
        c, A_ub, b_ub, A_eq, b_eq = build_lp_arrays_batch(sub)
        wb = None if warm_start is None else np.asarray(warm_start)[rows]
        res = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq, maxiter=maxiter,
                             warm_basis=wb, impl=impl, method=method)
        xbar = res.x.reshape(len(sub), fleet.n, fleet.m + 1)[:B]
        st = np.asarray(res.status)[:B]
        bad = (st != OPTIMAL) & (st != INFEASIBLE)
        if bad.any() and on_error != "mark":
            raise RuntimeError(
                f"LP relaxation failed (status={int(st[bad][0])})")
        assignment = np.argmax(xbar, axis=2).astype(np.int64)
        infeas = st == INFEASIBLE
        if infeas.any():
            assignment[infeas] = np.argmin(fleet.p_ed[infeas], axis=2)
        status = np.where(infeas, _STATUS_CODE["infeasible"],
                          ST_BOUND).astype(np.int64)
        status[bad] = ST_UNSOLVED
        lp_acc = np.asarray(-res.fun, dtype=np.float64)[:B].copy()
        lp_acc[infeas | bad] = np.nan
        return Solution(problem=fleet, assignment=assignment, status=status,
                        solver=np.full(B, "lp", dtype=object),
                        lp_accuracy=lp_acc,
                        basis=np.asarray(res.basis[:B], np.int64))
