"""Period-T serving loop (the paper's deployment model, §III-C).

Every period: drain the request queue, build the OffloadInstance from the
current TierProfile, plan (AMR^2 / AMDP / dual), execute across the tiers,
then *audit*: if measured per-model latency drifts from the profile by more
than `straggler_threshold`, the profile is re-measured (EMA update) so the
next period's p_ij reflect the degraded tier — the straggler-mitigation
loop.  An ES outage inside a period triggers the fallback replan.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import numpy as np

from ..api import Problem, Solution, solve
from ..core.problem import AUDIT_RTOL
from ..core.types import OffloadInstance
from .executor import ExecutionReport, execute
from .profile import TierProfile


def audit_profile(profile: TierProfile, predicted_ed: float,
                  measured_ed: float, *, threshold: float = 1.5,
                  ema: float = 0.5):
    """Shared straggler audit (single-device runtime AND fleet engine).

    When measured ED wall time drifts past ``threshold x`` the profile's
    prediction (by more than the `AUDIT_RTOL` rounding band), return a
    profile whose p_ed is EMA-rescaled toward the observed slowdown:
    ``p_ed * ((1 - ema) + ema * ratio)``.

    Returns ``(profile, updated)``; the input profile is never mutated.
    """
    if predicted_ed <= 0:
        return profile, False
    ratio = measured_ed / max(predicted_ed, 1e-9)
    if ratio <= threshold * (1 + AUDIT_RTOL):
        return profile, False
    scaled = dataclasses.replace(
        profile, p_ed=profile.p_ed * ((1 - ema) + ema * ratio))
    return scaled, True


@dataclasses.dataclass
class PeriodStats:
    n_jobs: int
    policy: str
    predicted_makespan: float
    wall_makespan: float
    total_accuracy: float
    plan_seconds: float
    violation: float
    replanned: bool
    profile_updated: bool
    # samples that fell through execution with no result (short apply-fn
    # output, unrouted job) — see executor.EXEC_DROPPED; consistent with
    # the fleet engine's n_dropped ladder metric
    n_dropped: int = 0


class ServingRuntime:
    def __init__(self, profile: TierProfile, apply_ed: List[Callable],
                 apply_es: Callable, *, T: float, policy: str = "auto",
                 straggler_threshold: float = 1.5, ema: float = 0.5):
        self.profile = profile
        self.apply_ed = apply_ed
        self.apply_es = apply_es
        self.T = T
        self.policy = policy
        self.straggler_threshold = straggler_threshold
        self.ema = ema
        self.history: List[PeriodStats] = []

    def run_period(self, jobs: List[object], job_classes: np.ndarray, *,
                   es_fail: bool = False) -> PeriodStats:
        inst = self.profile.instance(job_classes, self.T)
        sol = solve(Problem.from_instance(inst), policy=self.policy)
        report = execute(sol, self.apply_ed, self.apply_es, jobs,
                         es_fail=es_fail)
        updated = self._audit(sol, report, job_classes)
        stats = PeriodStats(
            n_jobs=len(jobs), policy=sol.solver_name,
            predicted_makespan=float(sol.makespan),
            wall_makespan=report.wall_makespan,
            total_accuracy=float(sol.accuracy),
            plan_seconds=sol.plan_seconds,
            violation=max(0.0, report.wall_makespan / self.T - 1.0),
            replanned=report.replanned, profile_updated=updated,
            n_dropped=report.n_dropped)
        self.history.append(stats)
        return stats

    def _audit(self, sol: Solution, report: ExecutionReport,
               job_classes: np.ndarray) -> bool:
        """Straggler detection: compare measured tier wall time against the
        profile's prediction; EMA-update the profile on drift.  Replanned
        periods are skipped — their measured walls reflect the fallback
        schedule, not the profile being audited.

        ``sol`` is an api `Solution` (or a legacy `Plan`, for callers still
        on the shims)."""
        if report.replanned:
            return False
        predicted_ed = (sol.schedule.ed_makespan if hasattr(sol, "schedule")
                        else float(sol.ed_makespan))
        self.profile, updated = audit_profile(
            self.profile, predicted_ed, report.ed_wall,
            threshold=self.straggler_threshold, ema=self.ema)
        return updated
