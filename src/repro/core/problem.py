"""First-class problem/solution values for the unified solver API.

`OffloadInstance`/`InstanceBatch` (types.py) are the validated NumPy
containers the core solvers consume.  This module adds the *API-level*
values `repro.api` traffics in:

  * ``Problem``       — one device's offloading problem, a frozen dataclass
                        registered as a JAX pytree so it can be
                        ``device_put`` / vmapped / (later) sharded.
  * ``FleetProblem``  — B stacked, padded, same-shape problems plus the
                        ``real_mask`` marking which job slots are real
                        (phantom padding rows carry p = 0 on every tier).
                        Also a registered pytree: ``tree_flatten`` yields
                        the five arrays, so a whole fleet moves across
                        devices as one value (ROADMAP: sharded 10k-device
                        planning).
  * ``Solution``      — the uniform result every registry solver returns:
                        dense assignment(s), status/solver tags, timing,
                        and lazily computed accuracy/makespan metrics.

Conversions to the legacy containers (`to_instance`, `to_batch`) are cheap
views over the same arrays, so the registry solvers reuse the existing
core implementations unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Union

import jax
import numpy as np

from .types import InstanceBatch, OffloadInstance, Schedule, next_pow2

# Shares codes with core.amr2.STATUS_NAMES (ok/fallback/infeasible from the
# vectorized rounding path, "unsolved" for an LP that hit its iteration
# limit or went unbounded) plus the LP bound-only pseudo-status at 3.
SOLUTION_STATUS_NAMES = ("ok", "fallback", "infeasible", "bound", "unsolved")
ST_BOUND = 3
ST_UNSOLVED = 4

# Uniform huge ES sentinel: makes offloading infeasible for real jobs on the
# ES-disabled (backpressure / outage) paths, same trick as the legacy
# `replan_without_es`.
ES_DISABLED_SENTINEL = 1e9

# Relative band an audit ratio must clear above the straggler threshold.
# With ema = 0.5 a 3x drift lands the EMA exactly on the threshold (belief
# 2x, ratio 3/2), so without the band the decision at that fixed point
# follows the rounding of whichever program computed the ratio: sharded vs
# unsharded, chip vs host, one compiler version vs the next.
AUDIT_RTOL = 1e-9


def _register_pytree(cls, fields: "tuple[str, ...]") -> None:
    """Register a frozen dataclass whose listed fields are all leaves.

    Unflatten bypasses ``__init__`` (object.__new__ + setattr) so traced
    values survive a flatten/unflatten round-trip without hitting the
    NumPy validation in ``__post_init__``.
    """
    def flatten(obj):
        return tuple(getattr(obj, f) for f in fields), None

    def unflatten(_aux, children):
        obj = object.__new__(cls)
        for f, v in zip(fields, children):
            object.__setattr__(obj, f, v)
        return obj

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)


@dataclasses.dataclass(frozen=True)
class Problem:
    """One device's offloading problem (the paper's P) as a pytree value."""

    p_ed: np.ndarray   # (n, m) float — per-job ED-model seconds
    p_es: np.ndarray   # (n,)  float — per-job total ES seconds (comm incl.)
    acc: np.ndarray    # (m+1,) float — model accuracies, acc[m] = ES
    T: float           # period budget

    def __post_init__(self):
        object.__setattr__(self, "p_ed", np.asarray(self.p_ed, np.float64))
        object.__setattr__(self, "p_es", np.asarray(self.p_es, np.float64))
        object.__setattr__(self, "acc", np.asarray(self.acc, np.float64))
        if self.p_ed.ndim != 2:
            raise ValueError("p_ed must be (n, m)")
        if self.p_es.shape != (self.n,):
            raise ValueError("p_es must be (n,)")
        if self.acc.shape != (self.m + 1,):
            raise ValueError("acc must be (m+1,)")

    @property
    def n(self) -> int:
        return self.p_ed.shape[0]

    @property
    def m(self) -> int:
        return self.p_ed.shape[1]

    @property
    def es_index(self) -> int:
        return self.m

    def is_identical(self, rtol: float = 1e-9) -> bool:
        return self.to_instance().is_identical(rtol=rtol)

    # ---- interop ---------------------------------------------------------
    @classmethod
    def from_instance(cls, inst: OffloadInstance) -> "Problem":
        return cls(p_ed=inst.p_ed, p_es=inst.p_es, acc=inst.acc,
                   T=float(inst.T))

    def to_instance(self) -> OffloadInstance:
        return OffloadInstance(p_ed=self.p_ed, p_es=self.p_es, acc=self.acc,
                               T=float(self.T))

    def es_disabled(self) -> "Problem":
        """The ES-disabled variant: offloading made infeasible for every
        job (the paper's m-model special case)."""
        return Problem(p_ed=self.p_ed.copy(),
                       p_es=np.full(self.n, ES_DISABLED_SENTINEL),
                       acc=self.acc.copy(), T=self.T)


@dataclasses.dataclass(frozen=True)
class FleetProblem:
    """B stacked same-shape problems + the real-job mask, as one pytree.

    Job slots where ``real_mask`` is False are phantom padding: p_ed and
    p_es are 0 (free on every tier, so they never distort the real jobs'
    trade-offs) and they are masked out of every `Solution` metric."""

    p_ed: np.ndarray       # (B, n, m) float
    p_es: np.ndarray       # (B, n)  float
    acc: np.ndarray        # (B, m+1) float
    T: np.ndarray          # (B,)  float
    real_mask: np.ndarray  # (B, n) bool

    def __post_init__(self):
        object.__setattr__(self, "p_ed", np.asarray(self.p_ed, np.float64))
        object.__setattr__(self, "p_es", np.asarray(self.p_es, np.float64))
        object.__setattr__(self, "acc", np.asarray(self.acc, np.float64))
        object.__setattr__(self, "T", np.asarray(self.T, np.float64))
        object.__setattr__(self, "real_mask",
                           np.asarray(self.real_mask, bool))
        if self.p_ed.ndim != 3:
            raise ValueError("p_ed must be (B, n, m)")
        B, n, m = self.p_ed.shape
        if self.p_es.shape != (B, n):
            raise ValueError("p_es must be (B, n)")
        if self.acc.shape != (B, m + 1):
            raise ValueError("acc must be (B, m+1)")
        if self.T.shape != (B,):
            raise ValueError("T must be (B,)")
        if self.real_mask.shape != (B, n):
            raise ValueError("real_mask must be (B, n)")

    def __len__(self) -> int:
        return self.p_ed.shape[0]

    @property
    def n(self) -> int:
        return self.p_ed.shape[1]

    @property
    def m(self) -> int:
        return self.p_ed.shape[2]

    def __getitem__(self, b: int) -> Problem:
        """Device b's (still padded) problem."""
        return Problem(p_ed=self.p_ed[b], p_es=self.p_es[b], acc=self.acc[b],
                       T=float(self.T[b]))

    def identical_mask(self, rtol: float = 1e-9) -> np.ndarray:
        """(B,) bool — `Problem.is_identical` vectorized over the batch
        (all job slots, phantoms included: the criterion the batched
        planner dispatch has always used)."""
        return self.to_batch().identical_mask(rtol=rtol)

    def take(self, rows: np.ndarray) -> "FleetProblem":
        """Row-subset (or row-repeat) view used for sub-batch dispatch."""
        return FleetProblem(p_ed=self.p_ed[rows], p_es=self.p_es[rows],
                            acc=self.acc[rows], T=self.T[rows],
                            real_mask=self.real_mask[rows])

    # ---- constructors ----------------------------------------------------
    @classmethod
    def from_arrays_unchecked(cls, p_ed, p_es, acc, T,
                              real_mask) -> "FleetProblem":
        """Construct WITHOUT `__post_init__` coercion/validation — for
        traced (jit/scan/shard_map) code where the fields are jax tracers,
        not NumPy arrays.  The pure-functional engine builds its period
        `FleetProblem` this way; everything downstream only relies on the
        pytree structure, so flatten/`device_put`/`shard_map` all work on
        the result exactly as on a validated instance."""
        obj = object.__new__(cls)
        for f, v in (("p_ed", p_ed), ("p_es", p_es), ("acc", acc),
                     ("T", T), ("real_mask", real_mask)):
            object.__setattr__(obj, f, v)
        return obj

    @classmethod
    def from_batch(cls, batch: InstanceBatch,
                   real_mask: Optional[np.ndarray] = None) -> "FleetProblem":
        if real_mask is None:
            real_mask = np.ones(batch.p_es.shape, dtype=bool)
        return cls(p_ed=batch.p_ed, p_es=batch.p_es, acc=batch.acc,
                   T=batch.T, real_mask=real_mask)

    @classmethod
    def from_problems(cls, problems: Sequence[Problem],
                      pad_to: Optional[int] = None) -> "FleetProblem":
        """Stack problems sharing one model count m, padding each job axis
        with phantom (p = 0) slots up to ``pad_to`` (default: the max job
        count, bucketed to a power of two for jit-trace reuse)."""
        problems = list(problems)
        if not problems:
            raise ValueError("cannot stack an empty problem list")
        m = problems[0].m
        for p in problems[1:]:
            if p.m != m:
                raise ValueError(
                    f"problems must share the model count m; got {p.m} "
                    f"vs {m}")
        n_pad = pad_to if pad_to is not None else next_pow2(
            max(p.n for p in problems))
        if any(p.n > n_pad for p in problems):
            raise ValueError(f"job count exceeds pad_to={n_pad}")
        B = len(problems)
        p_ed = np.zeros((B, n_pad, m))
        p_es = np.zeros((B, n_pad))
        mask = np.zeros((B, n_pad), dtype=bool)
        for b, p in enumerate(problems):
            p_ed[b, :p.n] = p.p_ed
            p_es[b, :p.n] = p.p_es
            mask[b, :p.n] = True
        return cls(p_ed=p_ed, p_es=p_es,
                   acc=np.stack([p.acc for p in problems]),
                   T=np.array([p.T for p in problems]), real_mask=mask)

    def to_batch(self) -> InstanceBatch:
        return InstanceBatch(p_ed=self.p_ed, p_es=self.p_es, acc=self.acc,
                             T=self.T)

    def instance(self, b: int, strip: bool = False) -> OffloadInstance:
        """Device b as a legacy OffloadInstance (``strip=True`` drops the
        phantom slots)."""
        if strip:
            keep = self.real_mask[b]
            return OffloadInstance(p_ed=self.p_ed[b][keep],
                                   p_es=self.p_es[b][keep],
                                   acc=self.acc[b], T=float(self.T[b]))
        return OffloadInstance(p_ed=self.p_ed[b], p_es=self.p_es[b],
                               acc=self.acc[b], T=float(self.T[b]))


_register_pytree(Problem, ("p_ed", "p_es", "acc", "T"))
_register_pytree(FleetProblem, ("p_ed", "p_es", "acc", "T", "real_mask"))


@dataclasses.dataclass
class Solution:
    """Uniform solver result for both single and fleet problems.

    ``assignment`` is (n,) for a `Problem` and (B, n) for a `FleetProblem`;
    ``status`` is an int code (or (B,) codes) into `SOLUTION_STATUS_NAMES`;
    ``solver`` is the registry name (or a (B,) object array of names when a
    dispatching policy mixed solvers across the fleet).  Metrics are
    computed on demand from the *current* assignment — they are not cached,
    so in-place assignment edits (e.g. the engine's backpressure rewrite)
    stay consistent."""

    problem: Union[Problem, FleetProblem]
    assignment: np.ndarray
    status: np.ndarray                 # () or (B,) int codes
    solver: Union[str, np.ndarray]
    plan_seconds: float = 0.0
    lp_accuracy: Optional[np.ndarray] = None    # A*_LP bound when available
    n_fractional: Optional[np.ndarray] = None
    # optimal simplex basis from LP-backed solvers (amr2/lp): (R,) or (B, R)
    # int, -1 rows for devices another solver handled.  Feed it back as
    # `solve(..., warm_start=solution.basis)` to warm-start the next period.
    basis: Optional[np.ndarray] = None
    # exact legacy Schedule(s) when the solver produced them (object paths)
    _schedules: Optional[List[Schedule]] = dataclasses.field(
        default=None, repr=False)
    _per_model: Optional[Dict[int, np.ndarray]] = dataclasses.field(
        default=None, repr=False)

    @property
    def is_fleet(self) -> bool:
        return self.assignment.ndim == 2

    # ---- status / solver tags -------------------------------------------
    @property
    def status_name(self) -> Union[str, List[str]]:
        if self.is_fleet:
            return [SOLUTION_STATUS_NAMES[int(s)] for s in
                    np.atleast_1d(self.status)]
        return SOLUTION_STATUS_NAMES[int(self.status)]

    @property
    def solver_name(self) -> str:
        """Scalar solver tag (fleet: unique name or 'mixed')."""
        if isinstance(self.solver, str):
            return self.solver
        names = {str(s) for s in np.atleast_1d(self.solver)}
        return names.pop() if len(names) == 1 else "mixed"

    # ---- derived metrics -------------------------------------------------
    def _mask(self) -> np.ndarray:
        if isinstance(self.problem, FleetProblem):
            return self.problem.real_mask
        return np.ones(self.assignment.shape, dtype=bool)

    @property
    def accuracy(self) -> Union[float, np.ndarray]:
        """Summed accuracy over real jobs (per device for fleets)."""
        p = self.problem
        if self.is_fleet:
            rows = np.arange(len(p))[:, None]
            acc_jobs = p.acc[rows, self.assignment]
            return np.where(self._mask(), acc_jobs, 0.0).sum(axis=1)
        return float(p.acc[self.assignment].sum())

    @property
    def ed_makespan(self) -> Union[float, np.ndarray]:
        p = self.problem
        m = p.m
        if self.is_fleet:
            on_ed = self._mask() & (self.assignment < m)
            picked = np.clip(self.assignment, 0, m - 1)[..., None]
            ed = np.take_along_axis(p.p_ed, picked, axis=2)[..., 0]
            return np.where(on_ed, ed, 0.0).sum(axis=1)
        on_ed = self.assignment < m
        if not on_ed.any():
            return 0.0
        j = np.nonzero(on_ed)[0]
        return float(p.p_ed[j, self.assignment[j]].sum())

    @property
    def es_makespan(self) -> Union[float, np.ndarray]:
        p = self.problem
        offl = self._mask() & (self.assignment == p.m)
        if self.is_fleet:
            return np.where(offl, p.p_es, 0.0).sum(axis=1)
        return float(p.p_es[offl].sum())

    @property
    def makespan(self) -> Union[float, np.ndarray]:
        return np.maximum(self.ed_makespan, self.es_makespan) \
            if self.is_fleet else max(self.ed_makespan, self.es_makespan)

    @property
    def violation(self) -> Union[float, np.ndarray]:
        if self.is_fleet:
            return np.maximum(0.0, self.makespan / self.problem.T - 1.0)
        return max(0.0, self.makespan / self.problem.T - 1.0)

    @property
    def per_model(self) -> Dict[int, np.ndarray]:
        """model index -> job ids (single-problem solutions only).  Cached:
        the executor reads it repeatedly, and single-problem assignments
        are never mutated in place (only fleet ones are, and those raise
        here)."""
        if self.is_fleet:
            raise ValueError("per_model is per-device; index a fleet "
                             "Solution via to_schedule(b)")
        if self._per_model is None:
            a = self.assignment
            self._per_model = {i: np.nonzero(a == i)[0]
                               for i in range(self.problem.m + 1)}
        return self._per_model

    # ---- legacy interop --------------------------------------------------
    def _lp_acc_at(self, b: Optional[int]) -> Optional[float]:
        """LP bound as a float-or-None (NaN marks 'no bound': LP infeasible
        rows in a batched solve)."""
        if self.lp_accuracy is None:
            return None
        v = float(np.atleast_1d(self.lp_accuracy)[b if b is not None else 0])
        return None if np.isnan(v) else v

    def to_schedule(self, b: Optional[int] = None) -> Schedule:
        """The device's legacy `Schedule` (pass ``b`` for fleet solutions)."""
        if self.is_fleet:
            if b is None:
                raise ValueError("fleet Solution: pass the device index b")
            if self._schedules is not None:
                return self._schedules[b]
            return Schedule(
                assignment=np.asarray(self.assignment[b]),
                instance=self.problem.instance(b),
                lp_accuracy=self._lp_acc_at(b),
                n_fractional=(None if self.n_fractional is None else
                              int(np.atleast_1d(self.n_fractional)[b])),
                status=SOLUTION_STATUS_NAMES[int(self.status[b])],
                solver=str(np.atleast_1d(self.solver)[b]
                           if not isinstance(self.solver, str)
                           else self.solver))
        if self._schedules is not None:
            return self._schedules[0]
        return Schedule(
            assignment=self.assignment,
            instance=self.problem.to_instance(),
            lp_accuracy=self._lp_acc_at(None),
            n_fractional=(None if self.n_fractional is None
                          else int(self.n_fractional)),
            status=SOLUTION_STATUS_NAMES[int(self.status)],
            solver=str(self.solver))

    def schedules(self) -> List[Schedule]:
        if not self.is_fleet:
            return [self.to_schedule()]
        return [self.to_schedule(b) for b in range(len(self.problem))]

    # ---- constructors ----------------------------------------------------
    @classmethod
    def from_schedule(cls, sched: Schedule, *, solver: str,
                      plan_seconds: float = 0.0,
                      problem: Optional[Problem] = None) -> "Solution":
        status = SOLUTION_STATUS_NAMES.index(sched.status) \
            if sched.status in SOLUTION_STATUS_NAMES else ST_BOUND
        return cls(problem=problem or Problem.from_instance(sched.instance),
                   assignment=sched.assignment,
                   status=np.int64(status), solver=solver,
                   plan_seconds=plan_seconds,
                   lp_accuracy=(None if sched.lp_accuracy is None
                                else np.float64(sched.lp_accuracy)),
                   n_fractional=(None if sched.n_fractional is None
                                 else np.int64(sched.n_fractional)),
                   _schedules=[sched])
