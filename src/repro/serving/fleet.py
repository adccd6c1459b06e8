"""Fleet-scale serving engine: N edge devices, a small ES pool, an
array-resident period loop that costs a handful of jitted/vectorized calls
regardless of fleet size.

The paper's deployment model is one ED offloading to one ES under a period
budget T (§III-C).  This engine runs N copies of that formulation
simultaneously and couples them through the resources the paper abstracts
away:

  * **Arrivals** — every device drains its own `RequestQueue` backlog each
    period (Poisson or trace), up to the planning-window cap.
  * **Planning** — devices live as *stacked arrays* per shape group
    (belief/base latency profiles, accuracies): padded-instance assembly is
    one masked gather per group into a `FleetProblem`, and the group plans
    via `repro.api.solve` — vmapped AMR^2 / AMDP / dual solvers from the
    registry, no per-device Schedule objects on the hot path.
  * **ES capacity** — the pool offers `n_servers x T` seconds of service per
    period.  Each server's admitted offload demand must fit in T (the
    paper's constraint (2), per server).  Devices that lose the admission
    race are *backpressured*: they replan ED-only in ONE batched
    ES-disabled solve (`api.solve(..., es_disabled=True)`) instead of a
    Python loop of scalar replans.
  * **Stragglers** — each device's true speed drifts (`DeviceSpec.drift`);
    the engine audits measured vs predicted ED wall time with the same EMA
    rule as the single-device runtime (`runtime.audit_profile`), vectorized
    across the fleet, so the next period's p_ij reflect the degraded device.
  * **Outages** — `DeviceSpec.outage` marks periods where a device's ES link
    is down; its instance is planned ED-only from the start.

`run_period_reference()` keeps the PR-1 per-device implementation (padding,
stripping, sequential backpressure replans, per-device audit) as the
benchmark baseline and parity oracle for the vectorized loop.

Padding uses phantom jobs with p_ed = 0 AND p_es = 0: free everywhere, so
the LP gives each phantom the max-accuracy (ES) assignment integrally at
zero budget cost, real-job tradeoffs are untouched, and phantoms are
stripped/masked before any accounting.  Phantom offload times must stay
*small* — a huge sentinel (e.g. 1e9) mixed into the same ES-budget row as
real sub-second p_es wrecks the simplex row scaling and silently voids the
constraint; only real jobs on the outage / backpressure paths use the
uniform huge sentinel (the same trick as `replan_without_es`).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..api import solve, solve_many
from ..core.faults import FaultModel
from ..core.instances import (PAPER_ACC, PAPER_COMM, PAPER_P_ED,
                              PAPER_P_ES_PROC)
from ..core.problem import (AUDIT_RTOL, ES_DISABLED_SENTINEL, FleetProblem,
                            Problem)
from ..core.types import OffloadInstance, Schedule, x64_scope
from .profile import TierProfile, roofline_profile
from .queue import RequestQueue
from .runtime import audit_profile

# ES-link down: uniform huge p_es, the same sentinel the api's es_disabled
# path applies to real jobs
_OUTAGE_ES = ES_DISABLED_SENTINEL


class UnsolvedPeriodError(RuntimeError):
    """A period's LP left ``n_unsolved`` lanes uncertified under
    ``strict="raise"``.

    Carries the failing ``period`` index and ``partial_stats`` — every
    `FleetPeriodStats` the engine completed *before* the failure — so a
    multi-period `run()` no longer discards the whole trajectory when one
    late period trips the iteration cap.  (`FleetEngine.history` holds
    the same records; the exception copies them for callers that lost
    the engine reference.)  The traced core has already re-planned the
    unsolved lanes with the greedy local-only fallback, so
    ``strict="warn"`` can book the period and continue instead."""

    def __init__(self, message: str, *, period: int, n_unsolved: int,
                 partial_stats: List["FleetPeriodStats"]):
        super().__init__(message)
        self.period = period
        self.n_unsolved = n_unsolved
        self.partial_stats = partial_stats


@dataclasses.dataclass
class DeviceSpec:
    """Static description of one edge device in the fleet.

    `profile` is the device's *believed* latency profile (the planner's
    starting point); `drift` holds the true per-period ED slowdown factors
    relative to that profile (cycled, 1.0 = nominal), and `outage` flags
    periods where the device's ES link is unreachable."""
    profile: TierProfile
    drift: Optional[np.ndarray] = None
    outage: Optional[np.ndarray] = None
    name: str = ""

    def drift_at(self, period: int) -> float:
        if self.drift is None or len(self.drift) == 0:
            return 1.0
        return float(self.drift[period % len(self.drift)])

    def outage_at(self, period: int) -> bool:
        if self.outage is None or len(self.outage) == 0:
            return False
        return bool(self.outage[period % len(self.outage)])


@dataclasses.dataclass
class _DeviceState:
    spec: DeviceSpec
    profile: TierProfile        # current belief (EMA-updated on stragglers)
    n_updates: int = 0


class _ShapeGroup:
    """Array-resident view of every device sharing one (classes, m) shape:
    stacked belief/base latency tables so one period's padded-instance
    assembly, pricing, and audit are whole-group array ops."""

    def __init__(self, ids: Sequence[int], states: Sequence[_DeviceState]):
        self.ids = np.asarray(ids, dtype=np.int64)
        self.classes = np.asarray(states[0].profile.classes)
        self.p_ed = np.stack([st.profile.p_ed for st in states]
                             ).astype(np.float64)          # belief (D, c, m)
        self.p_es = np.stack([st.profile.p_es for st in states]
                             ).astype(np.float64)          # (D, c)
        self.acc = np.stack([st.profile.acc for st in states]
                            ).astype(np.float64)           # (D, m+1)
        self.base_p_ed = np.stack([st.spec.profile.p_ed for st in states]
                                  ).astype(np.float64)     # truth (D, c, m)
        # last period's optimal simplex bases (D, R) for LP-backed policies
        # (-1 rows: device was planned by a non-LP solver); fed back as
        # `solve(..., warm_start=)` so consecutive periods price out of the
        # previous vertex instead of re-running two cold simplex phases
        self.warm_basis: Optional[np.ndarray] = None

    @property
    def m(self) -> int:
        return self.p_ed.shape[2]


def _ed_time_under(profile: TierProfile, job_classes: np.ndarray,
                   assignment: np.ndarray) -> float:
    """ED-tier time of a schedule priced with `profile`'s latencies."""
    if len(job_classes) == 0:
        return 0.0
    ci = np.searchsorted(np.asarray(profile.classes), job_classes)
    mask = assignment < profile.p_ed.shape[1]
    if not mask.any():
        return 0.0
    return float(profile.p_ed[ci[mask], assignment[mask]].sum())


@dataclasses.dataclass
class FleetPeriodStats:
    period: int
    n_devices: int
    n_jobs: int                 # real (non-phantom) jobs planned
    plan_seconds: float         # wall time spent planning the whole fleet
    total_accuracy: float
    mean_job_accuracy: float
    n_violations: int           # devices whose wall makespan exceeded T
    worst_violation: float      # max over devices of makespan/T - 1
    n_offloading: int           # devices that planned ES work
    n_backpressured: int        # devices bumped off the ES pool
    n_outage: int
    n_straggler_updates: int
    es_utilization: float       # admitted demand / (n_servers * T)
    backlog: int                # jobs still queued after this period
    # realized execution (chaos; see repro.serving.faults) — fault-free
    # periods report n_offload_ok == n_offload_samples, zero ladder
    # counters, and realized_makespan == the priced fleet makespan
    n_offload_samples: int = 0  # admitted offloaded samples this period
    n_offload_ok: int = 0       # of those, completed via the ES
    n_deadline_miss: int = 0    # samples past the 2T realized deadline
    n_retries: int = 0          # ladder rung 1: retransmission attempts
    n_fallback_local: int = 0   # ladder rung 2: local-model completions
    n_dropped: int = 0          # ladder rung 3: accuracy-0 drops
    realized_makespan: float = 0.0  # max realized device wall (seconds)
    n_es_audit_updates: int = 0  # ES-latency beliefs EMA-inflated (chaos)
    # online hierarchical inference (repro.serving.hi) — every sample
    # runs the local model, so n_hi_offloaded + n_hi_local_final ==
    # n_jobs per period; exact zeros while HI is disarmed
    n_hi_offloaded: int = 0      # samples that consulted the ES
    n_hi_local_final: int = 0    # samples served by the local model alone
    hi_regret: float = 0.0       # fleet cumulative pseudo-regret vs theta*


class EdgeServerPool:
    """A pool of `n_servers` ES tiers, each offering T seconds per period.

    Admission is a greedy heuristic — ascending demand, least-loaded server
    first — so small demands are favoured and every admitted server load
    respects the paper's constraint (2).  It is NOT optimal bin packing:
    adversarial demand sets can admit one device fewer than an exact
    packing would."""

    def __init__(self, n_servers: int):
        if n_servers <= 0:
            raise ValueError("n_servers must be positive")
        self.n_servers = n_servers

    def admit(self, demands: Dict[int, float], T: float):
        """demands: device id -> ES seconds requested.  Returns
        (admitted ids, per-server loads).

        Iteration order is (demand, device-id)-sorted — never dict
        insertion order — so admission is deterministic for any way the
        caller assembled the dict, and identical to the vectorized
        `admit_mask` / traced `repro.api.engine` admission scan
        (regression-pinned in tests/test_engine_v2.py)."""
        loads = np.zeros(self.n_servers)
        admitted: List[int] = []
        for dev in sorted(demands, key=lambda d: (demands[d], d)):
            need = demands[dev]
            slot = int(np.argmin(loads))
            if loads[slot] + need <= T + 1e-12:
                loads[slot] += need
                admitted.append(dev)
        return admitted, loads

    def admit_mask(self, demands: np.ndarray, T: float):
        """Dense-array admission: ``demands`` is (D,) ES seconds per device
        (<= 0 marks "not offloading").  Returns ``(admitted (D,) bool,
        per-server loads)`` with exactly the `admit` ordering semantics —
        ascending demand, device id on ties, least-loaded server first.
        This is the NumPy twin of the traced admission scan the
        pure-functional engine runs (`repro.api.engine.admit_mask_jnp`)."""
        demands = np.asarray(demands, dtype=np.float64)
        eff = np.where(demands > 0, demands, np.inf)
        order = np.argsort(eff, kind="stable")       # ties -> id order
        loads = np.zeros(self.n_servers)
        mask = np.zeros(len(demands), dtype=bool)
        for d in order:
            need = float(demands[d])
            if need <= 0:        # the +inf tail: non-offloaders
                break
            slot = int(np.argmin(loads))
            if loads[slot] + need <= T + 1e-12:
                loads[slot] += need
                mask[d] = True
        return mask, loads


def _padded_instance(profile: TierProfile, job_classes: np.ndarray, T: float,
                     n_total: int, *, disable_es: bool) -> OffloadInstance:
    """Device instance padded with phantom jobs to the fleet-wide job count."""
    k = len(job_classes)
    if k > n_total:
        raise ValueError(f"{k} jobs exceed planning window {n_total}")
    m = profile.p_ed.shape[1]
    p_ed = np.zeros((n_total, m))
    p_es = np.zeros(n_total)        # phantoms: free ES, stripped later
    if k:
        ci = np.searchsorted(np.asarray(profile.classes), job_classes)
        p_ed[:k] = profile.p_ed[ci]
        p_es[:k] = _OUTAGE_ES if disable_es else profile.p_es[ci]
    return OffloadInstance(p_ed=p_ed, p_es=p_es, acc=profile.acc.copy(), T=T)


def _strip_phantoms(padded: Schedule, k: int) -> Schedule:
    """Schedule over the first k (real) jobs of a padded instance."""
    inst = padded.instance
    real = OffloadInstance(p_ed=inst.p_ed[:k], p_es=inst.p_es[:k],
                           acc=inst.acc, T=inst.T)
    return Schedule(assignment=padded.assignment[:k].copy(), instance=real,
                    lp_accuracy=None, n_fractional=padded.n_fractional,
                    status=padded.status, solver=padded.solver)


@dataclasses.dataclass
class FleetConfig:
    """Declarative fleet-engine construction: the policy, the backpressure
    behaviour (ES pool size), the traffic model, and the fleet composition
    in one value — `FleetEngine.from_config` is the one-call equivalent of
    the `make_fleet` + `RequestQueue` + `FleetEngine` recipe.

    Pass ``devices`` to use an explicit fleet; otherwise a heterogeneous
    `make_fleet(n_devices, ...)` fleet is generated from ``seed`` and the
    composition fractions below."""

    # engine
    n_devices: int
    T: float
    n_servers: int = 1
    policy: str = "auto"
    backend: str = "jax"
    straggler_threshold: float = 1.5
    ema: float = 0.5
    # False forces the legacy host period pipeline even where the
    # engine-v2 delegation would apply (benchmark baselines, debugging)
    delegate: bool = True
    # chaos: fault injection + degradation ladder (engine-v2 delegation
    # only; see repro.serving.faults).  None/FaultModel.none() disarms.
    faults: Optional[FaultModel] = None
    max_retries: int = 2
    fault_seed: int = 0
    # multi-cell mobility (pure-functional engine only — the host period
    # pipeline has no position state; see repro.core.mobility).  None
    # disarms; `EngineParams.from_config` picks these up for rollouts.
    mobility: Optional[object] = None       # core.mobility.MobilityModel
    mobility_mode: str = "replay"
    routing: str = "nearest"
    mobility_seed: int = 0
    # online hierarchical inference (engine-v2 delegation only; see
    # repro.serving.hi).  None disarms; armed, ``hi_rule`` picks the
    # per-sample decision rule and the confidence gate replaces the LP
    # plan.  `EngineParams.from_config` picks these up for rollouts.
    hi: Optional[object] = None             # core.hi.HIModel
    hi_rule: str = "threshold"
    hi_stream: str = "fold"
    hi_arms: int = 9
    hi_seed: int = 0
    hi_local: int = 0
    # "raise" (default): an uncertified-LP period raises
    # UnsolvedPeriodError (carrying partial stats); "warn": warn and book
    # the period — its unsolved lanes were re-planned local-only by the
    # traced core
    strict: str = "raise"
    # traffic (RequestQueue)
    classes: Sequence[int] = (128, 512, 1024)
    rate: float = 10.0
    batch_max: int = 12
    trace: Optional[np.ndarray] = None
    class_probs: Optional[Sequence[float]] = None
    # fleet composition (make_fleet) — ignored when `devices` is given
    devices: Optional[Sequence[DeviceSpec]] = None
    roofline_frac: float = 0.5
    straggler_frac: float = 0.25
    outage_frac: float = 0.1
    drift_mag: float = 3.0
    horizon: int = 64
    seed: int = 0

    def build_devices(self) -> List[DeviceSpec]:
        if self.devices is not None:
            if len(self.devices) != self.n_devices:
                raise ValueError(
                    f"config names {self.n_devices} devices but "
                    f"{len(self.devices)} DeviceSpecs were given")
            return list(self.devices)
        return make_fleet(self.n_devices, classes=self.classes,
                          roofline_frac=self.roofline_frac,
                          straggler_frac=self.straggler_frac,
                          outage_frac=self.outage_frac,
                          drift_mag=self.drift_mag, horizon=self.horizon,
                          seed=self.seed)

    def build_queue(self) -> RequestQueue:
        return RequestQueue(self.n_devices, self.classes, rate=self.rate,
                            batch_max=self.batch_max, seed=self.seed,
                            trace=self.trace, class_probs=self.class_probs)


class FleetEngine:
    """Drives the whole fleet, one period at a time."""

    @classmethod
    def from_config(cls, config: FleetConfig) -> "FleetEngine":
        """Build the engine a `FleetConfig` describes (same fleet, queue,
        and policy as the equivalent manual construction)."""
        if config.mobility is not None \
                and not getattr(config.mobility, "is_null", lambda: True)():
            # positions/cells/handover live in the traced EngineState scan;
            # there is no host twin of the routing + segmented admission
            raise ValueError(
                "multi-cell mobility runs on the pure-functional engine "
                "only: build EngineParams.from_config(config) and use "
                "repro.api.engine.rollout / rollout_sharded instead of "
                "FleetEngine")
        return cls(config.build_devices(), config.build_queue(),
                   n_servers=config.n_servers, T=config.T,
                   policy=config.policy, backend=config.backend,
                   straggler_threshold=config.straggler_threshold,
                   ema=config.ema, delegate=config.delegate,
                   faults=config.faults, max_retries=config.max_retries,
                   fault_seed=config.fault_seed, strict=config.strict,
                   hi=config.hi, hi_rule=config.hi_rule,
                   hi_stream=config.hi_stream, hi_arms=config.hi_arms,
                   hi_seed=config.hi_seed, hi_local=config.hi_local)

    def __init__(self, devices: Sequence[DeviceSpec], queue: RequestQueue, *,
                 n_servers: int = 1, T: float, policy: str = "auto",
                 backend: str = "jax", straggler_threshold: float = 1.5,
                 ema: float = 0.5, delegate: bool = True,
                 faults: Optional[FaultModel] = None, max_retries: int = 2,
                 fault_seed: int = 0, strict: str = "raise",
                 hi: Optional[object] = None, hi_rule: str = "threshold",
                 hi_stream: str = "fold", hi_arms: int = 9,
                 hi_seed: int = 0, hi_local: int = 0):
        if queue.n_devices != len(devices):
            raise ValueError("queue.n_devices must match the fleet size")
        if strict not in ("raise", "warn"):
            raise ValueError(f"strict={strict!r}; expected 'raise' or "
                             f"'warn'")
        if policy != "auto":
            from ..api import get_solver
            info = get_solver(policy).info        # also rejects unknowns
            if info.bound_only:
                raise ValueError(
                    f"policy={policy!r} is a bound-only solver; its "
                    f"assignments need not satisfy the budgets, so it "
                    f"cannot drive the serving engine")
            if backend == "jax" and not info.batched:
                # fail at construction, not deep inside period 0 after
                # arrivals were already dequeued
                raise ValueError(
                    f"policy={policy!r} has no batched path; construct "
                    f"the engine with backend='numpy' for the sequential "
                    f"oracle loop")
        for d, spec in enumerate(devices):
            cls = np.asarray(spec.profile.classes)
            if cls.size > 1 and np.any(np.diff(cls) <= 0):
                # the searchsorted pricing below silently returns wrong
                # rows on an unsorted class table
                raise ValueError(
                    f"device {d} ({spec.profile.name}) profile classes "
                    f"{cls.tolist()} must be strictly ascending")
            missing = set(np.asarray(queue.classes).tolist()) \
                - set(cls.tolist())
            if missing:
                # searchsorted would silently price these as a neighbouring
                # class (or index past the table); fail loudly instead.
                raise ValueError(
                    f"device {d} ({spec.profile.name}) has no profile entry "
                    f"for queue classes {sorted(missing)}")
        self.devices = [_DeviceState(spec=d, profile=d.profile)
                        for d in devices]
        self.queue = queue
        self.pool = EdgeServerPool(n_servers)
        self.T = T
        self.policy = policy
        self.backend = backend
        self.straggler_threshold = straggler_threshold
        self.ema = ema
        self.strict = strict
        self.history: List[FleetPeriodStats] = []
        self._period = 0
        # ---- array residency: stack per-device profiles by shape group ---
        by_key: Dict[tuple, List[int]] = {}
        for d, st in enumerate(self.devices):
            key = (tuple(np.asarray(st.profile.classes).tolist()),
                   st.profile.p_ed.shape[1])
            by_key.setdefault(key, []).append(d)
        self._groups = [_ShapeGroup(ids, [self.devices[d] for d in ids])
                        for ids in by_key.values()]
        self._dev_slot: Dict[int, tuple] = {}    # device -> (group, row)
        for g in self._groups:
            for row, d in enumerate(g.ids):
                self._dev_slot[int(d)] = (g, row)
        # ---- engine-v2 delegation (PR 5): on the jax backend with a
        # traceable policy and a single shape group, `run_period` runs the
        # SAME jitted period core the pure-functional engine scans over
        # (`repro.api.engine._period_jit`) — one fused traced call per
        # period instead of the solve/admit/replan/audit host pipeline.
        # `self._v2_params` is None when any precondition fails (numpy
        # backend, auto/amdp policy, mixed profile shapes) or the caller
        # passed ``delegate=False``, and the host loop below runs
        # unchanged.
        self._v2_params = None
        from ..api import engine as _engine_v2
        if delegate and backend == "jax" \
                and policy in _engine_v2.TRACEABLE_POLICIES \
                and len(self._groups) == 1:
            self._v2_params = _engine_v2.EngineParams.from_fleet(
                devices, queue, T=T, n_servers=n_servers, policy=policy,
                horizon=1, arrivals="poisson",   # arrivals come from the
                #             host queue; the mode only gates presampling
                straggler_threshold=straggler_threshold, ema=ema,
                faults=faults, max_retries=max_retries,
                fault_seed=fault_seed)
            g = self._groups[0]
            self._v2_lut = np.searchsorted(np.asarray(g.classes),
                                           np.asarray(queue.classes))
            # arrival-value -> queue-class-index mapping that stays
            # correct when queue.classes is NOT sorted (searchsorted on
            # the raw table would silently mis-price every job there)
            qcls = np.asarray(queue.classes)
            self._v2_qorder = np.argsort(qcls, kind="stable")
            self._v2_qsorted = qcls[self._v2_qorder]
            # chaos-audited ES-latency belief (mirrors the scan's
            # EngineState.p_es_belief leaf; == p_es until the realized-
            # execution audit inflates rows)
            self._v2_es_belief = np.array(
                np.asarray(self._v2_params.p_es), dtype=np.float64)
            if hi is not None:
                # arm online hierarchical inference on the delegated
                # params (validates interplay: chaos must be disarmed)
                # and mirror the scan's EngineState.hi learner leaf
                self._v2_params = self._v2_params.with_hi(
                    hi, rule=hi_rule, stream=hi_stream, n_arms=hi_arms,
                    hi_seed=hi_seed, local_model=hi_local)
                self._v2_hi_state = _engine_v2.HILearnerState.init(
                    len(devices), hi_arms, hi.theta0)
        if faults is not None and not faults.is_null() \
                and self._v2_params is None:
            # the ladder lives in the traced period core; there is no
            # host twin of the realized-execution pass to fall back to
            raise ValueError(
                "fault injection needs the engine-v2 delegation (jax "
                "backend, amr2/dual policy, one profile shape group, "
                "delegate=True); this engine would run the host period "
                "pipeline")
        if hi is not None and self._v2_params is None:
            # the confidence gate + learner live in the traced period
            # core; there is no host twin of the per-sample decision pass
            raise ValueError(
                "online hierarchical inference needs the engine-v2 "
                "delegation (jax backend, amr2/dual policy, one profile "
                "shape group, delegate=True); this engine would run the "
                "host period pipeline")

    # ------------------------------------------------------------------
    def run(self, periods: int) -> List[FleetPeriodStats]:
        """Run ``periods`` periods.  Under ``strict="raise"``, a period
        with uncertified LP lanes raises `UnsolvedPeriodError` — the
        completed periods' stats survive on the exception's
        ``partial_stats`` (and on ``self.history``)."""
        return [self.run_period() for _ in range(periods)]

    # ------------------------------------------------------------------
    # vectorized period loop (the hot path)
    # ------------------------------------------------------------------
    def run_period(self) -> FleetPeriodStats:
        if self._v2_params is not None:
            return self._run_period_v2()
        return self._run_period_host()

    def _run_period_v2(self) -> FleetPeriodStats:
        """Delegate the period to the pure-functional engine's jitted core
        (`repro.api.engine._period_jit`): the host side only polls the
        queue, hands over padded class-index arrays, and books the stats —
        plan/admit/replan/price/audit are one traced call.  `run()` then
        produces bit-identical trajectories to `engine.rollout` on a
        replayed arrival trace (the same core scanned)."""
        import time as _time

        from ..api.engine import _period_jit

        t = self._period
        self._period += 1
        arrivals = self.queue.poll(t)
        D = len(self.devices)
        g = self._groups[0]
        params = self._v2_params
        n_pad = self.queue.batch_max
        take = np.fromiter((len(a) for a in arrivals), dtype=np.int32,
                           count=D)
        ci = np.zeros((D, n_pad), dtype=np.int32)
        for d, a in enumerate(arrivals):
            if len(a):
                ci[d, :len(a)] = self._v2_qorder[
                    np.searchsorted(self._v2_qsorted, a)]
        outage = np.fromiter((st.spec.outage_at(t) for st in self.devices),
                             dtype=bool, count=D)
        drift = np.fromiter((st.spec.drift_at(t) for st in self.devices),
                            dtype=np.float64, count=D)
        belief = np.ascontiguousarray(g.p_ed[:, self._v2_lut, :])
        warm = (np.asarray(g.warm_basis, np.int32)
                if g.warm_basis is not None
                else np.full((D, params.n_basis_rows), -1, np.int32))
        if t > 0:
            # a basis optimal for last period's LP is stale when the ES
            # column set changed underneath it (outage flip): cold-start
            # those lanes instead of warm-factoring the wrong problem
            prev = np.fromiter(
                (st.spec.outage_at(t - 1) for st in self.devices),
                dtype=bool, count=D)
            warm = np.where((prev != outage)[:, None], np.int32(-1), warm)

        t0 = _time.perf_counter()
        with x64_scope():
            fault_key = None
            if params.chaos:
                # the exact per-period draw step() makes inside the scan:
                # fold the dedicated fault seed by period index
                import jax as _jax
                fault_key = _jax.random.fold_in(
                    _jax.random.PRNGKey(params.fault_seed), np.int32(t))
            hi_key = hi_state = hi_t = None
            if params.hi_armed:
                # same idiom for the confidence stream: the exact
                # per-period fold step() makes, plus the learner state
                # threaded between host periods like the ES belief
                import jax as _jax
                hi_key = _jax.random.fold_in(
                    _jax.random.PRNGKey(params.hi_seed), np.int32(t))
                hi_state = self._v2_hi_state
                hi_t = np.int32(t)
            (_belief2, new_warm, upd, factor, new_es_belief, _cload,
             new_hi, m) = _period_jit(belief, warm, ci, take, drift,
                                      outage, params, fault_key,
                                      es_belief=self._v2_es_belief,
                                      hi_key=hi_key, hi_state=hi_state,
                                      hi_t=hi_t)
        self._v2_es_belief = np.asarray(new_es_belief, dtype=np.float64)
        if params.hi_armed:
            import jax as _jax
            self._v2_hi_state = _jax.tree.map(np.asarray, new_hi)
        m = {k: np.asarray(v) for k, v in m.items()}
        plan_seconds = _time.perf_counter() - t0
        if int(m["n_unsolved"]):
            # mirror api.solve's strict=True default: never silently
            # serve best-effort roundings of a non-converged LP.  The
            # traced core has already re-planned the unsolved lanes with
            # the greedy local-only fallback, so "warn" mode can book the
            # period; "raise" keeps the completed periods on the error.
            msg = (f"period {t}: {int(m['n_unsolved'])} device plan(s) "
                   f"were not solved to optimality (simplex iteration "
                   f"limit or unbounded LP); raise maxiter — the lanes "
                   f"were served by the greedy local-only fallback")
            if self.strict == "warn":
                warnings.warn(msg, RuntimeWarning, stacklevel=3)
            else:
                raise UnsolvedPeriodError(
                    msg, period=t, n_unsolved=int(m["n_unsolved"]),
                    partial_stats=list(self.history))

        if self.policy == "amr2":   # LP-backed: carry the warm bases
            g.warm_basis = np.asarray(new_warm, np.int64)
        upd = np.asarray(upd)
        if upd.any():
            factor = np.asarray(factor)
            g.p_ed[upd] *= factor[upd, None, None]
            for r in np.nonzero(upd)[0]:
                st = self.devices[int(g.ids[r])]
                st.profile = dataclasses.replace(
                    st.profile, p_ed=g.p_ed[r].copy())
                st.n_updates += 1

        n_jobs = int(m["n_jobs"])
        total_acc = float(m["total_accuracy"])
        stats = FleetPeriodStats(
            period=t, n_devices=D, n_jobs=n_jobs,
            plan_seconds=plan_seconds, total_accuracy=total_acc,
            mean_job_accuracy=total_acc / n_jobs if n_jobs else 0.0,
            n_violations=int(m["n_violations"]),
            worst_violation=float(m["worst_violation"]),
            n_offloading=int(m["n_offloading"]),
            n_backpressured=int(m["n_backpressured"]),
            n_outage=int(m["n_outage"]),
            n_straggler_updates=int(m["n_straggler_updates"]),
            es_utilization=float(m["es_utilization"]),
            backlog=self.queue.backlog,
            n_offload_samples=int(m["n_offload_samples"]),
            n_offload_ok=int(m["n_offload_ok"]),
            n_deadline_miss=int(m["n_deadline_miss"]),
            n_retries=int(m["n_retries"]),
            n_fallback_local=int(m["n_fallback_local"]),
            n_dropped=int(m["n_dropped"]),
            realized_makespan=float(m["realized_makespan"]),
            n_es_audit_updates=int(m["n_es_audit_updates"]),
            n_hi_offloaded=int(m["n_hi_offloaded"]),
            n_hi_local_final=int(m["n_hi_local_final"]),
            hi_regret=float(m["hi_regret"]))
        self.history.append(stats)
        return stats

    def _run_period_host(self) -> FleetPeriodStats:
        """The pre-v2 host period pipeline (numpy backend, auto/amdp
        dispatch, mixed shape groups): batched api solves + host
        admission/audit bookkeeping."""
        t = self._period
        self._period += 1
        arrivals = self.queue.poll(t)
        n_pad = self.queue.batch_max
        D_all = len(self.devices)
        outage = np.fromiter((st.spec.outage_at(t) for st in self.devices),
                             dtype=bool, count=D_all)
        drift = np.fromiter((st.spec.drift_at(t) for st in self.devices),
                            dtype=np.float64, count=D_all)

        plan_seconds = 0.0
        staged = []                   # (group, fleet_problem, base, assign)
        es_demand_all = np.zeros(D_all)
        stale_all = None
        if t > 0:
            prev = np.fromiter(
                (st.spec.outage_at(t - 1) for st in self.devices),
                dtype=bool, count=D_all)
            stale_all = prev != outage     # ES column set changed: the
            #                                carried basis labels a
            #                                different LP — cold-start
        for g in self._groups:
            fp, base = self._assemble(g, arrivals, outage, n_pad)
            warm = {}
            if self.backend == "jax" and g.warm_basis is not None:
                wb = np.asarray(g.warm_basis)
                if stale_all is not None:
                    wb = np.where(stale_all[g.ids][:, None], -1, wb)
                warm["warm_start"] = wb
            sol = solve(fp, policy=self.policy, backend=self.backend,
                        **warm)
            if sol.basis is not None:   # LP-backed rows warm the next period
                g.warm_basis = np.asarray(sol.basis)
            else:
                # e.g. the policy switched to a non-LP solver ("auto"
                # dispatching every lane to the DP): drop the stale carry
                # rather than hand it to a later LP period
                g.warm_basis = None
            plan_seconds += sol.plan_seconds
            assign = sol.assignment
            es_demand_all[g.ids] = sol.es_makespan
            staged.append((g, fp, base, assign))

        # --- ES capacity: admit offload demand server by server ----------
        offl_mask = es_demand_all > 0
        admitted_mask, loads = self.pool.admit_mask(es_demand_all, self.T)
        bumped = np.nonzero(offl_mask & ~admitted_mask)[0].tolist()
        n_offloading = int(offl_mask.sum())

        # --- backpressure: ONE batched ES-disabled replan per group ------
        for g, fp, base, assign in staged:
            rows = np.nonzero(np.isin(g.ids, bumped))[0]
            if not len(rows):
                continue
            if self.backend == "jax":
                fb = solve(fp.take(rows), policy=self.policy,
                           es_disabled=True)
                plan_seconds += fb.plan_seconds
                assign[rows] = fb.assignment
            else:                     # sequential oracle path (PR-1 exact)
                t0 = time.perf_counter()
                mask = fp.real_mask
                for r in rows:
                    k = int(mask[r].sum())
                    stripped = Problem(
                        p_ed=fp.p_ed[r, :k], p_es=fp.p_es[r, :k],
                        acc=fp.acc[r], T=self.T)
                    fbp = solve(stripped, policy=self.policy,
                                backend="numpy", es_disabled=True)
                    assign[r, :k] = fbp.assignment
                plan_seconds += time.perf_counter() - t0

        # --- vectorized pricing, accounting, and straggler audit ---------
        n_jobs = 0
        total_acc = 0.0
        worst_viol = 0.0
        n_viol = 0
        n_updates = 0
        n_off_samples = 0
        realized_makespan = 0.0
        for g, fp, base, assign in staged:
            m = g.m
            mask = fp.real_mask
            n_jobs += int(mask.sum())
            # fault-free realized execution (host twin of the engine-v2
            # fields): every admitted offload completes via the ES
            n_off_samples += int((mask & (assign == m)).sum())
            acc_jobs = fp.acc[np.arange(len(g.ids))[:, None], assign]
            total_acc += float(np.where(mask, acc_jobs, 0.0).sum())

            on_ed = mask & (assign < m)
            picked = np.clip(assign, 0, m - 1)[..., None]
            ed_pred = np.where(
                on_ed, np.take_along_axis(fp.p_ed, picked, axis=2)[..., 0],
                0.0).sum(axis=1)
            # ground truth: the device's BASE latencies times its true
            # drift.  Pricing with the (EMA-updated) belief instead would
            # make the audit see the raw drift factor forever and inflate
            # the belief geometrically; against the base, it converges.
            ed_wall = np.where(
                on_ed, np.take_along_axis(base, picked, axis=2)[..., 0],
                0.0).sum(axis=1) * drift[g.ids]
            es_wall = np.where(admitted_mask[g.ids], es_demand_all[g.ids],
                               0.0)
            wall = np.maximum(ed_wall, es_wall)
            realized_makespan = max(realized_makespan,
                                    float(wall.max(initial=0.0)))
            viol = np.maximum(0.0, wall / self.T - 1.0)
            worst_viol = max(worst_viol, float(viol.max(initial=0.0)))
            n_viol += int((viol > 0).sum())

            ratio = ed_wall / np.maximum(ed_pred, 1e-9)
            upd = (ed_pred > 0) & (ratio > self.straggler_threshold
                                   * (1 + AUDIT_RTOL))
            if upd.any():
                factor = (1 - self.ema) + self.ema * ratio
                g.p_ed[upd] *= factor[upd, None, None]
                for r in np.nonzero(upd)[0]:
                    st = self.devices[int(g.ids[r])]
                    st.profile = dataclasses.replace(
                        st.profile, p_ed=g.p_ed[r].copy())
                    st.n_updates += 1
                n_updates += int(upd.sum())

        stats = FleetPeriodStats(
            period=t, n_devices=D_all, n_jobs=n_jobs,
            plan_seconds=plan_seconds, total_accuracy=total_acc,
            mean_job_accuracy=total_acc / n_jobs if n_jobs else 0.0,
            n_violations=n_viol, worst_violation=worst_viol,
            n_offloading=n_offloading, n_backpressured=len(bumped),
            n_outage=int(outage.sum()), n_straggler_updates=n_updates,
            es_utilization=float(loads.sum()) / (self.pool.n_servers * self.T),
            backlog=self.queue.backlog,
            n_offload_samples=n_off_samples, n_offload_ok=n_off_samples,
            realized_makespan=realized_makespan)
        self.history.append(stats)
        return stats

    def _assemble(self, g: _ShapeGroup, arrivals, outage: np.ndarray,
                  n_pad: int):
        """One group's padded `FleetProblem` as masked array gathers: no
        per-device instance objects, one searchsorted + fancy-index per
        group.  Returns (fleet problem, base ED latencies)."""
        D = len(g.ids)
        lens = np.fromiter((len(arrivals[d]) for d in g.ids),
                           dtype=np.int64, count=D)
        mask = np.arange(n_pad)[None, :] < lens[:, None]
        cls = np.full((D, n_pad), g.classes[0],
                      dtype=np.asarray(self.queue.classes).dtype)
        if lens.sum():
            cls[mask] = np.concatenate(
                [arrivals[d] for d in g.ids if len(arrivals[d])])
        ci = np.searchsorted(g.classes, cls)
        rows = np.arange(D)[:, None]
        p_ed = g.p_ed[rows, ci]
        p_es = g.p_es[rows, ci]
        base = g.base_p_ed[rows, ci]
        p_ed[~mask] = 0.0
        p_es[~mask] = 0.0
        base[~mask] = 0.0
        p_es[outage[g.ids][:, None] & mask] = _OUTAGE_ES
        fp = FleetProblem(p_ed=p_ed, p_es=p_es, acc=g.acc.copy(),
                          T=np.full(D, self.T), real_mask=mask)
        return fp, base

    # ------------------------------------------------------------------
    # PR-1 per-device reference loop (benchmark baseline + parity oracle)
    # ------------------------------------------------------------------
    def run_period_reference(self) -> FleetPeriodStats:
        """The pre-vectorization period loop: per-device padding/stripping,
        sequential backpressure replans, per-device audit.  Kept as the
        oracle the array-resident `run_period` is tested against and as the
        baseline `benchmarks/fleet_bench.py` measures speedup over."""
        t = self._period
        self._period += 1
        arrivals = self.queue.poll(t)
        n_pad = self.queue.batch_max
        outages = [st.spec.outage_at(t) for st in self.devices]

        padded = [_padded_instance(st.profile, arrivals[d], self.T, n_pad,
                                   disable_es=outages[d])
                  for d, st in enumerate(self.devices)]
        sols = solve_many([Problem.from_instance(p) for p in padded],
                          policy=self.policy, backend=self.backend)
        plan_seconds = sum(s.plan_seconds for s in sols)
        scheds = [_strip_phantoms(s.to_schedule(), len(arrivals[d]))
                  for d, s in enumerate(sols)]

        # --- ES capacity: admit offload demand server by server ----------
        demands = {d: s.es_makespan for d, s in enumerate(scheds)
                   if s.es_makespan > 0}
        admitted, loads = self.pool.admit(demands, self.T)
        bumped = sorted(set(demands) - set(admitted))
        for d in bumped:  # backpressure: replan ED-only (few devices)
            fb = solve(Problem.from_instance(scheds[d].instance),
                       policy=self.policy, es_disabled=True)
            scheds[d] = fb.to_schedule()
            plan_seconds += fb.plan_seconds

        # --- simulated execution + straggler audit -----------------------
        n_jobs = 0
        total_acc = 0.0
        worst_viol = 0.0
        n_viol = 0
        n_updates = 0
        n_off_samples = 0
        realized_makespan = 0.0
        for d, st in enumerate(self.devices):
            sched = scheds[d]
            n_jobs += sched.instance.n
            total_acc += sched.total_accuracy
            n_off_samples += int(
                (sched.assignment == sched.instance.p_ed.shape[1]).sum())
            ed_wall = _ed_time_under(st.spec.profile, arrivals[d],
                                     sched.assignment) * st.spec.drift_at(t)
            es_wall = 0.0 if d in bumped else sched.es_makespan
            wall = max(ed_wall, es_wall)
            realized_makespan = max(realized_makespan, wall)
            viol = max(0.0, wall / self.T - 1.0)
            worst_viol = max(worst_viol, viol)
            n_viol += viol > 0
            new_profile, updated = audit_profile(
                st.profile, sched.ed_makespan, ed_wall,
                threshold=self.straggler_threshold, ema=self.ema)
            if updated:
                st.profile = new_profile
                st.n_updates += 1
                n_updates += 1
                g, row = self._dev_slot[d]      # keep the stacks in sync
                g.p_ed[row] = new_profile.p_ed

        stats = FleetPeriodStats(
            period=t, n_devices=len(self.devices), n_jobs=n_jobs,
            plan_seconds=plan_seconds, total_accuracy=total_acc,
            mean_job_accuracy=total_acc / n_jobs if n_jobs else 0.0,
            n_violations=n_viol, worst_violation=worst_viol,
            n_offloading=len(demands), n_backpressured=len(bumped),
            n_outage=int(sum(outages)), n_straggler_updates=n_updates,
            es_utilization=float(loads.sum()) / (self.pool.n_servers * self.T),
            backlog=self.queue.backlog,
            n_offload_samples=n_off_samples, n_offload_ok=n_off_samples,
            realized_makespan=realized_makespan)
        self.history.append(stats)
        return stats

    # ------------------------------------------------------------------
    def summary(self) -> Dict[str, float]:
        h = self.history
        if not h:
            return {}
        jobs = sum(s.n_jobs for s in h)
        return {
            "periods": len(h),
            "jobs": jobs,
            "mean_job_accuracy": (sum(s.total_accuracy for s in h) / jobs
                                  if jobs else 0.0),
            "violation_rate": sum(s.n_violations for s in h) / (
                len(h) * len(self.devices)),
            "backpressure_rate": sum(s.n_backpressured for s in h) / (
                len(h) * len(self.devices)),
            "plan_seconds_per_period": (sum(s.plan_seconds for s in h)
                                        / len(h)),
            "devices_per_second": (len(self.devices) * len(h)
                                   / max(sum(s.plan_seconds for s in h),
                                         1e-12)),
            "straggler_updates": sum(s.n_straggler_updates for s in h),
            "final_backlog": h[-1].backlog,
        }


# --------------------------------------------------------------------------
# Heterogeneous fleet construction
# --------------------------------------------------------------------------
def paper_style_profile(rng: np.random.Generator,
                        classes: Sequence[int] = (128, 512, 1024)
                        ) -> TierProfile:
    """The paper's Raspberry-Pi/ResNet50 testbed numbers with per-device
    jitter — one 'measured' device in the fleet."""
    jit_ed = rng.uniform(0.8, 1.3, size=(len(classes), 2))
    jit_es = rng.uniform(0.9, 1.2, size=len(classes))
    p_ed = np.array([PAPER_P_ED[c] for c in classes]) * jit_ed
    p_es = np.array([PAPER_COMM[c] + PAPER_P_ES_PROC[c]
                     for c in classes]) * jit_es
    return TierProfile(name="paper-jittered", p_ed=p_ed, p_es=p_es,
                       acc=PAPER_ACC.copy(), classes=list(classes))


def roofline_style_profile(rng: np.random.Generator,
                           classes: Sequence[int] = (128, 512, 1024)
                           ) -> TierProfile:
    """A roofline-derived device: LM-ladder latencies from analytic
    compute/memory terms instead of testbed measurements, scaled so they
    land in the same regime as the paper's numbers."""
    dims = np.asarray(classes, np.float64)
    flops = 4e9 * (dims / dims[0])                  # per-request useful flops
    acts = 6e7 * (dims / dims[0])                   # activation traffic bytes
    payload = 3.0 * dims ** 2                       # image-ish upload bytes
    derate = rng.uniform(0.7, 1.4)
    return roofline_profile(
        "roofline", list(classes),
        flops_per_class=flops, bytes_per_class=acts,
        model_scales=(0.25, 0.75), acc=(0.42, 0.58, 0.78),
        payload_bytes=payload,
        ed_peak_flops=1.2e12 * derate, ed_hbm_bw=40e9 * derate,
        link_gbps=0.08)


def make_fleet(n_devices: int, *, classes: Sequence[int] = (128, 512, 1024),
               roofline_frac: float = 0.5, straggler_frac: float = 0.25,
               outage_frac: float = 0.1, drift_mag: float = 3.0,
               horizon: int = 64, seed: int = 0) -> List[DeviceSpec]:
    """A heterogeneous fleet mixing paper-style and roofline-derived devices,
    with `straggler_frac` of them drifting to `drift_mag x` slowdown partway
    through the horizon and `outage_frac` suffering ES-link outages."""
    rng = np.random.default_rng(seed)
    specs: List[DeviceSpec] = []
    for d in range(n_devices):
        if rng.uniform() < roofline_frac:
            prof = roofline_style_profile(rng, classes)
        else:
            prof = paper_style_profile(rng, classes)
        drift = None
        if rng.uniform() < straggler_frac:
            onset = rng.integers(1, max(2, horizon // 2))
            drift = np.ones(horizon)
            drift[onset:] = drift_mag
        outage = None
        if rng.uniform() < outage_frac:
            outage = rng.uniform(size=horizon) < 0.2
        specs.append(DeviceSpec(profile=prof, drift=drift, outage=outage,
                                name=f"dev{d}"))
    return specs
