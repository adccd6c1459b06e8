"""Pure-functional fleet engine: `EngineState` pytree + `step`/`rollout`/
`shard`.

`FleetEngine` (PR 1-4) plans each period in a handful of jitted calls, but
the period LOOP — queue arrivals, ES-pool admission, drift/outage,
straggler audit, warm-basis carry — is host Python over NumPy state, so a
multi-period rollout pays one host round-trip per period and cannot be
`lax.scan`-ed or `shard_map`-ed.  This module redesigns the serving API
around a pure state machine:

  * ``EngineParams`` — everything static over a rollout, as one registered
    pytree: per-device latency/accuracy tables (re-indexed to the queue's
    class table), precomputed drift/outage schedules, the arrival model
    (a replayed count/class-stream trace with bit-parity to the host
    `RequestQueue`, or array-native Poisson sampling with `jax.random`),
    and the solver configuration as static aux data.
  * ``EngineState`` — everything that evolves, as one pytree of arrays:
    the belief latency tables (EMA straggler audit state), per-device
    backlog counts and stream cursors, the PRNG key, and the previous
    period's warm simplex bases (PR 4).
  * ``step(state, params) -> (state, PeriodMetrics)`` — ONE pure traced
    period: release arrivals, assemble the padded `FleetProblem`
    (`FleetProblem.from_arrays_unchecked` — the same stacked pytree the
    host engine solves), plan it with the traceable warm-or-cold batched
    simplex + AMR^2 rounding (`lp.simplex_batch_core`,
    `amr2.round_relaxation_jnp`) or the vmapped dual solver, run the
    vectorized ES-pool admission scan, replan bumped devices ES-disabled
    (a lane-masked second solve: non-bumped lanes cost zero pivots),
    price/audit, and emit scalar metrics.
  * ``rollout(state, params, periods)`` — a whole fleet epoch as ONE
    `jax.lax.scan` over jitted `step`: no per-period host sync.
  * ``shard(state, params, mesh)`` + ``step_sharded``/``rollout_sharded``
    — `device_put` the stacked fleet axis across a mesh and run the same
    step under `shard_map`; the only cross-device traffic is one
    `all_gather` of the (D,) ES-demand vector for the global admission
    scan plus scalar `psum`s for the metrics.  CPU-validated with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Everything runs in float64 (`core.types.x64_scope` around every
public entry point, like `solve_lp_batch`), so `step` is bit-comparable
with the host `FleetEngine.run_period` — which now *delegates* to the same
jitted period core on the jax backend (see `serving.fleet`).

The engine traces itself: each public entry point opens `jax.profiler`
host spans (``repro.<entry>`` over ``repro.validate``, ``repro.horizon``,
``repro.launch``; see `_entry_spans`), every op of a period carries one
stage scope in its HLO ``op_name`` (see `_step_impl`), and
`PeriodMetrics` counts the LP's pivots and the lockstep loop's pivot
slots (``lp_pivots``, ``lp_pivot_slots``).

Typical use::

    from repro.api import engine
    params = engine.EngineParams.from_config(cfg, horizon=64)
    state = engine.init_state(params)
    state, metrics = engine.rollout(state, params, periods=64)
    # metrics.total_accuracy is a (64,) array, one entry per period

The dtype discipline inside the scan: every integer state leaf is int32
and every new value is explicitly cast back, so the `lax.scan` carry
structure is stable.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.amr2 import (build_lp_arrays_jnp, lp_column_rows,
                         round_relaxation_jnp, soft_assignment_weights,
                         straight_through_weights)
from ..core.dual import _dual_one
from ..core.faults import (FaultModel, greedy_local_fill,
                           realize_execution, sample_realization)
from ..core.hi import (HILearnerState, HIModel, hi_period,
                       sample_confidence, validate_hi)
from ..core.lp import (_bucket_maxiter, simplex_batch_core,
                       simplex_batch_grad)
from ..core.mobility import (MobilityModel, admit_mask_pool,
                             admit_mask_segmented, route_cells,
                             validate_mobility)
from ..core.problem import (AUDIT_RTOL, ES_DISABLED_SENTINEL,
                            ST_UNSOLVED as _ST_UNSOLVED, FleetProblem)
from ..core.types import x64_scope

# Policies with a fully-traceable batched path (the scan/shard requirement;
# "auto"/"amdp" need host-side identical-job dispatch and stay on the host
# engine).
TRACEABLE_POLICIES = ("amr2", "dual")
FLEET_AXIS = "fleet"


def _register(cls, leaf_fields: Tuple[str, ...],
              aux_fields: Tuple[str, ...] = ()) -> None:
    """Register a frozen dataclass pytree: ``leaf_fields`` are children,
    ``aux_fields`` ride along as (hashable) static aux data.  Unflatten
    bypasses ``__init__`` so tracers survive the round-trip."""
    def flatten(obj):
        return (tuple(getattr(obj, f) for f in leaf_fields),
                tuple(getattr(obj, f) for f in aux_fields))

    def unflatten(aux, children):
        obj = object.__new__(cls)
        for f, v in zip(leaf_fields, children):
            object.__setattr__(obj, f, v)
        for f, v in zip(aux_fields, aux):
            object.__setattr__(obj, f, v)
        return obj

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)


@dataclasses.dataclass(frozen=True)
class EngineParams:
    """Rollout-invariant fleet description (pytree; solver config is aux).

    All per-class tables are indexed by the QUEUE class table (the arrival
    streams sample class indices, not values), re-indexed from each
    device's profile at construction.  ``drift``/``outage`` are
    precomputed per-period schedules; periods beyond their horizon cycle.

    Arrival models (``arrivals`` aux):
      * ``"replay"`` — ``counts`` (H, D) and ``stream`` (D, S) hold a
        presampled arrival trace (`RequestQueue.presample`), giving
        BIT-IDENTICAL arrivals to the host queue for the same seed: the
        parity mode.
      * ``"poisson"`` — arrival counts are drawn inside the traced step
        with `jax.random.poisson` (per-device folded keys, so sharded and
        unsharded sampling agree) and job classes with `jax.random.choice`
        at release time; backlogged jobs re-sample their class at release,
        which is distributionally identical for i.i.d. classes.  The
        no-host-data mode for 10k+-device fleets.
    """

    # ---- pytree leaves --------------------------------------------------
    classes: np.ndarray     # (c,) queue class labels (reference only)
    base_p_ed: np.ndarray   # (D, c, m) ground-truth ED latencies
    p_es: np.ndarray        # (D, c) ES latencies (comm incl.)
    acc: np.ndarray         # (D, m+1) accuracies
    T: np.ndarray           # ()  period budget
    rate: np.ndarray        # (D,) Poisson arrival rates
    class_probs: np.ndarray  # (c,) class sampling distribution
    drift: np.ndarray       # (D, H) true per-period ED slowdown factors
    outage: np.ndarray      # (D, H) bool, ES link down
    counts: np.ndarray      # (Hc, D) replayed arrival counts (replay mode)
    stream: np.ndarray      # (D, S) replayed class indices (replay mode)
    # chaos: the fault distribution sampled inside the traced step (all
    # float64 scalar leaves — sweeping fault rates reuses one compiled
    # rollout).  Only consulted when the static ``chaos`` aux is True;
    # the fault-free trace carries the leaves but never reads them.
    faults: FaultModel = dataclasses.field(default_factory=FaultModel.none)
    # multi-cell mobility: cell geometry + device motion (all-float64-leaf
    # pytree like `faults`; only consulted when the static
    # ``mobility_mode`` aux is not "off" — the single-pool trace carries
    # the leaves but never reads them)
    mobility: MobilityModel = dataclasses.field(
        default_factory=MobilityModel.none)
    # online hierarchical inference: calibration curves + learner
    # hyper-parameters (all-float64-leaf pytree like `faults`; only
    # consulted when the static ``hi_rule`` aux is not "off" — the
    # planned trace carries the leaves but never reads them)
    hi: HIModel = dataclasses.field(default_factory=HIModel.none)
    # ---- static aux -----------------------------------------------------
    policy: str = "amr2"
    arrivals: str = "replay"
    n_servers: int = 1
    batch_max: int = 12
    straggler_threshold: float = 1.5
    ema: float = 0.5
    frac_tol: float = 1e-4
    iters: int = 40            # dual bisection iterations
    maxiter: Optional[int] = None
    tol: float = 1e-7
    # simplex pivot representation: "tableau" (dense, bit-compatible with
    # the PR-5 pins) or "revised" (reduced-tableau eta-factor path — the
    # 100k-lane memory/throughput shape; see core.lp.simplex_batch_core)
    lp_method: str = "tableau"
    # chaos (static, so the fault-free trace is byte-identical to an
    # engine without the fault subsystem): ``chaos`` arms the realized-
    # execution pass, ``max_retries`` bounds the unrolled retry rounds of
    # the degradation ladder, ``fault_seed`` seeds the replayed fault
    # stream (independent of the arrival PRNG — arming chaos never
    # perturbs arrivals)
    chaos: bool = False
    max_retries: int = 2
    fault_seed: int = 0
    # multi-cell mobility (static): "off" keeps the byte-identical
    # single-pool trace; "replay" reads positions from ``mobility.trace``;
    # "walk" integrates Gaussian steps from the folded ``mobility_seed``
    # stream (independent of the arrival PRNG, like ``fault_seed``).
    # ``n_cells`` partitions the ``n_servers`` pool evenly across cells;
    # ``routing`` picks the serving cell ("nearest" / "min_time");
    # ``shard_by_cell`` elides the admission all_gather under shard_map
    # (valid when each shard's devices route only to its own cells)
    mobility_mode: str = "off"
    routing: str = "nearest"
    n_cells: int = 1
    mobility_seed: int = 0
    shard_by_cell: bool = False
    # online hierarchical inference (static): ``hi_rule`` "off" keeps the
    # byte-identical planned trace; "fixed"/"threshold"/"ucb"/"exp3"
    # replace the LP plan with the per-sample confidence gate
    # (`core.hi`).  ``hi_stream`` picks fold-keyed ("fold", from
    # ``hi_seed`` — independent of the arrival PRNG, like ``fault_seed``)
    # or replayed ("replay", from ``hi.conf_trace``) confidences;
    # ``hi_arms`` sizes the bandit rules' threshold grid; ``hi_local``
    # names the local model every sample runs on.
    hi_rule: str = "off"
    hi_stream: str = "fold"
    hi_arms: int = 9
    hi_seed: int = 0
    hi_local: int = 0
    # differentiable rollout (static; False keeps the forward trace
    # byte-identical to an engine without the gradient subsystem).
    # ``smooth_mode`` picks the relaxation of the two discrete stages:
    # "st" (straight-through: forward = the hard Algorithm-2 rounding +
    # first-fit admission, backward = the smoothed Jacobians) or "soft"
    # (forward itself runs the temperature-softened blend — the mode
    # finite-difference checks validate, since the hard forward is
    # piecewise constant).  ``smooth_tau`` tempers the assignment softmax
    # (`core.amr2.soft_assignment_weights`), ``admit_tau`` the sigmoid
    # capacity test (in units of T).  ``grad_leaves`` names the default
    # EngineParams leaves `rollout_grad` differentiates.
    differentiable: bool = False
    smooth_mode: str = "st"
    smooth_tau: float = 0.25
    admit_tau: float = 0.05
    grad_leaves: Tuple[str, ...] = ("p_es", "T", "acc")

    @property
    def n_devices(self) -> int:
        return self.base_p_ed.shape[0]

    @property
    def m(self) -> int:
        return self.base_p_ed.shape[2]

    @property
    def n_basis_rows(self) -> int:
        """Simplex rows R = batch_max + 2 (warm-basis width)."""
        return self.batch_max + 2

    @property
    def servers_per_cell(self) -> int:
        """ES tiers fronted by each cell (the whole pool when S=1)."""
        return self.n_servers // max(self.n_cells, 1)

    @property
    def hi_armed(self) -> bool:
        """Online hierarchical inference replaces the LP plan."""
        return self.hi_rule != "off"

    # ---- constructors ----------------------------------------------------
    @classmethod
    def from_fleet(cls, devices, queue, *, T: float, n_servers: int = 1,
                   policy: str = "amr2", horizon: int = 64,
                   arrivals: str = "replay",
                   straggler_threshold: float = 1.5, ema: float = 0.5,
                   frac_tol: float = 1e-4, iters: int = 40,
                   maxiter: Optional[int] = None,
                   tol: float = 1e-7,
                   lp_method: str = "tableau",
                   faults: Optional[FaultModel] = None,
                   max_retries: int = 2,
                   fault_seed: int = 0,
                   mobility: Optional[MobilityModel] = None,
                   mobility_mode: str = "replay",
                   routing: str = "nearest",
                   mobility_seed: int = 0) -> "EngineParams":
        """Build params from `DeviceSpec`s + a `RequestQueue` (the host
        engine's vocabulary).  Requires one shape group — every profile
        sharing a class table and model count — which is what
        `make_fleet`/`FleetConfig` fleets always are."""
        if policy == "auto":
            policy = "amr2"     # the traceable LP path; the DP dispatch
            #                     of "auto" is a host-engine feature
        if policy not in TRACEABLE_POLICIES:
            raise ValueError(
                f"policy={policy!r} has no traceable batched path; the "
                f"pure-functional engine supports {TRACEABLE_POLICIES}")
        if arrivals not in ("replay", "poisson"):
            raise ValueError(f"unknown arrivals mode {arrivals!r}")
        if lp_method not in ("tableau", "revised"):
            raise ValueError(f"unknown lp_method {lp_method!r}; expected "
                             f"'tableau' or 'revised'")
        if horizon <= 0:
            raise ValueError("horizon must be positive")
        if max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        if queue.n_devices != len(devices):
            raise ValueError("queue.n_devices must match the fleet size")
        mob = mobility if mobility is not None else MobilityModel.none()
        mob_mode = mobility_mode if mobility is not None else "off"
        validate_mobility(mob, n_devices=len(devices), n_servers=n_servers,
                          mode=mob_mode, routing=routing)
        qcls = np.asarray(queue.classes)
        key0 = None
        for d, spec in enumerate(devices):
            pcls = np.asarray(spec.profile.classes)
            if pcls.size > 1 and np.any(np.diff(pcls) <= 0):
                # the searchsorted re-indexing below silently mis-prices
                # (or IndexErrors) on an unsorted table — same guard as
                # FleetEngine.__init__, needed here too because
                # FleetConfig(devices=...) can reach this path directly
                raise ValueError(
                    f"device {d} ({spec.profile.name}) profile classes "
                    f"{pcls.tolist()} must be strictly ascending")
            key = (tuple(pcls.tolist()), spec.profile.p_ed.shape[1])
            if key0 is None:
                key0 = key
            elif key != key0:
                raise ValueError(
                    "EngineParams.from_fleet needs a single shape group "
                    "(one class table and model count across the fleet); "
                    f"device {d} has {key}, device 0 has {key0}")
            missing = set(qcls.tolist()) - set(pcls.tolist())
            if missing:
                raise ValueError(
                    f"device {d} has no profile entry for queue classes "
                    f"{sorted(missing)}")
        # re-index every per-class table to the queue's class axis
        pcls = np.asarray(devices[0].profile.classes)
        lut = np.searchsorted(pcls, qcls)
        base_p_ed = np.stack([d.profile.p_ed[lut] for d in devices]
                             ).astype(np.float64)
        p_es = np.stack([d.profile.p_es[lut] for d in devices]
                        ).astype(np.float64)
        acc = np.stack([d.profile.acc for d in devices]).astype(np.float64)
        drift = np.stack([[d.drift_at(t) for t in range(horizon)]
                          for d in devices]).astype(np.float64)
        outage = np.stack([[d.outage_at(t) for t in range(horizon)]
                           for d in devices]).astype(bool)
        if arrivals == "replay":
            counts, stream = queue.presample(horizon)
        else:
            counts = np.zeros((1, len(devices)), dtype=np.int64)
            stream = np.zeros((len(devices), 1), dtype=np.int32)
        probs = (np.full(len(qcls), 1.0 / len(qcls))
                 if queue.class_probs is None
                 else np.asarray(queue.class_probs, np.float64))
        return cls(
            classes=qcls.astype(np.int64),
            base_p_ed=base_p_ed, p_es=p_es, acc=acc,
            T=np.float64(T),
            rate=np.asarray(queue.rate, np.float64),
            class_probs=probs, drift=drift, outage=outage,
            counts=counts.astype(np.int32), stream=stream,
            faults=faults if faults is not None else FaultModel.none(),
            mobility=mob, mobility_mode=mob_mode, routing=routing,
            n_cells=mob.n_cells if mob_mode != "off" else 1,
            mobility_seed=mobility_seed,
            policy=policy, arrivals=arrivals, n_servers=n_servers,
            batch_max=queue.batch_max,
            straggler_threshold=straggler_threshold, ema=ema,
            frac_tol=frac_tol, iters=iters, maxiter=maxiter, tol=tol,
            lp_method=lp_method,
            chaos=faults is not None and not faults.is_null(),
            max_retries=max_retries, fault_seed=fault_seed)

    @classmethod
    def from_config(cls, config, *, horizon: Optional[int] = None,
                    arrivals: str = "replay",
                    policy: Optional[str] = None,
                    lp_method: str = "tableau") -> "EngineParams":
        """Build params from a declarative `serving.FleetConfig` — the
        engine-v2 twin of `FleetEngine.from_config`.  The replayed arrival
        trace covers ``horizon`` periods (default: the config's
        straggler/outage ``horizon``)."""
        horizon = horizon if horizon is not None else config.horizon
        return cls.from_fleet(
            config.build_devices(), config.build_queue(), T=config.T,
            n_servers=config.n_servers,
            policy=policy if policy is not None else config.policy,
            horizon=horizon, arrivals=arrivals,
            straggler_threshold=config.straggler_threshold, ema=config.ema,
            lp_method=lp_method,
            faults=getattr(config, "faults", None),
            max_retries=getattr(config, "max_retries", 2),
            fault_seed=getattr(config, "fault_seed", 0),
            mobility=getattr(config, "mobility", None),
            mobility_mode=getattr(config, "mobility_mode", "replay"),
            routing=getattr(config, "routing", "nearest"),
            mobility_seed=getattr(config, "mobility_seed", 0)).with_hi(
                getattr(config, "hi", None),
                rule=getattr(config, "hi_rule", "threshold"),
                stream=getattr(config, "hi_stream", "fold"),
                n_arms=getattr(config, "hi_arms", 9),
                hi_seed=getattr(config, "hi_seed", 0),
                local_model=getattr(config, "hi_local", 0))

    def with_faults(self, faults: Optional[FaultModel], *,
                    max_retries: Optional[int] = None,
                    fault_seed: Optional[int] = None) -> "EngineParams":
        """Arm (or disarm, with ``None``/`FaultModel.none()`) chaos on an
        existing params value, keeping the static ``chaos`` flag
        consistent with the model's nullness."""
        fm = faults if faults is not None else FaultModel.none()
        if self.hi_armed and not fm.is_null():
            raise ValueError(
                "chaos needs HI disarmed (hi_rule='off'): the realized-"
                "execution ladder re-decides admitted samples and would "
                "corrupt the learner's feedback; disarm with "
                "with_hi(None) first")
        return dataclasses.replace(
            self, faults=fm, chaos=not fm.is_null(),
            max_retries=(self.max_retries if max_retries is None
                         else max_retries),
            fault_seed=(self.fault_seed if fault_seed is None
                        else fault_seed))

    def with_mobility(self, mobility: Optional[MobilityModel], *,
                      mode: str = "replay", routing: str = "nearest",
                      mobility_seed: Optional[int] = None,
                      shard_by_cell: bool = False) -> "EngineParams":
        """Arm (or disarm, with ``None``) the multi-cell mobility
        subsystem on an existing params value.  Validates the geometry
        (`core.mobility.validate_mobility`) and keeps the static
        ``mobility_mode``/``n_cells`` aux consistent with the model."""
        mob = mobility if mobility is not None else MobilityModel.none()
        mob_mode = mode if mobility is not None else "off"
        if self.hi_armed and mob_mode != "off":
            raise ValueError(
                "mobility needs HI disarmed (hi_rule='off'): per-cell "
                "admission of confidence-gated offloads is a later rung; "
                "disarm with with_hi(None) first")
        validate_mobility(mob, n_devices=self.n_devices,
                          n_servers=self.n_servers, mode=mob_mode,
                          routing=routing)
        return dataclasses.replace(
            self, mobility=mob, mobility_mode=mob_mode, routing=routing,
            n_cells=mob.n_cells if mob_mode != "off" else 1,
            mobility_seed=(self.mobility_seed if mobility_seed is None
                           else mobility_seed),
            shard_by_cell=shard_by_cell)

    def with_differentiable(self, enabled: bool = True, *,
                            smooth_mode: str = "st",
                            smooth_tau: float = 0.25,
                            admit_tau: float = 0.05,
                            grad_leaves: Optional[Tuple[str, ...]] = None
                            ) -> "EngineParams":
        """Arm (or disarm) the differentiable rollout on an existing
        params value.  Differentiability needs the traced amr2 LP path
        (the implicit VJP lives at the simplex's converged basis) and a
        deterministic accuracy pipeline, so chaos and mobility must be
        disarmed; the sharded entry points reject it (gradients run on
        the single-host trace).  See the class docstring for the
        ``smooth_mode``/``smooth_tau``/``admit_tau`` knobs."""
        if enabled:
            if self.policy != "amr2":
                raise ValueError(
                    f"differentiable rollouts need policy='amr2' (the LP "
                    f"relaxation carries the gradient); got "
                    f"{self.policy!r}")
            if self.chaos:
                raise ValueError(
                    "differentiable rollouts need chaos disarmed: the "
                    "fault ladder's retry/drop counters are discrete and "
                    "the realized-execution pass is not relaxed")
            if self.mobility_mode != "off":
                raise ValueError(
                    "differentiable rollouts need mobility off: routing "
                    "and the per-cell admission are not relaxed yet")
            if self.hi_armed:
                raise ValueError(
                    "differentiable rollouts need HI disarmed "
                    "(hi_rule='off'): the per-sample threshold gate and "
                    "the learner's argmax/draw updates are discrete and "
                    "not relaxed; disarm with with_hi(None) first")
            if smooth_mode not in ("st", "soft"):
                raise ValueError(f"unknown smooth_mode {smooth_mode!r}; "
                                 f"expected 'st' or 'soft'")
            if not (smooth_tau > 0 and admit_tau > 0):
                raise ValueError("smooth_tau and admit_tau must be > 0")
            gl = tuple(grad_leaves) if grad_leaves is not None \
                else self.grad_leaves
            bad = [f for f in gl if f not in GRAD_LEAVES]
            if bad:
                raise ValueError(
                    f"grad_leaves {bad} not differentiable; the "
                    f"continuous EngineParams knobs are {GRAD_LEAVES}")
        else:
            gl = self.grad_leaves
        return dataclasses.replace(
            self, differentiable=enabled, smooth_mode=smooth_mode,
            smooth_tau=smooth_tau, admit_tau=admit_tau, grad_leaves=gl)

    def with_hi(self, hi: Optional[HIModel], *, rule: str = "threshold",
                stream: str = "fold", n_arms: int = 9,
                hi_seed: Optional[int] = None,
                local_model: int = 0) -> "EngineParams":
        """Arm (or disarm, with ``None``) online hierarchical inference
        on an existing params value.  Armed, the per-sample confidence
        gate REPLACES the LP plan: every sample runs the ``local_model``
        on-device and is additionally offloaded iff its calibrated
        confidence falls below the rule's threshold (`core.hi`); the
        learner state rides along as an `EngineState` leaf.  HI composes
        with drift/outage and the ES-pool admission but not (yet) with
        chaos, mobility, or the differentiable relaxation — arming
        raises while any of those is armed, mirroring their own guards."""
        if hi is None:
            return dataclasses.replace(
                self, hi=HIModel.none(), hi_rule="off", hi_stream="fold")
        if self.chaos:
            raise ValueError(
                "HI needs chaos disarmed: the realized-execution ladder "
                "re-decides admitted samples and would corrupt the "
                "learner's feedback; disarm with with_faults(None) first")
        if self.mobility_mode != "off":
            raise ValueError(
                "HI needs mobility off: per-cell admission of confidence-"
                "gated offloads is a later rung; disarm with "
                "with_mobility(None) first")
        if self.differentiable:
            raise ValueError(
                "HI needs the differentiable relaxation disarmed: the "
                "threshold gate and learner updates are discrete; disarm "
                "with with_differentiable(False) first")
        validate_hi(hi, n_devices=self.n_devices,
                    n_classes=self.base_p_ed.shape[1], n_models=self.m,
                    rule=rule, stream=stream, n_arms=n_arms,
                    local_model=local_model, batch_max=self.batch_max)
        return dataclasses.replace(
            self, hi=hi, hi_rule=rule, hi_stream=stream, hi_arms=n_arms,
            hi_seed=self.hi_seed if hi_seed is None else hi_seed,
            hi_local=local_model)


@dataclasses.dataclass(frozen=True)
class EngineState:
    """Everything a period mutates, as one pytree of arrays."""

    period: jnp.ndarray       # ()   int32
    key: jnp.ndarray          # (2,) uint32 PRNG key (poisson arrivals)
    p_ed: jnp.ndarray         # (D, c, m) belief latencies (audit state)
    pending: jnp.ndarray      # (D,) int32 backlog counts
    head: jnp.ndarray         # (D,) int32 replay-stream cursors
    warm_basis: jnp.ndarray   # (D, R) int32 previous optimal bases (-1 cold)
    n_updates: jnp.ndarray    # (D,) int32 straggler-audit update counts
    # multi-cell mobility (inert zeros while mobility_mode == "off")
    pos: jnp.ndarray          # (D, 2) device positions
    cell: jnp.ndarray         # (D,) int32 serving cell (-1: uncovered)
    cell_load: jnp.ndarray    # (S,) last period's admitted load per cell
    # ES-latency belief (chaos audit state; == params.p_es until the
    # realized-execution audit inflates it, handover resets rows)
    p_es_belief: jnp.ndarray  # (D, c)
    # online hierarchical inference: the learner's evolving state
    # (threshold / per-arm statistics / cumulative regret, `core.hi`).
    # Always populated by `init_state`; carried untouched while
    # ``hi_rule == "off"`` so the planned trace is unchanged.
    hi: HILearnerState = None


@dataclasses.dataclass(frozen=True)
class PeriodMetrics:
    """One period's fleet-level numbers (each a scalar; `rollout` stacks
    them into (periods,) arrays).  Field names match `FleetPeriodStats`."""

    period: jnp.ndarray
    n_jobs: jnp.ndarray
    total_accuracy: jnp.ndarray
    mean_job_accuracy: jnp.ndarray
    n_violations: jnp.ndarray
    worst_violation: jnp.ndarray
    n_offloading: jnp.ndarray
    n_backpressured: jnp.ndarray
    n_outage: jnp.ndarray
    n_straggler_updates: jnp.ndarray
    # solves that hit the simplex iteration cap / went unbounded: their
    # assignments are best-effort argmax roundings, not certified optima
    # (the host solve() raised under strict=True; a traced step cannot
    # raise, so the count is surfaced here — and the delegating
    # FleetEngine.run_period re-raises when it is nonzero)
    n_unsolved: jnp.ndarray
    es_utilization: jnp.ndarray
    backlog: jnp.ndarray
    # realized execution (the chaos subsystem, serving.faults): admitted
    # offloaded samples and how each one resolved — the per-period
    # accounting identity ``n_offload_samples == n_offload_ok +
    # n_fallback_local + n_dropped`` holds by construction.  With chaos
    # off, the ladder counters are exact zeros, ``n_offload_ok ==
    # n_offload_samples``, and ``realized_makespan`` equals the priced
    # fleet makespan.
    n_offload_samples: jnp.ndarray
    n_offload_ok: jnp.ndarray
    n_deadline_miss: jnp.ndarray
    n_retries: jnp.ndarray
    n_fallback_local: jnp.ndarray
    n_dropped: jnp.ndarray
    realized_makespan: jnp.ndarray
    # chaos -> planner feedback: devices whose REALIZED ES time blew past
    # the priced demand (or missed the 2T deadline) and had their
    # `p_es_belief` EMA-inflated this period.  Exact zero with chaos off.
    n_es_audit_updates: jnp.ndarray
    # mobility: devices that switched serving cells this period (handover
    # count; exact zero while mobility is off or S=1)
    n_handover: jnp.ndarray
    # online hierarchical inference (`core.hi`): samples that actually
    # consulted the ES (admitted offloads) vs samples served by the local
    # model alone — every sample runs the local model, so the accounting
    # identity ``n_hi_offloaded + n_hi_local_final == n_jobs`` holds per
    # period by construction (admission-bumped intended offloads land in
    # the local count) — plus the fleet's cumulative pseudo-regret vs the
    # clairvoyant threshold.  Exact zeros while HI is off.
    n_hi_offloaded: jnp.ndarray
    n_hi_local_final: jnp.ndarray
    hi_regret: jnp.ndarray
    # the LP's lockstep pivot loop: ``lp_pivots`` sums every solved lane's
    # simplex pivots (primary plan plus replan); ``lp_pivot_slots`` sums,
    # per lane batch (each lane chunk of the plan, and of the replan), the
    # batch's largest pivot count times its lanes — the pivots the loop
    # paid for, masked-out replan lanes counting as idle slots.  Their
    # ratio is the pivot loop's lane use.  Exact zeros under
    # ``policy="dual"`` and with HI armed.
    lp_pivots: jnp.ndarray
    lp_pivot_slots: jnp.ndarray


_STATE_FIELDS = ("period", "key", "p_ed", "pending", "head", "warm_basis",
                 "n_updates", "pos", "cell", "cell_load", "p_es_belief",
                 "hi")
_METRIC_FIELDS = tuple(f.name for f in dataclasses.fields(PeriodMetrics))
_PARAM_LEAVES = ("classes", "base_p_ed", "p_es", "acc", "T", "rate",
                 "class_probs", "drift", "outage", "counts", "stream",
                 "faults", "mobility", "hi")
_PARAM_AUX = ("policy", "arrivals", "n_servers", "batch_max",
              "straggler_threshold", "ema", "frac_tol", "iters", "maxiter",
              "tol", "lp_method", "chaos", "max_retries", "fault_seed",
              "mobility_mode", "routing", "n_cells", "mobility_seed",
              "shard_by_cell", "hi_rule", "hi_stream", "hi_arms",
              "hi_seed", "hi_local", "differentiable", "smooth_mode",
              "smooth_tau", "admit_tau", "grad_leaves")

# EngineParams leaves `rollout_grad` may differentiate: the continuous
# fleet knobs.  Integer/bool leaves (counts, stream, outage, classes) and
# the replayed schedules are bookkeeping — `partition_diff` fences them.
GRAD_LEAVES = ("p_es", "base_p_ed", "acc", "T")

_register(EngineParams, _PARAM_LEAVES, _PARAM_AUX)
_register(EngineState, _STATE_FIELDS)
_register(PeriodMetrics, _METRIC_FIELDS)


def init_state(params: EngineParams, *, seed: int = 0) -> EngineState:
    """A fresh fleet: beliefs = profiles, empty backlog, cold bases."""
    D = params.n_devices
    S = max(params.n_cells, 1)
    armed = params.mobility_mode != "off"
    return EngineState(
        period=np.zeros((), np.int32),
        key=np.asarray(jax.random.PRNGKey(seed)),
        p_ed=np.array(params.base_p_ed, np.float64),
        pending=np.zeros(D, np.int32),
        head=np.zeros(D, np.int32),
        warm_basis=np.full((D, params.n_basis_rows), -1, np.int32),
        n_updates=np.zeros(D, np.int32),
        pos=(np.array(params.mobility.trace[0], np.float64) if armed
             else np.zeros((D, 2), np.float64)),
        cell=np.full(D, -1 if armed else 0, np.int32),
        cell_load=np.zeros(S, np.float64),
        p_es_belief=np.array(params.p_es, np.float64),
        hi=HILearnerState.init(D, params.hi_arms, params.hi.theta0))


# --------------------------------------------------------------------------
# traced building blocks
# --------------------------------------------------------------------------
def admit_mask_jnp(demands, T, n_servers: int):
    """Traced `EdgeServerPool.admit`: ascending-demand (device id on
    ties), least-loaded-server-first first-fit as a `lax.scan` over the
    sorted device order.  ``demands`` (D,) with <= 0 marking
    non-offloaders.  Returns ``(admitted (D,) bool, loads (n_servers,))``
    — identical decisions to the host `admit`/`admit_mask`."""
    D = demands.shape[0]
    eff = jnp.where(demands > 0, demands, jnp.inf)
    order = jnp.argsort(eff, stable=True)

    def body(carry, d):
        loads, mask = carry
        need = demands[d]
        slot = jnp.argmin(loads)
        ok = (need > 0) & (loads[slot] + need <= T + 1e-12)
        loads = loads.at[slot].add(jnp.where(ok, need, 0.0))
        mask = mask.at[d].set(ok)
        return (loads, mask), None

    (loads, mask), _ = jax.lax.scan(
        body, (jnp.zeros(n_servers, demands.dtype),
               jnp.zeros(D, dtype=bool)), order)
    return mask, loads


# Lane-chunk width for the per-period plan: fleets larger than this are
# planned as `lax.map` over chunks of lanes so the whole build -> factor ->
# pivot -> round pipeline stays cache-resident per chunk.  Every lane's
# arithmetic is independent, so chunking is BIT-IDENTICAL to the flat plan
# (pinned by the rollout parity gates) — it only changes memory traffic: a
# flat 16k+-lane pivot loop streams the full (D, R, C0) working set from
# DRAM every iteration and runs ~2.5x slower per lane than the 256-lane
# point.  0 disables; fleets not divisible by the chunk run flat.
_PLAN_LANE_CHUNK = int(os.environ.get("REPRO_PLAN_LANE_CHUNK", "1024"))


def _lane_chunks(D: int) -> int:
    """Number of lane chunks `_plan` splits a D-lane batch into (1: flat;
    see `_PLAN_LANE_CHUNK`)."""
    chunk = _PLAN_LANE_CHUNK
    return 1 if not chunk or D <= chunk or D % chunk else D // chunk


def _plan(params: EngineParams, fp: FleetProblem, warm_basis,
          lane_mask=None):
    """Chunked wrapper over `_plan_flat` (see `_PLAN_LANE_CHUNK`)."""
    D = fp.p_es.shape[0]
    nc = _lane_chunks(D)
    if nc == 1:
        return _plan_flat(params, fp, warm_basis, lane_mask)
    chunk = D // nc

    def resh(x):
        return x.reshape((nc, chunk) + x.shape[1:])

    xs = (jax.tree.map(resh, fp),
          None if warm_basis is None else resh(warm_basis),
          None if lane_mask is None else resh(lane_mask))
    out = jax.lax.map(
        lambda a: _plan_flat(params, a[0], a[1], a[2]), xs)
    return jax.tree.map(lambda x: x.reshape((D,) + x.shape[2:]), out)


def _lockstep_slots(niter, axis_name: Optional[str] = None):
    """Pivot slots the lockstep simplex loop paid for: per lane batch of
    `_plan` (each lane chunk, or the whole flat batch), the batch's
    largest per-lane pivot count times its lanes.  ``niter`` (D,) int32
    per-lane pivots, zero on masked lanes (idle slots).  Under
    ``axis_name`` a batch's largest count is taken over every shard (the
    shards meet at the admission gather, so the slowest shard's loop sets
    the period), which keeps the psum-ed count equal to the unsharded
    one on a flat plan."""
    D = niter.shape[0]
    nc = _lane_chunks(D)
    peak = niter.reshape(nc, D // nc).max(axis=1)
    if axis_name:
        peak = jnp.max(jax.lax.all_gather(peak, axis_name), axis=0)
    return (jnp.sum(peak) * (D // nc)).astype(jnp.int32)


def _plan_flat(params: EngineParams, fp: FleetProblem, warm_basis,
               lane_mask=None):
    """One traced batched solve of a (padded) `FleetProblem`.

    amr2: warm-or-cold batched simplex + vectorized rounding — per-lane
    bit-comparable with the host `solve(..., policy="amr2")` dispatch.
    dual: the vmapped bisection (`core.dual._dual_one`).  Returns
    ``(assignment (D, n) int32, status (D,) int32, basis (D, R) int32,
    niter (D,) int32)`` — ``niter`` each lane's simplex pivots (zero
    under dual and on masked lanes) — plus the LP relaxation ``xbar (D,
    n, m+1)`` as a fifth element when the ``differentiable`` aux is
    armed (amr2 only): the smoothed accuracy blend needs the fractional
    solution, and the solve routes through `lp.simplex_batch_grad` so
    cotangents reach ``A/b/c`` via the implicit KKT solve instead of
    dying at the pivot while_loop.  The LP (build + solve) runs under
    the ``lp`` scope, the rounding under ``round``.
    """
    D, n = fp.p_es.shape
    m = fp.p_ed.shape[2]
    if params.policy == "amr2":
        with jax.named_scope("lp"):
            A, b, c_full = build_lp_arrays_jnp(fp.p_ed, fp.p_es, fp.acc,
                                               fp.T)
            maxiter = params.maxiter if params.maxiter is not None else \
                _bucket_maxiter(50 * (A.shape[1] + 2))
            kw = dict(nv=n * (m + 1), maxiter=maxiter, tol=params.tol,
                      lane_mask=lane_mask, method=params.lp_method)
            if params.differentiable:
                # the implicit VJP needs A itself: dense pricing
                solve = simplex_batch_grad
            else:
                solve = simplex_batch_core
                if params.lp_method == "revised":
                    kw["col_rows"] = lp_column_rows(n, m)
            x, _fun, st, niter, basis, _ok = solve(
                A, b, c_full, warm_basis, **kw)
            xbar = x.reshape(D, n, m + 1)
        with jax.named_scope("round"):
            assign, sched_status, _nf = round_relaxation_jnp(
                fp.p_ed, fp.p_es, fp.acc, fp.T, xbar, st,
                frac_tol=params.frac_tol)
        out = (assign.astype(jnp.int32), sched_status.astype(jnp.int32),
               basis.astype(jnp.int32), niter.astype(jnp.int32))
        return out + (xbar,) if params.differentiable else out
    # dual: no basis to carry, no pivots; status 0 = ok / 1 = fallback
    # (the shared SOLUTION_STATUS_NAMES codes)
    assign, st = jax.vmap(partial(_dual_one, iters=params.iters))(
        fp.p_ed, fp.p_es, fp.acc, fp.T)
    basis = (jnp.asarray(warm_basis, jnp.int32) if warm_basis is not None
             else jnp.full((D, params.n_basis_rows), -1, jnp.int32))
    return (assign.astype(jnp.int32), st.astype(jnp.int32), basis,
            jnp.zeros(D, jnp.int32))


def _recover_unsolved(assign, unsolved, p_ed_jobs, mask, acc, T):
    """Greedy local-only recovery for ``unsolved`` lanes: a lane whose
    simplex hit the iteration cap (or went unbounded) used to ship a
    best-effort argmax rounding that could oversubscribe the ES pool and
    poison the whole period's admission; instead, re-assign its samples
    with the same greedy masked-argmax fill the degradation ladder uses
    (largest local model fitting the residual budget, job order), and
    give no-fit samples the fastest local model (the infeasible-rounding
    convention).  Solved lanes pass through untouched (`jnp.where`), so
    unsolved-free periods are bitwise-unchanged.  The lane still counts
    in ``n_unsolved`` — recovery is damage control, not certification."""
    D, _n, m = p_ed_jobs.shape
    eligible = unsolved[:, None] & mask
    choice, fit, _ = greedy_local_fill(
        p_ed_jobs, acc[:, :m], jnp.broadcast_to(T, (D,)), eligible)
    cheapest = jnp.argmin(p_ed_jobs, axis=2).astype(jnp.int32)
    local = jnp.where(fit, choice, cheapest)
    return jnp.where(eligible, local, assign).astype(jnp.int32)


def _period_impl(belief_p_ed, warm_basis, ci, take, drift_t, outage_t,
                 params: EngineParams, axis_name: Optional[str] = None,
                 fault_key=None, es_belief=None, link_factor=None,
                 covered=None, cell=None, hi_key=None, hi_state=None,
                 hi_t=None):
    """The pure period core shared by `step`, the sharded step, and the
    host `FleetEngine.run_period` delegation: everything AFTER arrivals
    (the released job-class indices ``ci`` (D, n) + counts ``take`` (D,))
    and BEFORE state/stats bookkeeping.

    Under ``axis_name`` (inside `shard_map`) the ES-pool admission runs on
    the `all_gather`-ed global demand vector and every metric scalar is
    `psum`- or max-reduced, so sharded and unsharded outputs agree.

    Mobility plumbing (all optional, None = single-pool semantics):
    ``es_belief`` (D, c) replaces `params.p_es` as the PRICED ES-latency
    table (the chaos audit inflates it; realized execution always prices
    from the true `params.p_es`); ``link_factor`` (D,) scales each
    device's ES latencies by its link to the serving cell; ``covered``
    (D,) False disables a device's ES column like an outage; ``cell``
    (D,) int32 routes admission through the segmented per-cell scan when
    the static ``n_cells`` aux is > 1.

    HI plumbing (consulted only when the static ``hi_rule`` aux is not
    "off"): ``hi_key`` is the period's confidence/arm key
    (`fold_in(PRNGKey(hi_seed), period)` — independent of the arrival
    PRNG), ``hi_state`` the incoming `HILearnerState`, ``hi_t`` the
    period index (step-size decay + replay-trace cursor).

    Returns ``(new_belief_p_ed, new_warm_basis, upd (D,) bool,
    audit_factor (D,), new_es_belief (D, c), cell_load (S,),
    new_hi_state, metrics)``
    with ``metrics`` a dict of scalars (no period/backlog — the callers
    own those).  ``audit_factor`` is the EMA rescale each updated
    device's belief was multiplied by — the host `FleetEngine` delegation
    applies it to its profile-space tables (which may cover more classes
    than the queue's).
    """
    D, _c, m = belief_p_ed.shape
    n = params.batch_max
    with jax.named_scope("arrivals"):
        mask = jnp.arange(n)[None, :] < take[:, None]
        rows = jnp.arange(D)[:, None]
        ci = jnp.clip(ci, 0, params.p_es.shape[1] - 1)
        p_ed_jobs = jnp.where(mask[..., None], belief_p_ed[rows, ci], 0.0)
        base_jobs = jnp.where(mask[..., None], params.base_p_ed[rows, ci],
                              0.0)
        if covered is not None:
            # out-of-coverage == ES link down for this period
            outage_t = outage_t | ~covered

        def _es_jobs(tbl):
            e = jnp.where(mask, tbl[rows, ci], 0.0)
            if link_factor is not None:
                e = e * link_factor[:, None]
            return jnp.where(outage_t[:, None] & mask, ES_DISABLED_SENTINEL,
                             e)

        es_tbl = params.p_es if es_belief is None else es_belief
        p_es_jobs = _es_jobs(es_tbl)
        Tvec = jnp.broadcast_to(params.T, (D,))
        fp = FleetProblem.from_arrays_unchecked(p_ed_jobs, p_es_jobs,
                                                params.acc, Tvec, mask)

    # ---- plan the whole (local) fleet in one traced solve ---------------
    diff = params.differentiable and params.policy == "amr2"
    hi_armed = params.hi_armed
    if hi_armed:
        # ---- online hierarchical inference: the confidence gate IS the
        # plan (core.hi).  Every sample runs ``hi_local`` on-device; the
        # gate additionally offloads the low-confidence ones.  The LP
        # never runs — there is no accuracy table to plan from in the
        # online problem — so basis/unsolved are inert passthroughs.
        with jax.named_scope("hi_gate"):
            lm = params.hi_local
            acc_es_col = params.acc[:, m]
            kc, ka = jax.random.split(hi_key)
            uni = (jnp.take(params.hi.conf_trace,
                            hi_t % params.hi.conf_trace.shape[0], axis=0)
                   if params.hi_stream == "replay" else None)
            conf, correct_local, correct_es = sample_confidence(
                kc, params.hi, params.acc[:, lm], acc_es_col, ci,
                uniforms=uni, axis_name=axis_name)
            offload_int, _theta_t, new_hi, _reg = hi_period(
                params.hi_rule, params.hi, hi_state, conf, correct_local,
                correct_es, mask, acc_es_col, hi_t, ka, params.hi_arms,
                axis_name=axis_name)
            assign = jnp.where(offload_int, jnp.int32(m),
                               jnp.int32(lm)).astype(jnp.int32)
            basis = (jnp.asarray(warm_basis, jnp.int32)
                     if warm_basis is not None
                     else jnp.full((D, params.n_basis_rows), -1, jnp.int32))
            n_unsolved = jnp.zeros(D, jnp.int32)
            pivots = slots = jnp.zeros((), jnp.int32)
        # an outage period needs no special-casing: the ES column prices
        # at the disabled sentinel, so intended offloads carry infeasible
        # demand, lose admission, and fall back local below
    else:
        with jax.named_scope("plan"):
            new_hi = hi_state
            plan_out = _plan(params, fp, warm_basis)
            assign, status, basis, niter = plan_out[:4]
            xbar = plan_out[4] if diff else None
            unsolved_lane = status == _ST_UNSOLVED
            n_unsolved = unsolved_lane.astype(jnp.int32)
            # per-lane recovery: unsolved lanes fall back to a greedy
            # local-only plan (no ES demand) instead of racing uncertified
            # roundings into the admission scan
            assign = _recover_unsolved(assign, unsolved_lane, p_ed_jobs,
                                       mask, params.acc, params.T)
            pivots = jnp.sum(niter)
            slots = _lockstep_slots(niter, axis_name)

    # ---- ES-pool admission on the GLOBAL demand vector ------------------
    # S=1 runs the one-cell fast path of the segmented admission
    # (`core.mobility.admit_mask_pool` — bitwise-pinned to the retired
    # sequential `admit_mask_jnp` scan, ceil(D/k) scan steps instead of
    # D); multi-cell fleets run the segmented per-cell formulation — pure
    # sort/cumsum work, no O(D) sequential pass (core.mobility).  Under
    # `shard_by_cell` the all_gather is elided outright: each shard admits
    # its own cells locally and only the per-cell loads are psum-merged.
    with jax.named_scope("admission"):
        demand = jnp.where(mask & (assign == m), p_es_jobs, 0.0).sum(axis=1)
        use_cells = params.mobility_mode != "off" and params.n_cells > 1
        inc = None      # inclusive chain loads (the admission relaxation)
        if axis_name is None:
            if use_cells:
                admitted, cloads = admit_mask_segmented(
                    demand, cell, params.T, params.n_cells,
                    params.servers_per_cell)
            else:
                admitted, loads, inc = admit_mask_pool(demand, params.T,
                                                       params.n_servers)
        elif use_cells and params.shard_by_cell:
            admitted, cloads = admit_mask_segmented(
                demand, cell, params.T, params.n_cells,
                params.servers_per_cell)
            cloads = jax.lax.psum(cloads, axis_name)
        elif use_cells:
            demand_g = jax.lax.all_gather(demand, axis_name, tiled=True)
            cell_g = jax.lax.all_gather(cell, axis_name, tiled=True)
            admitted_g, cloads = admit_mask_segmented(
                demand_g, cell_g, params.T, params.n_cells,
                params.servers_per_cell)
            idx = jax.lax.axis_index(axis_name)
            admitted = jax.lax.dynamic_slice_in_dim(admitted_g, idx * D, D)
        else:
            demand_g = jax.lax.all_gather(demand, axis_name, tiled=True)
            admitted_g, loads, _inc_g = admit_mask_pool(demand_g, params.T,
                                                        params.n_servers)
            idx = jax.lax.axis_index(axis_name)
            admitted = jax.lax.dynamic_slice_in_dim(admitted_g, idx * D, D)
        if use_cells:
            cell_load_out = cloads.sum(axis=1)              # (S,) global
            loads_total = jnp.sum(cloads)
        else:
            cell_load_out = jnp.sum(loads)[None]            # (1,)
            loads_total = jnp.sum(loads)
        offl = demand > 0
        bumped = offl & ~admitted

    # ---- backpressure: lane-masked ES-disabled replan -------------------
    # Skipped entirely (lax.cond) on no-bump periods; otherwise known-cold
    # (warm_basis=None skips the basis factorization) and non-bumped lanes
    # get a zeroed tableau (amr2) — zero pivots — so the second solve only
    # pays for the devices that actually lost the race.  The predicate is
    # a per-shard scalar, so sharded and unsharded runs agree: a shard
    # with no bumped devices skips a solve whose result its jnp.where
    # would have discarded anyway.
    def _bp_problem():
        p_es_crippled = jnp.where(mask, ES_DISABLED_SENTINEL, 0.0)
        return FleetProblem.from_arrays_unchecked(
            p_ed_jobs, p_es_crippled, params.acc, Tvec, mask)

    with jax.named_scope("replan"):
        if hi_armed:
            # backpressure under HI needs no second LP: a bumped device's
            # intended offloads simply stay on the local model (the sample
            # already ran it — hierarchical inference's graceful fallback)
            assign = jnp.where(bumped[:, None] & mask,
                               jnp.int32(params.hi_local), assign)
        elif diff and axis_name is None:
            # Differentiable mode: the smoothed admission gives EVERY
            # offloader partial weight on its ES-disabled alternative, so
            # the replan runs unconditionally (lane_mask widened from
            # `bumped` to `offl`) — the hard assignment merge below still
            # only reads the bumped lanes, so the hard forward numbers are
            # unchanged.
            bp5 = _plan(params, _bp_problem(), None, lane_mask=offl)
            assign_bp, st_bp, _bas_bp, niter_bp, xbar_bp = bp5
            unsolved_bp = bumped & (st_bp == _ST_UNSOLVED)
            assign_bp = _recover_unsolved(assign_bp, unsolved_bp, p_ed_jobs,
                                          mask, params.acc, params.T)
            assign_pre = assign                 # primary plan, post-recovery
            assign = jnp.where(bumped[:, None], assign_bp, assign)
            n_unsolved = n_unsolved + unsolved_bp.astype(jnp.int32)
            pivots = pivots + jnp.sum(niter_bp)
            slots = slots + _lockstep_slots(niter_bp)
        else:
            def _replan(assign):
                assign_bp, st_bp, _bas, niter_bp = _plan(
                    params, _bp_problem(), None,
                    lane_mask=bumped if params.policy == "amr2"
                    else None)[:4]
                unsolved_bp_lane = bumped & (st_bp == _ST_UNSOLVED)
                assign_bp = _recover_unsolved(assign_bp, unsolved_bp_lane,
                                              p_ed_jobs, mask, params.acc,
                                              params.T)
                return (jnp.where(bumped[:, None], assign_bp, assign),
                        unsolved_bp_lane.astype(jnp.int32), niter_bp)

            assign, unsolved_bp, niter_bp = jax.lax.cond(
                bumped.any(), _replan,
                lambda a: (a, jnp.zeros_like(n_unsolved),
                           jnp.zeros(D, jnp.int32)), assign)
            n_unsolved = n_unsolved + unsolved_bp
            pivots = pivots + jnp.sum(niter_bp)
            slots = slots + _lockstep_slots(niter_bp, axis_name)

    # ---- pricing, violations, straggler audit ---------------------------
    def _sum(x):
        s = jnp.sum(x)
        return jax.lax.psum(s, axis_name) if axis_name else s

    def _max(x):
        v = jnp.max(x, initial=0.0)
        # the TPU lowers no float64 all-reduce but a sum: gather the
        # per-shard maxima and reduce them locally instead of `pmax`
        return (jnp.max(jax.lax.all_gather(v, axis_name)) if axis_name
                else v)

    with jax.named_scope("pricing"):
        acc_jobs = params.acc[rows, assign]
        n_jobs = _sum(mask.astype(jnp.int32))
        # the audits fire only past the threshold by more than rounding
        audit_bar = params.straggler_threshold * (1.0 + AUDIT_RTOL)

        if hi_armed:
            # hierarchical: EVERY masked sample runs the local model (the
            # offloaded ones too), so the ED load prices the full batch at
            # ``hi_local`` regardless of the final assignment
            ed_pred = p_ed_jobs[..., params.hi_local].sum(axis=1)
            ed_wall = base_jobs[..., params.hi_local].sum(axis=1) * drift_t
        else:
            on_ed = mask & (assign < m)
            picked = jnp.clip(assign, 0, m - 1)[..., None]
            ed_pred = jnp.where(
                on_ed, jnp.take_along_axis(p_ed_jobs, picked, axis=2)[..., 0],
                0.0).sum(axis=1)
            ed_wall = jnp.where(
                on_ed, jnp.take_along_axis(base_jobs, picked, axis=2)[..., 0],
                0.0).sum(axis=1) * drift_t
        es_wall = jnp.where(admitted, demand, 0.0)
        es_samp = mask & (assign == m)       # admitted offloads (post-replan)

    # ---- realized execution (chaos): inject faults, walk the ladder -----
    # `params.chaos` is static aux, so the fault-free trace below is the
    # byte-identical pre-chaos graph; armed with a zero-rate FaultModel,
    # every factor is exactly 1.0 / every mask empty, and the realized
    # quantities reproduce the priced ones bit for bit.
    if params.chaos:
        with jax.named_scope("ladder"):
            real = sample_realization(fault_key, params.faults, D, n,
                                      params.max_retries + 1,
                                      axis_name=axis_name)
            lat_local = base_jobs * (drift_t * real.straggler_factor
                                     )[:, None, None]
            # realized execution prices from the TRUE ES table — the
            # audit's inflated belief steers planning/admission, not
            # physics
            true_es_jobs = p_es_jobs if es_belief is None \
                else _es_jobs(params.p_es)
            rx = realize_execution(
                params.faults, real, mask=mask, es_samp=es_samp,
                acc_jobs=acc_jobs, p_es_jobs=true_es_jobs, ed_wall=ed_wall,
                lat_local=lat_local, acc=params.acc, T=params.T,
                max_retries=params.max_retries)
        with jax.named_scope("pricing"):
            total_acc = _sum(jnp.where(mask, rx.acc, 0.0))
            wall = rx.wall
            ed_audit = rx.ed_audit   # excl. fallback compute: the audit
            #                          tracks per-op slowdown, not load
            # chaos -> planner feedback: a device whose realized ES time
            # blew past its priced demand (or whose offloads got dropped)
            # has its ES-latency belief EMA-inflated, so next period's
            # plan offloads less / demands more conservatively.  Null
            # faults realize the priced times bit for bit -> ratio == 1
            # -> no updates.
            es_ratio = rx.es_wall / jnp.maximum(es_wall, 1e-9)
            es_upd = (es_wall > 0) & ((es_ratio > audit_bar)
                                      | (rx.n_dropped > 0))
            es_factor = (1.0 - params.ema) + params.ema * jnp.maximum(
                es_ratio, params.straggler_threshold)
            new_es_belief = jnp.where(es_upd[:, None],
                                      es_tbl * es_factor[:, None], es_tbl)
            ladder = {
                "n_offload_samples": _sum(rx.n_offload),
                "n_offload_ok": _sum(rx.n_offload_ok),
                "n_deadline_miss": _sum(rx.n_deadline_miss),
                "n_retries": _sum(rx.n_retries),
                "n_fallback_local": _sum(rx.n_fallback_local),
                "n_dropped": _sum(rx.n_dropped),
                "n_es_audit_updates": _sum(es_upd.astype(jnp.int32)),
            }
    else:
        with jax.named_scope("pricing"):
            if diff and axis_name is None:
                # ---- smoothed accuracy: the differentiable twin ---------
                # Two discrete stages get relaxed: Algorithm-2 rounding
                # (temperature-softened assignment weights over the LP
                # relaxation) and first-fit admission (a sigmoid capacity
                # test on each offloader's inclusive chain load `inc` —
                # the EXACT value the hard first-fit compared against T).
                # Per device: accP from the primary plan, accBP from the
                # ES-disabled replan, blended by the admission weight; the
                # "st" mode forwards the HARD decisions (one-hot weights,
                # boolean admission) and routes gradients through the soft
                # ones, so served numbers match the hard path while the
                # cotangents stay alive.
                if params.smooth_mode == "st":
                    wP = straight_through_weights(xbar, assign_pre,
                                                  tau=params.smooth_tau)
                    wBP = straight_through_weights(xbar_bp, assign_bp,
                                                   tau=params.smooth_tau)
                else:
                    wP = soft_assignment_weights(xbar, tau=params.smooth_tau)
                    wBP = soft_assignment_weights(xbar_bp,
                                                  tau=params.smooth_tau)
                accP = jnp.where(mask, jnp.einsum("dsi,di->ds", wP,
                                                  params.acc),
                                 0.0).sum(axis=1)
                accBP = jnp.where(mask, jnp.einsum("dsi,di->ds", wBP,
                                                   params.acc),
                                  0.0).sum(axis=1)
                adm_soft = jax.nn.sigmoid(
                    (params.T + 1e-12 - inc) / (params.admit_tau * params.T))
                if params.smooth_mode == "st":
                    adm_use = adm_soft + jax.lax.stop_gradient(
                        admitted.astype(adm_soft.dtype) - adm_soft)
                else:
                    adm_use = adm_soft
                dev_acc = jnp.where(offl, adm_use * accP
                                    + (1.0 - adm_use) * accBP, accP)
                total_acc = jnp.sum(dev_acc)
            elif hi_armed:
                # expected served accuracy under perfect calibration: an
                # admitted offload scores the ES accuracy, a locally-served
                # sample its own confidence (E[correct | conf] == conf)
                total_acc = _sum(jnp.where(
                    mask, jnp.where(es_samp, acc_es_col[:, None], conf),
                    0.0))
            else:
                total_acc = _sum(jnp.where(mask, acc_jobs, 0.0))
            wall = jnp.maximum(ed_wall, es_wall)
            ed_audit = ed_wall
            new_es_belief = es_tbl
            n_off = _sum(es_samp.astype(jnp.int32))
            zero = jnp.zeros((), jnp.int32)
            ladder = {
                "n_offload_samples": n_off, "n_offload_ok": n_off,
                "n_deadline_miss": zero, "n_retries": zero,
                "n_fallback_local": zero, "n_dropped": zero,
                "n_es_audit_updates": zero,
            }
    with jax.named_scope("pricing"):
        viol = jnp.maximum(0.0, wall / params.T - 1.0)

        ratio = ed_audit / jnp.maximum(ed_pred, 1e-9)
        upd = (ed_pred > 0) & (ratio > audit_bar)
        factor = (1.0 - params.ema) + params.ema * ratio
        new_belief = jnp.where(upd[:, None, None],
                               belief_p_ed * factor[:, None, None],
                               belief_p_ed)
        new_warm = basis if params.policy == "amr2" else warm_basis

        metrics = {
            "n_jobs": n_jobs,
            "total_accuracy": total_acc,
            "n_violations": _sum((viol > 0).astype(jnp.int32)),
            "worst_violation": _max(viol),
            "n_offloading": _sum(offl.astype(jnp.int32)),
            "n_backpressured": _sum(bumped.astype(jnp.int32)),
            "n_outage": _sum(outage_t.astype(jnp.int32)),
            "n_straggler_updates": _sum(upd.astype(jnp.int32)),
            "n_unsolved": _sum(n_unsolved),
            "es_utilization": loads_total / (params.n_servers * params.T),
            "realized_makespan": _max(wall),
            "lp_pivots": _sum(pivots),
            "lp_pivot_slots": _sum(slots),
            **ladder,
        }
        if hi_armed:
            metrics.update(
                n_hi_offloaded=_sum(es_samp.astype(jnp.int32)),
                n_hi_local_final=_sum((mask & (assign != m)
                                       ).astype(jnp.int32)),
                hi_regret=_sum(new_hi.cum_regret))
        else:
            metrics.update(n_hi_offloaded=jnp.zeros((), jnp.int32),
                           n_hi_local_final=jnp.zeros((), jnp.int32),
                           hi_regret=jnp.zeros((), jnp.float64))
        new_warm = new_warm.astype(jnp.int32)
    return (new_belief, new_warm, upd, factor, new_es_belief, cell_load_out,
            new_hi, metrics)


def _arrivals(state: EngineState, params: EngineParams,
              axis_name: Optional[str] = None):
    """Release this period's jobs: ``(ci (D, n) int32 class indices,
    take (D,) int32, pending' , head', key')``."""
    D = state.pending.shape[0]
    n = params.batch_max
    t = state.period
    if params.arrivals == "replay":
        counts_t = jnp.take(params.counts, t % params.counts.shape[0],
                            axis=0).astype(jnp.int32)
        key = state.key
    else:
        k_counts, k_classes, key = jax.random.split(state.key, 3)
        offset = (jax.lax.axis_index(axis_name) * D
                  if axis_name else jnp.int32(0))
        gid = offset + jnp.arange(D, dtype=jnp.int32)
        # per-device folded keys: sharded and unsharded sampling agree
        kd = jax.vmap(lambda g: jax.random.fold_in(k_counts, g))(gid)
        counts_t = jax.vmap(
            lambda k, lam: jax.random.poisson(k, lam))(
                kd, params.rate).astype(jnp.int32)
        kc = jax.vmap(lambda g: jax.random.fold_in(k_classes, g))(gid)
    avail = state.pending + counts_t
    take = jnp.minimum(avail, n).astype(jnp.int32)
    if params.arrivals == "replay":
        S = params.stream.shape[1]
        idx = state.head[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
        ci = jnp.take_along_axis(params.stream,
                                 jnp.clip(idx, 0, S - 1), axis=1)
        head = (state.head + take).astype(jnp.int32)
    else:
        c = params.class_probs.shape[0]
        ci = jax.vmap(lambda k: jax.random.choice(
            k, c, shape=(n,), p=params.class_probs))(kc)
        head = state.head
    return (ci.astype(jnp.int32), take,
            (avail - take).astype(jnp.int32), head, key)


def _step_impl(state: EngineState, params: EngineParams,
               axis_name: Optional[str] = None
               ) -> Tuple[EngineState, PeriodMetrics]:
    """One pure period: arrivals + `_period_impl` + state/metric assembly.

    Every op of the period sits under one top-level `jax.named_scope`,
    in order: ``arrivals``, ``route`` (mobility armed), ``plan`` (or
    ``hi_gate``), ``admission``, ``replan``, ``ladder`` (chaos armed),
    ``pricing``; ``plan`` and ``replan`` nest ``lp`` and ``round``.
    Scopes are HLO metadata only: they change no op and no number."""
    with jax.named_scope("arrivals"):
        t = state.period
        D = state.pending.shape[0]
        H = params.drift.shape[1]
        drift_t = jnp.take(params.drift, t % H, axis=1)
        outage_t = jnp.take(params.outage, t % H, axis=1)
        # A basis optimal for last period's LP is meaningless when the ES
        # column set changed underneath it (outage flipping on/off swaps
        # the offload columns for the disabled sentinel): mask those lanes
        # back to -1 so `_warm_init` cold-starts them instead of factoring
        # a basis of the wrong problem.
        outage_prev = jnp.take(params.outage, (t - 1) % H, axis=1)
        stale = (t > 0) & (outage_prev != outage_t)
    # ---- mobility: move, route, detect handover -------------------------
    n_handover = None
    if params.mobility_mode != "off":
        with jax.named_scope("route"):
            mob = params.mobility
            if params.mobility_mode == "replay":
                pos_t = jnp.take(mob.trace, t % mob.trace.shape[0], axis=0)
            else:                                           # random walk
                # folded replayed stream (the fault_seed idiom): per-device
                # GLOBAL-id folds, so sharded and unsharded walks agree and
                # arming mobility never perturbs the arrival PRNG
                kw = jax.random.fold_in(
                    jax.random.PRNGKey(params.mobility_seed), t)
                offset = (jax.lax.axis_index(axis_name) * D
                          if axis_name else jnp.int32(0))
                gid = offset + jnp.arange(D, dtype=jnp.int32)
                kd = jax.vmap(lambda g: jax.random.fold_in(kw, g))(gid)
                steps = jax.vmap(
                    lambda k: jax.random.normal(k, (2,), jnp.float64))(kd)
                pos_t = state.pos + mob.walk_sigma * steps
            load_frac = state.cell_load / (params.servers_per_cell
                                           * params.T)
            cell_t, covered, link_factor = route_cells(
                pos_t, mob, load_frac, params.routing)
            # handover: the previous cell's basis labels an LP whose ES
            # column was priced for a different link — cold-start it, and
            # migrate the ES belief back to the new cell's nominal table
            switched = (t > 0) & (cell_t != state.cell)
            stale = stale | switched
            es_belief0 = jnp.where(switched[:, None], params.p_es,
                                   state.p_es_belief)
            n_handover = jnp.sum(switched.astype(jnp.int32))
    else:
        pos_t, cell_t = state.pos, state.cell
        covered = link_factor = None
        es_belief0 = state.p_es_belief
    with jax.named_scope("arrivals"):
        warm0 = jnp.where(stale[:, None], jnp.int32(-1), state.warm_basis)
        ci, take, pending, head, key = _arrivals(state, params, axis_name)
    # the fault stream is replayed — folded from a dedicated seed, never
    # drawn from state.key — so arming chaos leaves the arrival (and
    # fault-free metric) trajectory bitwise-untouched, and the host
    # delegation can reproduce the exact same draw per period
    fkey = hikey = None
    if params.chaos:
        with jax.named_scope("ladder"):
            fkey = jax.random.fold_in(
                jax.random.PRNGKey(params.fault_seed), t)
    # the confidence stream is replayed the same way — folded from its
    # own seed — so arming HI never perturbs arrivals either
    if params.hi_armed:
        with jax.named_scope("hi_gate"):
            hikey = jax.random.fold_in(jax.random.PRNGKey(params.hi_seed), t)
    (new_belief, new_warm, upd, _factor, new_es_belief, cell_load,
     new_hi, m) = _period_impl(
        state.p_ed, warm0, ci, take, drift_t, outage_t, params,
        axis_name=axis_name, fault_key=fkey, es_belief=es_belief0,
        link_factor=link_factor, covered=covered, cell=cell_t,
        hi_key=hikey, hi_state=state.hi, hi_t=t)
    with jax.named_scope("pricing"):
        backlog = jnp.sum(pending)
        if n_handover is None:
            n_handover = jnp.zeros((), jnp.int32)
        if axis_name:
            backlog = jax.lax.psum(backlog, axis_name)
            n_handover = jax.lax.psum(n_handover, axis_name)
        n_jobs = m["n_jobs"]
        metrics = PeriodMetrics(
            period=t,
            mean_job_accuracy=jnp.where(
                n_jobs > 0, m["total_accuracy"] / jnp.maximum(n_jobs, 1),
                0.0),
            backlog=backlog.astype(jnp.int32),
            n_handover=n_handover.astype(jnp.int32), **m)
        new_state = EngineState(
            period=(t + 1).astype(jnp.int32), key=key, p_ed=new_belief,
            pending=pending, head=head, warm_basis=new_warm,
            n_updates=(state.n_updates + upd.astype(jnp.int32)),
            pos=pos_t, cell=cell_t.astype(jnp.int32), cell_load=cell_load,
            p_es_belief=new_es_belief, hi=new_hi)
    return new_state, metrics


@jax.jit
def _step_jit(state, params):
    return _step_impl(state, params)


@jax.jit
def _period_jit(belief, warm_basis, ci, take, drift_t, outage_t, params,
                fault_key=None, es_belief=None, hi_key=None,
                hi_state=None, hi_t=None):
    """The host `FleetEngine.run_period` delegation target: the same
    period core `step` scans over, minus the arrival/state bookkeeping
    (the host engine owns its queue and stats).  ``fault_key`` replays
    one period of the fault stream (`fold_in(PRNGKey(fault_seed),
    period)` — the exact draw `step` makes), or None when chaos is
    disarmed.  ``es_belief`` threads the chaos-audited ES price table
    between host periods (None prices from the nominal `params.p_es`).
    ``hi_key``/``hi_state``/``hi_t`` replay one period of the HI stream
    and thread the learner state the same way (None while disarmed)."""
    return _period_impl(belief, warm_basis, ci, take, drift_t, outage_t,
                        params, fault_key=fault_key, es_belief=es_belief,
                        hi_key=hi_key, hi_state=hi_state, hi_t=hi_t)


def _rollout_impl(state, params, periods: int):
    def body(s, _):
        return _step_impl(s, params)
    return jax.lax.scan(body, state, None, length=periods)


_rollout_jit = partial(jax.jit, static_argnames=("periods",))(_rollout_impl)
# the donated variant consumes the input EngineState's buffers in place —
# at 100k devices the (D, R, R)-adjacent state leaves are the allocation
# high-water mark, and a rollout that donates them runs at half the peak
# memory of one that keeps the input alive
_rollout_donate = partial(jax.jit, static_argnames=("periods",),
                          donate_argnums=(0,))(_rollout_impl)


def _require_f64(tag: str, tree) -> None:
    """Reject float32 leaves loudly instead of computing with them.

    The engine is float64 end-to-end (the LP parity contract): every entry
    point wraps its jit in `enable_x64`, but that scope cannot UPCAST
    arrays that were already materialized as float32 — e.g. a state
    `device_put` outside any x64 scope while jax's global x64 mode is off.
    Silently running the rollout at single precision breaks the host
    bit-parity guarantees, so fail with the leaf's path instead."""
    for f in dataclasses.fields(tree):
        leaf = getattr(tree, f.name)
        if dataclasses.is_dataclass(leaf) and not isinstance(leaf, type):
            _require_f64(f"{tag}.{f.name}", leaf)   # e.g. params.faults
            continue
        dt = getattr(leaf, "dtype", None)
        if (dt is not None and jnp.issubdtype(dt, jnp.floating)
                and dt != jnp.float64):
            raise TypeError(
                f"{tag}.{f.name} is {dt} but the "
                f"engine is float64-only; build arrays as float64 and do "
                f"device transfers inside repro.core.types.x64_scope() "
                f"(with jax's global x64 mode off, an unscoped "
                f"device_put downcasts to float32)")


def _check_horizon(state: EngineState, params: EngineParams,
                   periods: int) -> None:
    if params.arrivals != "replay":
        return
    end = int(np.asarray(state.period)) + periods
    if end > params.counts.shape[0]:
        raise ValueError(
            f"replayed arrival trace covers {params.counts.shape[0]} "
            f"periods but the rollout needs {end}; presample a longer "
            f"horizon (EngineParams.from_config(..., horizon=)) or use "
            f"arrivals='poisson'")


@contextlib.contextmanager
def _entry_spans(entry: str, state: EngineState, params: EngineParams,
                 periods: int):
    """Host spans of a public entry point, for `jax.profiler` traces: an
    outer ``repro.<entry>`` holding, in order, ``repro.validate`` (the
    float64 checks), ``repro.horizon`` (`_check_horizon`, whose
    ``state.period`` read is a device-to-host copy) and ``repro.launch``
    (`x64_scope` and the jitted call until it returns, the body of the
    ``with``).  With no profiler running each span costs about a
    microsecond."""
    with jax.profiler.TraceAnnotation(f"repro.{entry}"):
        with jax.profiler.TraceAnnotation("repro.validate"):
            _require_f64("state", state)
            _require_f64("params", params)
        with jax.profiler.TraceAnnotation("repro.horizon"):
            _check_horizon(state, params, periods)
        with jax.profiler.TraceAnnotation("repro.launch"), x64_scope():
            yield


def step(state: EngineState, params: EngineParams
         ) -> Tuple[EngineState, PeriodMetrics]:
    """One jitted period transition (float64, like the host LP path)."""
    with _entry_spans("step", state, params, 1):
        return _step_jit(state, params)


def rollout(state: EngineState, params: EngineParams, periods: int,
            *, donate: bool = False
            ) -> Tuple[EngineState, PeriodMetrics]:
    """A whole fleet epoch as ONE `lax.scan` over the jitted step — zero
    per-period host round-trips.  Returns ``(final_state, metrics)`` with
    every `PeriodMetrics` field stacked to a (periods,) array.

    ``donate=True`` donates the input state's buffers to the scan (the
    caller must not reuse ``state`` afterwards) — at the 100k-device
    scale this halves peak memory, since the old and new fleet state
    never need to coexist."""
    fn = _rollout_donate if donate else _rollout_jit
    with _entry_spans("rollout", state, params, periods):
        return fn(state, params, int(periods))


# --------------------------------------------------------------------------
# differentiation: pytree partition + rollout gradients
# --------------------------------------------------------------------------
# Placeholder for the non-selected half of a partitioned pytree.  None on
# purpose: jax treats None as an EMPTY subtree, so `jax.grad` over the
# diff half traces ONLY the float leaves (an opaque sentinel object would
# be rejected as "not a valid JAX type" the moment the half crosses a
# jit/grad boundary).  `combine_diff` re-materializes the placeholders as
# leaves via ``is_leaf`` when zipping the halves back together.
_NONDIFF = None


def partition_diff(tree):
    """Split a pytree into (diff, nondiff) halves by leaf dtype.

    Inexact (float) leaves keep their value in the ``diff`` half and
    become ``None`` in ``nondiff``; integer/bool/key leaves — warm basis
    labels, stream cursors, PRNG keys, fault counters — go the other
    way.  Both halves keep the ORIGINAL node structure, so ``jax.grad``
    over the diff half traces only continuous leaves (a naive grad over
    a full `EngineState` dies on the int32 bookkeeping) and
    `combine_diff` reassembles losslessly."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    isf = [jnp.issubdtype(getattr(l, "dtype", np.asarray(l).dtype),
                          jnp.inexact) for l in leaves]
    diff = treedef.unflatten(
        [l if f else _NONDIFF for l, f in zip(leaves, isf)])
    nondiff = treedef.unflatten(
        [_NONDIFF if f else l for l, f in zip(leaves, isf)])
    return diff, nondiff


def combine_diff(diff, nondiff):
    """Inverse of `partition_diff`: merge the two halves back into one
    pytree (each leaf comes from whichever half is not the ``None``
    placeholder).  ``is_leaf`` keeps the placeholders visible to the
    zip — without it each None is an empty subtree and the two halves
    would not share a structure."""
    return jax.tree_util.tree_map(
        lambda d, n: d if n is _NONDIFF else n, diff, nondiff,
        is_leaf=lambda x: x is _NONDIFF)


def _vag_impl(leaf_vals, state, params, periods: int, wrt: tuple):
    """Differentiable rollout objective: total served accuracy over the
    epoch as a function of the selected `EngineParams` leaves.

    The belief tables are re-rooted at the (differentiated) nominal
    tables — `_period_impl` PRICES from `state.p_ed`/`state.p_es_belief`,
    not the params leaves, so without the rebinding every cotangent
    w.r.t. ``p_es``/``base_p_ed`` would be zero.  With chaos disarmed
    (the `with_differentiable` contract) the rebinding is semantically
    what `init_state` does anyway."""
    params = dataclasses.replace(params, **dict(zip(wrt, leaf_vals)))
    state = dataclasses.replace(
        state, p_ed=jnp.asarray(params.base_p_ed, jnp.float64),
        p_es_belief=jnp.asarray(params.p_es, jnp.float64))
    _, metrics = _rollout_impl(state, params, periods)
    return jnp.sum(metrics.total_accuracy)


_vag_jit = partial(jax.jit, static_argnames=("periods", "wrt"))(
    jax.value_and_grad(_vag_impl))


def _grad_entry(state, params, periods, wrt):
    if not params.differentiable:
        raise ValueError(
            "rollout_grad/rollout_value_and_grad need "
            "params.with_differentiable() — with the flag off the "
            "forward trace is the hard (piecewise-constant) path and "
            "every gradient would be zero")
    _require_f64("state", state)
    _require_f64("params", params)
    _check_horizon(state, params, int(periods))
    wrt = tuple(wrt) if wrt is not None else tuple(params.grad_leaves)
    bad = [f for f in wrt if f not in GRAD_LEAVES]
    if bad:
        raise ValueError(f"wrt {bad} not differentiable; the continuous "
                         f"EngineParams knobs are {GRAD_LEAVES}")
    # the leaves are float64 already (checked above); materializing them
    # with jnp.asarray OUTSIDE an enable_x64 scope would downcast
    leaf_vals = tuple(getattr(params, f) for f in wrt)
    return leaf_vals, wrt


def rollout_value_and_grad(state: EngineState, params: EngineParams,
                           periods: int, *,
                           wrt: Optional[Tuple[str, ...]] = None):
    """``(value, grads)`` of the rolled-out TOTAL ACCURACY w.r.t. the
    named continuous `EngineParams` leaves (default: the params'
    ``grad_leaves`` aux — ES capacity ``p_es``, deadline ``T``, ladder
    mix ``acc``).  ``grads`` is a dict keyed by leaf name, each entry
    shaped like the leaf.

    The whole epoch runs as the same single `lax.scan` as `rollout`,
    with the LP differentiated implicitly at its converged basis and the
    rounding/admission stages smoothed per the params' ``smooth_mode``
    ("st": value == the hard rollout's served accuracy; "soft": value is
    the softened surrogate the finite-difference gates check).  Requires
    `EngineParams.with_differentiable`; sharded rollouts are not
    differentiable (run gradients on the single-host trace)."""
    leaf_vals, wrt = _grad_entry(state, params, periods, wrt)
    with x64_scope():
        val, grads = _vag_jit(leaf_vals, state, params,
                              periods=int(periods), wrt=wrt)
    return val, dict(zip(wrt, grads))


def rollout_grad(state: EngineState, params: EngineParams, periods: int,
                 *, wrt: Optional[Tuple[str, ...]] = None):
    """`rollout_value_and_grad` without the value (same one compiled
    pass — `jax.value_and_grad` underneath)."""
    return rollout_value_and_grad(state, params, periods, wrt=wrt)[1]


# --------------------------------------------------------------------------
# sharding: device_put the fleet axis, run step/rollout under shard_map
# --------------------------------------------------------------------------
def fleet_mesh(n_shards: Optional[int] = None):
    """A 1-D mesh over the first ``n_shards`` local jax devices (all by
    default) with the ``"fleet"`` axis.  On CPU, spawn host platform
    devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    BEFORE importing jax."""
    from jax.sharding import Mesh
    devices = jax.devices()
    n = n_shards if n_shards is not None else len(devices)
    if n > len(devices):
        raise ValueError(f"asked for {n} shards but only "
                         f"{len(devices)} jax devices exist")
    return Mesh(np.asarray(devices[:n]), (FLEET_AXIS,))


def _state_specs():
    from jax.sharding import PartitionSpec as P
    dev = P(FLEET_AXIS)
    return EngineState(period=P(), key=P(), p_ed=dev, pending=dev,
                       head=dev, warm_basis=dev, n_updates=dev,
                       pos=dev, cell=dev, cell_load=P(), p_es_belief=dev,
                       hi=HILearnerState(theta=dev, arm=dev, arms_sum=dev,
                                         arms_cnt=dev, es_sum=dev,
                                         es_cnt=dev, cum_regret=dev))


def _param_specs(params: EngineParams):
    """Spec pytree matching ``params``' structure (the static aux rides
    along so tree_map/shard_map can pair specs with leaves)."""
    from jax.sharding import PartitionSpec as P
    dev = P(FLEET_AXIS)
    fault_specs = FaultModel(
        **{f.name: P() for f in dataclasses.fields(FaultModel)})
    # the trace is (H, D, 2): replicated horizon axis, sharded fleet axis
    # (cells themselves are global — every shard sees all S of them).
    # Disarmed, the null model's (1, 1, 2) placeholder trace cannot split
    # over the fleet axis — replicate it instead.
    mobility_specs = MobilityModel(
        cell_xy=P(), cell_rate=P(), radius=P(), link_alpha=P(),
        walk_sigma=P(),
        trace=(P(None, FLEET_AXIS) if params.mobility_mode != "off"
               else P()))
    # armed HI never reaches the sharded entries (`_reject_hi_sharded`),
    # so the null model's placeholder leaves just replicate
    hi_specs = HIModel(
        **{f.name: P() for f in dataclasses.fields(HIModel)})
    return dataclasses.replace(
        params, classes=P(), base_p_ed=dev, p_es=dev, acc=dev, T=P(),
        rate=dev, class_probs=P(), drift=dev, outage=dev,
        counts=P(None, FLEET_AXIS), stream=dev, faults=fault_specs,
        mobility=mobility_specs, hi=hi_specs)


def _metric_specs():
    from jax.sharding import PartitionSpec as P
    return PeriodMetrics(**{f: P() for f in _METRIC_FIELDS})


def shard(state: EngineState, params: EngineParams, mesh
          ) -> Tuple[EngineState, EngineParams]:
    """`device_put` the stacked fleet axis across ``mesh``: every
    per-device leaf of the state and params — the same arrays a period's
    `FleetProblem` is gathered from — lands block-partitioned along
    ``"fleet"``; scalars and class tables are replicated.  The fleet size
    must divide the mesh."""
    from jax.sharding import NamedSharding
    _reject_hi_sharded(params)
    _require_f64("state", state)
    _require_f64("params", params)
    D = params.n_devices
    n_shards = int(np.prod(mesh.devices.shape))
    if D % n_shards:
        raise ValueError(
            f"fleet size {D} does not divide the {n_shards}-device mesh")
    put = lambda tree, specs: jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)
    with x64_scope():  # keep float64 leaves f64 across the device_put
        return put(state, _state_specs()), put(params, _param_specs(params))


@functools.lru_cache(maxsize=None)
def _sharded_fn(mesh, periods: Optional[int], params_aux: tuple,
                donate: bool = False):
    """Build (and cache) the shard_mapped step / rollout for a mesh.

    ``params_aux`` (the `EngineParams` static fields) is part of the cache
    key because the in_specs pytree must carry the same aux as the actual
    params being passed; ``donate`` keys the variant that consumes the
    input state's buffers."""
    spec_params = _param_specs(
        EngineParams(**{f: None for f in _PARAM_LEAVES},
                     **dict(zip(_PARAM_AUX, params_aux))))
    if periods is None:
        fn = partial(_step_impl, axis_name=FLEET_AXIS)
    else:
        def fn(state, params):
            return jax.lax.scan(
                lambda s, _: _step_impl(s, params, axis_name=FLEET_AXIS),
                state, None, length=periods)
    mapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(_state_specs(), spec_params),
        out_specs=(_state_specs(), _metric_specs()),
        check_vma=False)
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def _aux_of(params: EngineParams) -> tuple:
    return tuple(getattr(params, f) for f in _PARAM_AUX)


def _reject_diff_sharded(params: EngineParams) -> None:
    """The `with_differentiable` contract: gradients run on the
    single-host trace.  The smoothed pricing and the unconditional
    replan only exist on the ``axis_name is None`` branch of
    `_period_impl`, so a sharded "differentiable" rollout would silently
    run the hard forward — reject instead of letting the flag lie."""
    if params.differentiable:
        raise ValueError(
            "sharded entry points do not support differentiable params; "
            "disarm with with_differentiable(False) or run "
            "rollout_value_and_grad on the single-host trace")


def _reject_hi_sharded(params: EngineParams) -> None:
    """Armed HI carries learner state whose replay-trace slicing and
    per-arm bookkeeping have not been validated under `shard_map` yet —
    reject instead of silently diverging from the unsharded trajectory
    (the confidence stream itself already folds GLOBAL device ids, so
    this rung is small; see ROADMAP)."""
    if params.hi_armed:
        raise ValueError(
            "sharded entry points do not support armed HI "
            f"(hi_rule={params.hi_rule!r}); disarm with with_hi(None) or "
            "run the single-host rollout")


def step_sharded(state: EngineState, params: EngineParams, mesh
                 ) -> Tuple[EngineState, PeriodMetrics]:
    """`step` under `shard_map`: the fleet axis stays partitioned across
    the mesh; admission gathers the (D,) demand vector and metrics are
    psum-reduced, so the output matches the unsharded `step`."""
    _reject_diff_sharded(params)
    _reject_hi_sharded(params)
    with _entry_spans("step_sharded", state, params, 1):
        return _sharded_fn(mesh, None, _aux_of(params))(state, params)


def rollout_sharded(state: EngineState, params: EngineParams,
                    periods: int, mesh, *, donate: bool = False
                    ) -> Tuple[EngineState, PeriodMetrics]:
    """`rollout` under `shard_map`: one scan, fleet axis sharded
    throughout — the ROADMAP's 10k+-device shape.  ``donate=True``
    consumes the input state's shards (see `rollout`)."""
    _reject_diff_sharded(params)
    _reject_hi_sharded(params)
    with _entry_spans("rollout_sharded", state, params, periods):
        return _sharded_fn(mesh, int(periods), _aux_of(params),
                           donate)(state, params)
