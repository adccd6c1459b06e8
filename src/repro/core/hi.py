"""Online hierarchical inference: confidence-gated per-sample offloading
with in-rollout learning.

The paper's AMR^2 plans from a KNOWN accuracy table.  Moothedath &
Champati (arXiv 2304.00891) study the online twin of the same problem:
the ED runs its small local model on EVERY sample (that is the
"hierarchical" part), observes a confidence for the local prediction,
and must decide per sample — from that confidence alone, with no prior
knowledge of how accurate the ES model is — whether to ALSO offload.
Offloading buys the ES accuracy at a fixed per-sample cost ``beta``
(``offload_cost``: transmission + ES occupancy in accuracy units), so
under a perfectly calibrated confidence the clairvoyant per-sample rule
is a THRESHOLD: offload iff ``conf < theta*`` with ``theta* = acc_es -
beta``.  The learners below compete with that clairvoyant:

``"fixed"``
    Serve a constant threshold ``theta0`` (the sweepable baseline; a
    per-device ``theta0 = clip(acc_es - beta, 0, 1)`` IS the clairvoyant
    and accrues exactly zero regret).
``"threshold"``
    The paper's one-dimensional online learner: OGD on the threshold
    with a sigmoid-kernel surrogate gradient (the true per-sample loss
    is piecewise constant in ``theta``) and a ``lr / sqrt(t+1)`` step.
    The surrogate's stationary point is ``theta = a_hat_es - beta``
    where ``a_hat_es`` is the running ES-accuracy estimate built from
    the learner's own offloads (optimistic prior 1.0, so early periods
    explore the ES), hence the iterates converge to the clairvoyant
    threshold and the regret is sublinear on a replayed stream.
``"ucb"`` / ``"exp3"``
    Bandit baselines over ``n_arms`` discretized thresholds
    (`arm_grid`): one arm is pulled per device per period, rewarded
    with the period's mean realized per-sample reward.  They bracket
    the threshold learner the way the greedy/dual baselines bracket
    AMR^2.

Everything is pure traced array math in the `core.faults` idiom:

* ``HIModel`` — all-float64-leaf pytree (no static aux), so sweeping
  ``offload_cost``/``lr``/``theta0`` reuses ONE compiled rollout.
* ``HILearnerState`` — the learner's evolving state (threshold, per-arm
  statistics, ES-accuracy counts, cumulative regret), carried as an
  `EngineState` leaf so the whole learning trajectory runs inside the
  engine's single `lax.scan` with zero host sync.
* The confidence stream is REPLAYED — `fold_in(PRNGKey(hi_seed),
  period)` then per-device folds of the GLOBAL device id — independent
  of the arrival PRNG, so arming HI never perturbs arrivals and an
  8-shard and an unsharded run draw identical streams.  ``conf_trace``
  alternatively replays presampled uniforms (`presample_stream`
  produces a trace that reproduces the fold-keyed stream bit for bit).

Calibration: per-sample confidence is drawn as ``p = mu + spread_c *
(u**((1-mu)/mu) - mu)`` with ``mu`` the local model's table accuracy —
the power-law is the closed-form inverse-CDF choice with ``E[p] = mu``
exactly, and the mean-preserving per-class ``spread`` blend keeps it
exact for any spread in [0, 1] — and the local outcome is then Bernoulli
in that confidence, so ``P(correct | conf) == conf`` by construction
(perfect calibration, the regime where the threshold rule is optimal).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .types import x64_scope

__all__ = [
    "HI_RULES", "HI_STREAMS", "EXP3_GAMMA",
    "HIModel", "HILearnerState",
    "arm_grid", "sample_confidence", "presample_stream", "hi_period",
    "validate_hi",
]

# decision rules an armed engine accepts ("off" is the aux default that
# keeps the subsystem out of the trace entirely)
HI_RULES = ("fixed", "threshold", "ucb", "exp3")
HI_STREAMS = ("fold", "replay")
# EXP3 exploration floor (uniform mixing weight); the learning rate is
# the model's ``explore`` leaf
EXP3_GAMMA = 0.1


@dataclasses.dataclass(frozen=True)
class HIModel:
    """Calibration curves + learner hyper-parameters (pytree; every field
    is a float64 leaf — no static aux, so sweeping costs/rates/thresholds
    reuses one compiled rollout, the `FaultModel` contract)."""

    spread: np.ndarray        # (c,) or (1,) per-class calibration spread
    offload_cost: np.ndarray  # () beta: per-sample cost of consulting ES
    lr: np.ndarray            # () OGD step size (decayed by 1/sqrt(t+1))
    tau: np.ndarray           # () surrogate sigmoid temperature
    theta0: np.ndarray        # () or (D,) initial / fixed threshold
    explore: np.ndarray       # () UCB bonus coefficient / EXP3 rate
    conf_trace: np.ndarray    # (H, D, n, 3) replayed uniforms; (1,1,1,3)
    #                           placeholder when the stream is fold-keyed

    @classmethod
    def none(cls) -> "HIModel":
        """The null model: HI disarmed, bitwise-invisible to the trace."""
        z = np.float64(0.0)
        return cls(spread=np.zeros(1, np.float64), offload_cost=z,
                   lr=z, tau=np.float64(1.0), theta0=np.float64(0.5),
                   explore=z, conf_trace=np.zeros((1, 1, 1, 3)))

    @classmethod
    def make(cls, *, spread=0.8, offload_cost: float = 0.15,
             lr: float = 0.2, tau: float = 0.05, theta0=0.5,
             explore: float = 0.5,
             conf_trace: Optional[np.ndarray] = None) -> "HIModel":
        """Keyword constructor with float64 coercion and range checks.
        ``spread`` is a scalar or per-class vector in [0, 1]; ``theta0``
        a scalar or per-device vector in [0, 1] (a per-device ``theta0 =
        clip(acc_es - beta, 0, 1)`` under rule "fixed" is the
        zero-regret clairvoyant)."""
        sp = np.atleast_1d(np.asarray(spread, np.float64))
        if sp.ndim != 1 or np.any(sp < 0) or np.any(sp > 1):
            raise ValueError("spread must be scalar or 1-D in [0, 1]")
        if not 0.0 <= float(offload_cost) < 1.0:
            raise ValueError("offload_cost must be in [0, 1)")
        if lr <= 0 or tau <= 0:
            raise ValueError("lr and tau must be > 0")
        th = np.asarray(theta0, np.float64)
        if np.any(th < 0) or np.any(th > 1) or th.ndim > 1:
            raise ValueError("theta0 must be scalar or 1-D in [0, 1]")
        if explore < 0:
            raise ValueError("explore must be >= 0")
        if conf_trace is None:
            tr = np.zeros((1, 1, 1, 3))
        else:
            tr = np.asarray(conf_trace, np.float64)
            if tr.ndim != 4 or tr.shape[3] != 3:
                raise ValueError(
                    f"conf_trace must be (periods, D, n, 3) uniforms; "
                    f"got {tr.shape}")
        return cls(spread=sp, offload_cost=np.float64(offload_cost),
                   lr=np.float64(lr), tau=np.float64(tau), theta0=th,
                   explore=np.float64(explore), conf_trace=tr)

    @classmethod
    def from_profiles(cls, p_ed, *, spread_range: Tuple[float, float]
                      = (0.35, 0.95), **kw) -> "HIModel":
        """Per-class calibration spreads sampled from the roofline/paper
        latency profiles: classes are ranked by their mean ED latency and
        the spread interpolates ``spread_range`` over that rank — slower
        (harder) classes produce confidences that swing further from the
        model's mean accuracy, i.e. carry more per-sample signal.
        ``p_ed`` is a (c, m) profile table or the engine's stacked
        (D, c, m) ``base_p_ed``; remaining kwargs go to `make`."""
        tbl = np.asarray(p_ed, np.float64)
        if tbl.ndim == 3:
            tbl = tbl.mean(axis=0)
        if tbl.ndim != 2:
            raise ValueError(f"p_ed must be (c, m) or (D, c, m); got "
                             f"shape {tbl.shape}")
        c = tbl.shape[0]
        lo, hi = spread_range
        if not 0.0 <= lo <= hi <= 1.0:
            raise ValueError("spread_range must satisfy 0 <= lo <= hi <= 1")
        if c == 1:
            sp = np.array([(lo + hi) / 2.0])
        else:
            rank = np.argsort(np.argsort(tbl.mean(axis=1)))
            sp = lo + (hi - lo) * rank / (c - 1)
        return cls.make(spread=sp, **kw)

    def is_null(self) -> bool:
        """Host-side: this model carries no confidence signal and no
        learner (the engine keeps HI out of the trace entirely)."""
        return (float(np.max(self.spread)) == 0.0
                and float(self.offload_cost) == 0.0
                and float(self.lr) == 0.0
                and float(self.explore) == 0.0)


_HI_FIELDS = tuple(f.name for f in dataclasses.fields(HIModel))


def _hi_unflatten(aux, children):
    obj = object.__new__(HIModel)
    for f, v in zip(_HI_FIELDS, children):
        object.__setattr__(obj, f, v)
    return obj


jax.tree_util.register_pytree_node(
    HIModel,
    lambda hm: (tuple(getattr(hm, f) for f in _HI_FIELDS), None),
    _hi_unflatten)


@dataclasses.dataclass(frozen=True)
class HILearnerState:
    """The learner's evolving state, one row per device — carried as an
    `EngineState` leaf so the whole trajectory lives inside the scan.
    Counts are float64 on purpose: they feed ratios/bonuses directly and
    keep every learner leaf a single dtype for the f64 discipline."""

    theta: jnp.ndarray       # (D,) current threshold
    arm: jnp.ndarray         # (D,) int32 last pulled arm (bandit rules)
    arms_sum: jnp.ndarray    # (D, K) per-arm reward sum (UCB) / EXP3 gains
    arms_cnt: jnp.ndarray    # (D, K) per-arm pull counts
    es_sum: jnp.ndarray      # (D,) observed ES-correct count
    es_cnt: jnp.ndarray      # (D,) observed offload count
    cum_regret: jnp.ndarray  # (D,) cumulative pseudo-regret vs theta*

    @classmethod
    def init(cls, n_devices: int, n_arms: int,
             theta0=0.5) -> "HILearnerState":
        D, K = n_devices, n_arms
        th = np.broadcast_to(np.asarray(theta0, np.float64), (D,)).copy()
        return cls(theta=th, arm=np.zeros(D, np.int32),
                   arms_sum=np.zeros((D, K)), arms_cnt=np.zeros((D, K)),
                   es_sum=np.zeros(D), es_cnt=np.zeros(D),
                   cum_regret=np.zeros(D))


_HI_STATE_FIELDS = tuple(f.name for f in dataclasses.fields(HILearnerState))


def _hi_state_unflatten(aux, children):
    obj = object.__new__(HILearnerState)
    for f, v in zip(_HI_STATE_FIELDS, children):
        object.__setattr__(obj, f, v)
    return obj


jax.tree_util.register_pytree_node(
    HILearnerState,
    lambda s: (tuple(getattr(s, f) for f in _HI_STATE_FIELDS), None),
    _hi_state_unflatten)


def arm_grid(n_arms: int) -> jnp.ndarray:
    """The bandit rules' discretized thresholds: K evenly spaced interior
    points of [0, 1] (K=9 gives 0.1 .. 0.9)."""
    return jnp.linspace(1.0 / (n_arms + 1), n_arms / (n_arms + 1.0),
                        n_arms, dtype=jnp.float64)


def _draw_uniforms(key, n_devices: int, n_jobs: int,
                   axis_name: Optional[str] = None,
                   gid_offset: Optional[int] = None) -> jnp.ndarray:
    """(D, n, 3) uniforms from per-device GLOBAL-id folds (the
    `sample_realization` idiom): channel 0 shapes the confidence,
    channel 1 the local Bernoulli outcome, channel 2 the ES outcome.
    ``gid_offset`` overrides the axis-derived offset for unit tests of
    the shard fold itself."""
    if gid_offset is None:
        offset = (jax.lax.axis_index(axis_name) * n_devices
                  if axis_name else jnp.int32(0))
    else:
        offset = jnp.int32(gid_offset)
    gid = offset + jnp.arange(n_devices, dtype=jnp.int32)
    kd = jax.vmap(lambda g: jax.random.fold_in(key, g))(gid)
    return jax.vmap(lambda k: jax.random.uniform(
        k, (n_jobs, 3), dtype=jnp.float64))(kd)


def sample_confidence(key, hm: HIModel, acc_local, acc_es, ci, *,
                      uniforms=None, axis_name: Optional[str] = None,
                      gid_offset: Optional[int] = None):
    """One period of the calibrated confidence stream.

    ``acc_local`` (D,) is the designated local model's table accuracy,
    ``acc_es`` (D,) the ES accuracy, ``ci`` (D, n) per-sample class
    indices.  ``uniforms`` replays a presampled (D, n, 3) slice instead
    of drawing from ``key`` (`HIModel.conf_trace` / `presample_stream`).
    Returns ``(conf, correct_local, correct_es)``, each (D, n): the
    confidence is exactly mean-``acc_local`` (see module docstring) and
    ``P(correct_local | conf) == conf`` — perfect calibration."""
    D, n = ci.shape
    u = _draw_uniforms(key, D, n, axis_name, gid_offset) \
        if uniforms is None else uniforms
    mu = jnp.clip(jnp.asarray(acc_local, jnp.float64), 1e-6, 1.0 - 1e-6)
    p_raw = u[..., 0] ** ((1.0 - mu) / mu)[:, None]
    sp = jnp.asarray(hm.spread, jnp.float64)
    spread_j = sp[ci] if sp.shape[0] > 1 else sp[0]
    conf = jnp.clip(mu[:, None] + spread_j * (p_raw - mu[:, None]),
                    0.0, 1.0)
    correct_local = u[..., 1] < conf
    correct_es = u[..., 2] < jnp.asarray(acc_es, jnp.float64)[:, None]
    return conf, correct_local, correct_es


def presample_stream(seed: int, n_devices: int, n_jobs: int,
                     periods: int) -> np.ndarray:
    """A replayed confidence trace ``(periods, D, n, 3)`` that reproduces
    the fold-keyed stream BIT FOR BIT: period ``t`` holds exactly the
    uniforms an armed engine with ``hi_seed=seed`` draws at period ``t``
    (fold the seed by period, split off the confidence key, fold global
    device ids).  Feeding it back via ``HIModel(conf_trace=...)`` +
    ``stream="replay"`` therefore pins replay == fold."""
    out = np.zeros((periods, n_devices, n_jobs, 3))
    with x64_scope():
        base = jax.random.PRNGKey(seed)
        for t in range(periods):
            kc, _ka = jax.random.split(jax.random.fold_in(base, t))
            out[t] = np.asarray(_draw_uniforms(kc, n_devices, n_jobs))
    return out


def hi_period(rule: str, hm: HIModel, hst: HILearnerState, conf,
              correct_local, correct_es, mask, acc_es, t, key,
              n_arms: int, axis_name: Optional[str] = None):
    """One traced HI period: pick this period's threshold, decide per
    sample, feed the observations back into the learner, and account the
    pseudo-regret.

    ``conf``/``correct_local``/``correct_es`` come from
    `sample_confidence`, ``mask`` (D, n) marks real samples, ``acc_es``
    (D,) is the TRUE ES accuracy (used only for the regret metric — the
    learners never read it), ``t`` the period index (step-size decay and
    the UCB bonus), ``key`` the period's arm-draw key (EXP3 only).

    Returns ``(offload (D, n) bool — the INTENDED decisions, theta_t
    (D,), new_state, regret_inc (D,))``.  The regret increment is the
    expected pseudo-regret of the intended decisions against the
    clairvoyant threshold ``theta* = acc_es - beta`` given the realized
    confidences: per sample ``max(conf, acc_es - beta)`` minus the
    chosen side's expected reward — nonnegative, exactly zero for the
    clairvoyant, and deterministic given the stream."""
    if rule not in HI_RULES:
        raise ValueError(f"unknown HI rule {rule!r}; expected one of "
                         f"{HI_RULES}")
    D, _n = conf.shape
    beta = hm.offload_cost
    njobs = mask.sum(axis=1).astype(jnp.float64)
    has = njobs > 0
    tf = jnp.asarray(t, jnp.float64)
    probs = None

    # ---- this period's threshold per device -----------------------------
    if rule == "ucb":
        grid = arm_grid(n_arms)
        cnt = hst.arms_cnt
        mean = hst.arms_sum / jnp.maximum(cnt, 1.0)
        # untried arms get an infinite bonus: argmax sweeps the grid in
        # index order before any exploitation starts
        bonus = jnp.where(cnt > 0.0,
                          hm.explore * jnp.sqrt(jnp.log(tf + 2.0)
                                                / jnp.maximum(cnt, 1.0)),
                          jnp.inf)
        arm = jnp.argmax(mean + bonus, axis=1).astype(jnp.int32)
        theta_t = grid[arm]
    elif rule == "exp3":
        grid = arm_grid(n_arms)
        g = hm.explore * hst.arms_sum
        g = g - jnp.max(g, axis=1, keepdims=True)
        w = jnp.exp(g)
        probs = ((1.0 - EXP3_GAMMA) * w / w.sum(axis=1, keepdims=True)
                 + EXP3_GAMMA / n_arms)
        offset = (jax.lax.axis_index(axis_name) * D if axis_name
                  else jnp.int32(0))
        gid = offset + jnp.arange(D, dtype=jnp.int32)
        kd = jax.vmap(lambda gg: jax.random.fold_in(key, gg))(gid)
        u = jax.vmap(lambda k: jax.random.uniform(
            k, dtype=jnp.float64))(kd)
        cdf = jnp.cumsum(probs, axis=1)
        arm = jnp.minimum((u[:, None] >= cdf).sum(axis=1),
                          n_arms - 1).astype(jnp.int32)
        theta_t = grid[arm]
    else:                                       # "fixed" / "threshold"
        theta_t = hst.theta
        arm = hst.arm

    offload = mask & (conf < theta_t[:, None])

    # ---- learner updates from the period's observations -----------------
    # running ES-accuracy estimate with an optimistic Beta(1,1)-style
    # prior at 1.0: an untried ES looks perfect, so early thresholds
    # drift up and the learner explores offloading
    a_hat = (hst.es_sum + 1.0) / (hst.es_cnt + 1.0)
    new_es_sum = hst.es_sum + (offload & correct_es).sum(
        axis=1).astype(jnp.float64)
    new_es_cnt = hst.es_cnt + offload.sum(axis=1).astype(jnp.float64)

    if rule == "threshold":
        # sigmoid-kernel surrogate gradient of the per-sample threshold
        # loss: d/dtheta [sigma((theta-p)/tau) * cost_gap] — the kernel
        # concentrates at p == theta, so the stationary point is
        # theta = a_hat - beta (the clairvoyant threshold once a_hat
        # converges); E[correct_local | conf] == conf keeps the realized
        # outcome an unbiased plug-in for the local side's value
        z = (theta_t[:, None] - conf) / hm.tau
        sig = jax.nn.sigmoid(z)
        ker = sig * (1.0 - sig) / hm.tau
        gsamp = ker * (beta - a_hat[:, None]
                       + correct_local.astype(jnp.float64))
        gmean = jnp.where(mask, gsamp, 0.0).sum(axis=1) \
            / jnp.maximum(njobs, 1.0)
        step = hm.lr / jnp.sqrt(tf + 1.0)
        new_theta = jnp.where(
            has, jnp.clip(theta_t - step * gmean, 0.0, 1.0), theta_t)
    else:
        new_theta = theta_t

    if rule in ("ucb", "exp3"):
        # realized (observable) per-sample reward: the ES answer minus
        # the offload cost when consulted, else the local outcome
        r = jnp.where(offload, correct_es.astype(jnp.float64) - beta,
                      correct_local.astype(jnp.float64))
        r_mean = jnp.where(mask, r, 0.0).sum(axis=1) \
            / jnp.maximum(njobs, 1.0)
        onehot = (jnp.arange(n_arms, dtype=jnp.int32)[None, :]
                  == arm[:, None])
        upd = has[:, None] & onehot
        if rule == "ucb":
            new_sum = hst.arms_sum + jnp.where(upd, r_mean[:, None], 0.0)
        else:
            r01 = (r_mean + beta) / (1.0 + beta)    # EXP3 wants [0, 1]
            p_arm = jnp.take_along_axis(probs, arm[:, None],
                                        axis=1)[:, 0]
            ghat = r01 / jnp.maximum(p_arm, 1e-9)   # importance weight
            new_sum = hst.arms_sum + jnp.where(upd, ghat[:, None], 0.0)
        new_cnt = hst.arms_cnt + upd.astype(jnp.float64)
    else:
        new_sum, new_cnt = hst.arms_sum, hst.arms_cnt

    # ---- pseudo-regret vs the clairvoyant theta* = acc_es - beta --------
    r_es = jnp.asarray(acc_es, jnp.float64)[:, None] - beta
    chosen = jnp.where(offload, r_es, conf)
    regret_inc = jnp.where(mask, jnp.maximum(conf, r_es) - chosen,
                           0.0).sum(axis=1)

    new_hst = HILearnerState(
        theta=new_theta, arm=arm, arms_sum=new_sum, arms_cnt=new_cnt,
        es_sum=new_es_sum, es_cnt=new_es_cnt,
        cum_regret=hst.cum_regret + regret_inc)
    return offload, theta_t, new_hst, regret_inc


def validate_hi(hm: HIModel, *, n_devices: int, n_classes: int,
                n_models: int, rule: str, stream: str, n_arms: int,
                local_model: int, batch_max: Optional[int] = None) -> None:
    """Host-side arming validation (the `validate_mobility` twin): shape
    and range checks that a traced step could only fail on silently."""
    if rule not in HI_RULES:
        raise ValueError(f"unknown HI rule {rule!r}; expected one of "
                         f"{HI_RULES} (or disarm with with_hi(None))")
    if stream not in HI_STREAMS:
        raise ValueError(f"unknown HI stream {stream!r}; expected one of "
                         f"{HI_STREAMS}")
    sp = np.asarray(hm.spread)
    if sp.shape not in ((1,), (n_classes,)):
        raise ValueError(
            f"HIModel.spread has shape {sp.shape}; expected (1,) or one "
            f"entry per queue class ({n_classes},)")
    th = np.asarray(hm.theta0)
    if th.ndim not in (0, 1) or (th.ndim == 1
                                 and th.shape != (n_devices,)):
        raise ValueError(
            f"HIModel.theta0 has shape {th.shape}; expected a scalar or "
            f"one entry per device ({n_devices},)")
    if rule in ("ucb", "exp3") and n_arms < 2:
        raise ValueError(f"bandit rules need n_arms >= 2; got {n_arms}")
    if not 0 <= local_model < n_models:
        raise ValueError(
            f"hi_local={local_model} is not a local model index; the "
            f"fleet has {n_models} local models (0 .. {n_models - 1})")
    if stream == "replay":
        tr = np.asarray(hm.conf_trace)
        if tr.ndim != 4 or tr.shape[1] != n_devices or tr.shape[3] != 3:
            raise ValueError(
                f"stream='replay' needs conf_trace shaped (periods, "
                f"{n_devices}, batch_max, 3); got {tr.shape} "
                f"(presample_stream builds one)")
        if batch_max is not None and tr.shape[2] != batch_max:
            raise ValueError(
                f"conf_trace replays {tr.shape[2]} job slots per device "
                f"but the queue's batch_max is {batch_max}")
