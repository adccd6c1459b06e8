"""Differentiable serving stack: implicit-gradient simplex, smoothed
rounding/admission twins, the S=1 pool-admission bitwise pin, the pytree
partition helper, and finite-difference gates on jax.grad-able rollouts.

FD gates probe at JITTERED base points: the ladder generator's p_es
values land exactly on LP vertex boundaries where the optimum has only
one-sided derivatives (the implicit VJP returns the subgradient of the
converged basis; central FD averages the two sides).  A ~1e-3 nudge
moves the base into a linearity region where both must agree to 1e-4.
"""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.api import engine as E
from repro.core.mobility import admit_mask_pool
from repro.serving import FleetConfig

RTOL = 1e-4
ATOL = 1e-6            # absolute floor for ~zero gradients


def _config(n_devices=8, *, seed=0, horizon=6, n_servers=2, rate=9.0):
    return FleetConfig(n_devices=n_devices, T=1.2, n_servers=n_servers,
                       policy="amr2", backend="jax", rate=rate,
                       batch_max=8, horizon=horizon, seed=seed,
                       straggler_frac=0.25, outage_frac=0.1)


def _diff_params(seed, *, smooth_mode="soft", jitter=True):
    params = E.EngineParams.from_config(
        _config(seed=seed), horizon=6).with_differentiable(
            smooth_mode=smooth_mode)
    if jitter:
        rng = np.random.default_rng(1000 + seed)
        arr = np.asarray(params.p_es, np.float64)
        nudge = (rng.uniform(1e-3, 3e-3, size=arr.shape)
                 * rng.choice([-1.0, 1.0], size=arr.shape))
        params = dataclasses.replace(params, p_es=arr + nudge)
    return params


def _value(params, periods=4):
    _, m = E.rollout(E.init_state(params), params, periods)
    return float(np.sum(np.asarray(m.total_accuracy)))


def _fd_leaf(params, leaf, idx, eps=1e-5, periods=4):
    base = np.asarray(getattr(params, leaf), np.float64)
    flat = np.atleast_1d(base).ravel()
    up, dn = flat.copy(), flat.copy()
    up[idx] += eps
    dn[idx] -= eps
    shape = np.shape(base)
    mk = lambda f: dataclasses.replace(
        params, **{leaf: f.reshape(shape) if shape else float(f[0])})
    return (_value(mk(up), periods) - _value(mk(dn), periods)) / (2 * eps)


def _assert_close(fd, an, label):
    if abs(fd - an) < ATOL:
        return
    rel = abs(fd - an) / max(abs(fd), abs(an))
    assert rel < RTOL, f"{label}: fd={fd!r} analytic={an!r} rel={rel:.3e}"


# ---------------------------------------------------------------------------
# LP layer: the implicit-function VJP of the converged simplex optimum
# ---------------------------------------------------------------------------
def _lp_batch(seed, nb=4, n=6, mc=3):
    rng = np.random.default_rng(seed)
    c = rng.normal(size=(nb, n))
    A_ub = rng.uniform(0, 1, size=(nb, mc, n))
    b_ub = rng.uniform(1, 3, size=(nb, mc))
    A_eq = np.ones((nb, 1, n))
    b_eq = np.ones((nb, 1))
    return c, A_ub, b_ub, A_eq, b_eq


def _canon(seed, **kw):
    from repro.core.lp import _canonicalize_batch
    A, b, cf, nv, _ = _canonicalize_batch(*_lp_batch(seed, **kw))
    return np.asarray(A), np.asarray(b), np.asarray(cf), nv


@pytest.mark.parametrize("method", ["tableau", "revised"])
def test_lp_grad_forward_bitwise_matches_core(method):
    """simplex_batch_grad's forward pass IS simplex_batch_core — same
    pivots, same outputs, bit for bit (the VJP only attaches a backward
    rule)."""
    from repro.core.types import x64_scope

    from repro.core.lp import simplex_batch_core, simplex_batch_grad
    A, b, cf, nv = _canon(0)
    with x64_scope():
        args = (jnp.asarray(A), jnp.asarray(b), jnp.asarray(cf), None)
        kw = dict(nv=nv, maxiter=200, method=method)
        ref = simplex_batch_core(*args, **kw)
        out = simplex_batch_grad(*args, **kw)
    for r, o in zip(ref, out):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))


def _lp_fd_probe(seed, n_probes=3, eps=1e-6):
    """FD-check d/d(b, c) of a random linear functional of (x, fun)."""
    from repro.core.types import x64_scope

    from repro.core.lp import OPTIMAL, simplex_batch_grad
    A, b, cf, nv = _canon(seed)
    rng = np.random.default_rng(seed + 77)
    wx = rng.normal(size=(A.shape[0], nv))
    wf = rng.normal(size=A.shape[0])

    with x64_scope():
        def loss(b_, c_):
            x, fun, status, *_ = simplex_batch_grad(
                jnp.asarray(A), b_, c_, None, nv=nv, maxiter=200)
            ok = (status == OPTIMAL)[:, None]
            return (jnp.sum(jnp.where(ok, wx * x[:, :nv], 0.0))
                    + jnp.sum(jnp.where(ok[:, 0], wf * fun, 0.0)))

        lval = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
        val, (gb, gc) = lval(jnp.asarray(b), jnp.asarray(cf))
        val, gb, gc = float(val), np.asarray(gb), np.asarray(gc)

        fl = jax.jit(loss)
        for arr, g, name in ((b, gb, "b"), (cf, gc, "c")):
            flat = arr.ravel()
            for idx in rng.choice(flat.size, size=n_probes, replace=False):
                up, dn = flat.copy(), flat.copy()
                up[idx] += eps
                dn[idx] -= eps
                pert = lambda f: (jnp.asarray(f.reshape(arr.shape)
                                              if name == "b" else b),
                                  jnp.asarray(f.reshape(arr.shape)
                                              if name == "c" else cf))
                fd = (float(fl(*pert(up))) - float(fl(*pert(dn)))) \
                    / (2 * eps)
                _assert_close(fd, g.ravel()[idx],
                              f"seed={seed} {name}[{idx}]")


@pytest.mark.parametrize("seed", [0, 1])
def test_lp_implicit_vjp_matches_fd(seed):
    _lp_fd_probe(seed)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(min_value=10, max_value=2000))
def test_lp_implicit_vjp_matches_fd_hypothesis(seed):
    _lp_fd_probe(seed, n_probes=1)


def test_lp_masked_lane_cotangents_zero():
    """Masked lanes carry garbage tableaus — their input cotangents must
    be EXACTLY zero, not NaN-contaminated."""
    from repro.core.types import x64_scope

    from repro.core.lp import simplex_batch_grad
    A, b, cf, nv = _canon(3)
    mask = np.array([True, False, True, False])
    with x64_scope():
        def loss(b_):
            x, fun, *_ = simplex_batch_grad(
                jnp.asarray(A), b_, jnp.asarray(cf), None, nv=nv,
                maxiter=200, lane_mask=jnp.asarray(mask))
            return jnp.sum(jnp.where(jnp.asarray(mask), fun, 0.0))

        gb = np.asarray(jax.jit(jax.grad(loss))(jnp.asarray(b)))
    np.testing.assert_array_equal(gb[~mask], 0.0)
    assert np.all(np.isfinite(gb))


def test_lp_grad_int_outputs_are_fences():
    """status/niter/basis outputs must yield float0/zero cotangents, and
    differentiating THROUGH them must not be attempted by jax (they are
    integer outputs — grad of the float outputs alone must trace)."""
    from repro.core.types import x64_scope

    from repro.core.lp import simplex_batch_grad
    A, b, cf, nv = _canon(5)
    with x64_scope():
        # warm restart from the converged basis, THEN differentiate: the
        # basis0 int input gets a symbolic-zero cotangent internally.
        _, _, _, _, bases, _ = simplex_batch_grad(
            jnp.asarray(A), jnp.asarray(b), jnp.asarray(cf), None,
            nv=nv, maxiter=200)

        def loss(b_):
            _, fun, *_ = simplex_batch_grad(
                jnp.asarray(A), b_, jnp.asarray(cf), bases, nv=nv,
                maxiter=200)
            return jnp.sum(fun)

        gb = np.asarray(jax.jit(jax.grad(loss))(jnp.asarray(b)))
    assert np.all(np.isfinite(gb)) and np.any(gb != 0.0)


# ---------------------------------------------------------------------------
# S=1 admission: round-robin pool scan == sequential first-fit, bitwise
# ---------------------------------------------------------------------------
def _pool_case(rng, D, k):
    kind = rng.integers(0, 4)
    if kind == 0:        # heavy ties
        d = rng.choice([0.3, 0.6, 0.6, 1.2], size=D)
    elif kind == 1:      # near-capacity chains
        d = rng.uniform(0.35, 0.65, size=D)
    elif kind == 2:      # tiny demands, deep chains
        d = rng.uniform(1e-3, 0.05, size=D)
    else:                # mixed with non-offloaders
        d = rng.uniform(-0.2, 0.9, size=D)
    d[rng.random(D) < 0.2] = 0.0
    return d


@pytest.mark.parametrize("D,k", [(8, 2), (7, 3), (16, 1), (3, 5), (24, 4)])
def test_admit_pool_bitwise_matches_sequential(D, k):
    T = 1.2
    for rep in range(4):
        rng = np.random.default_rng(100 * D + 10 * k + rep)
        d = jnp.asarray(_pool_case(rng, D, k), jnp.float64)
        m_ref, l_ref = E.admit_mask_jnp(d, T, k)
        m_new, l_new, inc = admit_mask_pool(d, T, k)
        np.testing.assert_array_equal(np.asarray(m_ref),
                                      np.asarray(m_new))
        np.testing.assert_array_equal(np.asarray(l_ref),
                                      np.asarray(l_new))
        # inc is the inclusive chain load the first-fit compares vs T:
        # admitted devices must satisfy it, by the same <= as the scan.
        inc = np.asarray(inc)
        assert np.all(inc[np.asarray(m_new)] <= T + 1e-12)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       D=st.integers(min_value=1, max_value=24),
       k=st.integers(min_value=1, max_value=6))
def test_admit_pool_bitwise_hypothesis(seed, D, k):
    rng = np.random.default_rng(seed)
    d = jnp.asarray(_pool_case(rng, D, k), jnp.float64)
    m_ref, l_ref = E.admit_mask_jnp(d, 1.2, k)
    m_new, l_new, _ = admit_mask_pool(d, 1.2, k)
    np.testing.assert_array_equal(np.asarray(m_ref), np.asarray(m_new))
    np.testing.assert_array_equal(np.asarray(l_ref), np.asarray(l_new))


# ---------------------------------------------------------------------------
# engine: smoothed twins, FD gates, forward pins
# ---------------------------------------------------------------------------
def test_st_forward_matches_hard_rollout():
    """smooth_mode='st' is a straight-through twin: the FORWARD value is
    the hard rollout's served accuracy (backward is softened).  Only the
    contraction order differs (one-hot einsum vs where-select), so allow
    roundoff but nothing more."""
    params = _diff_params(0, smooth_mode="st", jitter=False)
    hard = dataclasses.replace(params, differentiable=False)
    val, grads = E.rollout_value_and_grad(
        E.init_state(params), params, 4)
    np.testing.assert_allclose(float(val), _value(hard, 4),
                               rtol=0, atol=1e-9)
    assert set(grads) == set(params.grad_leaves)
    for f, g in grads.items():
        assert np.shape(np.asarray(g)) == np.shape(
            np.asarray(getattr(params, f))), f


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_rollout_grad_matches_fd(seed):
    """The acceptance gate: jax.grad of rolled-out total accuracy w.r.t.
    ES capacity (p_es), deadline (T), and ladder mix (acc) matches
    central finite differences to rtol 1e-4 (soft mode, jittered base —
    see module docstring)."""
    params = _diff_params(seed, smooth_mode="soft")
    val, grads = E.rollout_value_and_grad(
        E.init_state(params), params, 4, wrt=("p_es", "T", "acc"))
    assert np.isfinite(float(val))
    rng = np.random.default_rng(seed + 55)

    g_es = np.asarray(grads["p_es"], np.float64).ravel()
    for idx in rng.choice(g_es.size, size=2, replace=False):
        _assert_close(_fd_leaf(params, "p_es", idx), g_es[idx],
                      f"seed={seed} p_es[{idx}]")

    _assert_close(_fd_leaf(params, "T", 0),
                  float(np.asarray(grads["T"])), f"seed={seed} T")

    g_acc = np.asarray(grads["acc"], np.float64).ravel()
    idx = int(rng.integers(g_acc.size))
    _assert_close(_fd_leaf(params, "acc", idx), g_acc[idx],
                  f"seed={seed} acc[{idx}]")


def test_rollout_grad_default_wrt_and_nonzero():
    params = _diff_params(0, smooth_mode="soft")
    grads = E.rollout_grad(E.init_state(params), params, 4)
    assert set(grads) == set(params.grad_leaves)
    norms = {f: float(jnp.linalg.norm(jnp.asarray(g, jnp.float64)))
             for f, g in grads.items()}
    assert all(np.isfinite(v) for v in norms.values())
    assert norms["p_es"] > 0 and norms["acc"] > 0


# ---------------------------------------------------------------------------
# partition helper: grad over the float half of a mixed pytree
# ---------------------------------------------------------------------------
def test_partition_diff_regression():
    """The bug this helper fixes: jax.grad over a full EngineState dies
    on the int32/uint32 bookkeeping leaves.  Partitioned, the same
    objective differentiates, and combine_diff round-trips bitwise."""
    params = E.EngineParams.from_config(_config(), horizon=6)
    state = E.init_state(params)

    with pytest.raises(TypeError):
        jax.grad(lambda s: jnp.sum(s.p_ed))(state)

    diff, nondiff = E.partition_diff(state)
    back = E.combine_diff(diff, nondiff)
    for f in E._STATE_FIELDS:
        for a, b in zip(jax.tree.leaves(getattr(back, f)),
                        jax.tree.leaves(getattr(state, f))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), f)

    g = jax.grad(
        lambda d: jnp.sum(E.combine_diff(d, nondiff).p_ed))(diff)
    np.testing.assert_array_equal(np.asarray(g.p_ed),
                                  np.ones_like(np.asarray(state.p_ed)))
    # int leaves stayed in the nondiff half: sentinel in the diff tree
    assert diff.pending is E._NONDIFF and diff.key is E._NONDIFF


def test_partition_diff_keeps_f64():
    """partition_diff must not silently downcast f64 leaves (jnp.asarray
    outside an enable_x64 scope would)."""
    params = E.EngineParams.from_config(_config(), horizon=6)
    diff, _ = E.partition_diff(E.init_state(params))
    assert diff.p_ed.dtype == jnp.float64


# ---------------------------------------------------------------------------
# validators
# ---------------------------------------------------------------------------
def test_with_differentiable_validators():
    params = E.EngineParams.from_config(_config(), horizon=6)
    with pytest.raises(ValueError, match="smooth_mode"):
        params.with_differentiable(smooth_mode="gumbel")
    with pytest.raises(ValueError, match="must be > 0"):
        params.with_differentiable(smooth_tau=0.0)
    with pytest.raises(ValueError, match="not differentiable"):
        params.with_differentiable(grad_leaves=("warm_basis",))
    with pytest.raises(ValueError, match="chaos"):
        from repro.core.faults import FaultModel
        params.with_faults(FaultModel.make(es_crash_prob=0.1),
                           fault_seed=1).with_differentiable()
    # armed HI is discrete per-sample gating: the relaxation must refuse
    with pytest.raises(ValueError, match="HI disarmed"):
        from repro.core.hi import HIModel
        params.with_hi(HIModel.make(),
                       rule="threshold").with_differentiable()

    # disarm round-trips to a hard-path params value
    off = params.with_differentiable().with_differentiable(False)
    assert not off.differentiable


def test_grad_entry_requires_flag():
    params = E.EngineParams.from_config(_config(), horizon=6)
    with pytest.raises(ValueError, match="with_differentiable"):
        E.rollout_grad(E.init_state(params), params, 2)
    armed = params.with_differentiable()
    with pytest.raises(ValueError, match="not differentiable"):
        E.rollout_grad(E.init_state(armed), armed, 2, wrt=("stream",))
