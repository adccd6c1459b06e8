"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU) — shape/dtype
sweeps per kernel, plus hypothesis property tests for the DP kernel."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.types import x64_scope
from repro.kernels.cckp_dp.cckp_dp import cckp_model_dp
from repro.kernels.cckp_dp.ref import cckp_model_dp_ref
from repro.kernels.decode_attention.decode_attention import \
    decode_attention_fwd
from repro.kernels.decode_attention.ops import decode_attention, \
    ring_validity
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rglru_scan.rglru_scan import rglru_scan_fwd
from repro.kernels.rglru_scan.ref import rglru_scan_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_sequential_ref

NEG = -1e30


# ------------------------------------------------------------ flash attn --
@pytest.mark.parametrize("mask_kind,window", [("causal", 0), ("none", 0),
                                              ("window", 24)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sq,sk,d,bq,bk", [
    (64, 64, 32, 16, 16),
    (48, 48, 16, 16, 16),      # non-multiple seq (padding path)
    (32, 96, 64, 32, 32),      # cross-ish Sk > Sq
])
def test_flash_attention_sweep(mask_kind, window, dtype, sq, sk, d, bq, bk):
    if mask_kind in ("causal", "window") and sq != sk:
        pytest.skip("self-attention masks assume square positions")
    key = jax.random.key(0)
    BH = 4
    q = jax.random.normal(jax.random.fold_in(key, 0), (BH, sq, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (BH, sk, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (BH, sk, d), dtype)
    out = flash_attention_fwd(q, k, v, mask_kind=mask_kind, window=window,
                              bq=bq, bk=bk, interpret=True)
    ref = attention_ref(q, k, v, mask_kind=mask_kind, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_flash_attention_gqa_group_mapping():
    key = jax.random.key(1)
    B, KH, G, S, D = 2, 2, 3, 32, 16
    q = jax.random.normal(jax.random.fold_in(key, 0), (B * KH * G, S, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B * KH, S, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B * KH, S, D))
    out = flash_attention_fwd(q, k, v, mask_kind="causal", group=G,
                              bq=16, bk=16, interpret=True)
    kr = jnp.repeat(k, G, axis=0)
    vr = jnp.repeat(v, G, axis=0)
    ref = attention_ref(q, kr, vr, mask_kind="causal")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ----------------------------------------------------------- decode attn --
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sk,g,d,bk", [(128, 4, 32, 32), (100, 6, 16, 32),
                                       (64, 1, 64, 16)])
def test_decode_attention_sweep(dtype, sk, g, d, bk):
    key = jax.random.key(2)
    BKH = 3
    q = jax.random.normal(jax.random.fold_in(key, 0), (BKH, g, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (BKH, sk, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (BKH, sk, d), dtype)
    valid = (jax.random.uniform(jax.random.fold_in(key, 3), (BKH, sk))
             > 0.3).astype(jnp.int32)
    valid = valid.at[:, 0].set(1)      # at least one valid slot
    out = decode_attention_fwd(q, k, v, valid, bk=bk, interpret=True)
    ref = decode_attention_ref(q, k, v, valid)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_decode_attention_matches_model_decode_path():
    """Kernel ring-buffer semantics vs layers.attn_decode math."""
    B, W, KH, G, D = 2, 16, 2, 2, 8
    H = KH * G
    key = jax.random.key(3)
    q = jax.random.normal(jax.random.fold_in(key, 0), (B, 1, H, D))
    ck = jax.random.normal(jax.random.fold_in(key, 1), (B, W, KH, D))
    cv = jax.random.normal(jax.random.fold_in(key, 2), (B, W, KH, D))
    for index, window in [(5, 0), (20, 0), (20, 7)]:
        out = decode_attention(q, ck, cv, jnp.asarray(index), window=window)
        # reference: mask from ring validity + grouped dense attention
        ok = ring_validity(W, jnp.asarray(index), window)
        kr = jnp.repeat(ck, G, axis=2).transpose(0, 2, 1, 3).reshape(
            B * H, W, D)
        vr = jnp.repeat(cv, G, axis=2).transpose(0, 2, 1, 3).reshape(
            B * H, W, D)
        qf = q[:, 0].transpose(0, 1, 2).reshape(B * H, 1, D)
        s = jnp.einsum("bqd,bkd->bqk", qf, kr) * D ** -0.5
        s = jnp.where(ok[None, None, :] != 0, s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        ref = jnp.einsum("bqk,bkd->bqd", p, vr).reshape(B, H, 1, D
                                                        ).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


# ------------------------------------------------------------------- ssd --
@pytest.mark.parametrize("s,h,p,n,chunk", [(32, 2, 8, 4, 8), (40, 3, 4, 8, 16),
                                           (16, 1, 16, 16, 16)])
def test_ssd_kernel_vs_sequential(s, h, p, n, chunk):
    key = jax.random.key(4)
    B = 2
    x = jax.random.normal(jax.random.fold_in(key, 0), (B, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (B, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (h,)))
    B_ = jax.random.normal(jax.random.fold_in(key, 3), (B, s, n))
    C_ = jax.random.normal(jax.random.fold_in(key, 4), (B, s, n))
    y, state = ssd_scan(x, dt, A, B_, C_, chunk)
    yr, stater = ssd_sequential_ref(x, dt, A, B_, C_)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(state),
                               np.asarray(stater.transpose(0, 1, 2, 3)),
                               atol=1e-3, rtol=1e-3)


def test_ssd_jnp_chunked_matches_sequential():
    from repro.models.layers import ssd_scan_chunked
    key = jax.random.key(5)
    B, s, h, p, n = 2, 24, 2, 4, 8
    x = jax.random.normal(jax.random.fold_in(key, 0), (B, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1),
                                           (B, s, h)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (h,)))
    B_ = jax.random.normal(jax.random.fold_in(key, 3), (B, s, n))
    C_ = jax.random.normal(jax.random.fold_in(key, 4), (B, s, n))
    y, state = ssd_scan_chunked(x, dt, A, B_, C_, 8)
    yr, stater = ssd_sequential_ref(x, dt, A, B_, C_)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(state), np.asarray(stater),
                               atol=1e-3, rtol=1e-3)


# ----------------------------------------------------------------- rglru --
@pytest.mark.parametrize("s,w,bs,bw", [(32, 16, 8, 8), (50, 24, 16, 16),
                                       (16, 8, 16, 8)])
def test_rglru_kernel_vs_ref(s, w, bs, bw):
    key = jax.random.key(6)
    B = 2
    a = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 0),
                                         (B, s, w)))
    b = jax.random.normal(jax.random.fold_in(key, 1), (B, s, w))
    y = rglru_scan_fwd(a, b, bs=bs, bw=bw, interpret=True)
    yr = rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4,
                               rtol=1e-4)


# --------------------------------------------------------------- cckp dp --
@pytest.mark.parametrize("T,K,p,n_steps", [(20, 6, 3, 6), (50, 10, 7, 11),
                                           (10, 4, 0, 4)])
def test_cckp_kernel_vs_ref(T, K, p, n_steps):
    rng = np.random.default_rng(0)
    y0 = np.full((T + 1, K + 1), NEG, np.float32)
    y0[:, 0] = 0.0
    y0[5:, 1] = rng.uniform(0, 1)      # some pre-existing partial solutions
    y = jnp.asarray(y0)
    a = jnp.asarray(0.37, jnp.float32)
    out, bq = cckp_model_dp(y, a, p=p, n_steps=n_steps, interpret=True)
    outr, bqr = cckp_model_dp_ref(y, 0.37, p=p, n_steps=n_steps)
    np.testing.assert_allclose(np.asarray(out), np.asarray(outr), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(bq), np.asarray(bqr))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), m=st.integers(1, 3),
       n_l=st.integers(1, 5), T_int=st.integers(1, 25))
def test_cckp_pallas_impl_end_to_end(seed, m, n_l, T_int):
    """solve_cckp(impl='pallas') is bit-identical to the jnp DP."""
    from repro.core.amdp import solve_cckp
    rng = np.random.default_rng(seed)
    p = rng.integers(1, 8, size=m).astype(np.int64)
    a = rng.uniform(0.1, 1.0, size=m)
    c1, v1 = solve_cckp(p, a, T_int, n_l, impl="jnp")
    c2, v2 = solve_cckp(p, a, T_int, n_l, impl="pallas")
    if c1 is None:
        assert c2 is None
    else:
        assert v1 == pytest.approx(v2, abs=1e-5)
        np.testing.assert_array_equal(c1, c2)


# ---------------------------------------------- model-level pallas path --
def test_model_attention_pallas_path_matches_dense():
    """cfg.attn_impl='pallas' routes layers.attention through the kernel
    (interpret mode on CPU) and must match the dense path."""
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.models import forward, init_params

    cfg_d = dataclasses.replace(get_smoke_config("internlm2_20b"),
                                attn_impl="dense")
    cfg_p = dataclasses.replace(cfg_d, attn_impl="pallas")
    key = jax.random.key(7)
    params = init_params(cfg_d, key)
    batch = {"tokens": jax.random.randint(jax.random.fold_in(key, 1),
                                          (2, 16), 0, cfg_d.vocab_size)}
    h_d = forward(params, batch, cfg_d)
    h_p = forward(params, batch, cfg_p)
    np.testing.assert_allclose(np.asarray(h_d, np.float32),
                               np.asarray(h_p, np.float32), atol=6e-2)


# ---------------------------------------------------------- simplex pivot --
@pytest.mark.parametrize("B,R1,C1", [(4, 5, 9), (8, 15, 41), (1, 3, 4)])
def test_simplex_pivot_kernel_vs_ref(B, R1, C1):
    from repro.kernels.simplex_pivot.ref import pivot_update_ref
    from repro.kernels.simplex_pivot.simplex_pivot import simplex_pivot
    rng = np.random.default_rng(B * 100 + C1)
    tabs = rng.normal(size=(B, R1, C1))
    # keep pivots well away from zero so ref/kernel divide identically
    r = rng.integers(0, R1 - 1, size=B)
    j = rng.integers(0, C1 - 1, size=B)
    tabs[np.arange(B), r, j] += np.sign(tabs[np.arange(B), r, j]) + 1.0
    mask = rng.uniform(size=B) < 0.7
    tabs = jnp.asarray(tabs, jnp.float32)
    got = simplex_pivot(tabs, jnp.asarray(r), jnp.asarray(j),
                        jnp.asarray(mask), interpret=True)
    ref = pivot_update_ref(tabs, jnp.asarray(r), jnp.asarray(j),
                           jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)
    # masked lanes must pass through untouched
    np.testing.assert_array_equal(np.asarray(got)[~mask],
                                  np.asarray(tabs)[~mask])


def test_simplex_pivot_ref_is_a_simplex_pivot():
    """The reference update must do an actual Gauss-Jordan pivot: pivot
    column becomes a unit vector, pivot row is normalized."""
    from repro.kernels.simplex_pivot.ref import pivot_update_ref
    rng = np.random.default_rng(0)
    tabs = jnp.asarray(rng.normal(size=(2, 4, 6)) + 2.0)
    r = jnp.array([1, 2])
    j = jnp.array([0, 3])
    out = np.asarray(pivot_update_ref(tabs, r, j,
                                      jnp.ones(2, dtype=bool)))
    for b in range(2):
        col = out[b, :, int(j[b])]
        expect = np.zeros(4)
        expect[int(r[b])] = 1.0
        np.testing.assert_allclose(col, expect, atol=1e-12)


def _reduced_state(B, R, C0, seed):
    """A valid cold revised-simplex state: identity factor, xB = b > 0,
    every row basic on its VIRTUAL artificial (labels >= C0)."""
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(B, R, C0))
    xB = rng.uniform(0.5, 2.0, size=(B, R))
    c_phase = np.zeros((B, C0))        # phase 1: artificials cost art_cost
    Binv = np.broadcast_to(np.eye(R), (B, R, R)).copy()
    basis = np.broadcast_to(C0 + np.arange(R, dtype=np.int32), (B, R)).copy()
    with x64_scope():
        return tuple(jnp.asarray(x) for x in (A, c_phase, Binv, xB)) + (
            jnp.asarray(basis, jnp.int32),)


@pytest.mark.parametrize("B,R,C0", [(4, 5, 9), (8, 11, 27), (1, 3, 4)])
def test_reduced_pivot_kernel_vs_ref(B, R, C0):
    """The fused reduced-factor pivot kernel must replay the jnp oracle:
    all pivot DECISIONS (basis labels, has_enter/unbounded/degenerate
    flags) exactly, and the updated [Binv | xB] factor to within a few
    ulps — the ref prices via einsum (dot-general) while the kernel uses
    an elementwise multiply-reduce, so the accumulation order can differ
    at shapes where XLA picks different lowerings.  (At the fleet LP
    shape the two are measured bit-identical; `tests/test_lp.py` pins
    that.)  Masked lanes must pass through untouched, bit for bit."""
    from repro.kernels.simplex_pivot.ops import reduced_pivot
    from repro.kernels.simplex_pivot.ref import reduced_pivot_ref
    with x64_scope():
        A, c_phase, Binv, xB, basis = _reduced_state(B, R, C0, B * 10 + C0)
        rng = np.random.default_rng(1)
        use_bland = jnp.asarray(rng.uniform(size=B) < 0.3)
        may_pivot = jnp.ones(B, bool)
        lane_ok = jnp.asarray(rng.uniform(size=B) < 0.8)
        args = (A, c_phase, Binv, xB, basis, use_bland, may_pivot, lane_ok)
        got = reduced_pivot(*args, art_cost=1.0, tol=1e-7)
        ref = reduced_pivot_ref(*args, art_cost=1.0, tol=1e-7)
        for g, r in zip(got[:2], ref[:2]):       # Binv', xB': ulp-close
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=1e-13, atol=1e-15)
        for g, r in zip(got[2:], ref[2:]):       # basis + flags: exact
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
        ok = np.asarray(lane_ok)
        np.testing.assert_array_equal(np.asarray(got[0])[~ok],
                                      np.asarray(Binv)[~ok])
        np.testing.assert_array_equal(np.asarray(got[2])[~ok],
                                      np.asarray(basis)[~ok])


def test_reduced_pivot_ref_maintains_basis_inverse():
    """After a pivot the updated factor must still be the inverse of the
    basis matrix the updated labels describe (virtual label C0+k <-> e_k,
    real label j <-> column A[:, j]) — i.e. the eta update is a genuine
    product-form basis-inverse update, not just a tableau transform."""
    from repro.kernels.simplex_pivot.ref import reduced_pivot_ref
    with x64_scope():
        B, R, C0 = 6, 5, 12
        A, c_phase, Binv, xB, basis = _reduced_state(B, R, C0, 3)
        on = jnp.ones(B, bool)
        for _ in range(3):                    # a few successive pivots
            Binv, xB, basis, has_enter, unbounded, _deg = reduced_pivot_ref(
                A, c_phase, Binv, xB, basis, ~on, on, on,
                art_cost=1.0, tol=1e-7)
        An, Bn, bn = (np.asarray(A), np.asarray(Binv),
                      np.asarray(basis))
        for b in range(B):
            Bmat = np.stack(
                [An[b, :, l] if l < C0 else np.eye(R)[l - C0]
                 for l in bn[b]], axis=1)
            np.testing.assert_allclose(Bn[b] @ Bmat, np.eye(R), atol=1e-9)
