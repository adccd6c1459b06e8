"""Flash attention (forward) — TPU Pallas.

Grid (B*H, nq, nk), kv innermost/sequential; 128x128 MXU-aligned Q/KV tiles;
online-softmax accumulators (acc, m, l) live in VMEM scratch across the kv
sweep.  Causal/sliding-window masks are index-derived; blocks entirely
outside the mask are *structurally skipped* with pl.when (no MXU work).

GQA without materialising repeated KV: the kv BlockSpec index_map folds the
query-head index h to kv-head h // group so each q-head tile streams its own
group's KV tiles straight from HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, mask_kind: str, window: int, bq: int, bk: int,
            sq: int, sk: int):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_first = i * bq
    q_last = q_first + bq - 1
    k_first = j * bk
    k_last = k_first + bk - 1

    live = jnp.bool_(True)
    if mask_kind in ("causal", "window"):
        live = live & (k_first <= q_last)
    if mask_kind == "window":
        live = live & (k_last > q_first - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0]                                   # (bq, d)
        k = k_ref[0]                                   # (bk, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        qp = q_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kp = k_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kp < sk                                  # kv padding
        if mask_kind in ("causal", "window"):
            mask &= kp <= qp
        if mask_kind == "window":
            mask &= kp > qp - window
        s = jnp.where(mask, s, NEG)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("mask_kind", "window", "group", "bq", "bk",
                     "interpret"))
def flash_attention_fwd(q, k, v, *, mask_kind: str = "causal",
                        window: int = 0, group: int = 1, bq: int = 128,
                        bk: int = 128, interpret: bool = True):
    """q: (BH, Sq, D); k, v: (B*KH, Sk, D) with BH = B*KH*group.
    D should be a multiple of 128 on real TPUs (ops.py pads)."""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    nq = -(-Sq // bq)
    nk = -(-Sk // bk)
    pq = nq * bq - Sq
    pk = nk * bk - Sk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))

    kernel = functools.partial(
        _kernel, scale=D ** -0.5, mask_kind=mask_kind, window=window,
        bq=bq, bk=bk, sq=Sq, sk=Sk)
    out = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D),
                         lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, bk, D),
                         lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, nq * bq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :Sq]
