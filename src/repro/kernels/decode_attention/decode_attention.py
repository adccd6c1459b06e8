"""Flash-decode attention — TPU Pallas.

One new token against a long KV cache.  Grid (B*KH, nk) sweeps the cache
sequence; each step computes the G grouped query heads (packed as matmul
rows, so GQA groups feed the MXU together) against one KV tile, carrying
(m, l, acc) partials in VMEM scratch — the flash-decode combine.

Ring-buffer semantics are handled by a per-(batch, slot) validity mask the
wrapper precomputes (O(S) int32), so the kernel itself is position-agnostic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, bk: int):
    j = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                        # (G, d)
    k = k_ref[0]                                        # (bk, d)
    v = v_ref[0]
    ok = valid_ref[0] != 0                              # (bk,)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok[None, :], s, NEG)                  # (G, bk)

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


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention_fwd(q, k, v, valid, *, bk: int = 512,
                         interpret: bool = True):
    """q: (BKH, G, D); k, v: (BKH, Sk, D); valid: (BKH, Sk) int32."""
    BKH, G, D = q.shape
    Sk = k.shape[1]
    bk = min(bk, Sk)
    nk = -(-Sk // bk)
    pk = nk * bk - Sk
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, pk)))

    kernel = functools.partial(_kernel, scale=D ** -0.5, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=(BKH, nk),
        in_specs=[
            pl.BlockSpec((1, G, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk), lambda b, j: (b, j)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BKH, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, valid)
