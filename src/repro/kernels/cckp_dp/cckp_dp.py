"""CCKP dynamic-program kernel (AMDP §VI-B) — TPU Pallas.

The paper reimplements this DP in C to hit <1 ms on a Raspberry Pi; this is
the TPU-native equivalent: the whole (T+1, K+1) value grid stays resident in
VMEM (a 4001x301 f32 grid is ~4.8 MB of the ~16 MB budget) and the q-loop
runs as a fori_loop of *static* (p_i, 1) shifts (`pltpu.roll` plus an iota
mask) + elementwise max — pure VPU work, no HBM round-trips per item.

One pallas_call handles one model group:
    Y'[t, k]   = max_q  Y[t - q*p, k - q] + q*a
    bestq[t,k] = argmax (for AMDP's O(m) backtrack)
`p` is a *static* kernel parameter (shift offsets must be static on TPU);
AMDP calls it once per model, so there are at most m compiled variants.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
# typed fill: under x64 a Python float is a float64 scalar, which Mosaic
# cannot hold
_NEG32 = np.float32(NEG)


def _shift(s, p: int):
    """``s`` shifted down by ``p`` rows and right by one column, NEG-filled.

    Rolls and then masks the wrapped-in rows/columns: Mosaic has no
    scatter, so the ``.at[p:, 1:].set`` form of `ref.py` cannot lower."""
    T1, K1 = s.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (T1, K1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (T1, K1), 1)
    if 0 < p < T1:
        s = pltpu.roll(s, jnp.int32(p), 0)   # int32 shifts: a Python
    if K1 > 1:                               # int is int64 under x64
        s = pltpu.roll(s, jnp.int32(1), 1)
    return jnp.where((rows >= p) & (cols >= 1), s, _NEG32)


def _kernel(y_ref, a_ref, out_ref, bestq_ref, s_ref, *, p: int,
            n_steps: int):
    T1, K1 = y_ref.shape
    s_ref[...] = y_ref[...]
    out_ref[...] = jnp.full((T1, K1), _NEG32)
    bestq_ref[...] = jnp.zeros((T1, K1), jnp.int32)
    a = a_ref[0]

    def body(q, _):
        s = s_ref[...]
        val = s + q.astype(jnp.float32) * a
        best = out_ref[...]
        take = val > best
        out_ref[...] = jnp.where(take, val, best)
        bestq_ref[...] = jnp.where(take, q, bestq_ref[...])
        s_ref[...] = _shift(s, p)
        return ()

    # int32 bounds: under x64 a Python-int loop index would be int64,
    # which Mosaic cannot hold
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_steps), body, ())


@functools.partial(jax.jit, static_argnames=("p", "n_steps", "interpret"))
def cckp_model_dp(y: jnp.ndarray, a: jnp.ndarray, *, p: int, n_steps: int,
                  interpret: bool = False):
    """y: (T+1, K+1) f32 value grid; a: () accuracy of this model's items.
    Returns (y', bestq)."""
    T1, K1 = y.shape
    kernel = functools.partial(_kernel, p=p, n_steps=n_steps)
    return pl.pallas_call(
        kernel,
        grid=(),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T1, K1), jnp.float32),
            jax.ShapeDtypeStruct((T1, K1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((T1, K1), jnp.float32)],
        interpret=interpret,
    )(y, a.reshape(1))
