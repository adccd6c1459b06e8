"""RG-LRU gated linear recurrence — TPU Pallas.

h_t = a_t * h_{t-1} + b_t, elementwise over the LRU width.  Grid
(B, nW, nS): width tiles are lane-parallel, the sequence runs innermost and
sequential with the (1, Wb) hidden state carried in VMEM scratch — so one
HBM pass over (a, b) produces the full hidden sequence.

ops.py computes the gates (sigmoid/softplus mixing, conv) in jnp — the
recurrence is the only part XLA cannot fuse into a single pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, y_ref, h_ref, *, bs: int):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0]                     # (bs, Wb)
    b = b_ref[0]

    def body(t, h):
        h = a[t] * h + b[t]
        y_ref[0, t, :] = h
        return h

    h = jax.lax.fori_loop(0, bs, body, h_ref[0])
    h_ref[0] = h


@functools.partial(jax.jit, static_argnames=("bs", "bw", "interpret"))
def rglru_scan_fwd(a, b, *, bs: int = 128, bw: int = 512,
                   interpret: bool = True):
    """a, b: (B, S, W) f32. Returns the full hidden sequence (B, S, W)."""
    B, S, W = a.shape
    bs = min(bs, S)
    bw = min(bw, W)
    ns = -(-S // bs)
    nw = -(-W // bw)
    ps = ns * bs - S
    pw = nw * bw - W
    if ps or pw:
        a = jnp.pad(a, ((0, 0), (0, ps), (0, pw)))
        b = jnp.pad(b, ((0, 0), (0, ps), (0, pw)))

    y = pl.pallas_call(
        functools.partial(_kernel, bs=bs),
        grid=(B, nw, ns),
        in_specs=[
            pl.BlockSpec((1, bs, bw), lambda bb, w, s: (bb, s, w)),
            pl.BlockSpec((1, bs, bw), lambda bb, w, s: (bb, s, w)),
        ],
        out_specs=pl.BlockSpec((1, bs, bw), lambda bb, w, s: (bb, s, w)),
        out_shape=jax.ShapeDtypeStruct((B, ns * bs, nw * bw), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return y[:, :S, :W]
