"""Mamba2 SSD (state-space duality) chunked scan — TPU Pallas.

Grid (B*H, n_chunks), chunks sequential; the (P, N) inter-chunk state lives
in VMEM scratch across the chunk sweep.  Per chunk: the intra-chunk
quadratic term runs as two MXU matmuls ((Q,N)x(N,Q) scores and the masked
(Q,Q)x(Q,P) apply), the state contribution as (N,Q)x(Q,P); decays are VPU
elementwise on cumulative dA.

B/C are per-(batch, group=1) and shared across heads — their BlockSpec
index_map folds the head axis (b // H) so nothing is materialised per head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, state_ref, *,
            q_len: int):
    c_idx = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)                    # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)                  # (Q,)
    A = a_ref[0, 0]                                     # ()
    B_ = b_ref[0].astype(jnp.float32)                   # (Q, N)
    C_ = c_ref[0].astype(jnp.float32)                   # (Q, N)

    dA = dt * A                                         # (Q,)
    cum = jnp.cumsum(dA)                                # (Q,)
    xdt = x * dt[:, None]                               # (Q, P)

    # intra-chunk: Y = (exp(segsum) ∘ (C B^T)) @ xdt
    seg = cum[:, None] - cum[None, :]                   # (Q, Q)
    qi = jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 1)
    L = jnp.where(ki <= qi, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(C_, B_, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot(L * scores, xdt,
                    preferred_element_type=jnp.float32)  # (Q, P)

    # inter-chunk: contribution of the carried state
    decay_from_start = jnp.exp(cum)                     # (Q,)
    y += (jax.lax.dot(C_, state_ref[...].T,
                      preferred_element_type=jnp.float32)
          * decay_from_start[:, None])                  # (Q, P)
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: state' = state * exp(sum dA) + sum_k decay_k B_k x_k
    decay_to_end = jnp.exp(cum[-1] - cum)               # (Q,)
    new_contrib = jax.lax.dot_general(
        (xdt * decay_to_end[:, None]), B_, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (P, N)
    state_ref[...] = state_ref[...] * jnp.exp(cum[-1]) + new_contrib

    @pl.when(c_idx == nc - 1)
    def _emit_state():
        st_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "interpret"))
def ssd_scan_fwd(x, dt, A, B_, C_, *, heads: int, chunk: int = 256,
                 interpret: bool = True):
    """x: (BH, S, P); dt: (BH, S) (softplus already applied); A: (BH, 1);
    B_, C_: (B, S, N) shared across the `heads` per batch.
    Returns (y (BH, S, P), final_state (BH, P, N))."""
    BH, S, P = x.shape
    N = B_.shape[-1]
    Q = min(chunk, S)
    nc = -(-S // Q)
    pad = nc * Q - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0)))

    kernel = functools.partial(_kernel, q_len=Q)
    y, state = pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, Q), lambda b, c: (b, c)),
            pl.BlockSpec((1, 1), lambda b, c: (b, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c, h=heads: (b // h, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, c, h=heads: (b // h, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, P, N), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, nc * Q, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A, B_, C_)
    return y[:, :S], state
