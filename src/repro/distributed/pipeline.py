"""GPipe-style pipeline parallelism over a mesh "stage" axis.

For 1000+-node scale-out beyond what DP x TP covers, stages are laid out on
an extra mesh axis; microbatches stream through stages with
`jax.lax.ppermute` boundary transfers inside `shard_map`.  The schedule is
the classic GPipe fill-drain: T = M + S - 1 ticks for M microbatches over
S stages (bubble fraction (S-1)/(M+S-1)).

This module is deliberately self-contained (it pipelines any per-stage
`fn(params_stage, x) -> x`), with a correctness test on an 8-device host
mesh in tests/test_distributed.py.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(fn: Callable, params_stacked, x, *, mesh: Mesh,
                   stage_axis: str = "stage", microbatches: int = None):
    """Run ``y = fn_S(... fn_1(x))`` with stages sharded over `stage_axis`.

    params_stacked: pytree with leading dim = n_stages (sharded over the
    stage axis).  x: (B, ...) batch, split into `microbatches` chunks.
    Returns y with the same shape as x.
    """
    n_stages = mesh.shape[stage_axis]
    M = microbatches or n_stages
    B = x.shape[0]
    assert B % M == 0, "batch must divide microbatches"
    mb = B // M

    def per_stage(params_st, x_all):
        # params_st: this stage's params (leading dim 1); x_all: full batch
        # slice living on every stage (only stage 0's content matters).
        stage = jax.lax.axis_index(stage_axis)
        params_me = jax.tree.map(lambda p: p[0], params_st)
        T = M + n_stages - 1

        x_mb = x_all.reshape((M, mb) + x_all.shape[1:])
        out = jnp.zeros_like(x_mb)
        # current activation flowing through this stage
        cur = jnp.zeros((mb,) + x_all.shape[1:], x_all.dtype)

        def tick(t, state):
            cur, out = state
            # stage 0 ingests microbatch t (if in range)
            take = jnp.clip(t, 0, M - 1)
            fresh = x_mb[take]
            cur = jnp.where(stage == 0,
                            jnp.where(t < M, fresh, cur * 0), cur)
            # compute
            y = fn(params_me, cur)
            # emit: last stage writes microbatch t - (S-1) when valid
            emit_idx = t - (n_stages - 1)
            valid = (stage == n_stages - 1) & (emit_idx >= 0) & (emit_idx < M)
            out = jax.lax.cond(
                valid,
                lambda o: o.at[jnp.clip(emit_idx, 0, M - 1)].set(y),
                lambda o: o, out)
            # shift activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            cur = jax.lax.ppermute(y, stage_axis, perm)
            return cur, out

        cur, out = jax.lax.fori_loop(0, T, tick, (cur, out))
        # only the last stage holds real outputs; broadcast them back
        out = jax.lax.psum(
            jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out)),
            stage_axis)
        return out.reshape(x_all.shape)

    spec_params = jax.tree.map(lambda _: P(stage_axis), params_stacked)
    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(spec_params, P()), out_specs=P(),
        check_vma=False,
    )(params_stacked, x)
