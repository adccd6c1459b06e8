"""Compile rehearsals for the TPU v5e, with no chip attached.

The fleet Pallas kernels at fleet widths and the engine's jitted period
step are compiled for a *described* v5e chip: the TPU compiler refuses
here what it would refuse on the chip (unaligned blocks, primitives Mosaic
cannot lower, 64-bit scalars), at no chip time.  Nothing runs, so these
say nothing about results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the test workers
all import this file.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.types import x64_scope

LANES = 1024                 # fleet lanes per kernel call
N_JOBS = 12                  # jobs per planning window (batch_max)
M = 2                        # local models in the paper's ladder
R = N_JOBS + 2               # LP rows: ED budget, ES budget, n assignments
C0 = N_JOBS * (M + 1) + 2    # LP columns: variables + 2 slacks
DP_GRID = (1201, N_JOBS + 1)  # CCKP grid: T = 1.2 s at 1 ms, 0..n jobs


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to a persistent cache but
    # cannot be read back without one; keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_kernel(fn, shapes, one_chip, x64: bool):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    with jax.enable_x64(x64):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# the LP entry points trace the kernels inside the float64 scope, so each
# kernel must also compile with x64 on (int32 index maps, typed constants)
@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
def test_cckp_dp_compiles_for_v5e(one_chip, x64):
    from repro.kernels.cckp_dp.cckp_dp import cckp_model_dp
    for p in (0, 40):
        _compile_kernel(partial(cckp_model_dp, p=p, n_steps=N_JOBS + 1),
                        [(DP_GRID, jnp.float32), ((), jnp.float32)],
                        one_chip, x64)


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
def test_simplex_pivot_compiles_for_v5e(one_chip, x64):
    from repro.kernels.simplex_pivot.simplex_pivot import simplex_pivot
    _compile_kernel(simplex_pivot,
                    [((LANES, R + 1, C0 + R + 1), jnp.float32),
                     ((LANES,), jnp.int32), ((LANES,), jnp.int32),
                     ((LANES,), jnp.bool_)], one_chip, x64)


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
def test_reduced_pivot_compiles_for_v5e(one_chip, x64):
    from repro.kernels.simplex_pivot.simplex_pivot import reduced_pivot
    f32 = jnp.float32
    _compile_kernel(partial(reduced_pivot, art_cost=1e6, tol=1e-7),
                    [((LANES, R, C0), f32), ((LANES, C0), f32),
                     ((LANES, R, R), f32), ((LANES, R), f32),
                     ((LANES, R), jnp.int32), ((LANES,), jnp.bool_),
                     ((LANES,), jnp.bool_), ((LANES,), jnp.bool_)],
                    one_chip, x64)


def test_engine_period_step_compiles_for_v5e(one_chip):
    """The engine's jitted float64 period step (the body `rollout` scans)
    at 64 devices on the reduced-tableau simplex, as the chip runs it.
    Its pivot loops price from the LP's column form: no op comes from the
    dense pricing or FTRAN einsums, only BTRAN's ``cB·Binv``."""
    from repro.api import engine as E
    from repro.serving import FleetConfig
    cfg = FleetConfig(n_devices=64, T=1.2, n_servers=4, policy="amr2",
                      rate=10.0, batch_max=N_JOBS, seed=7)
    params = E.EngineParams.from_config(cfg, horizon=4, lp_method="revised")
    state = E.init_state(params)
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                       sharding=one_chip), (state, params))
    with x64_scope():
        compiled = E._step_jit.lower(*shapes).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    hlo = compiled.as_text()
    assert "/br,brk->bk/" in hlo
    assert "/bk,bkc->bc/" not in hlo and "/brk,bk->br/" not in hlo


def _wide_call(kernel):
    """A wrapper call of ``kernel`` on float64 operands (built in the
    float64 scope; the call itself happens later, outside it)."""
    from repro.kernels.cckp_dp import ops as cckp_ops
    from repro.kernels.simplex_pivot import ops as pivot_ops
    with x64_scope():
        f64 = lambda *s: jnp.zeros(s, jnp.float64)
        i32 = jnp.zeros(4, jnp.int32)
        on = jnp.ones(4, bool)
        if kernel == "cckp_dp":
            y = f64(9, 4)
            return lambda: cckp_ops.model_dp(y, 2, 0.5, 4)
        if kernel == "simplex_pivot":
            tabs = f64(4, 3, 5)
            return lambda: pivot_ops.pivot_update(tabs, i32, i32, on)
        args = (f64(4, 3, 5), f64(4, 5), f64(4, 3, 3), f64(4, 3),
                jnp.zeros((4, 3), jnp.int32), on, on, on)
        return lambda: pivot_ops.reduced_pivot(*args, art_cost=1.0,
                                               tol=1e-7)


@pytest.mark.parametrize("kernel", ["cckp_dp", "simplex_pivot",
                                    "reduced_pivot"])
def test_float64_pallas_is_refused_on_tpu(monkeypatch, kernel):
    """On a TPU the wrappers refuse float64 before any lowering, naming
    the jnp path, instead of failing deep in Mosaic or dropping to
    interpret mode."""
    call = _wide_call(kernel)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match=r"64-bit floats.*impl='jnp'"):
        call()


def test_interpret_mode_follows_the_backend(monkeypatch):
    from repro.kernels import interpret_mode
    x = jnp.zeros(3, jnp.float32)
    assert interpret_mode("k", x) is (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode("k", x) is False
