"""The batched solvers run under LOCAL `repro.core.types.x64_scope` scopes
so their results are float64 regardless of the process-global
``jax_enable_x64`` flag.  Toggling the global flag mid-process must neither
change results nor trip stale-trace / dtype-mismatch errors — the jit
caches key on the traced avals (f64 inside the scope either way), and this
file pins that contract by solving the same instances with the flag off
and on in one process."""
import jax
import numpy as np
import pytest

from repro.core import InstanceBatch, random_instance, solve_lp_batch
from repro.core.amr2 import build_lp_arrays_batch
from repro.core.dual import dual_schedule_batch_arrays

B, N, M = 5, 8, 2


def _batch(seed=0):
    return InstanceBatch.stack(
        [random_instance(N, M, T=1.2, seed=seed + s) for s in range(B)])


def _lp_inputs(batch):
    return build_lp_arrays_batch(batch)


@pytest.fixture
def x64_toggle():
    """Restore the global flag no matter how the test exits."""
    prev = jax.config.jax_enable_x64
    yield
    jax.config.update("jax_enable_x64", prev)


def test_solve_lp_batch_invariant_to_global_x64(x64_toggle):
    batch = _batch(0)
    c, A_ub, b_ub, A_eq, b_eq = _lp_inputs(batch)
    res_off = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq)
    warm_off = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq,
                              warm_basis=res_off.basis)

    jax.config.update("jax_enable_x64", True)
    res_on = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq)
    warm_on = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq,
                             warm_basis=res_off.basis)

    np.testing.assert_array_equal(res_on.status, res_off.status)
    np.testing.assert_array_equal(res_on.niter, res_off.niter)
    np.testing.assert_array_equal(res_on.basis, res_off.basis)
    np.testing.assert_array_equal(res_on.x, res_off.x)      # bit parity
    np.testing.assert_array_equal(res_on.fun, res_off.fun)
    np.testing.assert_array_equal(warm_on.warm, warm_off.warm)
    np.testing.assert_array_equal(warm_on.x, warm_off.x)

    jax.config.update("jax_enable_x64", False)              # and back again
    res_off2 = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq)
    np.testing.assert_array_equal(res_off2.x, res_off.x)


def test_dual_schedule_batch_invariant_to_global_x64(x64_toggle):
    batch = _batch(10)
    assign_off, status_off = dual_schedule_batch_arrays(batch)

    jax.config.update("jax_enable_x64", True)
    assign_on, status_on = dual_schedule_batch_arrays(batch)

    np.testing.assert_array_equal(assign_on, assign_off)
    np.testing.assert_array_equal(status_on, status_off)

    jax.config.update("jax_enable_x64", False)
    assign_off2, _ = dual_schedule_batch_arrays(batch)
    np.testing.assert_array_equal(assign_off2, assign_off)


def test_both_solvers_interleaved_under_toggles(x64_toggle):
    """Interleave LP and dual solves across three flag states in one
    process — the scenario that would surface a stale-trace/dtype bug."""
    batch = _batch(20)
    c, A_ub, b_ub, A_eq, b_eq = _lp_inputs(batch)
    ref_lp = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq)
    ref_dual = dual_schedule_batch_arrays(batch)

    for flag in (True, False, True):
        jax.config.update("jax_enable_x64", flag)
        got_lp = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq)
        got_dual = dual_schedule_batch_arrays(batch)
        np.testing.assert_array_equal(got_lp.x, ref_lp.x)
        np.testing.assert_array_equal(got_dual[0], ref_dual[0])


def test_engine_f32_guard_under_global_x64_off():
    """The engine's float64 guard, end to end in a FRESH interpreter with
    the global x64 flag off: a `device_put` of the state outside any
    `enable_x64` scope silently materializes float32 buffers, and
    `engine.step` must refuse them with a TypeError naming the leaf (the
    old behavior ran the whole rollout at single precision, quietly
    voiding the documented parity claims)."""
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import jax
        assert not jax.config.jax_enable_x64
        import numpy as np
        from repro.api import engine as E
        from repro.serving import FleetConfig

        cfg = FleetConfig(n_devices=4, T=1.2, n_servers=1, policy="amr2",
                          backend="jax", rate=6.0, batch_max=8,
                          horizon=6, seed=0)
        params = E.EngineParams.from_config(cfg, horizon=6)
        state = E.init_state(params)
        # the buggy pattern: an unscoped transfer downcasts to f32
        bad = jax.tree.map(jax.device_put, state)
        assert np.asarray(bad.p_ed).dtype == np.float32
        try:
            E.step(bad, params)
        except TypeError as e:
            assert "state.p_ed" in str(e) and "float32" in str(e), e
            print("GUARDED")
        else:
            raise SystemExit("f32 state was accepted silently")

        # the correct pattern still works: scoped transfers stay f64
        from repro.core.types import x64_scope
        with x64_scope():
            good = jax.tree.map(jax.device_put, state)
        st2, m = E.step(good, params)
        assert np.asarray(st2.p_ed).dtype == np.float64
        print("OK")
    """)
    env = dict(os.environ)
    env.pop("JAX_ENABLE_X64", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert "GUARDED" in out.stdout and "OK" in out.stdout
