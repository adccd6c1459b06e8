"""The engine's own tracing: device scopes in the compiled HLO, host spans
in a profiler trace, and the LP's lockstep pivot counters."""
import contextlib
import glob
import re

import jax
import numpy as np
import pytest

from repro.api import engine as E
from repro.core.hi import HIModel
from repro.core.mobility import MobilityModel
from repro.core.types import x64_scope
from repro.serving import FaultModel, FleetConfig

STAGES = ("arrivals", "route", "plan", "hi_gate", "admission", "replan",
          "ladder", "pricing")
_OPNAME = re.compile(r'op_name="([^"]+)"')


def _config(n_devices=16, *, policy="amr2", horizon=8):
    return FleetConfig(n_devices=n_devices, T=1.2, n_servers=2,
                       policy=policy, backend="jax", rate=9.0, batch_max=8,
                       horizon=horizon, seed=5, straggler_frac=0.25,
                       outage_frac=0.1)


def _params(policy="amr2", horizon=8):
    return E.EngineParams.from_config(_config(policy=policy,
                                              horizon=horizon),
                                      horizon=horizon)


def _step_hlo(params):
    """Compiled HLO text of one period, traced anew (a new function: the
    module-level `_PLAN_LANE_CHUNK` and the scopes are read at trace
    time, and jax caches traces by function)."""
    with x64_scope():
        return jax.jit(lambda s, p: E._step_impl(s, p)).lower(
            E.init_state(params), params).compile().as_text()


def _rollout(params, periods):
    """A rollout traced anew (see `_step_hlo`)."""
    with x64_scope():
        return jax.jit(lambda s, p: E._rollout_impl(s, p, periods))(
            E.init_state(params), params)


def _stages(op_name):
    return [p for p in op_name.split("/") if p in STAGES]


def _op_names(text):
    # an op the compiler merged from several keeps their names joined by
    # ";"; names relative to a sub-computation (a sort's comparator, a
    # reduction's body) carry no jit(...) root: they are no ops of their own
    return [n for joined in _OPNAME.findall(text)
            for n in joined.split(";") if n.startswith("jit(")]


@pytest.mark.parametrize("chunk", [0, 4], ids=["flat", "chunked"])
def test_compiled_step_scopes_every_op_by_stage(monkeypatch, chunk):
    monkeypatch.setattr(E, "_PLAN_LANE_CHUNK", chunk)
    names = _op_names(_step_hlo(_params()))
    assert names
    for n in names:
        assert len(_stages(n)) == 1, n         # exactly one stage per op
    tops = {_stages(n)[0] for n in names}
    assert tops == {"arrivals", "plan", "admission", "replan", "pricing"}
    paths = {"/".join(p for p in n.split("/") if p in STAGES + ("lp",
                                                                 "round"))
             for n in names}
    for stage in ("plan", "replan"):
        assert {f"{stage}/lp", f"{stage}/round"} <= paths, stage
    # the replan's LP runs inside the backpressure lax.cond
    assert any(re.search(r"/replan/cond/branch_\d+_fun/.*lp/", n)
               for n in names)
    # the chunked plan maps `_plan_flat` over lane chunks
    chunked = [n for n in names if re.search(r"/plan/while/body/.*lp/", n)]
    assert bool(chunked) == bool(chunk)


@pytest.mark.parametrize("arm,stage", [
    (lambda p: p.with_mobility(
        MobilityModel.make(cell_xy=np.zeros((1, 2)),
                           trace=np.zeros((10, 16, 2)))), "route"),
    (lambda p: p.with_faults(FaultModel.make(loss_rate=0.1),
                             fault_seed=3), "ladder"),
    (lambda p: p.with_hi(HIModel.make(), rule="threshold"), "hi_gate"),
], ids=["route", "ladder", "hi_gate"])
def test_armed_subsystems_run_under_their_own_scope(arm, stage):
    names = _op_names(_step_hlo(arm(_params())))
    for n in names:
        assert len(_stages(n)) == 1, n
    tops = {_stages(n)[0] for n in names}
    assert stage in tops
    if stage == "hi_gate":
        assert "plan" not in tops           # the gate replaces the plan


def test_scopes_change_no_op(monkeypatch):
    """Scopes are metadata: with them stripped, the compiled period is the
    one compiled without any scope, instruction for instruction."""
    def strip(text):
        # instructions only: the stack-frame tables (numbered lines) name
        # source lines, this test's own among them
        return [re.sub(r", metadata=\{[^}]*\}", "", line)
                for line in text.splitlines()
                if not re.match(r"\s*\d+ ", line)]

    params = _params()
    scoped = _step_hlo(params)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _step_hlo(params)
    assert "/plan/" in scoped and "/plan/" not in plain
    assert strip(scoped) == strip(plain)


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events
                        if ev.name.startswith("repro.")]
    return sorted(out, key=lambda e: e[1])


@pytest.mark.parametrize("entry", ["step", "rollout"])
def test_profiler_trace_holds_the_entry_spans_in_order(tmp_path, entry):
    params = _params()
    state = E.init_state(params)
    call = ((lambda s: E.step(s, params)) if entry == "step"
            else (lambda s: E.rollout(s, params, 2)))
    jax.block_until_ready(call(state))            # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(call(state))
    events = _host_events(tmp_path)
    (outer,) = [e for e in events if e[0] == f"repro.{entry}"]
    inner = [e for e in events
             if e[1] >= outer[1] and e[2] <= outer[2] and e is not outer]
    assert [e[0] for e in inner] == ["repro.validate", "repro.horizon",
                                     "repro.launch"]
    assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))


def test_pivot_counters_bound_the_lockstep_loop(monkeypatch):
    params = _params()
    monkeypatch.setattr(E, "_PLAN_LANE_CHUNK", 0)
    _, flat = _rollout(params, 4)
    monkeypatch.setattr(E, "_PLAN_LANE_CHUNK", 4)
    _, chunked = _rollout(params, 4)
    piv = np.asarray(flat.lp_pivots)
    slots = np.asarray(flat.lp_pivot_slots)
    assert (piv > 0).all() and (piv <= slots).all()
    # the same lanes pivot alike in chunks; each chunk waits only for its
    # own slowest lane, so chunks pay at most the flat batch's slots
    np.testing.assert_array_equal(np.asarray(chunked.lp_pivots), piv)
    assert (np.asarray(chunked.lp_pivot_slots) <= slots).all()
    assert (np.asarray(chunked.lp_pivot_slots)
            >= np.asarray(chunked.lp_pivots)).all()


@pytest.mark.parametrize("case", ["dual", "hi"])
def test_pivot_counters_are_zero_without_the_lp(case):
    params = (_params(policy="dual") if case == "dual"
              else _params().with_hi(HIModel.make(), rule="threshold"))
    _, m = E.rollout(E.init_state(params), params, 3)
    assert np.asarray(m.lp_pivots).sum() == 0
    assert np.asarray(m.lp_pivot_slots).sum() == 0


def test_step_and_rollout_count_the_same_pivots():
    params = _params()
    state = E.init_state(params)
    _, ms = E.rollout(state, params, 3)
    s, rows = state, []
    for _ in range(3):
        s, m = E.step(s, params)
        rows.append((int(m.lp_pivots), int(m.lp_pivot_slots)))
    assert rows == list(zip(np.asarray(ms.lp_pivots).tolist(),
                            np.asarray(ms.lp_pivot_slots).tolist()))
