"""The program's own spans and scopes, read from a trace (`program_trace`
and the readers ``scope_time`` / ``program_span``), on synthetic data and
on a CPU run of the harness."""
import os

import pytest
from conftest import drive

import program_trace as PT
import tracing
from readers import program_span, scope_time

HLO = """HloModule jit_step, is_scheduled=true

%fused_computation (param_0: f64[8]) -> f64[8] {
  %param_0 = f64[8]{0} parameter(0)
  %c = f64[8]{0} broadcast(%param_0), dimensions={0}, metadata={op_name="jit(step)/plan/lp/mul"}
  ROOT %m = f64[8]{0} multiply(%param_0, %c)
}

%body (s: f64[8]) -> f64[8] {
  %s = f64[8]{0} parameter(0)
  %copy.7 = f64[8]{0} copy(%s)
  ROOT %add.8 = f64[8]{0} add(%copy.7, %s), metadata={op_name="jit(step)/replan/cond/branch_1_fun/lp/while/body/add"}
}

%cond (s: f64[8]) -> pred[] {
  %s = f64[8]{0} parameter(0)
  ROOT %t = pred[] constant(true)
}

ENTRY %main (p: f64[8]) -> /*index=0*/f64[8] {
  %p = f64[8]{0} parameter(0)
  %fusion.1 = f64[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation
  %while.2 = f64[8]{0} while(%fusion.1), condition=%cond, body=%body, metadata={op_name="jit(step)/replan/cond/branch_1_fun/lp/while"}
  %add.3 = f64[8]{0} add(%while.2, %p), metadata={op_name="jit(step)/pricing/add;jit(step)/arrivals/add"}
  ROOT %sort.4 = f64[8]{0} sort(%add.3), dimensions={0}
}
"""


def test_scope_paths_name_the_top_stage_and_its_nested_lp():
    assert PT.scope_path("jit(f)/while/body/closed_call/replan/cond/"
                         "branch_1_fun/lp/while/body/mul") == "replan/lp"
    assert PT.scope_path("jit(f)/plan/round/add") == "plan/round"
    assert PT.top_scope("jit(f)/admission/sort") == "admission"
    assert PT.top_scope("jit(f)/mul") == ""
    # an op merged from several keeps the first path that names a scope
    assert PT.top_scope("jit(f)/mul;jit(f)/pricing/add") == "pricing"


def test_op_names_fill_fusions_and_loop_bodies():
    got = PT.op_names(HLO)
    # a fusion without metadata: the scoped op inside it
    assert got["fusion.1"] == "jit(step)/plan/lp/mul"
    # a body's copy without metadata: the loop that runs it
    assert PT.scope_path(got["copy.7"]) == "replan/lp"
    assert PT.top_scope(got["add.3"]) == "pricing"
    # an op without metadata: the first operand's
    assert got["sort.4"] == got["add.3"]
    assert got["p"] == ""                   # nothing to inherit


def _op(name, start, dur, opcode="fusion", device=0):
    return tracing.Op(name=name, module="jit_step", device=device,
                      start=start, dur=dur, opcode=opcode, source="")


def _trace():
    names = {"fusion.1": "jit(step)/plan/lp/mul",
             "add.8": "jit(step)/replan/cond/branch_1_fun/lp/while/body/add",
             "while.2": "jit(step)/replan/cond/branch_1_fun/lp/while",
             "add.3": "jit(step)/pricing/add"}
    spans = [("bench:dispatch", 10.0, 10.4), ("bench:fetch", 10.4, 11.0),
             ("bench:dispatch", 11.0, 11.3), ("bench:fetch", 11.3, 12.0),
             ("repro.step", 10.01, 10.39), ("repro.validate", 10.01, 10.02),
             ("repro.horizon", 10.02, 10.10), ("repro.launch", 10.10, 10.39),
             ("repro.step", 11.01, 11.29), ("repro.validate", 11.01, 11.02),
             ("repro.horizon", 11.02, 11.05), ("repro.launch", 11.05, 11.29),
             # a call outside the traced ones is not counted
             ("repro.horizon", 12.5, 12.9)]
    return PT.Trace(path="x", spans=spans, op_names={"jit_step": names})


@pytest.fixture
def ctx(monkeypatch):
    ops = [_op("while.2", 0.10, 0.40, "while"),
           _op("add.8", 0.10, 0.30, "add"),
           _op("fusion.1", 0.50, 0.20),
           _op("add.3", 0.70, 0.05, "add"),
           _op("custom.9", 0.80, 0.10, "custom-call"),      # no scope
           _op("fusion.1", 0.50, 0.30, device=1)]
    spans = [("dispatch", 0.0, 0.4), ("fetch", 0.4, 1.0),
             ("dispatch", 1.0, 1.3), ("fetch", 1.3, 2.0)]
    c = tracing.Context(ops, spans, (0.0, 2.0), periods=2, n_chips=2)
    monkeypatch.setattr(PT, "for_context", lambda ctx: _trace())
    return c


def test_scope_time_reads_leaf_ops_under_a_stage(ctx):
    assert scope_time.read(ctx, scope="plan") == pytest.approx(
        1000 * (0.20 + 0.30) / 2 / 2)
    # the while loop spans its body's add: counted once, by the add
    assert scope_time.read(ctx, scope="replan") == pytest.approx(
        1000 * 0.30 / 2 / 2)
    assert scope_time.read(ctx, scope="ladder") is None


def test_program_span_counts_spans_inside_the_traced_calls(ctx):
    check = program_span.read(ctx, spans=["repro.validate",
                                          "repro.horizon"])
    assert check == pytest.approx(1000 * (0.01 + 0.08 + 0.01 + 0.03) / 2)
    assert program_span.read(ctx, spans=["repro.launch"]) == pytest.approx(
        1000 * (0.29 + 0.24) / 2)
    assert program_span.read(ctx, spans=["repro.none"]) is None


def test_readers_find_nothing_without_the_program_trace(ctx, monkeypatch):
    monkeypatch.setattr(PT, "for_context", lambda ctx: None)
    assert scope_time.read(ctx, scope="plan") is None
    assert program_span.read(ctx, spans=["repro.launch"]) is None


def test_a_trace_of_other_calls_is_not_the_context(ctx, monkeypatch):
    monkeypatch.undo()
    monkeypatch.setattr(PT, "newest", lambda: "x")
    monkeypatch.setattr(PT, "load", lambda path: _trace())
    assert PT.for_context(ctx) is not None         # two traced calls each
    one = tracing.Context([], ctx.spans[:2], (0.0, 1.0), 1, 1)
    assert PT.for_context(one) is None
    monkeypatch.setattr(PT, "newest", lambda: None)
    assert PT.for_context(ctx) is None


def test_idle_gaps_take_the_innermost_span():
    spans = _trace().spans
    ops = [_op("a", 10.00, 0.015), _op("b", 10.12, 0.30),
           _op("c", 11.00, 0.60)]
    gaps = dict((round(s, 6), n) for n, s in
                PT.idle_gaps(ops, spans, (10.0, 12.0), 1))
    assert gaps[round(10.12 - 10.015, 6)] == "repro.horizon"
    assert gaps[round(11.0 - 10.42, 6)] == "bench:fetch"
    assert gaps[round(12.0 - 11.6, 6)] == "bench:fetch"
    assert PT.innermost(spans, 10.015) == "repro.validate"
    assert PT.innermost(spans, 9.0) == "between calls"


def test_a_cpu_run_carries_the_program_spans(checkout):
    rc, result, err = drive(checkout, "tiny.plan", trace=1, seconds=1.5)
    assert rc == 0, err[-3000:]
    tr = PT.load(PT.newest(os.path.join(checkout, ".bench_trace")))
    calls = tr.dispatches()
    assert len(calls) == 2                         # the cell's traced calls
    for s, e in calls:
        inner = sorted((a, n) for n, a, b in tr.spans
                       if n.startswith("repro.") and s <= a and b <= e)
        assert [n for _, n in inner] == ["repro.step", "repro.validate",
                                         "repro.horizon", "repro.launch"]
    assert tr.span_seconds(("repro.launch",)) > 0
