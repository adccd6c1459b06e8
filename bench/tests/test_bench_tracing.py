"""The reduction from a trace to the per-layer metrics, on a small
synthetic trace."""
import pytest

import tracing
from readers import host_span, idle_share, module_time

HLO = """HloModule jit_step, is_scheduled=true

FileNames
1 "/ckout/src/repro/core/lp.py"
2 "/ckout/src/repro/api/engine.py"
3 "/ckout/src/repro/core/mobility.py"

FunctionNames
1 "f"

FileLocations
1 {file_name_id=1 function_name_id=1 line=10 end_line=10 column=1 end_column=2}
2 {file_name_id=2 function_name_id=1 line=20 end_line=20 column=1 end_column=2}
3 {file_name_id=3 function_name_id=1 line=30 end_line=30 column=1 end_column=2}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=2}
3 {file_location_id=3 parent_frame_id=2}


%fused_computation (param_0: f64[8]) -> f64[8] {
  %param_0 = f64[8]{0} parameter(0)
  %c = f64[8]{0} broadcast(%param_0), dimensions={0}, metadata={op_name="jit(step)/mul" stack_frame_id=2}
  ROOT %m = f64[8]{0} multiply(%param_0, %c), metadata={op_name="jit(step)/mul" stack_frame_id=1}
}

ENTRY %main (p: f64[8]) -> f64[8] {
  %p = f64[8]{0} parameter(0)
  %fusion.1 = f64[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/mul" stack_frame_id=2}
  %sort.2 = f64[8]{0} sort(%fusion.1), dimensions={0}, metadata={op_name="jit(step)/sort" stack_frame_id=3}
  %all-gather.3 = f64[32]{0} all-gather(%sort.2), dimensions={0}, metadata={op_name="jit(step)/all_gather" stack_frame_id=2}
  ROOT %add.4 = f64[8]{0} add(%sort.2, %p), metadata={op_name="jit(step)/add" source_file="/ckout/src/repro/api/engine.py" source_line=5}
}
"""


def test_parse_hlo_attributes_fusions_to_their_root():
    got = tracing.parse_hlo(HLO)
    assert got["fusion.1"][:2] == ("fusion", "/ckout/src/repro/core/lp.py")
    assert got["sort.2"][:2] == ("sort", "/ckout/src/repro/core/mobility.py")
    assert got["all-gather.3"][0] == "all-gather"
    assert got["add.4"][1] == "/ckout/src/repro/api/engine.py"


def _op(name, start, dur, opcode, source, device=0):
    return tracing.Op(name=name, module="jit_step", device=device,
                      start=start, dur=dur, opcode=opcode, source=source)


@pytest.fixture
def ctx():
    lp = "/x/src/repro/core/lp.py"
    eng = "/x/src/repro/api/engine.py"
    ops = [
        # a while loop spanning two of its body's ops: busy once
        _op("while.1", 0.10, 0.40, "while", eng),
        _op("fusion.1", 0.10, 0.20, "fusion", lp),
        _op("fusion.2", 0.25, 0.25, "fusion", lp),      # overlaps fusion.1
        _op("all-gather.3", 0.60, 0.05, "all-gather", eng),
        _op("add.4", 0.70, 0.10, "add", eng),
        _op("custom.5", 0.80, 0.10, "custom-call", ""),   # unattributed
        _op("fusion.1", 0.10, 0.30, "fusion", lp, device=1),
    ]
    spans = [("dispatch", 0.0, 0.05), ("fetch", 0.05, 0.95),
             ("dispatch", 0.95, 1.0)]
    return tracing.Context(ops, spans, (0.0, 1.0), periods=2, n_chips=2)


def test_busy_is_the_union_of_overlapping_ops(ctx):
    # chip 0: [0.1, 0.5] + [0.6, 0.65] + [0.7, 0.9] = 0.65; chip 1: 0.3
    assert ctx.busy_s == pytest.approx((0.65 + 0.3) / 2)
    assert idle_share.read(ctx) == pytest.approx(1 - 0.475)


def test_module_time_counts_leaf_ops_per_period(ctx):
    lp = module_time.read(ctx, modules=["repro/core/lp.py"])
    assert lp == pytest.approx(1000 * (0.20 + 0.25 + 0.30) / 2 / 2)
    eng = module_time.read(ctx, modules=["repro/api/engine.py"])
    assert eng == pytest.approx(1000 * (0.05 + 0.10) / 2 / 2)
    assert module_time.read(ctx, modules=["repro/core/amr2.py"]) is None


def test_unattributed_bucket_and_host_span(ctx):
    metrics = [
        {"name": "lp_ms", "unit": "ms", "reader": "module_time",
         "args": {"modules": ["repro/core/lp.py"]}},
        {"name": "engine_ms", "unit": "ms", "reader": "module_time",
         "args": {"modules": ["repro/api/engine.py"]}},
        {"name": "nothing_ms", "unit": "ms", "reader": "module_time",
         "args": {"modules": ["repro/none.py"]}},
    ]
    out, note = tracing.per_layer(ctx, metrics)
    assert set(out) == {"lp_ms", "engine_ms"}       # nothing to read: left out
    leaf = 0.20 + 0.25 + 0.05 + 0.10 + 0.10 + 0.30
    assert f"unattributed_share={0.10 / leaf!r}" in note
    assert host_span.read(ctx, span="dispatch") == pytest.approx(
        1000 * 0.10 / 2)


def test_breakdown_names_idle_gaps_by_host_span(ctx):
    b = ctx.breakdown()
    assert b["device_ops"][0][0].startswith("fusion.1")
    gaps = dict((n, s) for n, s in b["idle_gaps"])
    assert "fetch" in gaps                  # 0.5-0.6 lies in the fetch span
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_moved_private_api_fails_loudly(monkeypatch):
    from jax._src.lib import _jax
    monkeypatch.delattr(_jax.HloModule, "from_serialized_hlo_module_proto")
    with pytest.raises(RuntimeError, match="cannot be read"):
        tracing.hlo_module_text(b"")
