"""The reduction of the program's spans and scopes (``chipbench/phases.py``)
on hand-made traces, on the recorded trace of the harness's spans alone,
and on a recorded TPU v5e slice that holds the program's spans and scopes;
and one traced window of a tiny cell on the CPU."""
import json
import time
from pathlib import Path
from types import SimpleNamespace

import pytest
from conftest import CELLS, tiny_cell

from chipbench import phases, readers, spec
from chipbench import trace as tracing

HERE = Path(__file__).resolve().parent
OLD = HERE / "trace_v5e_unet48_brats240.json"
SPANS = HERE / "trace_v5e_unet48_brats240_spans.json"
MS = 1e6  # ns


def _hand_trace():
    """One admission and one step in a 1000 ms window: idle 0 to 380 ms
    (admission, then the launch) and 780 to 1000 ms (the collection, then
    nothing); the device runs an im2col, the kernel and a pack."""
    s = [("chipbench.window", 0, 1000), ("chipbench.next", 0, 900),
         ("segserve.admit", 0, 200), ("segserve.plan", 0, 50),
         ("segserve.canvas", 50, 100), ("segserve.classify", 100, 180),
         ("chipbench.step", 200, 900), ("segserve.step", 210, 890),
         ("segserve.gather", 210, 300), ("segserve.upload", 300, 350),
         ("segserve.dispatch", 350, 400), ("segserve.fetch", 400, 800),
         ("segserve.stitch", 800, 880)]
    ops = [("concatenate.1", 380, 420), ("mma_matmul_pallas_p5.2", 420, 700),
           ("pad.3", 700, 760), ("copy-done", 760, 780)]
    scopes = ["jit(forward)/conv00/im2col", "jit(forward)/conv00/mma/jit(k)",
              "jit(forward)/conv00/pack/jit(_pad)", ""]
    return phases.ProgramTrace(
        {0: [(n, a * MS, b * MS) for n, a, b in ops]},
        [(n, a * MS, b * MS) for n, a, b in s], (0.0, 1000 * MS),
        {0: scopes})


def _ctx(trace, images=2):
    return SimpleNamespace(trace=trace,
                           completed_in_window=lambda: list(range(images)))


def _read(name, ctx):
    return spec.reader(name)(ctx)


def test_idle_goes_to_the_innermost_span_piece_by_piece():
    tr = _hand_trace()
    got = {k: round(v * 1e3, 6) for k, v in phases.idle_seconds(tr).items()}
    assert got == {"segserve.plan": 50, "segserve.canvas": 50,
                   "segserve.classify": 80, "segserve.admit": 20,
                   "chipbench.step": 20, "segserve.gather": 90,
                   "segserve.upload": 50, "segserve.dispatch": 30,
                   "segserve.fetch": 20, "segserve.stitch": 80,
                   "segserve.step": 10, "outside spans": 100}
    assert phases.self_seconds(tr, "segserve.step") == pytest.approx(0.010)


def test_phase_readers_on_a_hand_made_trace():
    ctx = _ctx(_hand_trace())
    assert _read("idle_admit.backlog", ctx) == pytest.approx(20.0)
    assert _read("idle_launch.backlog", ctx) == pytest.approx(17.0)
    assert _read("idle_collect.backlog", ctx) == pytest.approx(10.0)
    assert _read("im2col_ms_per_image", ctx) == pytest.approx(20.0)
    assert _read("pack_ms_per_image", ctx) == pytest.approx(30.0)
    assert _read("pack_ms_per_image", _ctx(_hand_trace(), 0)) is None


def test_the_midpoint_sweep_is_the_harness_attribution():
    tr = _hand_trace()
    for chip, ops in tr.ops.items():
        assert phases.idle_by_midpoint(ops, tr.spans, tr.window) == \
            tracing.idle_by_activity(ops, tr.spans, tr.window)


@pytest.mark.parametrize("path", [OLD, SPANS], ids=["harness", "program"])
def test_the_midpoint_sweep_matches_on_recorded_traces(path):
    tr = phases.ProgramTrace.from_json(json.loads(path.read_text()))
    a = phases.idle_by_midpoint(tr.ops[0], tr.spans, tr.window)
    b = tracing.idle_by_activity(tr.ops[0], tr.spans, tr.window)
    assert a.keys() == b.keys()
    assert all(a[k] == pytest.approx(b[k]) for k in a)


@pytest.mark.parametrize("name", phases.NEW_METRICS)
def test_new_readers_find_nothing_in_the_old_recording(name):
    assert _read(name, _ctx(tracing.load(OLD))) is None
    assert _read(name, _ctx(phases.ProgramTrace.from_json(
        json.loads(OLD.read_text())))) is None


def test_the_old_recording_reads_the_same_through_either_class():
    old = tracing.load(OLD)
    new = phases.ProgramTrace.from_json(json.loads(OLD.read_text()))
    assert (new.ops, new.spans, new.window) == (old.ops, old.spans, old.window)
    assert tracing.summary(new) == tracing.summary(old)
    ctx = SimpleNamespace(trace_summary=tracing.summary(new))
    assert readers.idle_share(ctx) == readers.idle_share(
        SimpleNamespace(trace_summary=tracing.summary(old)))


def test_program_trace_json_round_trip():
    tr = _hand_trace()
    back = phases.ProgramTrace.from_json(json.loads(json.dumps(tr.to_json())))
    assert (back.ops, back.spans, back.window, back.scopes) == (
        tr.ops, tr.spans, tr.window, tr.scopes)


def test_recorded_slice_phases_and_remainder_sum_to_the_idle_share():
    tr = phases.ProgramTrace.from_json(json.loads(SPANS.read_text()))
    ctx = _ctx(tr, images=1)
    ctx.trace_summary = tracing.summary(tr)
    parts = [_read(f"idle_{p}.backlog", ctx) for p in phases.PHASES]
    assert all(p > 0 for p in parts)
    owned = {n for names in phases.PHASES.values() for n in names}
    rest = sum(v for k, v in phases.idle_seconds(tr).items() if k not in owned)
    total = sum(parts) + 100.0 * rest / (tr.window_ns * 1e-9)
    assert total == pytest.approx(readers.idle_share(ctx), abs=0.1)


def test_recorded_slice_ops_carry_the_forward_scopes():
    tr = phases.ProgramTrace.from_json(json.loads(SPANS.read_text()))
    by = phases.scope_seconds(tr, phases.forward_scope)
    assert {"conv00", "pool", "upsample", "head"} <= set(by)
    assert by.get("outside", 0.0) <= 0.05 * sum(by.values())
    ctx = _ctx(tr, images=1)
    assert _read("im2col_ms_per_image", ctx) > 0
    assert _read("pack_ms_per_image", ctx) > 0
    kernel = phases.scope_seconds(tr, lambda p: "mma" if "mma" in p.split("/") else None)
    assert kernel["mma"] == pytest.approx(readers.kernel_seconds(
        SimpleNamespace(trace=tr), r"^mma_matmul(_scaled)?_pallas_p\d+u?(\.\d+)?$"))


_HLO = """\
%fused_computation.7 (param_0.1: s8[4,64,64,48]) -> s8[4,64,64,432] {
  %constant.1 = s8[] constant(0), metadata={op_name="jit(forward)/conv00/im2col/jit(_pad)/convert_element_type"}
  %pad.9 = s8[4,66,66,48]{1,3,2,0} pad(%param_0.1, %constant.1), padding=0_0x1_1x1_1x0_0, metadata={op_name="jit(forward)/conv01/im2col/jit(_pad)/pad"}
  ROOT %slice.4 = s8[4,64,64,48]{1,3,2,0} slice(%pad.9), slice={[0:4], [0:64], [0:64], [0:48]}, metadata={op_name="jit(forward)/conv01/im2col/slice"}
}

ENTRY %main.2 (x: f32[4,64,64,48]) -> s8[4,64,64,432] {
  %pad.20 = s8[768,2048]{1,0:T(8,128)(4,1)S(1)} pad(%reshape.34, %constant.41), padding=0_92x0_320, metadata={op_name="jit(forward)/conv03/pack/jit(_pad)/pad"}
  ROOT %slice_dynamic-update-slice_fusion.7 = s8[4,64,64,432]{1,3,2,0} fusion(%clamp_convert_fusion.3), kind=kLoop, calls=%fused_computation.7
  %copy-start = (f32[1,1,48,4]{2,3,1,0}, u32[]) copy-start(%params__head____w__.1)
}
"""


def test_scopes_come_from_the_compiled_text():
    table = phases.scope_table([_HLO])
    event = ("%pad.20 = s8[768,2048]{1,0:T(8,128)(4,1)S(1)} pad(s8[676,1728]"
             "{1,0:T(8,128)(4,1)S(1)} %reshape.34, s8[]{:T(512)} %constant.41)")
    assert table[phases.instruction_key(event)] == "jit(forward)/conv03/pack/jit(_pad)"
    # a fusion without metadata: what its instructions share, constants aside
    key = phases.instruction_key(
        "%slice_dynamic-update-slice_fusion.7 = s8[4,64,64,432]{1,3,2,0} "
        "fusion(s8[4,64,64,48]{1,3,2,0} %clamp_convert_fusion.3)")
    assert table[key] == "jit(forward)/conv01/im2col"
    assert phases.instruction_key("%copy-start = (f32[1,1,48,4]{2,3,1,0}, "
                                  "u32[]) copy-start(...)") not in table
    # two programs that disagree keep what they share
    other = _HLO.replace("conv03/pack", "conv04/pack")
    assert phases.scope_table([_HLO, other])[phases.instruction_key(event)] == \
        "jit(forward)"


def test_a_traced_tiny_window_on_the_cpu():
    """The whole measurement path on the CPU: the spans reach the trace,
    one per counted step and admission, and the device metrics stay
    silent, since the CPU trace has no device plane."""
    name = CELLS[0]
    cell, conf = tiny_cell(name)
    out, tr = phases.measure(name, cell, conf, seed=2**40 + 13, seconds=1.0,
                             t_start=time.perf_counter())
    c = out["counters"]
    assert c["steps"] > 0 and c["host_syncs"] == c["steps"]
    assert out["spans"]["segserve.step"] == c["steps"]
    assert out["spans"]["segserve.admit"] == c["admitted"]
    for metric in phases.NEW_METRICS:
        assert out["metrics"][metric] is None
