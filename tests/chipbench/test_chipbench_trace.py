"""Trace reduction: idle share from the union of op intervals, kernel time
matched by name, idle gaps attributed to the host's spans; on hand-made
intervals and on a small trace recorded on a TPU v5e."""
import re
from pathlib import Path

import pytest

from chipbench import trace as tracing

FIXTURE = Path(__file__).with_name("trace_v5e_unet48_brats240.json")


def _hand_trace():
    ops = {0: [("fusion.1", 10.0, 30.0), ("mma_kernel", 20.0, 40.0),
               ("mma_kernel", 60.0, 70.0), ("copy", 95.0, 130.0)]}
    spans = [("chipbench.window", 0.0, 100.0), ("chipbench.next", 0.0, 80.0),
             ("chipbench.step", 5.0, 75.0), ("chipbench.wait", 80.0, 100.0)]
    return tracing.Trace(ops, spans, (0.0, 100.0))


def test_busy_time_is_the_union_of_overlapping_ops_inside_the_window():
    tr = _hand_trace()
    # [10, 40) + [60, 70) + [95, 100) clipped at the window's end
    assert tracing.busy_ns(tr.ops[0], tr.window) == 30.0 + 10.0 + 5.0
    assert tracing.idle_gaps(tr.ops[0], tr.window) == [
        (0.0, 10.0), (40.0, 60.0), (70.0, 95.0)]


def test_kernel_time_is_matched_by_name():
    tr = _hand_trace()
    assert tracing.op_time(tr.ops[0], tr.window, r"^mma") == {"mma_kernel": 30.0}
    assert sum(tracing.op_time(tr.ops[0], tr.window).values()) == 20 + 30 + 5


def test_each_gap_goes_to_the_innermost_span_at_its_middle():
    tr = _hand_trace()
    got = tracing.idle_by_activity(tr.ops[0], tr.spans, tr.window)
    # (0, 10): middle 5 is in step; (40, 60): step; (70, 95): middle 82.5 in wait
    assert got == {"chipbench.step": 30.0, "chipbench.wait": 25.0}


def test_summary_averages_busy_time_over_chips():
    tr = _hand_trace()
    tr.ops[1] = [("mma_kernel", 0.0, 100.0)]
    s = tracing.summary(tr)
    assert s["busy_s"] == pytest.approx((45.0 + 100.0) / 2 * 1e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["device_ops"][0][0] == "mma_kernel"


def test_json_round_trip():
    tr = _hand_trace()
    back = tracing.Trace.from_json(tr.to_json())
    assert back.ops == tr.ops and back.spans == tr.spans and back.window == tr.window


def _union_by_sweep(intervals, lo, hi):
    """Busy time by an event sweep, independent of ``busy_ns``."""
    events = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            events += [(s, 1), (e, -1)]
    events.sort(key=lambda ev: (ev[0], -ev[1]))
    depth, last, busy = 0, None, 0.0
    for t, d in events:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_trace_idle_share_is_one_minus_the_union_of_ops():
    tr = tracing.load(FIXTURE)
    ops = tr.ops[0]
    busy = tracing.busy_ns(ops, tr.window)
    assert busy == pytest.approx(_union_by_sweep([(s, e) for _, s, e in ops], *tr.window))
    # the "XLA Ops" line of one core holds no overlapping ops
    assert busy == sum(min(e, tr.window[1]) - max(s, tr.window[0]) for _, s, e in ops
                      if e > tr.window[0] and s < tr.window[1])
    gaps = tracing.idle_gaps(ops, tr.window)
    assert sum(e - s for s, e in gaps) == pytest.approx(tr.window_ns - busy)
    assert 0.0 < 1.0 - busy / tr.window_ns < 1.0


def test_recorded_trace_kernel_time_is_matched_by_name():
    from chipbench import spec

    tr = tracing.load(FIXTURE)
    ops = tr.ops[0]
    pattern = spec.HERE.joinpath("metrics", "mma_roofline.py").read_text()
    kernel = re.search(r'KERNEL = r"(.*)"', pattern).group(1)
    got = tracing.op_time(ops, tr.window, kernel)
    assert got and all(n.startswith("mma_matmul_pallas_p") for n in got)
    want = sum(min(e, tr.window[1]) - max(s, tr.window[0]) for n, s, e in ops
               if n.startswith("mma_matmul_pallas_p") and e > tr.window[0]
               and s < tr.window[1])
    assert sum(got.values()) == pytest.approx(want)
    # the kernel's output feeding a copy does not count as kernel time
    assert not any(re.search(kernel, n) for n, _, _ in ops
                   if not n.startswith("mma_matmul_pallas_p"))


def test_recorded_trace_gaps_go_to_the_harness_spans():
    tr = tracing.load(FIXTURE)
    by = tracing.idle_by_activity(tr.ops[0], tr.spans, tr.window)
    busy = tracing.busy_ns(tr.ops[0], tr.window)
    assert sum(by.values()) == pytest.approx(tr.window_ns - busy)
    assert set(by) <= {"chipbench.step", "chipbench.next", "chipbench.submit",
                       "chipbench.wait", "outside spans"}
    assert by["chipbench.step"] > 0
    s = tracing.summary(tr)
    assert len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10
    assert s["device_ops"][0][0] in ("mma_matmul_pallas_p5", "concatenate")
