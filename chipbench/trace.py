"""Reduction of a profiler trace to device busy time, kernel time and the
host activity behind each idle gap.

``from_xplane`` reads the ``.xplane.pb`` the JAX profiler writes into a
plain :class:`Trace` (device ops per chip, host spans of the harness, and
the measured window); everything else works on that, so a small recorded
trace in JSON tests the same arithmetic the chip runs use.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from pathlib import Path

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"\.\d+$")


def op_name(hlo: str) -> str:
    """An op's own name from its event name, which is the HLO text of the
    instruction (``%mma_matmul_pallas_p5.13 = s32[...] custom-call(...)``)."""
    return hlo.split(" = ", 1)[0].lstrip("%") if " = " in hlo else hlo


def op_kind(name: str) -> str:
    """An op's name without its numeric suffix: ``concatenate.19`` ->
    ``concatenate``."""
    return _SUFFIX.sub("", name)


@dataclass
class Trace:
    # per chip: [(op name, start ns, end ns)], one clock with the host
    ops: dict[int, list[tuple[str, float, float]]] = field(default_factory=dict)
    spans: list[tuple[str, float, float]] = field(default_factory=list)
    window: tuple[float, float] = (0.0, 0.0)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "spans": self.spans, "window": list(self.window)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({int(k): [tuple(e) for e in v] for k, v in d["ops"].items()},
                   [tuple(s) for s in d["spans"]], tuple(d["window"]))


def from_xplane(path: str | Path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == _OPS_LINE:
                tr.ops.setdefault(int(m.group(1)), []).extend(
                    (op_name(e.name), e.start_ns, e.end_ns) for e in line.events)
            elif plane.name.startswith("/host:"):
                tr.spans.extend((e.name, e.start_ns, e.end_ns)
                                for e in line.events
                                if e.name.startswith(SPAN_PREFIX))
    windows = [s for s in tr.spans if s[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW_SPAN} spans in {path}")
    tr.window = windows[0][1:]
    return tr


def _clipped(ops, window):
    lo, hi = window
    return sorted((max(s, lo), min(e, hi)) for _, s, e in ops if e > lo and s < hi)


def busy_ns(ops, window) -> float:
    """Length of the union of the op intervals inside the window."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in _clipped(ops, window):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(ops, window) -> list[tuple[float, float]]:
    """Intervals of the window in which no op ran."""
    out, t = [], window[0]
    for s, e in _clipped(ops, window):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def host_activity(gap, spans) -> str:
    """What the host was doing in a gap: the innermost harness span that
    holds the gap's midpoint, or ``outside spans``."""
    mid = 0.5 * (gap[0] + gap[1])
    holding = [(e - s, name) for name, s, e in spans
               if s <= mid < e and name != WINDOW_SPAN]
    return min(holding)[1] if holding else "outside spans"


def idle_by_activity(ops, spans, window) -> dict[str, float]:
    out: dict[str, float] = {}
    for g in idle_gaps(ops, window):
        name = host_activity(g, spans)
        out[name] = out.get(name, 0.0) + g[1] - g[0]
    return out


def op_time(ops, window, pattern: str | None = None) -> dict[str, float]:
    """Device ns per op name inside the window, for names matching
    ``pattern`` (a regular expression) or all."""
    rx = re.compile(pattern) if pattern else None
    out: dict[str, float] = {}
    lo, hi = window
    for name, s, e in ops:
        if e > lo and s < hi and (rx is None or rx.search(name)):
            out[name] = out.get(name, 0.0) + min(e, hi) - max(s, lo)
    return out


def summary(tr: Trace, top: int = 10) -> dict:
    """Busy seconds averaged over chips, the window's length, and the
    ``breakdown`` of the result line: device seconds per op kind, and idle
    seconds per host activity (averaged over chips)."""
    chips = sorted(tr.ops)
    busy = [busy_ns(tr.ops[c], tr.window) for c in chips]
    ops = [op for c in chips for op in tr.ops[c]]
    idle: dict[str, float] = {}
    for c in chips:
        for k, v in idle_by_activity(tr.ops[c], tr.spans, tr.window).items():
            idle[k] = idle.get(k, 0.0) + v / len(chips)
    by_op: dict[str, float] = {}
    for name, t in op_time(ops, tr.window).items():
        by_op[op_kind(name)] = by_op.get(op_kind(name), 0.0) + t / len(chips)
    return {
        "busy_s": sum(busy) / len(busy) * 1e-9 if busy else 0.0,
        "window_s": tr.window_ns * 1e-9,
        "device_ops": [[n, t * 1e-9] for n, t in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, t * 1e-9] for n, t in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
    }


def save(tr: Trace, path: Path) -> None:
    path.write_text(json.dumps(tr.to_json()))


def load(path: Path) -> Trace:
    return Trace.from_json(json.loads(path.read_text()))
