"""Program spans and device scopes in a profiler trace: which host phase of
the segmentation server owns the device's idle time, and how long the
device spends in each named scope of the tile forward.

The server puts ``jax.profiler.TraceAnnotation`` spans named ``segserve.*``
around its own phases (``repro.segserve.engine``), and the tile forward
puts ``jax.named_scope`` paths on its ops (``conv03/im2col``,
``conv03/pack``, ``conv03/mma``, ``pool``, ``upsample``, ``head``).
:func:`from_xplane` reads what ``trace.from_xplane`` reads, the server's
spans besides the harness's, and each device op's scope path, into a
:class:`ProgramTrace`.  A TPU's op events carry no HLO metadata, so the
scope of an op comes from the compiled programs' HLO text
(``SegEngine.compiled_texts``), matched by the instruction's name and
result type.  The readers here return ``None`` on a trace that holds no
program spans or scopes, such as one that ``trace.from_xplane`` made.

Run as a script, it serves one cell's traffic for one traced window, as
``run.py --trace 1`` does, and prints the phase and scope readings, the
idle seconds per innermost span, and the engine's counters over the
window:

    python3 chipbench/phases.py --workload <cell> --seed <n> --seconds <s> \\
        [--slice-ms 40 --slice-out <file.json>]
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import bisect  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

if __name__ == "__main__":
    _ROOT = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from chipbench import trace as tracing  # noqa: E402

PROGRAM_PREFIX = "segserve."
OUTSIDE = "outside spans"
# the server's phases, each a span and its children (repro.segserve.engine)
PHASES = {
    "admit": ("segserve.admit", "segserve.plan", "segserve.canvas",
              "segserve.classify"),
    "launch": ("segserve.gather", "segserve.upload", "segserve.dispatch"),
    "collect": ("segserve.fetch", "segserve.stitch"),
}
# the tile forward's top-level scopes (repro.models.unet.forward)
FORWARD_SCOPE = re.compile(r"^(conv\d+|pool|upsample|head)$")
NEW_METRICS = ("idle_admit.backlog", "idle_launch.backlog",
               "idle_collect.backlog", "im2col_ms_per_image",
               "pack_ms_per_image")


@dataclass
class ProgramTrace(tracing.Trace):
    # per chip, parallel to ``ops``: each op's scope path, the HLO
    # ``op_name`` metadata without its last part (``jit(forward)/conv03/
    # im2col``), "" where the compiled programs give none
    scopes: dict[int, list[str]] = field(default_factory=dict)

    def to_json(self) -> dict:
        return {**super().to_json(),
                "scopes": {str(k): v for k, v in self.scopes.items()}}

    @classmethod
    def from_json(cls, d: dict) -> "ProgramTrace":
        tr = tracing.Trace.from_json(d)
        return cls(tr.ops, tr.spans, tr.window,
                   {int(k): list(v) for k, v in d.get("scopes", {}).items()})


# ------------------------------------------------- scopes from the HLO text

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def instruction_key(text: str) -> str | None:
    """``name type`` of an HLO instruction's text (``%pad.20 = s8[768,2048]
    {...} pad(...)`` -> ``pad.20 s8[768,2048]{...}``): a device op event's
    name is that text, so the key finds the op in the compiled program."""
    name, eq, rest = text.strip().removeprefix("ROOT ").partition(" = ")
    if not eq or not name.startswith("%"):
        return None
    return name[1:] + " " + rest.split(" ", 1)[0]


def _common(paths) -> str:
    """The longest run of leading parts that every path shares."""
    parts = [p.split("/") for p in paths]
    out = []
    for level in zip(*parts):
        if any(x != level[0] for x in level):
            break
        out.append(level[0])
    return "/".join(out)


def scope_table(texts) -> dict[str, str]:
    """Instruction key -> scope path, from compiled programs' HLO text.  A
    fusion without metadata takes what the instructions it calls share
    (constants aside); a key that several programs hold takes what their
    paths share."""
    found: dict[str, set[str]] = {}
    for text in texts:
        inside: dict[str, list[str]] = {}  # computation -> its op_names
        calls: dict[str, str] = {}  # key -> computation it calls
        current = None
        for line in text.splitlines():
            m = _COMPUTATION.match(line)
            if m:
                current = inside.setdefault(m.group(1), [])
                continue
            key = instruction_key(line)
            if key is None:
                continue
            name = _OP_NAME.search(line)
            if name:
                path = name.group(1).rsplit("/", 1)[0]
                found.setdefault(key, set()).add(path)
                # XLA shares one constant between scopes: it names neither
                if current is not None and " constant(" not in line:
                    current.append(path)
            elif _CALLS.search(line):
                calls[key] = _CALLS.search(line).group(1)
        for key, comp in calls.items():
            if inside.get(comp):
                found.setdefault(key, set()).add(_common(inside[comp]))
    return {k: _common(v) for k, v in found.items()}


def from_xplane(path: str | Path, texts=()) -> ProgramTrace:
    """What ``trace.from_xplane`` reads, the server's ``segserve.*`` spans,
    and each device op's scope path found in ``texts``, the HLO text of the
    programs the trace ran."""
    from jax.profiler import ProfileData

    table = scope_table(texts)
    data = ProfileData.from_file(str(path))
    tr = ProgramTrace()
    keep = (tracing.SPAN_PREFIX, PROGRAM_PREFIX)
    for plane in data.planes:
        m = tracing._DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == tracing._OPS_LINE:
                chip = int(m.group(1))
                for e in line.events:
                    tr.ops.setdefault(chip, []).append(
                        (tracing.op_name(e.name), e.start_ns, e.end_ns))
                    tr.scopes.setdefault(chip, []).append(
                        table.get(instruction_key(e.name) or "", ""))
            elif plane.name.startswith("/host:"):
                tr.spans.extend((e.name, e.start_ns, e.end_ns)
                                for e in line.events if e.name.startswith(keep))
    windows = [s for s in tr.spans if s[0] == tracing.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {tracing.WINDOW_SPAN} spans in {path}")
    tr.window = windows[0][1:]
    return tr


def sliced(tr: ProgramTrace, lo: float, hi: float) -> ProgramTrace:
    """The ops and spans of ``tr`` that overlap ``[lo, hi)``, with that as
    the window: a small recorded trace for the tests."""
    out = ProgramTrace(window=(lo, hi))
    for c, ops in tr.ops.items():
        scopes = tr.scopes.get(c, [""] * len(ops))
        keep = [i for i, (_, s, e) in enumerate(ops) if e > lo and s < hi]
        out.ops[c] = [ops[i] for i in keep]
        out.scopes[c] = [scopes[i] for i in keep]
    out.spans = [s for s in tr.spans
                 if s[2] > lo and s[1] < hi and s[0] != tracing.WINDOW_SPAN]
    return out


# ----------------------------------------------------------- idle by phase


def has_program_spans(tr) -> bool:
    return tr is not None and any(s[0].startswith(PROGRAM_PREFIX)
                                  for s in tr.spans)


def innermost(spans) -> list[tuple[float, float, str]]:
    """The host's innermost span over time, as disjoint ``(start, end,
    name)`` pieces in order, for spans that nest as ``with`` blocks on one
    thread do.  The harness's window span holds everything and is left
    out, as ``trace.host_activity`` leaves it out."""
    out, stack, t = [], [], None

    def upto(end):
        nonlocal t
        if stack and end > t:
            out.append((t, end, stack[-1][0]))
        t = end if t is None else max(t, end)

    for name, s, e in sorted((s for s in spans if s[0] != tracing.WINDOW_SPAN),
                             key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][2] <= s:
            upto(stack[-1][2])
            stack.pop()
        upto(s)
        stack.append((name, s, e))
    while stack:
        upto(stack[-1][2])
        stack.pop()
    return out


def idle_split(ops, spans, window) -> dict[str, float]:
    """Idle ns of the window per innermost host span: each gap is cut where
    the innermost span changes."""
    pieces = innermost(spans)
    out: dict[str, float] = {}
    k = 0
    for lo, hi in tracing.idle_gaps(ops, window):
        while k < len(pieces) and pieces[k][1] <= lo:
            k += 1
        t, j = lo, k
        while j < len(pieces) and pieces[j][0] < hi:
            s, e, name = pieces[j]
            if s > t:
                out[OUTSIDE] = out.get(OUTSIDE, 0.0) + s - t
            out[name] = out.get(name, 0.0) + min(e, hi) - max(s, t)
            t = min(e, hi)
            j += 1
        if hi > t:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + hi - t
    return out


def idle_by_midpoint(ops, spans, window) -> dict[str, float]:
    """``trace.idle_by_activity`` in one sweep: each whole gap goes to the
    innermost span at its middle."""
    pieces = innermost(spans)
    starts = [p[0] for p in pieces]
    out: dict[str, float] = {}
    for lo, hi in tracing.idle_gaps(ops, window):
        mid = 0.5 * (lo + hi)
        i = bisect.bisect_right(starts, mid) - 1
        name = pieces[i][2] if i >= 0 and mid < pieces[i][1] else OUTSIDE
        out[name] = out.get(name, 0.0) + hi - lo
    return out


def idle_seconds(tr, by=idle_split) -> dict[str, float]:
    """Idle seconds of the window per innermost span, averaged over chips."""
    chips = sorted(tr.ops)
    out: dict[str, float] = {}
    for c in chips:
        for k, v in by(tr.ops[c], tr.spans, tr.window).items():
            out[k] = out.get(k, 0.0) + v * 1e-9 / len(chips)
    return out


def idle_share(ctx, phase: str) -> float | None:
    """Share of the traced window in which the device idled while the
    host's innermost span was one of ``phase``'s spans."""
    tr = ctx.trace
    if not has_program_spans(tr) or not tr.ops or tr.window_ns <= 0:
        return None
    idle = idle_seconds(tr)
    return 100.0 * sum(idle.get(n, 0.0) for n in PHASES[phase]) / (
        tr.window_ns * 1e-9)


def self_seconds(tr, name: str) -> float:
    """Time of the window in which ``name`` was the innermost span."""
    lo, hi = tr.window
    return 1e-9 * sum(max(0.0, min(e, hi) - max(s, lo))
                      for s, e, n in innermost(tr.spans) if n == name)


# ------------------------------------------------------ device time by scope


def has_scopes(tr) -> bool:
    return tr is not None and any(any(v) for v in getattr(tr, "scopes", {}).values())


def scope_seconds(tr, pick) -> dict[str, float]:
    """Device seconds inside the window per ``pick(scope path)`` (ops for
    which it returns ``None`` are left out), averaged over chips."""
    lo, hi = tr.window
    chips = sorted(tr.ops)
    out: dict[str, float] = {}
    for c in chips:
        for (_, s, e), path in zip(tr.ops[c], tr.scopes.get(c, [])):
            key = pick(path)
            if key is not None and e > lo and s < hi:
                out[key] = out.get(key, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9 / len(chips)
    return out


def forward_scope(path: str) -> str:
    """The tile forward's top-level scope an op ran under (``conv03``,
    ``pool``, ...), or ``outside``."""
    return next((p for p in path.split("/") if FORWARD_SCOPE.match(p)), "outside")


def ms_per_image(ctx, scope: str) -> float | None:
    """Device ms of the ops under a ``scope`` part of their path, inside
    the window, per image completed in the window."""
    tr = ctx.trace
    if not has_scopes(tr):
        return None
    n = len(ctx.completed_in_window())
    if not n:
        return None
    t = scope_seconds(tr, lambda p: scope if scope in p.split("/") else None)
    return 1e3 * t.get(scope, 0.0) / n


# ------------------------------------------------------------------ a run


def measure(cell_name: str, cell: dict, conf: dict, *, seed: int,
            seconds: float, t_start: float) -> tuple[dict, ProgramTrace]:
    """One traced window of a cell (set-up and traffic as ``bench.run``
    has them), read with the program's spans and scopes."""
    import dataclasses
    import shutil

    import jax

    from chipbench import bench, spec

    sv = bench.setup(conf, seed)
    trace_dir = bench.TRACE_DIR / (cell_name + ".phases")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    counted = {"open": dataclasses.asdict(sv.engine.counters)}

    def close():
        counted["close"] = dataclasses.asdict(sv.engine.counters)
        jax.profiler.stop_trace()

    win = bench.drive(sv.engine, sv.pool, cell["traffic"], seconds, seed,
                      sv.win_ref, on_close=close)
    texts = list(sv.engine.compiled_texts().values())
    ctx = bench.Context(cell_name, conf, seconds, setup_s, win,
                        sv.engine.batch)
    dev = jax.devices()[0]
    ctx.peaks = spec.peaks(dev.device_kind) if dev.platform == "tpu" else None
    ctx.trace = tr = from_xplane(sorted(trace_dir.rglob("*.xplane.pb"))[-1],
                                 texts)
    shutil.rmtree(trace_dir, ignore_errors=True)
    harness = tracing.Trace(tr.ops, [s for s in tr.spans
                                     if not s[0].startswith(PROGRAM_PREFIX)],
                            tr.window)
    ctx.trace_summary = tracing.summary(harness)
    names = [m["name"] for m in spec.benchmark()["per_layer"]] + list(NEW_METRICS)
    by_scope = scope_seconds(tr, forward_scope)
    op_total = sum(by_scope.values())

    def ranked(d):
        return sorted(d.items(), key=lambda kv: -kv[1])

    out = {
        "cell": cell_name, "seed": seed, "device": dev.device_kind,
        "setup_s": setup_s,
        "images_per_s": len(ctx.completed_in_window()) / ctx.window_s,
        "busy_s": ctx.trace_summary["busy_s"],
        "window_s": ctx.trace_summary["window_s"],
        "metrics": {n: spec.reader(n)(ctx) for n in names},
        "idle_split": ranked(idle_seconds(tr)),
        "idle_midpoint": ranked(idle_seconds(tr, idle_by_midpoint)),
        "idle_harness_only": ctx.trace_summary["idle_gaps"],
        "self_s": {n: self_seconds(tr, n) for n in (
            "chipbench.step", "segserve.step", "segserve.admit")},
        "spans": {n: sum(1 for s in tr.spans if s[0] == n)
                  for n in sorted({s[0] for s in tr.spans})},
        "forward_scope_s": ranked(by_scope),
        "outside_forward_share": (by_scope.get("outside", 0.0) / op_total
                                  if op_total else None),
        "part_scope_s": ranked(scope_seconds(
            tr, lambda p: next((q for q in p.split("/") if q in (
                "im2col", "pack", "mma", "quant", "rescale")), None))),
        "counters": {k: counted["close"][k] - counted["open"][k]
                     for k in counted["open"]},
        "ops": sum(len(v) for v in tr.ops.values()),
        "ops_with_scope": sum(bool(p) for v in tr.scopes.values() for p in v),
    }
    return out, tr


def main(argv=None) -> int:
    import argparse
    import json
    import os

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--slice-ms", type=float, default=40.0)
    ap.add_argument("--slice-out", default=None)
    args = ap.parse_args(argv)

    from chipbench import spec

    cell = spec.cell(args.workload)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(spec.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out, tr = measure(args.workload, cell, spec.config(cell["config"]),
                      seed=args.seed, seconds=args.seconds, t_start=T_START)
    if args.slice_out:
        mid = 0.5 * (tr.window[0] + tr.window[1])
        Path(args.slice_out).write_text(json.dumps(
            sliced(tr, mid, mid + args.slice_ms * 1e6).to_json()))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
