"""One run of one cell: set-up, the measured window, the check, the metrics.

``run`` takes the cell, its configuration and the benchmark's metric list
as dicts, so the tests rehearse it on the CPU at a tiny size; ``run.py``
loads them by name and refuses to run without a TPU.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from dataclasses import dataclass, field

import numpy as np

from chipbench import check, generator, geometry, images, spec
from chipbench import trace as tracing

GRACE_S = 60.0  # how long past the window's close an answer may come
TRACE_DIR = spec.ROOT / ".chipbench_trace"


@dataclass
class Image:
    rid: int
    index: int  # into the pool
    due: float
    submit: float
    request: object = None
    done: float | None = None


@dataclass
class Step:
    t0: float
    t1: float
    tiles: list  # [(rid, core)]


@dataclass
class Window:
    t0: float = 0.0
    t1: float = 0.0
    images: dict = field(default_factory=dict)  # rid -> Image
    steps: list = field(default_factory=list)
    due: int = 0  # images due in the window, closed and open loop
    compiles: int = 0


def _span(name):
    import jax

    return jax.profiler.TraceAnnotation(tracing.SPAN_PREFIX + name)


class _CompileCounter:
    """Counts JAX compile events while ``on``."""

    def __init__(self):
        import jax

        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event.startswith("/jax/core/compile"):
            self.n += 1

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._event)


def record_steps(engine, win_ref: list, clock=time.perf_counter) -> None:
    """Wrap ``engine.step`` so every micro-batch is spanned and recorded
    (which tiles ran together, and when) in ``win_ref[0].steps``."""
    step = engine.step

    def recorded(*args, **kw):
        t0 = clock()
        with _span("step"):
            events = step(*args, **kw)
        if events:
            win_ref[0].steps.append(
                Step(t0, clock(), [(e.rid, tuple(e.core)) for e in events]))
        return events

    engine.step = recorded


def warm(engine, conf: dict, pool: list) -> int:
    """Serve the fewest pool images that hit every (window shape, class
    schedule) the pool's tiles hit; returns how many were served."""
    arch, base = spec.arch(conf), conf["plane_schedule"]
    need, hits = set(), []
    for img in pool:
        cv = arch.canvas(img, conf)
        amax = float(np.max(np.abs(cv)))
        keys = {(t.shape, geometry.class_planes(
                    base, geometry.budget_class(cv[t.y0:t.y1, t.x0:t.x1], amax)))
                for t in arch.plan(img.shape[0], img.shape[1], conf)}
        hits.append(keys)
        need |= keys
    chosen = []
    while need:
        i = max(range(len(pool)), key=lambda j: len(hits[j] & need))
        chosen.append(pool[i])
        need -= hits[i]
    engine.run(chosen)
    return len(chosen)


def drive(engine, pool, traffic: dict, seconds: float, seed: int, win_ref,
          *, clock=time.perf_counter, on_close=None) -> Window:
    """Serve the cell's traffic for ``seconds``, then drain what is in
    flight (at most ``GRACE_S`` more).  ``on_close`` runs as the window
    closes, before the drain."""
    win = win_ref[0]
    traffic = generator.checked(traffic)
    arrivals = generator.arrival_times(traffic, seconds, seed)
    win.due = len(arrivals)
    state = {"stream": None, "k": 0, "a": 0}

    def submit(due):
        img = pool[state["k"] % len(pool)]
        with _span("submit"):
            req = engine.submit(img)
        win.images[req.rid] = Image(req.rid, state["k"] % len(pool), due,
                                    clock(), req)
        state["k"] += 1

    def loop(stop_at, keep_queued) -> bool:
        """Serve until drained (True) or ``stop_at`` (False), topping the
        queue up to ``keep_queued`` waiting images."""
        while True:
            now = clock()
            if now >= stop_at:
                return False
            while len(engine.queue) < keep_queued:
                submit(now)
                win.due += 1
            while state["a"] < len(arrivals) and win.t0 + arrivals[state["a"]] <= now:
                submit(win.t0 + arrivals[state["a"]])
                state["a"] += 1
            if state["stream"] is None:
                state["stream"] = engine.serve_stream([])
            with _span("next"):
                ev = next(state["stream"], None)
            if ev is not None:
                if ev.done:
                    win.images[ev.rid].done = clock()
                continue
            state["stream"] = None
            if state["a"] < len(arrivals):
                with _span("wait"):
                    time.sleep(max(0.0, min(stop_at, win.t0 + arrivals[state["a"]])
                                   - clock()))
            elif not keep_queued:
                return True

    win.t0 = clock()
    with _span("window"):
        loop(win.t0 + seconds, traffic["keep_queued"])
        win.t1 = clock()
    if on_close is not None:
        on_close()
    loop(win.t1 + GRACE_S, 0)
    return win


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


@dataclass
class Context:
    """What a metric reader reads."""

    cell: str
    conf: dict
    seconds: float
    setup_s: float
    window: Window
    batch: int
    peaks: dict | None = None
    trace: tracing.Trace | None = None
    trace_summary: dict | None = None

    @property
    def window_s(self) -> float:
        return self.window.t1 - self.window.t0

    def completed_in_window(self) -> list[Image]:
        w = self.window
        return [im for im in w.images.values()
                if im.done is not None and w.t0 <= im.done <= w.t1]

    def window_steps(self) -> list[Step]:
        w = self.window
        return [s for s in self.window.steps if w.t0 <= s.t0 < w.t1]


def device_info() -> dict:
    import jax

    devs = jax.devices()
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peak = [p for p in peak if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peak) if peak else None}


@dataclass
class Served:
    """The system under test, set up for one seed."""

    params: object
    pool: list
    engine: object
    win_ref: list
    warm_images: int


def setup(conf: dict, seed: int) -> Served:
    """Weights, images, the engine and its warm-up."""
    import jax

    arch = spec.arch(conf)
    params = arch.make_params(conf["model"], seed)
    jax.block_until_ready(params)
    pool = images.pool(conf, seed)
    engine = arch.make_engine(conf, params)
    win_ref = [Window()]
    record_steps(engine, win_ref)
    n_warm = warm(engine, conf, pool)
    win_ref[0] = Window()
    return Served(params, pool, engine, win_ref, n_warm)


def verify(cell: dict, conf: dict, sv: Served, win: Window, seed: int, *,
           bits: tuple[int, ...] = (8,)) -> dict:
    """Served masks of a sample of the window's images against the
    reference; with ``bits=(8, 4)`` also the control (int4) against it.
    Frees the engine first."""
    served = {rid: im.request.result.logits for rid, im in win.images.items()
              if im.done is not None}
    image_of = {rid: sv.pool[im.index] for rid, im in win.images.items()}
    batch = sv.engine.batch
    for im in win.images.values():
        im.request = None
    sv.engine = None
    picked = check.sample(sorted(served), cell["check"]["images"], seed)
    refr = check.Reference(conf, sv.params, image_of, batch)
    ref8 = refr.canvases(win.steps, picked)
    out = {"picked": picked,
           "gaps": [refr.gap(r, served[r], ref8[r]) for r in picked]}
    if 4 in bits:
        ref4 = refr.canvases(win.steps, picked, bits=4)
        out["control_gaps"] = [
            refr.gap(r, ref4[r][:served[r].shape[0], :served[r].shape[1]],
                     ref8[r]) for r in picked]
    return out


def run(cell_name: str, cell: dict, conf: dict, metric_list: list[dict], *,
        seed: int, seconds: float, traced: bool, t_start: float) -> dict:
    """One run; returns the result line's object (checks last)."""
    log = sys.stderr
    import jax

    counter = _CompileCounter()
    try:
        sv = setup(conf, seed)
        kind = jax.devices()[0].device_kind
        trace_dir = None
        if traced:
            trace_dir = TRACE_DIR / cell_name
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # keep the harness's spans only
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        setup_s = time.perf_counter() - t_start
        counter.on = True

        def close():
            counter.on = False
            if traced:
                jax.profiler.stop_trace()

        win = drive(sv.engine, sv.pool, cell["traffic"], seconds, seed,
                    sv.win_ref, on_close=close)
        win.compiles = counter.n
    finally:
        counter.close()
    device = device_info()
    ctx = Context(cell_name, conf, seconds, setup_s, win, sv.engine.batch)
    if traced:
        ctx.peaks = spec.peaks(kind)
        xplanes = sorted(trace_dir.rglob("*.xplane.pb"))
        ctx.trace = tracing.from_xplane(xplanes[-1])
        ctx.trace_summary = tracing.summary(ctx.trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
    unfinished = win.due - sum(im.done is not None for im in win.images.values())
    late = [im.submit - im.due for im in win.images.values()]
    print(f"info warm_images={sv.warm_images} setup_s={setup_s!r} "
          f"images={len(win.images)} completed_in_window="
          f"{len(ctx.completed_in_window())} steps={len(win.steps)} "
          f"compiles_in_window={win.compiles} generator_late_ms_max="
          f"{max(late, default=0.0) * 1e3:.3f} generator_late_ms_p95="
          f"{percentile(late, 95) * 1e3 if late else 0.0:.3f}", file=log)
    metrics = {}
    for m in metric_list:
        v = spec.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    res = verify(cell, conf, sv, win, seed)
    gaps = res["gaps"]
    gap = max(gaps) if gaps else float("inf")
    limit = cell["check"]["logit_gap"]
    print(f"info images_checked={len(gaps)}", file=log)
    checks = {"logit_gap": {"value": gap, "limit": limit},
              "unfinished": {"value": unfinished, "limit": 0}}
    correct = gap <= limit and unfinished == 0
    if traced:
        device["busy_s"] = ctx.trace_summary["busy_s"]
        device["window_s"] = ctx.trace_summary["window_s"]
    out = {"correct": bool(correct), "attempted": win.due,
           "failed": unfinished + sum(g > limit for g in gaps),
           "metrics": metrics, "device": device}
    if traced:
        out["breakdown"] = {k: ctx.trace_summary[k]
                            for k in ("device_ops", "idle_gaps")}
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}", file=log)
    return out


def emit(result: dict) -> None:
    print(json.dumps(result), flush=True)
