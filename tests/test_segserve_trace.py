"""The segmentation server's own spans and counters.

The spans are ``jax.profiler.TraceAnnotation`` s on the profiler's host
plane; these tests serve tiny images under ``jax.profiler.trace`` on the
CPU and read the ``.xplane.pb`` back.  The counters are plain integers
checked against what the engine returned.
"""
import functools

import jax
import numpy as np
import pytest

from repro.models import unet
from repro.segserve import SegEngine

BATCH = 4
STEP_CHILDREN = ("gather", "upload", "dispatch", "fetch", "stitch")
ADMIT_CHILDREN = ("plan", "canvas", "classify")


@functools.lru_cache(maxsize=4)
def _net(quant_mode="none"):
    cfg = unet.UNetConfig(hw=16, in_ch=3, base=4, depth=2, convs_per_stage=1,
                          n_classes=3, quant_mode=quant_mode)
    return cfg, unet.init_params(jax.random.PRNGKey(0), cfg)


def _images():
    rng = np.random.default_rng(0)
    return [rng.normal(size=(21, 38, 3)).astype(np.float32),
            rng.normal(size=(16, 20, 3)).astype(np.float32)]


def _recorded(engine):
    """Wrap ``engine.step`` to keep the events of each step that ran."""
    steps = []
    step = engine.step

    def recorded(*args, **kw):
        events = step(*args, **kw)
        if events:
            steps.append(events)
        return events

    engine.step = recorded
    return steps


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two tiny images served under the profiler: the engine, and the
    ``segserve.*`` spans as ``(name, start_ns, end_ns, stats)``."""
    from jax.profiler import ProfileData

    cfg, params = _net()
    engine = SegEngine(cfg, params, tile=8, batch=BATCH, max_active=2)
    engine.run(_images()[:1])  # compile outside the trace
    out = tmp_path_factory.mktemp("xplane")
    with jax.profiler.trace(str(out)):
        engine.run(_images())
    path = sorted(out.rglob("*.xplane.pb"))[-1]
    spans = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("segserve.")]
    return engine, spans


def _named(spans, name):
    return [s for s in spans if s[0] == "segserve." + name]


def _inside(child, parents):
    return [p for p in parents if p[1] <= child[1] and child[2] <= p[2]]


def test_every_child_span_nests_inside_its_parent(traced):
    _, spans = traced
    for parent, children in (("step", STEP_CHILDREN), ("admit", ADMIT_CHILDREN)):
        outer = _named(spans, parent)
        assert outer
        for name in children:
            inner = _named(spans, name)
            assert len(inner) == len(outer)
            assert all(len(_inside(c, outer)) == 1 for c in inner), name


def test_step_children_cover_the_step(traced):
    _, spans = traced
    kids = [s for s in spans if s[0].split(".", 1)[1] in STEP_CHILDREN]
    for step in _named(spans, "step"):
        covered = sum(e - s for _, s, e, _ in kids
                      if step[1] <= s and e <= step[2])
        assert covered >= 0.9 * (step[2] - step[1])


def test_spans_carry_the_request_and_the_micro_batch(traced):
    engine, spans = traced
    rids = [s[3]["rid"] for s in _named(spans, "admit")]
    assert rids == [1, 2]  # rid 0 ran before the trace
    for name in ADMIT_CHILDREN:
        assert [s[3]["rid"] for s in _named(spans, name)] == rids
    for step in _named(spans, "step"):
        assert set(step[3]) == {"in_h", "in_w", "klass", "tiles"}
        assert 1 <= step[3]["tiles"] <= BATCH


def _window(ev):
    t = ev.request.plan.tiles[ev.tile]
    return t.in_h, t.in_w


def test_counters_match_what_the_engine_returned():
    cfg, params = _net()
    engine = SegEngine(cfg, params, tile=8, batch=BATCH, max_active=2)
    steps = _recorded(engine)
    engine.run(_images())
    c = engine.counters
    emitted = [ev for events in steps for ev in events]
    assert (c.admitted, c.completed) == (2, 2)
    assert (c.steps, c.tiles, c.host_syncs) == (len(steps), len(emitted), len(steps))
    assert c.tile_slots == c.steps * BATCH
    assert c.tiles / c.tile_slots == len(emitted) / (len(steps) * BATCH)
    # every tile of every plan ran once
    plans = {ev.rid: ev.request.plan for ev in emitted}
    assert c.window_pixels == sum(h * w for h, w in map(_window, emitted))
    assert c.window_pixels == sum(t.in_h * t.in_w for p in plans.values()
                                  for t in p.tiles)
    assert c.executables == len({(*_window(ev), ev.klass) for ev in emitted})
    pixels = sum(BATCH * h * w for h, w in (_window(ev[0]) for ev in steps))
    assert c.upload_bytes == pixels * cfg.in_ch * 4
    assert c.fetch_bytes == pixels * cfg.n_classes * 4


def test_the_engine_keeps_no_event_bus():
    cfg, params = _net()
    engine = SegEngine(cfg, params, tile=8)
    assert not hasattr(engine, "obs") and not hasattr(engine, "_obs_seq")


def test_compiled_texts_name_each_conv_and_its_parts():
    cfg, params = _net("mma_int8")
    engine = SegEngine(cfg, params, tile=8, batch=BATCH)
    steps = _recorded(engine)
    engine.run(_images()[1:])
    texts = engine.compiled_texts()
    assert set(texts) == {(*_window(ev), ev.klass)
                          for events in steps for ev in events}
    assert len(texts) == engine.counters.executables
    for text in texts.values():
        for scope in ("conv00/quant", "conv00/im2col", "conv00/rescale",
                      "conv01/im2col", "pool", "upsample", "head"):
            assert f"/{scope}" in text, scope
