"""The segmentation engine's one-ahead micro-batch pipeline.

An unscoped ``SegEngine.step`` dispatches the next micro-batch before it
blocks on the oldest one still on the device; a scoped ``step(group)``
stays synchronous.  Requests submitted without a group form the group
``None``, so ``step(None)`` steps every tile synchronously: it is the
reference order these tests compare the pipelined order with.  All on the
CPU at tiny sizes.
"""
import functools

import jax
import numpy as np
import pytest

from repro.models import unet
from repro.segserve import SegEngine

BATCH = 4


@functools.lru_cache(maxsize=4)
def _net(quant_mode):
    cfg = unet.UNetConfig(hw=16, in_ch=3, base=4, depth=2, convs_per_stage=1,
                          n_classes=3, quant_mode=quant_mode)
    return cfg, unet.init_params(jax.random.PRNGKey(0), cfg)


def _images(n=3):
    rng = np.random.default_rng(0)
    shapes = [(21, 38), (16, 20), (30, 17), (24, 24), (9, 33)]
    return [rng.normal(size=(*shapes[i % 5], 3)).astype(np.float32)
            for i in range(n)]


def _ramped(n=3):
    """Images whose amplitude ramps across the width: a group holds many
    tiles of unequal amplitude, so a batch's shared scale depends on which
    of them it packs."""
    rng = np.random.default_rng(1)
    ramp = np.linspace(0.5, 1.0, 40, dtype=np.float32)[None, :, None]
    return [(rng.normal(size=(40, 40, 3)) * ramp).astype(np.float32)
            for _ in range(n)]


def _engine(path):
    """An engine on one of the datapaths whose numerics do not depend on
    the order micro-batches run in, with images for it."""
    if path == "plan":
        from test_gateway import _plan_for, _small_unet

        cfg, params = _small_unet()
        imgs = [np.linspace(a, b, 32 * 32 * 2, dtype=np.float32)
                .reshape(32, 32, 2) for a, b in ((0, 1), (1, -1), (-2, 3))]
        return SegEngine(cfg, params, plan=_plan_for(params, stale=False),
                         batch=2), imgs
    cfg, params = _net("none" if path == "float" else "mma_int8")
    return SegEngine(cfg, params, tile=8, batch=BATCH, max_active=4), _ramped()


def _admitted(eng, images, groups=None):
    reqs = [eng.submit(im, group=None if groups is None else groups[i])
            for i, im in enumerate(images)]
    eng.queue.pump(eng.slots, eng._admit)
    return reqs


def _window(ev):
    t = ev.request.plan.tiles[ev.tile]
    return t.in_h, t.in_w, ev.klass


@pytest.mark.parametrize("path", ["float", "plan", "shared_scale"])
def test_pipelined_logits_bit_identical_to_synchronous(path):
    """Requests at most ``max_active``: the pipelined ``run`` packs every
    micro-batch as the synchronous order does, so the stitch is
    bit-identical on the float datapath, under a tuned plan (per-tile
    scales) and on the batch-shared-scale path alike."""
    eng, imgs = _engine(path)
    sync = _admitted(eng, imgs)
    while eng.has_work():
        eng.step(None)
    assert eng.counters.launched_ahead == 0
    piped, _ = _engine(path)
    got = piped.run(imgs)
    assert piped.counters.launched_ahead > 0
    assert piped.counters.steps == eng.counters.steps
    for r, res in zip(sync, got):
        assert np.array_equal(r.result.logits, res.logits)
        assert r.result.cycles == res.cycles


def test_each_unscoped_step_emits_one_micro_batch_while_work_remains():
    """More images than slots: every unscoped call returns the events of
    exactly one micro-batch, is non-empty while any tile is queued or in
    flight, and ``pending`` counts the tiles in flight."""
    cfg, params = _net("mma_int8")
    eng = SegEngine(cfg, params, tile=8, batch=BATCH, max_active=2)
    imgs = _images(5)
    for im in imgs:
        eng.submit(im)
    batches = []
    while eng.queue or eng.has_work():
        eng.queue.pump(eng.slots, eng._admit)
        before = eng.pending()
        events = eng.step()
        assert events
        assert len(events) <= BATCH
        assert len({_window(ev) for ev in events}) == 1
        assert eng.pending() == before - len(events)
        batches.append(events)
    assert eng.step() == []
    assert eng.counters.completed == len(imgs)
    assert len(batches) == eng.counters.steps


@pytest.mark.parametrize("scoped", [False, True])
def test_next_cost_prices_what_the_next_step_emits(scoped):
    """``next_cost(g)`` equals the cycles of the events ``step(g)`` then
    returns.  A scoped call after unscoped ones also emits, and prices,
    the micro-batch they left in flight."""
    cfg, params = _net("mma_int8")
    eng = SegEngine(cfg, params, tile=8, batch=BATCH, max_active=4)
    _admitted(eng, _images(4), groups=["a", "b", "a", "b"])
    calls = 0
    while eng.has_work():
        group = ("a", "b")[calls % 2] if scoped and calls % 3 else ...
        cost = eng.next_cost(group)
        events = eng.step(group)
        assert sum(ev.cycles for ev in events) == cost
        calls += 1
    assert eng.next_cost() == 0 and eng.next_cost("a") == 0
    assert eng.counters.completed == 4


def test_scoped_step_drains_what_unscoped_steps_left_in_flight():
    cfg, params = _net("mma_int8")
    eng = SegEngine(cfg, params, tile=8, batch=BATCH, max_active=4)
    _admitted(eng, _images(2), groups=["a", "b"])
    first = eng.step()  # launches two, collects one
    ahead = eng.pending() - sum(len(g) for g in eng._tasks.values())
    assert first and ahead > 0
    events = eng.step("b")
    assert len(events) > ahead
    # the in-flight micro-batch comes out first, then one of group b
    assert len({_window(ev) for ev in events[ahead:]}) == 1
    assert {ev.request.group for ev in events[ahead:]} == {"b"}
    assert eng.pending() == sum(len(g) for g in eng._tasks.values())


@pytest.mark.parametrize("surface", ["run", "flush", "serve_stream"])
def test_serving_surfaces_end_with_nothing_in_flight(surface):
    cfg, params = _net("none")
    eng = SegEngine(cfg, params, tile=8, batch=BATCH, max_active=2)
    imgs = _images(4)
    if surface == "run":
        eng.run(imgs)
    elif surface == "flush":
        for im in imgs:
            eng.submit(im)
        eng.flush()
    else:
        assert list(eng.serve_stream(imgs))
    assert not eng.has_work() and eng.pending() == 0
    assert eng.next_cost() == 0
    assert eng.counters.completed == len(imgs)


def test_counters_count_the_launches_made_ahead():
    """A multi-batch run syncs once per collected micro-batch, and every
    launch but the first after a drain goes out while another micro-batch
    is in flight."""
    cfg, params = _net("none")
    eng = SegEngine(cfg, params, tile=8, batch=BATCH, max_active=2)
    eng.run(_images(4))
    c = eng.counters
    assert c.steps > 2
    assert c.host_syncs == c.steps
    assert 0 < c.launched_ahead <= c.steps
