"""The unified serving gateway: admission policies over a shared modeled
cycle budget (pure scheduling — synthetic adapters, no model in the loop),
plan invalidation at admission, and the progressive structure-first tile
stream (real SegEngine)."""
import functools

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.serve.gateway import (
    Gateway,
    GatewayRequest,
    StalePlanError,
)


# ------------------------------------------------------ synthetic adapter


class FakeAdapter:
    """A pure cycle-accounting engine: each request is ``cost`` modeled
    cycles of divisible work, served oldest-admitted-first in ``unit``-cycle
    micro-steps — the gateway protocol with the model taken out, so policy
    properties sweep traffic shapes at zero compute.

    ``preemptive=True`` (default): a micro-step that would exceed the
    offered budget is not started (unless ``force``), matching the real
    adapters' chunked path.  ``preemptive=False`` reproduces the PR 4
    atomic loop (runs while ``consumed < budget``, overshooting by up to
    ``unit - 1``)."""

    obs_enabled = False  # armed by Gateway.set_sink, like real adapters
    obs_sink = None

    def __init__(self, kind, *, slots=2, unit=1_000, preemptive=True):
        self.kind = kind
        self.slots = slots
        self.unit = unit
        self.preemptive = preemptive
        self._inflight = {}
        self._remaining = {}
        self.total_ops = 0
        self.fallback_reason = None
        self.work_calls = []  # (budget, consumed, forced) audit trail
        self.exec_log = []  # (rid, qos, cycles, offset) attribution

    def prepare(self, payload, *, rid):
        return int(payload)  # payload is the request's cycle cost

    def free_slots(self):
        return self.slots - len(self._inflight)

    def estimate_cycles(self, payload):
        return int(payload)

    def verify_info(self):
        return None

    def admit(self, greq):
        assert self.free_slots() > 0
        greq.handle = greq
        self._inflight[greq.rid] = greq
        self._remaining[greq.rid] = greq.payload
        return 0

    def has_work(self, qos=None):
        return any(
            qos is None or self._inflight[rid].qos == qos
            for rid in self._remaining
        )

    def work(self, budget, qos=None, force=False, soft_limit=None):
        consumed = 0
        completed = []
        forced = False
        while True:
            rids = [
                rid for rid in self._remaining
                if qos is None or self._inflight[rid].qos == qos
            ]
            if not rids:
                break
            rid = rids[0]
            chunk = min(self.unit, self._remaining[rid])
            if self.preemptive:
                at_soft = soft_limit is not None and consumed >= soft_limit
                if consumed + chunk > budget or at_soft:
                    if not (force and consumed == 0):
                        break
                    forced = True
            elif consumed >= budget:
                break
            force = False
            self._remaining[rid] -= chunk
            consumed += chunk
            self.total_ops += chunk  # 1 op/cycle: GOPS plumbing stays live
            if self.obs_enabled:
                self.exec_log.append(
                    (rid, self._inflight[rid].qos, chunk, consumed)
                )
            if self._remaining[rid] == 0:
                del self._remaining[rid]
                # protocol v3: completion at its own micro-step's offset
                completed.append((self._inflight.pop(rid), consumed))
        self.work_calls.append((budget, consumed, forced))
        return consumed, completed, []


def drain_stats(gw, max_rounds=10_000):
    gw.drain(max_rounds=max_rounds)
    return gw.stats()


# ------------------------------------------------------------- policies


def test_policy_validation():
    with pytest.raises(ValueError):
        Gateway([FakeAdapter("a")], policy="lifo")
    with pytest.raises(ValueError):
        Gateway([], policy="fifo")
    with pytest.raises(ValueError):
        Gateway([FakeAdapter("a")], round_budget=0)
    with pytest.raises(ValueError):
        Gateway([FakeAdapter("a")], on_stale="ignore")
    with pytest.raises(ValueError):
        Gateway(
            [FakeAdapter("a"), FakeAdapter("b")],
            shares={"a": 0.9, "b": 0.9},
        )
    # a silently share-less class would be starvable: submission rejects
    # any scheduling class (kind default or QoS label) not declared in
    # shares — loudly, at the front door
    gw = Gateway([FakeAdapter("a"), FakeAdapter("b")], shares={"a": 1.0})
    with pytest.raises(ValueError, match="undeclared"):
        gw.submit("b", 100)  # kind 'b' unlabeled -> class 'b': undeclared
    with pytest.raises(ValueError, match="undeclared"):
        gw.submit("a", 100, qos="gold")
    gw = Gateway([FakeAdapter("a")], policy="fair_share")  # alias
    assert gw.policy == "fair"
    with pytest.raises(ValueError):
        gw.submit("zzz", 100)


def test_fifo_head_of_line_blocks_minority():
    """The failure mode the gateway exists to fix: under strict FIFO a
    majority burst saturating its engine blocks the queue head, so the
    minority class behind it waits even though *its* engine sits idle.
    Fair-share admits it immediately."""

    def trace(policy):
        a, b = FakeAdapter("a", slots=1), FakeAdapter("b", slots=1)
        gw = Gateway([a, b], policy=policy, round_budget=1_000)
        majors = [gw.submit("a", 1_000) for _ in range(4)]
        minor = gw.submit("b", 1_000)
        gw.drain()
        return majors, minor

    _, minor_fifo = trace("fifo")
    _, minor_fair = trace("fair")
    assert minor_fair.admitted_round == 0
    assert minor_fifo.admitted_round > 0  # HOL-blocked behind the burst
    assert minor_fair.finished < minor_fifo.finished


def test_fair_share_minority_p99_beats_fifo():
    """The bench gate in miniature: same trace, fair-share strictly
    improves the minority class's p99 modeled latency."""

    def p99(policy):
        gw = Gateway(
            [FakeAdapter("a", slots=2), FakeAdapter("b", slots=2)],
            policy=policy, round_budget=2_000,
        )
        for _ in range(8):
            gw.submit("a", 2_000)
        for _ in range(2):
            gw.submit("b", 2_000)
        return drain_stats(gw)["per_class"]["b"]["p99_ms"]

    assert p99("fair") < p99("fifo")


def test_edf_admits_tightest_deadline_first():
    a = FakeAdapter("a", slots=1)
    gw = Gateway([a], policy="edf", round_budget=1_000)
    relaxed = gw.submit("a", 1_000, deadline_cycles=1_000_000)
    tight = gw.submit("a", 1_000, deadline_cycles=500)
    gw.drain()
    assert tight.admitted_round == 0
    assert relaxed.admitted_round > tight.admitted_round
    assert tight.finished < relaxed.finished


def test_work_conserving_when_one_class_idle():
    """An idle class's share is not wasted: a lone busy class drains at
    the full round budget, not at its nominal share."""
    gw = Gateway(
        [FakeAdapter("a", slots=1), FakeAdapter("b", slots=1)],
        policy="fair", round_budget=1_000,
    )
    gw.submit("a", 4_000)
    gw.drain()
    assert gw.rounds == 4  # ceil(4000 / 1000), not ceil(4000 / 500)


def test_stats_account_latency_and_ops():
    gw = Gateway([FakeAdapter("a", slots=1)], policy="fifo",
                 round_budget=1_000)
    r = gw.submit("a", 2_500)
    gw.drain()
    st = gw.stats()
    assert r.done and r.latency_cycles == 2_500  # finished mid round 3
    assert st["per_class"]["a"]["completed"] == 1
    assert st["total_ops"] == 2_500
    assert st["gops_w"] > 0
    assert not gw.pending()


@given(
    st.lists(st.integers(100, 5_000), min_size=1, max_size=12),
    st.lists(st.integers(100, 5_000), min_size=1, max_size=12),
    st.integers(500, 4_000),
)
@settings(max_examples=25, deadline=None)
def test_fair_share_never_starves_a_class(costs_a, costs_b, budget):
    """The no-starvation property: under cycle-budget fair-share every
    admitted request completes within a bounded number of rounds — each
    backlogged class receives at least its quantum (or, when the quantum
    cannot yet afford a micro-step, work-conserving slack keeps the round
    from idling), so every round with pending admitted work serves at
    least one ``unit`` micro-step.  Starved traffic would blow through the
    bound and fail the drain guard."""
    unit = 500
    gw = Gateway(
        [FakeAdapter("a", slots=2, unit=unit),
         FakeAdapter("b", slots=2, unit=unit)],
        policy="fair", round_budget=budget,
    )
    for c in costs_a:
        gw.submit("a", c)
    for c in costs_b:
        gw.submit("b", c)
    # every round serves >= one unit chunk (round_budget >= unit), plus one
    # admission round of slack per request for slot waits
    bound = 2 + len(costs_a) + len(costs_b) + sum(
        -(-c // unit) for c in costs_a + costs_b
    )
    gw.drain(max_rounds=bound)  # raises (fails the property) if exceeded
    assert all(g.done for g in gw.requests)
    assert not gw.pending()
    assert gw.stats()["forced"] == 0  # no step ever outsized a round


# ----------------------------------------------- plan invalidation (real)


@functools.lru_cache(maxsize=1)
def _small_unet():
    import jax

    from repro.models import unet

    cfg = unet.UNetConfig(
        hw=32, in_ch=2, base=4, depth=2, convs_per_stage=1, n_classes=3,
        quant_mode="mma_int8", impl="xla",
    )
    return cfg, unet.init_params(jax.random.PRNGKey(0), cfg)


def _plan_for(params, *, stale: bool):
    """A hand-built v2 plan bound (or mis-bound) to ``params``."""
    from repro.autotune.calibrate import params_fingerprint
    from repro.autotune.plan import TunedPlan

    pfp = "0" * 64 if stale else params_fingerprint(params)
    return TunedPlan(
        workload="unet",
        geometry=dict(depth=2, convs_per_stage=1),
        planes=(6,) * 5,
        target_rel_err=0.1,
        certificate=dict(cert=0.05),
        fingerprint="f" * 64,
        params_fingerprint=pfp,
        tile=28,
        halo=12,
    )


def test_stale_plan_rejected_at_admission_naming_both_fingerprints():
    from repro.autotune.calibrate import params_fingerprint
    from repro.serve.gateway import SegAdapter

    cfg, params = _small_unet()
    plan = _plan_for(params, stale=True)
    gw = Gateway([SegAdapter(cfg, params, plan=plan, batch=2)],
                 policy="fifo", on_stale="reject")
    img = np.zeros((32, 32, 2), np.float32)
    with pytest.raises(StalePlanError) as exc:
        gw.submit("seg", img)
    msg = str(exc.value)
    assert plan.params_fingerprint in msg  # the plan's binding
    assert params_fingerprint(params) in msg  # what is actually served
    assert "stale" in msg
    assert not gw.requests  # nothing entered the system


def test_fresh_plan_admits_and_serves():
    from repro.serve.gateway import SegAdapter

    cfg, params = _small_unet()
    plan = _plan_for(params, stale=False)
    gw = Gateway([SegAdapter(cfg, params, plan=plan, batch=2)],
                 policy="fifo", round_budget=50_000_000)
    r = gw.submit("seg", np.linspace(0, 1, 32 * 32 * 2, dtype=np.float32)
                  .reshape(32, 32, 2))
    gw.drain()
    assert r.done and r.handle.result is not None
    assert gw.stats()["fallbacks"] == {}


def test_preempted_seg_round_emits_its_in_flight_batch_next_round():
    """An unscoped preemptive ``SegAdapter.work`` round keeps one
    micro-batch dispatched ahead; when the quantum runs out with it still
    on the device, the next round emits it first.  Every step is charged
    exactly what ``next_cost`` priced before it."""
    from repro.serve.gateway import SegAdapter

    cfg, params = _small_unet()
    adapter = SegAdapter(cfg, params, tile=8, batch=2)
    eng = adapter.engine
    priced = []
    next_cost, step = eng.next_cost, eng.step

    def priced_step(*args):
        priced.append(next_cost(*args))
        return step(*args)

    eng.step = priced_step
    eng.submit(np.linspace(0, 1, 32 * 32 * 2, dtype=np.float32)
               .reshape(32, 32, 2))
    eng.queue.pump(eng.slots, eng._admit)
    first = eng.next_cost()
    used1, _, ev1 = adapter.work(first, qos=None)
    assert used1 == first and ev1
    [(_, in_flight, _)] = eng._inflight  # launched ahead, not yet collected
    assert eng.has_work()
    used2, _, ev2 = adapter.work(10**12, qos=None)
    assert [(e.rid, e.tile) for e in ev2[: len(in_flight)]] == [
        (req.rid, ti) for req, ti in in_flight]
    assert not eng.has_work()
    assert used1 + used2 == sum(priced) == sum(e.cycles for e in ev1 + ev2)
    assert len(priced) == eng.counters.steps
    assert eng.counters.launched_ahead > 0


def test_stale_plan_falls_back_to_uniform_schedule():
    from repro.serve.gateway import SegAdapter

    cfg, params = _small_unet()
    adapter = SegAdapter(cfg, params, plan=_plan_for(params, stale=True),
                         batch=2)
    gw = Gateway([adapter], policy="fair", on_stale="fallback",
                 round_budget=50_000_000)
    r = gw.submit("seg", np.ones((32, 32, 2), np.float32))
    gw.drain()
    assert r.done
    assert adapter.plan is None  # quarantined
    assert adapter.fallback_reason and "stale" in adapter.fallback_reason
    # the fallback engine runs the certified uniform full-digit schedule
    assert adapter.engine.base_schedule.planes == (8,) * 5
    assert "seg" in gw.stats()["fallbacks"]


# --------------------------------------- progressive tile stream (real)


def _quantized_seg(priority):
    import dataclasses

    import jax

    from repro.models import unet
    from repro.segserve import SegEngine

    cfg = unet.UNetConfig(
        hw=64, in_ch=3, base=4, depth=2, convs_per_stage=1, n_classes=3,
        quant_mode="mma_int8", impl="xla",
    )
    params = unet.init_params(jax.random.PRNGKey(1), cfg)
    sched = unet.schedule_from_params(params, 0.05)
    cfg = dataclasses.replace(cfg, plane_schedule=tuple(sched.planes))
    return SegEngine(cfg, params, tile=16, batch=4, adaptive=True,
                     priority=priority)


@functools.lru_cache(maxsize=1)
def _structured_image():
    from repro.segserve.synth import phantom_image

    return phantom_image(64, 48, 3)


def test_progressive_emission_structure_before_background():
    """The acceptance ordering property: within a request, emitted tile
    budget classes never decrease — every structure tile (low class, full
    amplitude) streams out before any background tile."""
    eng = _quantized_seg(priority=True)
    events = list(eng.serve_stream([np.asarray(_structured_image())]))
    classes = [ev.klass for ev in events]
    assert classes == sorted(classes)
    assert classes[0] == 0 and classes[-1] > 0  # both kinds exercised
    # the stream is complete and consistent
    req = events[-1].request
    assert events[-1].done and req.result is not None
    assert sorted(ev.tile for ev in events) == list(range(req.plan.n_tiles))
    # partial() after completion is the final stitch
    assert np.array_equal(req.partial(), req.result.logits)


def test_progressive_final_stitch_bit_identical_to_non_progressive():
    """Prioritization is scheduling only: the same image served with and
    without structure-first ordering stitches to bit-identical logits."""
    img = np.asarray(_structured_image())
    a = _quantized_seg(priority=True).run([img])[0]
    b = _quantized_seg(priority=False).run([img])[0]
    assert np.array_equal(a.logits, b.logits)
    assert a.cycles == b.cycles  # same tiles at the same class schedules


def test_partial_stitch_grows_monotonically():
    eng = _quantized_seg(priority=True)
    [req] = [eng.submit(np.asarray(_structured_image()))]
    eng.queue.pump(eng.slots, eng._admit)
    seen = 0
    while not req.done:
        events = eng.step()
        assert events
        partial = req.partial() if not req.done else req.result.logits
        written = np.abs(partial).sum(axis=-1) != 0
        seen_now = int(written.sum())
        assert seen_now >= seen  # cores only accumulate
        seen = seen_now


# ------------------------------------------------- mixed real end-to-end


def test_gateway_serves_mixed_real_traffic():
    """Both real engines behind one gateway: the LM burst and a seg image
    co-scheduled, everything completes, tile events stream through."""
    import jax

    from repro import models
    from repro.configs import get_smoke_config
    from repro.serve.gateway import LMAdapter, SegAdapter

    lm_cfg = get_smoke_config("minitron_4b")
    lm_params = models.build(lm_cfg).init_params(jax.random.PRNGKey(0), lm_cfg)
    seg_cfg, seg_params = _small_unet()
    seen = []
    gw = Gateway(
        [
            LMAdapter(lm_cfg, lm_params, batch=2, max_seq=24),
            SegAdapter(seg_cfg, seg_params, batch=2),
        ],
        policy="fair", round_budget=3_000_000, on_event=seen.append,
    )
    rng = np.random.default_rng(0)
    lms = [gw.submit("lm", rng.integers(0, lm_cfg.vocab, size=3), max_new=4)
           for _ in range(3)]
    # a pre-built Request whose rid collides with a gateway rid: completion
    # matching is by handle identity, so it must still finish cleanly
    from repro.serve.engine import Request

    prebuilt = gw.submit(
        "lm", Request(rid=0, prompt=rng.integers(0, lm_cfg.vocab, size=2),
                      max_new=4),
    )
    seg = gw.submit("seg", np.ones((32, 32, 2), np.float32))
    gw.drain(max_rounds=1_000)
    assert all(r.done for r in lms) and seg.done and prebuilt.done
    assert all(len(r.handle.out) == 4 for r in lms)
    assert len(prebuilt.handle.out) == 4
    assert seg.handle.result is not None
    assert seen and seen == list(gw.tile_events)
    st = gw.stats()
    assert st["per_class"]["lm"]["completed"] == 4
    assert st["per_class"]["seg"]["completed"] == 1
    assert st["gops_w"] > 0


# --------------------------------------------- per-completion stamp offsets


def test_per_completion_stamps_within_one_work_call():
    """Protocol v3 regression: two requests finishing inside one work()
    call are stamped at their own micro-step offsets.  Before the fix
    both inherited the call's full consumed — the short request paid the
    long one's latency."""
    ad = FakeAdapter("a", slots=2, unit=1_000)
    gw = Gateway([ad], policy="fair", round_budget=10_000)
    r1 = gw.submit("a", 1_000)
    r2 = gw.submit("a", 3_000)
    gw.step_round()
    assert r1.done and r2.done
    # oldest-first micro-steps: r1 finishes on the first 1000-cycle step,
    # r2 three steps later — distinct stamps, non-decreasing, >= arrival
    assert r1.finished == 1_000
    assert r2.finished == 4_000
    assert r1.arrival <= r1.finished <= r2.finished


def test_legacy_bare_completions_stamp_at_full_consumed():
    """Adapters predating protocol v3 return bare greqs; they keep the
    old semantics — every completion stamped at the call's consumed."""

    class LegacyAdapter(FakeAdapter):
        def work(self, budget, qos=None, force=False, soft_limit=None):
            consumed, completed, events = super().work(
                budget, qos=qos, force=force, soft_limit=soft_limit)
            return consumed, [g for g, _ in completed], events

    ad = LegacyAdapter("a", slots=2, unit=1_000)
    gw = Gateway([ad], policy="fair", round_budget=10_000)
    r1 = gw.submit("a", 1_000)
    r2 = gw.submit("a", 3_000)
    gw.step_round()
    assert r1.done and r2.done
    assert r1.finished == r2.finished == 4_000


def test_decreasing_completion_offsets_rejected():
    """The gateway refuses an adapter whose completion offsets go
    backwards — a stamp that time-travels would corrupt latency stats."""

    class ShuffledAdapter(FakeAdapter):
        def work(self, budget, qos=None, force=False, soft_limit=None):
            consumed, completed, events = super().work(
                budget, qos=qos, force=force, soft_limit=soft_limit)
            return consumed, list(reversed(completed)), events

    ad = ShuffledAdapter("a", slots=2, unit=1_000)
    gw = Gateway([ad], policy="fair", round_budget=10_000)
    gw.submit("a", 1_000)
    gw.submit("a", 3_000)
    with pytest.raises(AssertionError, match="decreasing completion"):
        gw.step_round()


# ------------------------------------------------- bounded event window


class EventfulAdapter(FakeAdapter):
    """FakeAdapter emitting one event per micro-step worked."""

    def work(self, budget, qos=None, force=False, soft_limit=None):
        seq0 = self.total_ops // self.unit
        consumed, completed, _ = super().work(
            budget, qos=qos, force=force, soft_limit=soft_limit)
        events = [dict(seq=seq0 + i) for i in range(consumed // self.unit)]
        return consumed, completed, events


def test_tile_events_bounded_and_on_event_lossless():
    """tile_events keeps only the newest max_kept_events records (the
    unbounded-growth leak), stats() accounts the drop, and the on_event
    callback still sees every event."""
    seen = []
    ad = EventfulAdapter("a", slots=2, unit=1_000)
    gw = Gateway([ad], policy="fair", round_budget=4_000,
                 max_kept_events=3, on_event=seen.append)
    r1 = gw.submit("a", 4_000)
    r2 = gw.submit("a", 4_000)
    gw.drain(max_rounds=50)
    assert r1.done and r2.done
    assert len(seen) == 8  # callback: lossless, 8 micro-steps total
    assert [e["seq"] for e in seen] == list(range(8))
    assert list(gw.tile_events) == seen[-3:]  # window: newest 3 survive
    st = gw.stats()
    assert st["tile_events_seen"] == 8
    assert st["tile_events_kept"] == 3
    assert st["tile_events_dropped"] == 5
    with pytest.raises(ValueError):
        Gateway([FakeAdapter("a")], max_kept_events=0)
