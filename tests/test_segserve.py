"""Tiled-segmentation serving: halo math, exact tiled-vs-whole equivalence
(property, over sizes and depths), content-adaptive tile budgets never
exceeding the layer schedule's certified bound, engine micro-batching, and
the satellite guards (schedule length validation, conv pad modes)."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.plane_schedule import PlaneSchedule
from repro.models import unet
from repro.segserve import SegEngine, adaptive, tiling


# ------------------------------------------------------------- halo math


def test_halo_known_values():
    """Hand-walked invalid-margin recurrences (see tiling.py docstring)."""
    assert tiling.halo_for(1, 1) == 6    # margin 5 -> ceil to mult 2
    assert tiling.halo_for(2, 1) == 12   # margin 11 -> mult 4
    assert tiling.halo_for(3, 1) == 24   # margin 23 -> mult 8
    assert tiling.halo_for(2, 2) == 24   # margin 22 -> mult 4
    assert tiling.halo_for(0, 1) == 1    # conv-only stack: 1 px, no pooling


def test_halo_alignment_and_monotonicity():
    for c in (1, 2, 3):
        halos = [tiling.halo_for(d, c) for d in range(5)]
        for d, h in enumerate(halos):
            if d:
                assert h % 2**d == 0
        assert all(a <= b for a, b in zip(halos, halos[1:]))
    for d in (1, 2, 3):
        h_by_c = [tiling.halo_for(d, c) for c in (1, 2, 3)]
        assert all(a <= b for a, b in zip(h_by_c, h_by_c[1:]))


def test_halo_validation():
    with pytest.raises(ValueError):
        tiling.halo_for(-1, 1)
    with pytest.raises(ValueError):
        tiling.halo_for(2, 0)


# ----------------------------------------------------------- tile planning


@given(st.integers(5, 70), st.integers(5, 70), st.integers(1, 2))
@settings(max_examples=20, deadline=None)
def test_plan_partitions_canvas(h, w, depth):
    """Cores tile the padded canvas exactly once; every input window is
    in-bounds, aligned to 2**depth, and contains its core."""
    plan = tiling.plan_tiles(h, w, depth=depth, tile=8)
    mult = 2**depth
    assert plan.pad_h % mult == 0 and plan.pad_w % mult == 0
    assert plan.pad_h - h < mult and plan.pad_w - w < mult
    cover = np.zeros((plan.pad_h, plan.pad_w), np.int32)
    for t in plan.tiles:
        cover[t.core_y0 : t.core_y1, t.core_x0 : t.core_x1] += 1
        assert 0 <= t.y0 <= t.core_y0 < t.core_y1 <= t.y1 <= plan.pad_h
        assert 0 <= t.x0 <= t.core_x0 < t.core_x1 <= t.x1 <= plan.pad_w
        for v in (t.y0, t.x0, t.y1, t.x1, t.core_y0, t.core_x0):
            assert v % mult == 0
        assert t.in_h % mult == 0 and t.in_w % mult == 0
    assert bool(np.all(cover == 1))


def test_plan_validation_and_halo_override():
    with pytest.raises(ValueError):
        tiling.plan_tiles(0, 8, depth=1, tile=8)
    with pytest.raises(ValueError):
        tiling.plan_tiles(8, 8, depth=2, tile=6)  # not a multiple of 4
    with pytest.raises(ValueError):
        tiling.plan_tiles(8, 8, depth=1, tile=8, halo=-1)
    # explicit halos round up to the alignment unit; 0 stays 0
    assert tiling.plan_tiles(16, 16, depth=2, tile=8, halo=5).halo == 8
    assert tiling.plan_tiles(16, 16, depth=2, tile=8, halo=0).halo == 0
    # default is the exact receptive-field halo
    assert tiling.plan_tiles(16, 16, depth=2, tile=8).halo == tiling.halo_for(2, 1)


def test_stitch_validation():
    plan = tiling.plan_tiles(8, 8, depth=1, tile=8)
    with pytest.raises(ValueError):
        tiling.stitch(plan, [])
    with pytest.raises(ValueError):
        tiling.stitch(plan, [np.zeros((3, 3, 2), np.float32)])


# ------------------------------------------- tiled-vs-whole equivalence


@functools.lru_cache(maxsize=8)
def _net(depth, base=4, in_ch=3, n_classes=3, **kw):
    cfg = unet.UNetConfig(hw=16, in_ch=in_ch, base=base, depth=depth,
                          convs_per_stage=1, n_classes=n_classes, **kw)
    return cfg, unet.init_params(jax.random.PRNGKey(0), cfg)


def _whole_ref(params, image, cfg):
    """forward on the 2**depth-aligned canvas, cropped to the image."""
    mult = 2**cfg.depth
    h, w = image.shape[:2]
    pad = np.pad(image, ((0, -h % mult), (0, -w % mult), (0, 0)))
    out = unet.forward(params, jnp.asarray(pad[None]), cfg)
    return np.asarray(out[0])[:h, :w]


@given(st.integers(7, 40), st.integers(7, 40), st.integers(1, 2))
@settings(max_examples=8, deadline=None)
def test_tiled_forward_matches_whole(h, w, depth):
    """The acceptance property: halo-exact tiling of an arbitrary-size
    image equals the whole-image forward within fp tolerance."""
    cfg, params = _net(depth)
    image = np.asarray(
        jax.random.normal(jax.random.PRNGKey(h * 101 + w), (h, w, cfg.in_ch))
    )
    got, plan = tiling.tiled_forward(params, image, cfg, tile=8)
    assert got.shape == (h, w, cfg.n_classes)
    assert plan.halo == tiling.halo_for(depth, 1)
    np.testing.assert_allclose(got, _whole_ref(params, image, cfg),
                               rtol=1e-4, atol=1e-4)


def test_tiled_forward_short_halo_is_inexact():
    """Sanity check that the halo is load-bearing: a halo one alignment
    unit short of exact leaves seam error; the exact halo leaves none."""
    cfg, params = _net(2)
    image = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (24, 24, 3)))
    want = _whole_ref(params, image, cfg)
    exact, _ = tiling.tiled_forward(params, image, cfg, tile=8)
    short, _ = tiling.tiled_forward(
        params, image, cfg, tile=8, halo=tiling.halo_for(2, 1) - 4
    )
    np.testing.assert_allclose(exact, want, rtol=1e-4, atol=1e-4)
    assert float(np.max(np.abs(short - want))) > 1e-3


# ------------------------------------------------- adaptive tile budgets


@given(st.lists(st.integers(1, 8), min_size=1, max_size=12),
       st.integers(1, 1000))
@settings(max_examples=25, deadline=None)
def test_refine_never_exceeds_certified_bound(planes, r_milli):
    """The satellite guarantee: a refined tile budget's worst-case error,
    scaled by the tile's amplitude ratio, never exceeds the layer
    schedule's certified bound (2^d - 1 in weight-colsum units)."""
    base = PlaneSchedule.from_list(planes)
    r = r_milli / 1000.0
    ref = base.refine(r)
    assert len(ref) == len(base)
    for b0, b1 in zip(base.planes, ref.planes):
        assert 1 <= b1 <= b0  # refinement only drops digits
        d0, d1 = 8 - b0, 8 - b1
        assert (2**d1 - 1) * r <= (2**d0 - 1)  # certified-budget invariant
        if d0 == 0:
            assert d1 == 0  # full-precision layers are never refined
        if d1 > d0:
            # maximality: one more dropped digit would break the budget
            assert (2 ** (d1 + 1) - 1) * r > (2**d0 - 1) or b1 == 1


def test_refine_identity_and_validation():
    s = PlaneSchedule.from_list([8, 5, 3])
    assert s.refine(1.0).planes == s.planes
    with pytest.raises(ValueError):
        s.refine(1.5)
    with pytest.raises(ValueError):
        s.refine(-0.25)
    # monotone: quieter tiles never get more planes
    prev = None
    for k in range(7):
        p = s.refine(2.0**-k).planes
        if prev is not None:
            assert all(a <= b for a, b in zip(p, prev))
        prev = p


def test_refine_edge_cases():
    """Satellite guards: flat-zero windows, non-finite ratios, the 1-plane
    floor, and per-layer ratio vectors."""
    s = PlaneSchedule.from_list([8, 5, 3, 1])
    # r = 0 (exactly flat window) refines maximally but never below 1 plane
    # and never touches full-precision (zero-budget) layers
    assert s.refine(0.0).planes == (8, 1, 1, 1)
    # non-finite ratios are calibration bugs — refuse loudly
    for bad in (float("nan"), float("inf"), -float("inf")):
        with pytest.raises(ValueError, match="not finite"):
            s.refine(bad)
    # a tiny-but-positive ratio also bottoms out at 1 plane
    assert all(b >= 1 for b in s.refine(1e-30).planes)
    # per-layer measured ratios: each layer refined at its own ratio
    per_layer = s.refine([1.0, 1.0, 0.25, 0.0])
    assert per_layer.planes == (8, 5, s.refine(0.25).planes[2], 1)
    with pytest.raises(ValueError, match="per layer"):
        s.refine([0.5, 0.5])


def test_refine_then_refine_never_exceeds_parent_certificate():
    """Chained refinement stays inside the parent schedule's certified
    budget at the product ratio: refine(r1).refine(r2) drops no more than
    the parent inequality allows at r1*r2."""
    for planes in ([8, 6, 4, 2], [7, 7, 7], [5, 1]):
        s = PlaneSchedule.from_list(planes)
        for r1 in (1.0, 0.5, 0.3, 0.01):
            for r2 in (1.0, 0.5, 0.125, 0.0):
                chained = s.refine(r1).refine(r2)
                for b0, b2 in zip(s.planes, chained.planes):
                    d0, d2 = 8 - b0, 8 - b2
                    # the parent certificate at the product ratio
                    assert (2**d2 - 1) * r1 * r2 <= (2**d0 - 1)
                    if d0 == 0:
                        assert d2 == 0
                # refining in one shot at the product is at least as deep
                one_shot = s.refine(r1 * r2)
                assert all(
                    c >= o for c, o in zip(chained.planes, one_shot.planes)
                )


def test_budget_class_edges():
    assert adaptive.budget_class(1.0) == 0
    assert adaptive.budget_class(0.6) == 0
    assert adaptive.budget_class(0.5) == 1
    assert adaptive.budget_class(0.25) == 2
    assert adaptive.budget_class(0.0) == adaptive.MAX_CLASS
    assert adaptive.budget_class(1e-9, max_class=4) == 4
    with pytest.raises(ValueError):
        adaptive.budget_class(1.5)
    base = PlaneSchedule.from_list([6, 4])
    assert adaptive.class_schedule(base, 0) is base
    assert adaptive.class_schedule(base, 3).planes == base.refine(0.125).planes


def test_classify_tiles_flat_background():
    plan = tiling.plan_tiles(32, 32, depth=1, tile=16, halo=0)
    canvas = np.zeros((32, 32, 1), np.float32)
    canvas[:16, :16] = 1.0  # one loud tile
    canvas[16:, 16:] = 0.01  # one quiet tile, two empty
    ks = adaptive.classify_tiles(canvas, plan)
    assert ks[0] == 0
    assert ks[3] == adaptive.budget_class(0.01)
    assert ks[1] == ks[2] == adaptive.MAX_CLASS


# ------------------------------------------------------------- the engine


def test_engine_float_matches_whole_image():
    """Acceptance: serving a non-square, non-multiple-of-tile image through
    the micro-batching engine equals the whole-image forward."""
    cfg, params = _net(2)
    images = [
        np.asarray(jax.random.normal(jax.random.PRNGKey(1), (21, 38, 3))),
        np.asarray(jax.random.normal(jax.random.PRNGKey(2), (16, 20, 3))),
        np.asarray(jax.random.normal(jax.random.PRNGKey(4), (21, 38, 3))),
    ]
    eng = SegEngine(cfg, params, tile=8, batch=4, max_active=2)
    results = eng.run(images)
    assert len(results) == 3
    for image, res in zip(images, results):
        assert res.logits.shape == image.shape[:2] + (cfg.n_classes,)
        np.testing.assert_allclose(
            res.logits, _whole_ref(params, image, cfg), rtol=1e-4, atol=1e-4
        )
        assert res.cycles > 0 and res.ops > 0 and res.gops_per_w > 0


def _flat_background_image(rng, h=48, w=64, c=3):
    img = rng.normal(0.0, 0.01, (h, w, c))
    img[8:24, 10:30] += rng.normal(0.0, 1.0, (16, 20, c))
    return img.astype(np.float32)


def test_engine_adaptive_reduces_cycles_at_same_error():
    """Acceptance: content-adaptive tile budgets cut modeled cycles vs the
    uniform per-layer schedule, without worsening the measured error."""
    _, params = _net(2)
    qcfg = dataclasses.replace(
        _net(2)[0], quant_mode="mma_int8", impl="xla",
        plane_schedule=(6, 6, 6, 5, 5),
    )
    image = _flat_background_image(np.random.default_rng(0))
    kw = dict(tile=16, batch=4)
    res_a = SegEngine(qcfg, params, adaptive=True, **kw).run([image])[0]
    res_u = SegEngine(qcfg, params, adaptive=False, **kw).run([image])[0]
    assert res_a.ops == res_u.ops
    assert res_a.cycles < res_u.cycles
    assert res_a.gops_per_w > res_u.gops_per_w
    assert any(k > 0 for k in res_a.class_counts)
    assert res_u.class_counts == {0: res_u.n_tiles}
    # neither schedule wrecks accuracy relative to the full-8 tiled run
    fcfg = dataclasses.replace(qcfg, plane_schedule=None, planes=8)
    ref = SegEngine(fcfg, params, adaptive=False, **kw).run([image])[0]
    denom = float(np.max(np.abs(ref.logits)))
    err_a = float(np.max(np.abs(res_a.logits - ref.logits))) / denom
    err_u = float(np.max(np.abs(res_u.logits - ref.logits))) / denom
    assert err_a <= err_u + 0.05


def test_engine_rejects_bad_image():
    cfg, params = _net(1)
    eng = SegEngine(cfg, params, tile=8)
    with pytest.raises(ValueError):
        eng.submit(np.zeros((8, 8, cfg.in_ch + 1), np.float32))
    with pytest.raises(ValueError):
        eng.submit(np.zeros((8, 8), np.float32))
    with pytest.raises(ValueError):
        eng.submit(np.zeros((0, 8, cfg.in_ch), np.float32))


def test_engine_validates_geometry_at_construction():
    """A bad tile must fail fast, not wedge a slot at first admission."""
    cfg, params = _net(2)
    with pytest.raises(ValueError):
        SegEngine(cfg, params, tile=6)  # not a multiple of 2**depth
    with pytest.raises(ValueError):
        SegEngine(cfg, params, tile=8, halo=-4)
    with pytest.raises(ValueError):
        SegEngine(cfg, params, tile=8, batch=0)  # would spin step() forever


# ----------------------------------------------------- satellite guards


def test_unet_schedule_length_validated():
    cfg = unet.UNetConfig(depth=2, convs_per_stage=1, plane_schedule=(8, 8))
    with pytest.raises(ValueError, match=r"5 3x3 convs"):
        cfg.schedule()
    assert unet.UNetConfig(depth=2, convs_per_stage=1,
                           plane_schedule=(8,) * 5).schedule().planes == (8,) * 5


def test_unet_forward_rejects_misaligned_input():
    cfg, params = _net(2)
    with pytest.raises(ValueError, match="divisible"):
        unet.forward(params, jnp.zeros((1, 18, 16, 3)), cfg)


def test_rectangular_conv_layers():
    from repro.core import cycle_model as cm

    sq = cm.unet_conv_layers(32, 3, 8, 2, 1)
    rect = cm.unet_conv_layers((32, 32), 3, 8, 2, 1)
    assert [(l.h, l.w, l.cin, l.cout) for l in sq] == \
        [(l.h, l.w, l.cin, l.cout) for l in rect]
    tall = cm.unet_conv_layers((32, 16), 3, 8, 2, 1)
    assert tall[0].h == 32 and tall[0].w == 16
    assert len(tall) == len(sq)


def test_segserve_bench_smoke(tmp_path):
    """The registered benchmark emits the tracker's JSON datapoint,
    demonstrates the tuned-vs-uniform cycle win, and enforces the
    certificate gate (measured <= cert <= target)."""
    import json

    from benchmarks import segserve as bench

    path = tmp_path / "BENCH_segserve.json"
    rows = bench.run(base=4, image_hw=(80, 64), tile=16, n_calib=1,
                     json_path=str(path))
    assert [r[0] for r in rows] == [
        "segserve/full-8", "segserve/uniform", "segserve/adaptive"
    ]
    data = json.loads(path.read_text())
    by_name = {r["name"]: r for r in data["rows"]}
    assert data["adaptive_speedup_vs_uniform"] > 1.0
    assert by_name["adaptive"]["cycles"] < by_name["uniform"]["cycles"]
    assert by_name["adaptive"]["gops_w"] > by_name["uniform"]["gops_w"]
    assert by_name["full-8"]["rel_err"] == 0.0
    for row in data["rows"]:
        for key in ("cycles", "ops", "time_ms", "gops", "gops_w",
                    "energy_mj", "rel_err"):
            assert key in row
    # the satellite gate: certified next to measured, and it must hold —
    # with a tuned plan the adaptive row actually meets the target
    gate = data["gate"]
    assert gate["holds"]
    assert gate["measured"] <= gate["cert"] <= gate["target"]
    assert by_name["adaptive"]["rel_err"] <= data["target_rel_err"]
    assert by_name["adaptive"]["cert"] == gate["cert"]
    assert data["plan"]["workload"] == "unet"


def test_engine_metered_energy_account():
    """The engine's integer-pJ account: per-tile emissions sum exactly to
    the request's metered energy, full-8 prices every cycle at the full
    plane rate, and adaptive truncation saves superlinearly (cheaper rate
    on top of fewer cycles)."""
    from repro.core import energy_model as em

    _, params = _net(2)
    image = _flat_background_image(np.random.default_rng(3))
    kw = dict(tile=16, batch=4)
    fcfg = dataclasses.replace(
        _net(2)[0], quant_mode="mma_int8", impl="xla", planes=8
    )
    eng = SegEngine(fcfg, params, adaptive=False, **kw)
    tile_pj = 0
    for ev in eng.serve_stream([image]):
        assert isinstance(ev.pj, int) and ev.pj > 0
        tile_pj += ev.pj
        res = ev.request.result
    # emissions close against the request account, integer-exactly
    assert res.pj == tile_pj
    # uniform full-8: metered == cycles x full rate, and the metered
    # figures agree with the analytic flat-power ones by construction
    assert res.pj == res.cycles * em.active_rate_pj(8)
    assert res.metered_mj == pytest.approx(
        em.pj_to_mj(res.cycles * em.PJ_FULL_CYCLE)
    )
    assert res.metered_gops_per_w == pytest.approx(
        1000.0 * res.ops / res.pj
    )
    # adaptive truncation: fewer cycles AND a cheaper per-cycle rate
    qcfg = dataclasses.replace(fcfg, planes=None,
                               plane_schedule=(6, 6, 6, 5, 5))
    res_a = SegEngine(qcfg, params, adaptive=True, **kw).run([image])[0]
    res_u = SegEngine(qcfg, params, adaptive=False, **kw).run([image])[0]
    assert res_a.pj < res_u.pj < res.pj
    assert res_a.metered_gops_per_w > res_u.metered_gops_per_w
    # superlinear: the pJ ratio beats the cycle ratio (rate savings ride
    # on top of the cycle shrink)
    assert res_a.pj * res_u.cycles < res_u.pj * res_a.cycles
