"""The benchmark's shape arithmetic against hand counts and against the
program it was copied from, and the U-Net's readings through
``spec.arch`` against the numbers the harness read before its
architectures had modules of their own."""
import hashlib

import numpy as np
import pytest
from conftest import CONFIGS

from chipbench import geometry, spec
from chipbench.archs import unet

UNET_CONFIGS = [n for n in CONFIGS if spec.config(n)["arch"] == "unet"]


@pytest.mark.parametrize("name,gop,halo_overhead,n_tiles", [
    ("unet48_brats240", 25.2813312, 112896 / 57600, 9),
    ("unet64_kits512", 444.831105024, 1.890625, 4),
])
def test_model_ops_and_halo_overhead_match_hand_counts(name, gop, halo_overhead,
                                                       n_tiles):
    conf = spec.config(name)
    h, w, _ = conf["image"]
    ops = geometry.model_ops(spec.arch(conf), conf["model"], h, w)
    assert ops == pytest.approx(gop * 1e9, rel=1e-12)
    tiles = spec.arch(conf).plan(h, w, conf)
    assert len(tiles) == n_tiles
    assert sum(t.shape[0] * t.shape[1] for t in tiles) / (h * w) == halo_overhead
    assert round(sum(t.shape[0] * t.shape[1] for t in tiles) / (h * w), 2) in (1.96, 1.89)


@pytest.mark.parametrize("name", UNET_CONFIGS)
def test_configs_hold_the_exact_halo_and_the_programs_tile_plan(name):
    from repro.segserve import tiling

    conf = spec.config(name)
    m = conf["model"]
    assert conf["halo"] == unet.halo_for(m["depth"], m["convs_per_stage"])
    assert conf["halo"] == tiling.halo_for(m["depth"], m["convs_per_stage"])
    h, w, _ = conf["image"]
    ours = unet.plan(h, w, conf)
    theirs = tiling.plan_tiles(h, w, depth=m["depth"],
                               convs_per_stage=m["convs_per_stage"],
                               tile=conf["tile"])
    assert [(t.y0, t.x0, t.y1, t.x1, t.core) for t in ours] == [
        (t.y0, t.x0, t.y1, t.x1, (t.core_y0, t.core_x0, t.core_y1, t.core_x1))
        for t in theirs.tiles]


@pytest.mark.parametrize("k", range(geometry.MAX_CLASS + 1))
def test_class_planes_match_the_programs_refinement(k):
    from repro.core.plane_schedule import PlaneSchedule
    from repro.segserve import adaptive

    base = [5, 8, 4, 5, 7, 1, 5]
    want = adaptive.class_schedule(PlaneSchedule.from_list(base), k).planes
    assert geometry.class_planes(base, k) == tuple(want)


def test_budget_class_matches_the_program_on_phantom_tiles():
    from repro.segserve import adaptive, tiling

    from chipbench import images

    img = images.phantom(96, 96, 2, seed=3)
    plan = tiling.plan_tiles(96, 96, depth=2, tile=32)
    cv = tiling.pad_canvas(img, plan)
    amax = float(np.max(np.abs(cv)))
    ours = [geometry.budget_class(cv[t.y0:t.y1, t.x0:t.x1], amax) for t in plan.tiles]
    assert ours == adaptive.classify_tiles(cv, plan)
    assert len(set(ours)) > 1


def test_conv_shapes_follow_the_programs_layer_list():
    from repro.core import cycle_model as cm

    for name in UNET_CONFIGS:
        m = spec.config(name)["model"]
        ours = [(c.h, c.w, c.cin, c.cout) for c in unet.layers(m, 1, 352, 352)]
        theirs = [(l.h, l.w, l.cin, l.cout) for l in cm.unet_conv_layers(
            352, m["in_ch"], m["base"], m["depth"], m["convs_per_stage"])]
        assert ours == theirs


def test_conv_bytes_count_int8_in_and_weights_and_32_bit_out():
    cv = geometry.Conv(n=2, h=4, w=8, cin=3, cout=5)
    assert cv.ops == 2 * 2 * 4 * 8 * 9 * 3 * 5
    assert cv.bytes == 2 * 4 * 8 * 3 + 9 * 3 * 5 + 4 * 2 * 4 * 8 * 5


@pytest.mark.parametrize("name", UNET_CONFIGS)
def test_the_reference_tile_plan_stitches_to_the_whole_image_forward(name):
    """Exact halos: in float, cores cut from the reference's tile windows
    and stitched give the whole-image forward."""
    import jax
    from conftest import tiny_conf

    from repro.models import unet as program

    from chipbench import images

    conf = tiny_conf(name)
    m = conf["model"]
    cfg = program.UNetConfig(in_ch=m["in_ch"], base=m["base"], depth=m["depth"],
                             convs_per_stage=m["convs_per_stage"],
                             n_classes=m["n_classes"])
    params = unet.make_params(m, 5)
    h, w, c = conf["image"]
    img = images.phantom(h, w, c, seed=4)
    cv = unet.canvas(img, conf)
    whole = np.asarray(program.forward(params, cv[None], cfg)[0])
    stitched = np.full_like(whole, np.nan)
    fwd = jax.jit(program.forward, static_argnums=2)
    for t in unet.plan(h, w, conf):
        out = np.asarray(fwd(params, cv[None, t.y0:t.y1, t.x0:t.x1], cfg)[0])
        y0, x0, y1, x1 = t.core
        stitched[y0:y1, x0:x1] = out[y0 - t.y0:y1 - t.y0, x0 - t.x0:x1 - t.x0]
    np.testing.assert_allclose(stitched, whole, rtol=0, atol=1e-5 * np.abs(whole).max())


# Readings of the harness before its architectures moved into modules of
# their own, on the fixed window of ``_parity_context`` and the recorded
# trace: the U-Net's numbers through ``spec.arch`` equal them to the bit.
PARITY = {
    "unet48_brats240": {
        "model_ops": 25281331200,
        "tiles": [
            ((0, 0, 104, 104), (0, 0, 80, 80)),
            ((0, 56, 104, 184), (0, 80, 80, 160)),
            ((0, 136, 104, 240), (0, 160, 80, 240)),
            ((56, 0, 184, 104), (80, 0, 160, 80)),
            ((56, 56, 184, 184), (80, 80, 160, 160)),
            ((56, 136, 184, 240), (80, 160, 160, 240)),
            ((136, 0, 240, 104), (160, 0, 240, 80)),
            ((136, 56, 240, 184), (160, 80, 240, 160)),
            ((136, 136, 240, 240), (160, 160, 240, 240)),
        ],
        "least_layer_seconds": 0.00024946171543282163,
        "readings": {
            "halo_overhead": 1.96,
            "batch_fill.backlog": 75.0,
            "mfu": 0.0006432908702290076,
            "mma_roofline": 3.6016767971778965,
            "idle_share.backlog": 57.02558499999999,
        },
        # sha256 of the reference canvases at 8 and 4 bits
        "reference": ("ea741cb285502ae9adaca57f8d02f621f26e520b510097e6d9127e4725656765",
                      "c43d1eb9a3e3a05582395ac55c3a26d038c3002b6e9bbbd84babc6ecc483f44c"),
    },
    "unet64_kits512": {
        "model_ops": 444831105024,
        "tiles": [
            ((0, 0, 352, 352), (0, 0, 256, 256)),
            ((0, 160, 352, 512), (0, 256, 256, 512)),
            ((160, 0, 512, 352), (256, 0, 512, 256)),
            ((160, 160, 512, 512), (256, 256, 512, 512)),
        ],
        "least_layer_seconds": 0.002544259797095918,
        "readings": {
            "halo_overhead": 1.890625,
            "batch_fill.backlog": 100.0,
            "mfu": 0.011318857634198473,
            "mma_roofline": 36.73349820951026,
            "idle_share.backlog": 57.02558499999999,
        },
        # sha256 of the reference canvases at 8 and 4 bits
        "reference": ("dd31274b60715a2051519d5b22178c70ebc0be9e5142aa59894706fca0e351a1",
                      "da761e70db91949330f22568c1e939f699432d98a03bf20ed3f5cd6e93203562"),
    },
}


def _parity_context(name):
    """One image of the configuration served in micro-batches of four of
    its tiles, sorted by window shape, completed inside a 10 s window, with
    the recorded TPU v5e trace as the window's trace."""
    from chipbench import bench, readers
    from chipbench import trace as tracing

    conf = spec.config(name)
    cell = name + ".parity"
    by_core = readers.tiles_by_core(
        bench.Context(cell, conf, 10.0, 1.0, bench.Window(0.0, 10.0), 4))
    tiles = sorted(((0, core) for core in by_core),
                   key=lambda rc: (by_core[rc[1]].shape, rc[0], rc[1]))
    win = bench.Window(0.0, 10.0)
    win.images[0] = bench.Image(0, 0, 0.0, 0.0, None, 5.0)
    win.steps = [bench.Step(0.5 + i, 0.6 + i, tiles[i:i + 4])
                 for i in range(0, len(tiles), 4)]
    tr = tracing.load(spec.ROOT / "tests" / "chipbench"
                      / "trace_v5e_unet48_brats240.json")
    return bench.Context(cell, conf, 10.0, 1.0, win, 4, spec.peaks("TPU v5 lite"),
                         tr, tracing.summary(tr))


@pytest.mark.parametrize("name", list(PARITY))
def test_unet_readings_through_the_arch_module_equal_the_old_harness(name):
    from chipbench import readers

    want = PARITY[name]
    ctx = _parity_context(name)
    h, w, _ = ctx.conf["image"]
    ops = geometry.model_ops(spec.arch(ctx.conf), ctx.conf["model"], h, w)
    assert ops == want["model_ops"]
    got = readers.tiles_by_core(ctx)
    assert [((t.y0, t.x0, t.y1, t.x1), core) for core, t in got.items()] == want["tiles"]
    assert all(core == t.core for core, t in got.items())
    assert readers.least_layer_seconds(ctx) == want["least_layer_seconds"]
    for metric, value in want["readings"].items():
        assert spec.reader(metric)(ctx) == value, metric


@pytest.mark.parametrize("name", list(PARITY))
def test_unet_reference_logits_equal_the_old_harness(name):
    """The reference's canvases of two tiny images after one micro-batch:
    the largest windows of budget class 0 of both, at 8 bits and at the
    control's 4 (every other core stays NaN)."""
    from conftest import tiny_conf

    from chipbench import bench, check, images

    conf = tiny_conf(name)
    h, w, c = conf["image"]
    params = spec.arch(conf).make_params(conf["model"], 2**33 + 7)
    image_of = {i: images.phantom(h, w, c, seed=40 + i) for i in range(2)}
    refr = check.Reference(conf, params, image_of, 4)
    groups = {}
    for rid in image_of:
        _, by_core, cls = refr.prepared(rid)
        for core, t in by_core.items():
            groups.setdefault((t.shape, cls[core]), []).append((rid, core))
    (shape, klass), tiles = max(groups.items())
    assert klass == 0 and len(tiles) == 2
    steps = [bench.Step(0.0, 0.0, tiles)]
    for bits, want in zip((8, 4), PARITY[name]["reference"]):
        cvs = refr.canvases(steps, sorted(image_of), bits=bits)
        got = hashlib.sha256(b"".join(np.ascontiguousarray(cvs[k]).tobytes()
                                      for k in sorted(cvs))).hexdigest()
        assert got == want, bits
