"""The benchmark's shape arithmetic against hand counts and against the
program it was copied from."""
import numpy as np
import pytest

from chipbench import geometry, spec


@pytest.mark.parametrize("name,gop,halo_overhead,n_tiles", [
    ("unet48_brats240", 25.2813312, 112896 / 57600, 9),
    ("unet64_kits512", 444.831105024, 1.890625, 4),
])
def test_model_ops_and_halo_overhead_match_hand_counts(name, gop, halo_overhead,
                                                       n_tiles):
    conf = spec.config(name)
    h, w, _ = conf["image"]
    assert geometry.model_ops(conf["model"], h, w) == pytest.approx(gop * 1e9, rel=1e-12)
    tiles = geometry.plan(h, w, depth=conf["model"]["depth"], tile=conf["tile"],
                          halo=conf["halo"])
    assert len(tiles) == n_tiles
    assert sum(t.shape[0] * t.shape[1] for t in tiles) / (h * w) == halo_overhead
    assert round(sum(t.shape[0] * t.shape[1] for t in tiles) / (h * w), 2) in (1.96, 1.89)


@pytest.mark.parametrize("name", ["unet48_brats240", "unet64_kits512"])
def test_configs_hold_the_exact_halo_and_the_programs_tile_plan(name):
    from repro.segserve import tiling

    conf = spec.config(name)
    m = conf["model"]
    assert conf["halo"] == geometry.halo_for(m["depth"], m["convs_per_stage"])
    assert conf["halo"] == tiling.halo_for(m["depth"], m["convs_per_stage"])
    h, w, _ = conf["image"]
    ours = geometry.plan(h, w, depth=m["depth"], tile=conf["tile"], halo=conf["halo"])
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

    conf = spec.config("unet64_kits512")
    m = conf["model"]
    ours = [(c.h, c.w, c.cin, c.cout) for c in geometry.convs(m, 1, 352, 352)]
    theirs = [(l.h, l.w, l.cin, l.cout) for l in cm.unet_conv_layers(
        352, m["in_ch"], m["base"], m["depth"], m["convs_per_stage"])]
    assert ours == theirs


def test_conv_bytes_count_int8_in_and_weights_and_32_bit_out():
    cv = geometry.Conv(n=2, h=4, w=8, cin=3, cout=5)
    assert cv.ops == 2 * 2 * 4 * 8 * 9 * 3 * 5
    assert cv.bytes == 2 * 4 * 8 * 3 + 9 * 3 * 5 + 4 * 2 * 4 * 8 * 5


@pytest.mark.parametrize("name", ["unet48_brats240", "unet64_kits512"])
def test_the_reference_tile_plan_stitches_to_the_whole_image_forward(name):
    """Exact halos: in float, cores cut from the reference's tile windows
    and stitched give the whole-image forward."""
    import jax
    from conftest import tiny_conf

    from repro.models import unet

    from chipbench import images, model

    conf = tiny_conf(name)
    m = conf["model"]
    cfg = unet.UNetConfig(in_ch=m["in_ch"], base=m["base"], depth=m["depth"],
                          convs_per_stage=m["convs_per_stage"],
                          n_classes=m["n_classes"])
    params = model.make_params(m, 5)
    h, w, c = conf["image"]
    img = images.phantom(h, w, c, seed=4)
    cv = geometry.canvas(img, m["depth"])
    whole = np.asarray(unet.forward(params, cv[None], cfg)[0])
    stitched = np.full_like(whole, np.nan)
    fwd = jax.jit(unet.forward, static_argnums=2)
    for t in geometry.plan(h, w, depth=m["depth"], tile=conf["tile"], halo=conf["halo"]):
        out = np.asarray(fwd(params, cv[None, t.y0:t.y1, t.x0:t.x1], cfg)[0])
        y0, x0, y1, x1 = t.core
        stitched[y0:y1, x0:x1] = out[y0 - t.y0:y1 - t.y0, x0 - t.x0:x1 - t.x0]
    np.testing.assert_allclose(stitched, whole, rtol=0, atol=1e-5 * np.abs(whole).max())
