"""Dynamic-precision plane schedules: analytic-bound properties, builder
invariants, static/traced equivalence on both MMA datapaths, and the
end-to-end U-Net + LM guarantees the serving knob advertises."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import bitplane, early_term, mma
from repro.core.plane_schedule import PlaneSchedule, layer_rel_bound
from repro.kernels import ref


# ------------------------------------------------------------- bound property


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_empirical_err_within_bound_all_plane_counts(seed):
    """For random int8 weights, the measured relative error of truncation is
    within the analytic bound at *every* plane count 1..8."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-128, 128, (6, 48)), jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (48, 5)), jnp.int8)
    exact = ref.mma_matmul_ref(x, w)
    denom = jnp.maximum(jnp.max(jnp.abs(exact.astype(jnp.float32))), 1.0)
    for planes in range(1, bitplane.N_BITS + 1):
        approx = ref.mma_matmul_ref(x, w, planes=planes, midpoint=True)
        emp = float(early_term.empirical_rel_err(exact, approx))
        bound = early_term.truncation_bound(w, planes, midpoint=True)
        rel_bound = float((jnp.max(bound).astype(jnp.float32) + 1) / denom)
        assert emp <= rel_bound, (planes, emp, rel_bound)
        # absolute, per-column form as well (the sharper statement)
        assert bool(jnp.all(jnp.abs(exact - approx) <= bound[None, :] + 1))


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_choose_planes_monotone_in_target(seed):
    """A looser error target never requires more planes."""
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.integers(-128, 128, (64, 8)), jnp.int8)
    targets = (0.5, 0.2, 0.1, 0.05, 0.02, 0.01, 0.005, 0.001, 1e-4, 0.0)
    # midpoint=False is the schedule-builder form (matches the deployed
    # uncorrected datapaths and layer_rel_bound)
    picks = [early_term.choose_planes(w, t, midpoint=False) for t in targets]
    assert picks == sorted(picks)  # targets descend -> planes ascend
    # and each pick actually meets its target (or is the 8-plane max)
    for t, b in zip(targets, picks):
        if b < bitplane.N_BITS:
            assert layer_rel_bound(w, b) <= t
    # the midpoint form (for midpoint-corrected consumers) is monotone too
    picks_mid = [early_term.choose_planes(w, t) for t in targets]
    assert picks_mid == sorted(picks_mid)
    assert all(a <= b for a, b in zip(picks_mid, picks))  # half-sized bound


def test_layer_rel_bound_decreases_with_planes():
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.integers(-128, 128, (128, 16)), jnp.int8)
    bounds = [layer_rel_bound(w, b) for b in range(1, 9)]
    assert all(a > b for a, b in zip(bounds, bounds[1:]))
    assert bounds[-1] == 0.0


# ---------------------------------------------------------------- the policy


def test_builders_and_validation():
    s = PlaneSchedule.uniform(4, 3)
    assert s.planes == (4, 4, 4)
    assert len(s) == 3 and list(s) == [4, 4, 4] and s[1] == 4
    assert s.arithmetic_fraction() == pytest.approx(0.5)
    assert PlaneSchedule.from_list([8, 3, 1]).planes == (8, 3, 1)
    with pytest.raises(ValueError):
        PlaneSchedule.from_list([])
    with pytest.raises(ValueError):
        PlaneSchedule.from_list([0, 4])
    with pytest.raises(ValueError):
        PlaneSchedule.uniform(9, 2)
    # clamping for deeper stacks
    assert PlaneSchedule.from_list([8, 4]).planes_for(17) == 4
    assert PlaneSchedule.uniform(6, 2).as_array().dtype == jnp.int32


def test_from_weights_meets_target():
    rng = np.random.default_rng(11)
    ws = [jnp.asarray(rng.integers(-128, 128, (72, 9)), jnp.int8)
          for _ in range(4)]
    tgt = 0.02
    s = PlaneSchedule.from_weights(ws, tgt)
    assert len(s) == 4
    assert s.target_rel_err == tgt
    assert s.layer_bounds is not None
    for w, b, lb in zip(ws, s.planes, s.layer_bounds):
        assert lb == pytest.approx(layer_rel_bound(w, b))
        if b < bitplane.N_BITS:
            assert lb <= tgt
    assert s.rel_err_bound() == pytest.approx(sum(s.layer_bounds))


# --------------------------------------------- static/traced plane equivalence


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("planes", [8, 6, 3, 1])
def test_traced_planes_match_static(impl, planes):
    """A schedule entry riding a scan (traced scalar) must be bit-identical
    to the statically specialized kernel at the same budget."""
    rng = np.random.default_rng(planes)
    x = jnp.asarray(rng.integers(-128, 128, (7, 64)), jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (64, 10)), jnp.int8)
    static = mma.mma_dot(x, w, planes=planes, impl=impl)
    traced = jax.jit(
        lambda a, p: mma.mma_dot(a, w, planes=p, impl=impl)
    )(x, jnp.int32(planes))
    np.testing.assert_array_equal(np.asarray(static), np.asarray(traced))
    want = ref.mma_matmul_ref(x, w, planes=planes)
    np.testing.assert_array_equal(np.asarray(static), np.asarray(want))


def test_truncate_to_planes_identity():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(-128, 128, (5, 32)), jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (32, 6)), jnp.int8)
    for planes in range(1, 9):
        xt = bitplane.truncate_to_planes(x, planes)
        full = mma.mma_dot(xt, w, planes=8, impl="xla")
        np.testing.assert_array_equal(
            np.asarray(full),
            np.asarray(ref.mma_matmul_ref(x, w, planes=planes)),
        )
    # planes=8 is the identity
    np.testing.assert_array_equal(
        np.asarray(bitplane.truncate_to_planes(x, 8)), np.asarray(x)
    )


def test_pallas_plane_variants_are_cached():
    from repro.kernels import mma_matmul as mk, ops

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.integers(-128, 128, (8, 32)), jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (32, 8)), jnp.int8)
    before = mk.plane_variant.cache_info()
    for _ in range(3):
        ops.mma_matmul(x, w, planes=3, interpret=True)
    after = mk.plane_variant.cache_info()
    # one new specialization, then cache hits — no retrace per call
    assert after.misses <= before.misses + 1
    assert after.hits >= before.hits + 2


# --------------------------------------------------------------- end to end


def _unet_setup():
    from repro.models import unet as um

    cfg = um.UNetConfig(
        hw=16, in_ch=3, base=4, depth=2, convs_per_stage=1,
        quant_mode="mma_int8", impl="xla",
    )
    params = um.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 16, 3))
    return um, cfg, params, x


def test_unet_schedule_within_advertised_bound():
    """Acceptance: a scheduled U-Net forward stays within the advertised
    (interval-propagated, worst-case sound) bound of the full-precision
    datapath, and the bound tightens monotonically with the target."""
    um, cfg, params, x = _unet_setup()
    prev_planes = 0
    for tgt in (0.05, 0.01, 0.001):
        sched = um.schedule_from_params(params, tgt)
        assert len(sched) == len(cfg.conv_layers())
        assert sum(sched.planes) >= prev_planes  # tighter target, >= planes
        prev_planes = sum(sched.planes)
        scfg = dataclasses.replace(cfg, plane_schedule=tuple(sched.planes))
        out_s, out_f, adv = um.forward_with_error_bound(params, x, scfg)
        emp = float(
            jnp.max(jnp.abs(out_s - out_f))
            / jnp.maximum(jnp.max(jnp.abs(out_f)), 1e-8)
        )
        assert np.isfinite(adv)
        assert emp <= adv, (tgt, emp, adv)
    # uniform 8 planes == the full-precision path exactly, bound collapses
    scfg = dataclasses.replace(cfg, plane_schedule=(8,) * 5)
    out_s, out_f, adv = um.forward_with_error_bound(params, x, scfg)
    assert adv == 0.0
    np.testing.assert_array_equal(np.asarray(out_s), np.asarray(out_f))


def test_unet_uniform_schedule_equals_global_knob():
    """schedule=(b,)*L must be bit-identical to the old global planes=b."""
    um, cfg, params, x = _unet_setup()
    for b in (6, 3):
        g = um.forward(params, x, dataclasses.replace(cfg, planes=b))
        s = um.forward(
            params, x, dataclasses.replace(cfg, plane_schedule=(b,) * 5)
        )
        np.testing.assert_array_equal(np.asarray(g), np.asarray(s))


def test_lm_schedule_matches_global_knob_when_uniform():
    """The schedule riding the layer scan (traced, bit-mask form) equals the
    static global knob on a scan-rolled transformer — same numerics, so the
    serving engine can swap knob for schedule with zero quality change."""
    from repro.configs import get_smoke_config
    from repro.configs.base import QuantConfig

    from repro import models

    cfg = get_smoke_config("yi_6b")
    mod = models.build(cfg)
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab, (2, 12)), jnp.int32
    )
    for b in (8, 5):
        g = mod.forward(
            params, toks, cfg.replace(quant=QuantConfig(mode="mma_int8", planes=b))
        )
        # plane_schedule governs the block stack; `planes` still governs
        # non-block linears (the lm head), so set both for exact equality
        s = mod.forward(
            params, toks,
            cfg.replace(
                quant=QuantConfig(
                    mode="mma_int8", planes=b,
                    plane_schedule=(b,) * cfg.n_layers,
                )
            ),
        )
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(s, np.float32), atol=0, rtol=0
        )


def test_lm_schedule_from_params_end_to_end():
    from repro.configs import get_smoke_config
    from repro.configs.base import QuantConfig
    from repro.serve.engine import lm_schedule_from_params

    from repro import models

    cfg = get_smoke_config("yi_6b")
    mod = models.build(cfg)
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    sched = lm_schedule_from_params(params, cfg, 0.01)
    assert len(sched) == cfg.n_layers
    assert all(1 <= b <= 8 for b in sched.planes)
    toks = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab, (2, 12)), jnp.int32
    )
    qcfg = cfg.replace(
        quant=QuantConfig(mode="mma_int8", plane_schedule=tuple(sched.planes))
    )
    out = mod.forward(params, toks, qcfg)
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
