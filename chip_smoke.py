"""Smoke run of the served paths on a TPU, through the normal entry points.

    python chip_smoke.py             # one chip: U-Net segmentation server,
                                     # then LM decode (smoke preset)
    python chip_smoke.py --chips 4   # four chips: the sharded train step on
                                     # a 2x2 data x model mesh, nothing else

Everything runs in one process with ``JAX_PLATFORMS=tpu``: a missing chip
is an error, never a silent CPU run.  Each phase prints one JSON line that
names the device; the last line is ``{"ok": true, "device": {...}}``.  Any
failed check raises, so the exit code is non-zero and that line is never
printed.  Wall times are smoke wall times, not a benchmark; modeled FPGA
figures are deliberately not printed here.

The phase functions take their sizes as arguments so that the tests can
rehearse them at a tiny size on the CPU (``tests/test_chip_smoke.py``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

LM_ARCH = "minitron_4b"  # the benchmark's dense LM, at its smoke preset
TRUNCATED_PLANES = 4  # the truncated budget each conv is checked at
# served-logit agreement, pallas vs xla, relative to the largest |logit|
LOGIT_RTOL = 1e-4
# sharded vs single-device loss (the bound tests/test_distributed.py uses)
LOSS_ATOL = 5e-2


def device_tag() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def unet_phase(*, image_hw: int = 240, n_images: int = 4, tile: int = 80,
               base: int = 48, seed: int = 0,
               require_kernel: bool = True) -> dict:
    """Serve ``n_images`` phantom slices through ``SegEngine`` with the
    Pallas datapath at the calibrated U-Net geometry, and check (a) every
    conv's kernel output against the oracle bit for bit on one full window,
    (b) the served logits against the ``impl="xla"`` engine, (c) that the
    compiled tile forward holds one Mosaic kernel per conv."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import quant
    from repro.kernels import ops, ref
    from repro.models import unet
    from repro.segserve import SegEngine, tiling
    from repro.segserve.synth import phantom_image

    cfg = unet.UNetConfig(in_ch=4, base=base, depth=3, convs_per_stage=1,
                          n_classes=4, quant_mode="mma_int8", impl="pallas")
    params = unet.init_params(jax.random.PRNGKey(seed), cfg)
    sched = unet.schedule_from_params(params, 0.05)
    cfg = dataclasses.replace(cfg, plane_schedule=tuple(sched.planes))
    images = [phantom_image(image_hw, image_hw, cfg.in_ch, seed=i)
              for i in range(n_images)]

    # (a) each conv at the largest served window: Pallas == oracle, exactly
    plan = tiling.plan_tiles(image_hw, image_hw, depth=cfg.depth,
                             convs_per_stage=cfg.convs_per_stage, tile=tile)
    win = max(t.in_shape for t in plan.tiles)
    layers = dataclasses.replace(cfg, hw=win).conv_layers()
    budgets = (8, TRUNCATED_PLANES)

    @jax.jit
    def conv_diffs(x, wq):
        return jnp.stack([
            jnp.max(jnp.abs(ops.mma_conv2d(x, wq, planes=p)
                            - ref.mma_conv2d_ref(x, wq, planes=p)))
            for p in budgets])

    rng = np.random.default_rng(seed)
    conv_diff = 0
    for spec, w in zip(layers, unet.conv_weights_in_order(params)):
        x = jnp.asarray(rng.integers(-128, 128, (1, spec.h, spec.w, spec.cin)),
                        jnp.int8)
        diffs = np.asarray(conv_diffs(x, quant.quantize_weights(w).values))
        if diffs.any():
            raise AssertionError(
                f"conv {spec}: kernel differs from the oracle by {diffs} at "
                f"{budgets} planes")
        conv_diff = max(conv_diff, int(diffs.max()))

    # (b) the served path: pallas twice (cold, then warm), xla once
    def serve(impl):
        eng = SegEngine(dataclasses.replace(cfg, impl=impl), params, tile=tile,
                        adaptive=True)
        return eng, eng.run(images)

    (eng, _), cold_s = _timed(lambda: serve("pallas"))
    (eng, got), warm_s = _timed(lambda: serve("pallas"))
    _, want = serve("xla")
    logit_diff = max(float(np.max(np.abs(g.logits - w.logits)))
                     for g, w in zip(got, want))
    logit_scale = max(float(np.max(np.abs(w.logits))) for w in want)
    if not logit_diff <= LOGIT_RTOL * logit_scale:
        raise AssertionError(
            f"served logits: pallas vs xla differ by {logit_diff} "
            f"(limit {LOGIT_RTOL} x {logit_scale})")
    for r in got:
        if r.logits.shape != (image_hw, image_hw, cfg.n_classes) \
                or not np.all(np.isfinite(r.logits)):
            raise AssertionError(f"bad logits {r.logits.shape}")

    # (c) no interpret-mode kernel: the compiled tile forward holds Mosaic
    x = jax.ShapeDtypeStruct((eng.batch, win[0], win[1], cfg.in_ch), jnp.float32)
    hlo = jax.jit(unet.forward, static_argnums=2).lower(
        params, x, eng.class_cfg(0)).compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    if require_kernel and n_kernels != len(layers):
        raise AssertionError(
            f"compiled tile forward holds {n_kernels} tpu_custom_call(s) for "
            f"{len(layers)} convs")

    return {
        "phase": "unet_segmentation", **device_tag(),
        "geometry": {"in_ch": cfg.in_ch, "base": base, "depth": cfg.depth,
                     "n_classes": cfg.n_classes, "image": [image_hw, image_hw],
                     "tile": tile, "window": list(win)},
        "plane_schedule": list(cfg.plane_schedule),
        "images": n_images, "tiles_per_image": plan.n_tiles,
        "budget_classes": got[0].class_counts,
        "compile_s": cold_s - warm_s,
        "smoke_wall_time_per_image_s": warm_s / n_images,
        "max_conv_diff_vs_oracle": conv_diff,
        "max_logit_diff_vs_xla": logit_diff, "max_abs_logit": logit_scale,
        "tpu_custom_calls": n_kernels,
        "peak_bytes_in_use": peak_bytes(),
    }


def lm_phase(*, n_requests: int = 4, max_new: int = 8, seed: int = 0) -> dict:
    """Greedy-decode ``n_requests`` seeded prompts with the continuous-
    batching ``Engine`` (smoke preset, MMA int8) through the Pallas kernel
    and through ``impl="xla"``; the tokens must be identical."""
    import jax
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.configs.base import QuantConfig
    from repro.models import build
    from repro.serve.engine import Engine, Request

    cfg = get_smoke_config(LM_ARCH)
    params = build(cfg).init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, size=int(rng.integers(4, 12)))
               for _ in range(n_requests)]

    def serve(impl):
        qcfg = cfg.replace(quant=QuantConfig(mode="mma_int8", impl=impl))
        eng = Engine(qcfg, params, batch=n_requests, max_seq=64)
        reqs = [Request(rid=i, prompt=p, max_new=max_new)
                for i, p in enumerate(prompts)]
        _, first_s = _timed(lambda: eng.admit(reqs[0]))
        for r in reqs[1:]:
            eng.admit(r)
        steps = []
        while eng.slots.any_active():
            _, dt = _timed(eng.step)
            steps.append(dt)
        return [r.out for r in reqs], first_s, steps

    got, first_s, steps = serve("pallas")
    want, _, _ = serve("xla")
    if got != want:
        raise AssertionError(f"greedy tokens differ: pallas {got} vs xla {want}")
    return {
        "phase": "lm_decode_smoke_preset", **device_tag(), "arch": LM_ARCH,
        "d_model": cfg.d_model, "n_layers": cfg.n_layers, "vocab": cfg.vocab,
        "requests": n_requests, "tokens_per_request": max_new,
        "compile_s": first_s,  # first prefill token: compile + one step
        "smoke_wall_time_per_decode_step_s": float(np.median(steps)),
        "tokens_identical_to_xla": True,
        "peak_bytes_in_use": peak_bytes(),
    }


def sharded_train_phase(*, steps: int = 3, seed: int = 0) -> dict:
    """The sharded train step on a (data, model) mesh of every local
    device; each step's loss must agree with the single-device step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import build
    from repro.optim import adamw
    from repro.train import train_step as ts

    cfg = get_smoke_config(LM_ARCH)
    batch, seq = 8, 32
    mesh = make_host_mesh(model=2)

    def fresh_state():  # the sharded step donates its input, so one each
        params = build(cfg).init_params(jax.random.PRNGKey(seed), cfg)
        return {"params": params, "opt": adamw.init(params)}

    rng = np.random.default_rng(seed)
    toks = [jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq + 1)), jnp.int32)
            for _ in range(steps)]
    ab_batch = {"tokens": jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32)}
    ab_state = ts.abstract_state(cfg)
    sharded = ts.build_jitted_train_step(cfg, mesh, ab_state, ab_batch)
    st_sh = ts.state_shardings(ab_state, cfg, mesh)
    b_sh = ts.batch_shardings(ab_batch, mesh)

    state = jax.device_put(fresh_state(), st_sh)
    times, losses = [], []
    for t in toks:
        (state, m), dt = _timed(lambda: jax.block_until_ready(
            sharded(state, {"tokens": jax.device_put(t, b_sh["tokens"])})))
        times.append(dt)
        losses.append(float(m["loss"]))

    one = jax.jit(lambda st, b: ts.train_step(st, b, cfg))
    state1 = jax.device_put(fresh_state(), jax.devices()[0])
    ref_losses = []
    for t in toks:
        state1, m1 = one(state1, {"tokens": t})
        ref_losses.append(float(m1["loss"]))
    diff = max(abs(a - b) for a, b in zip(losses, ref_losses))
    if not (np.all(np.isfinite(losses)) and diff < LOSS_ATOL):
        raise AssertionError(
            f"sharded losses {losses} vs single-device {ref_losses}")
    return {
        "phase": "sharded_train_smoke_preset", **device_tag(), "arch": LM_ARCH,
        "mesh": dict(mesh.shape), "steps": steps, "batch": batch, "seq": seq,
        "losses": losses, "single_device_losses": ref_losses,
        "max_loss_diff": diff,
        "compile_s": times[0],  # first step: compile + one step
        "smoke_wall_time_per_step_s": float(np.median(times[1:] or times)),
        "peak_bytes_in_use": peak_bytes(),
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step on a 2x2 mesh")
    args = ap.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "tpu"  # no chip -> error, not a CPU run
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < args.chips:
        raise SystemExit(f"need {args.chips} TPU chip(s), found {devs}")

    phases = [sharded_train_phase] if args.chips == 4 else [unet_phase, lm_phase]
    for phase in phases:
        print(json.dumps(phase()), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
