"""End-to-end autotuning demo: calibrate -> search -> certify -> serve.

Builds a quantized U-Net, calibrates it on a handful of synthetic
medical-style images, lets the autotuner derive a certified
precision/tile plan (``repro.autotune.tune_unet``), round-trips the plan
through JSON, and serves an image through :class:`repro.segserve.SegEngine`
at the tuned operating point — printing the measured error against the
certificate and the modeled relation-(2) account against the uniform
``from_weights`` baseline the tuner must beat.

    PYTHONPATH=src python examples/tune_unet.py \
        [--height 160] [--width 128] [--depth 3] [--base 16]
        [--target-rel-err 0.05] [--plan-path tuned_plan.json]
"""
import argparse
import dataclasses

import jax
import numpy as np

from repro import autotune
from repro.launch.compile_cache import enable_compile_cache
from repro.models import unet
from repro.segserve import SegEngine
from repro.segserve.synth import phantom_image


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--height", type=int, default=160)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--base", type=int, default=16)
    ap.add_argument("--target-rel-err", type=float, default=0.05)
    ap.add_argument("--n-calib", type=int, default=2)
    ap.add_argument("--plan-path", default=None,
                    help="write the certified plan JSON here")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = unet.UNetConfig(
        hw=args.height, in_ch=4, base=args.base, depth=args.depth,
        convs_per_stage=1, n_classes=4, quant_mode="mma_int8", impl="xla",
    )
    params = unet.init_params(jax.random.PRNGKey(0), cfg)
    images = [
        phantom_image(args.height, args.width, cfg.in_ch, seed=s)
        for s in range(args.n_calib)
    ]

    # ---- calibrate + search + certify (one call) ------------------------
    plan = autotune.tune_unet(
        params, cfg, images, target_rel_err=args.target_rel_err
    )
    print(plan.describe())
    cert = plan.certificate
    print(f"certificate: measured {cert['measured_rel_err']:.4g} * margin "
          f"{cert['margin']:g} = {cert['cert']:.4g} <= target "
          f"{cert['target_rel_err']:g}  (sound interval bound "
          f"{cert.get('sound_bound', float('nan')):.3g})")
    print(f"calibrated classes: thresholds {plan.class_thresholds}")
    print(f"fingerprint: {plan.fingerprint[:16]}…")

    if args.plan_path:
        plan.save(args.plan_path)
        plan = autotune.TunedPlan.load(args.plan_path)  # JSON round trip
        print(f"plan saved to {args.plan_path}")

    # ---- serve at the tuned operating point -----------------------------
    image = images[0]
    eng = autotune.engine_from_plan(cfg, params, plan)
    res = eng.run([image])[0]
    ref = autotune.engine_from_plan(
        cfg, params, autotune.reference_plan(plan)
    ).run([image])[0]
    err = float(np.max(np.abs(res.logits - ref.logits))) / max(
        float(np.max(np.abs(ref.logits))), 1e-8
    )
    print(f"served {args.height}x{args.width}: tiles={res.n_tiles} "
          f"(tile {plan.tile}, halo {plan.halo}), classes {res.class_counts}")
    print(f"measured rel err {err:.4g} <= cert {cert['cert']:.4g}: "
          f"{err <= cert['cert']}")

    # ---- vs the analytic from_weights baseline --------------------------
    sched = unet.schedule_from_params(params, args.target_rel_err)
    bcfg = dataclasses.replace(cfg, plane_schedule=tuple(sched.planes))
    base = SegEngine(bcfg, params, tile=32, adaptive=True).run([image])[0]
    print(f"modeled: tuned {res.cycles} cycles ({res.gops_per_w:.2f} GOPS/W)"
          f" vs from_weights@tile32 {base.cycles} cycles "
          f"({base.gops_per_w:.2f} GOPS/W) -> "
          f"{base.cycles / res.cycles:.2f}x fewer cycles")


if __name__ == "__main__":
    main()
