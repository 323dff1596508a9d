"""Segment an arbitrary-size image through the tiled serving engine, with
content-adaptive MSDF tile precision and an energy account per image.

A synthetic medical-style image (quiet background, one bright structure)
is tiled with the receptive-field-exact halo, tiles are micro-batched
through the quantized U-Net under the certified per-layer plane schedule,
flat-background tiles drop extra digits (budget classes), and the result
is stitched seamlessly and priced in relation-(2) cycles / GOPS/W.

    PYTHONPATH=src python examples/segment_image.py \
        [--height 160] [--width 128] [--tile 32] [--target-rel-err 0.05]
        [--no-adaptive] [--float]
"""
import argparse
import dataclasses

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.models import unet
from repro.segserve import SegEngine, halo_for
from repro.segserve.synth import phantom_image


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--height", type=int, default=160)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--base", type=int, default=16)
    ap.add_argument("--tile", type=int, default=32)
    ap.add_argument("--target-rel-err", type=float, default=0.05)
    ap.add_argument("--no-adaptive", action="store_true",
                    help="uniform per-layer schedule for every tile")
    ap.add_argument("--float", action="store_true", dest="float_mode",
                    help="float datapath (bit-comparable to whole-image "
                         "forward; no precision/energy story)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = unet.UNetConfig(
        hw=args.height, in_ch=4, base=args.base, depth=args.depth,
        convs_per_stage=1, n_classes=4,
        quant_mode="none" if args.float_mode else "mma_int8", impl="xla",
    )
    params = unet.init_params(jax.random.PRNGKey(0), cfg)
    if not args.float_mode:
        sched = unet.schedule_from_params(params, args.target_rel_err)
        cfg = dataclasses.replace(cfg, plane_schedule=tuple(sched.planes))
        print(f"layer schedule: {sched.describe()}")

    image = phantom_image(args.height, args.width, cfg.in_ch)
    eng = SegEngine(cfg, params, tile=args.tile,
                    adaptive=not args.no_adaptive)
    res = eng.run([image])[0]

    mask = np.argmax(res.logits, axis=-1)
    print(f"image {args.height}x{args.width} -> mask {mask.shape}, "
          f"classes present {sorted(np.unique(mask).tolist())}")
    print(f"tiles={res.n_tiles} (halo {halo_for(args.depth, 1)} px), "
          f"budget classes {res.class_counts}")
    print(f"modeled FPGA, not device time: {res.cycles} cycles = "
          f"{res.time_ms:.2f} ms @100MHz, {res.gops:.2f} GOPS, "
          f"{res.gops_per_w:.2f} GOPS/W, {res.energy_mj:.1f} mJ")


if __name__ == "__main__":
    main()
