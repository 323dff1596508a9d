"""End-to-end driver: train U-Net on synthetic segmentation, quantize, and
evaluate through the MMA int8 datapath — the paper's full deployment story.

    PYTHONPATH=src python examples/train_unet.py [--steps 120] [--full]

``--full`` uses the Table-1-calibrated geometry (slow on CPU); the default
is a reduced config that trains in ~2 minutes on one core.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.models import unet
from repro.optim import adamw
from repro.checkpoint.ckpt import Checkpointer


def synth_batch(cfg, step, b=4):
    """Blob segmentation: classes = concentric intensity rings."""
    rng = np.random.default_rng(step)
    img = rng.standard_normal((b, cfg.hw, cfg.hw, cfg.in_ch)).astype(np.float32)
    cy, cx = rng.integers(8, cfg.hw - 8, 2)
    yy, xx = np.mgrid[: cfg.hw, : cfg.hw]
    d = np.sqrt((yy - cy) ** 2 + (xx - cx) ** 2)
    mask = np.clip(d // 6, 0, cfg.n_classes - 1).astype(np.int32)
    mask = np.broadcast_to(mask, (b, cfg.hw, cfg.hw))
    img[..., 0] += (mask == 0) * 2.0  # signal channel
    return {"image": jnp.asarray(img), "mask": jnp.asarray(mask)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--ckpt", default="/tmp/unet_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = unet.UNetConfig() if args.full else unet.UNetConfig(
        hw=32, in_ch=2, base=8, depth=2, n_classes=3
    )
    params = unet.init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw.init(params)

    @jax.jit
    def step_fn(params, opt, batch):
        (loss, m), g = jax.value_and_grad(unet.loss_fn, has_aux=True)(
            params, batch, cfg
        )
        params, opt, om = adamw.update(params, g, opt, lr=3e-3, weight_decay=0.0)
        return params, opt, loss

    ck = Checkpointer(args.ckpt, keep=2)
    losses = []
    t0 = time.time()
    for s in range(args.steps):
        params, opt, loss = step_fn(params, opt, synth_batch(cfg, s))
        losses.append(float(loss))
        if s % 20 == 0:
            print(f"step {s:4d} loss {losses[-1]:.4f}")
        if (s + 1) % 50 == 0:
            ck.save_async(s + 1, {"params": params})
    ck.wait()
    print(f"trained {args.steps} steps in {time.time()-t0:.1f}s; "
          f"loss {losses[0]:.3f} -> {np.mean(losses[-10:]):.3f}")
    assert np.mean(losses[-10:]) < losses[0], "loss must decrease"

    # deploy: evaluate float vs MMA-int8 (the FPGA datapath)
    batch = synth_batch(cfg, 10_000)
    logits_f = unet.forward(params, batch["image"], cfg)
    acc_f = float((jnp.argmax(logits_f, -1) == batch["mask"]).mean())
    qcfg = dataclasses.replace(cfg, quant_mode="mma_int8", impl="xla")
    logits_q = unet.forward(params, batch["image"], qcfg)
    acc_q = float((jnp.argmax(logits_q, -1) == batch["mask"]).mean())
    print(f"accuracy float={acc_f:.3f}  mma_int8={acc_q:.3f}")
    for planes in (6, 4):
        pcfg = dataclasses.replace(qcfg, planes=planes)
        lp = unet.forward(params, batch["image"], pcfg)
        acc = float((jnp.argmax(lp, -1) == batch["mask"]).mean())
        print(f"accuracy mma_int8 planes={planes}: {acc:.3f}  (early termination)")


if __name__ == "__main__":
    main()
