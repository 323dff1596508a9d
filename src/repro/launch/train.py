"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch yi_6b --smoke \
        --steps 50 --ckpt-dir /tmp/ck [--resume] [--quant mma_int8]

On a real cluster this binary runs once per host (jax.distributed.initialize
picks up the coordinator from the environment) and the mesh comes from
launch.mesh.make_production_mesh; with --smoke it runs the reduced config on
local devices — the same code path the restart/elasticity tests exercise.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, get_smoke_config
from repro.configs.base import QuantConfig
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build
from repro.optim import adamw
from repro.train import train_step as ts
from repro.train import trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--quant", default="none")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.quant != "none":
        cfg = cfg.replace(quant=QuantConfig(mode=args.quant))
    mod = build(cfg)
    key = jax.random.PRNGKey(0)
    params = (mod.init_params(key, cfg, max_dec_pos=args.seq + 1)
              if cfg.family == "encdec" else mod.init_params(key, cfg))
    state = {"params": params, "opt": adamw.init(params)}

    extras = {}
    if cfg.family == "vlm":
        extras["patches"] = (cfg.vlm_patches, cfg.d_model)
    if cfg.family == "encdec":
        extras["frames"] = (cfg.enc_seq, cfg.d_model)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
                      extras=extras or None)
    tcfg = trainer.TrainerConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                                 ckpt_dir=args.ckpt_dir)

    start = 0
    if args.resume:
        restored, start = trainer.resume(jax.eval_shape(lambda: state), tcfg)
        if restored is not None:
            state = restored
            print(f"resumed from step {start}")

    step_fn = jax.jit(lambda st, b: ts.train_step(st, b, cfg))
    state, metrics = trainer.train(state, step_fn, dcfg, tcfg, start_step=start)
    print(f"final loss {metrics['losses'][-1]:.4f}; "
          f"stragglers flagged: {metrics['stragglers']}")


if __name__ == "__main__":
    main()
