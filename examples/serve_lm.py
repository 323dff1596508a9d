"""Batched LM serving through the continuous-batching engine, with the MMA
int8 datapath and MSDF dynamic precision: either a uniform plane budget
(--planes) or a per-layer schedule derived from the served weights at an
error target (--target-rel-err, overrides --planes).

    PYTHONPATH=src python examples/serve_lm.py [--arch yi_6b] [--quant]
        [--planes 6 | --target-rel-err 0.01]
"""
import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.configs.base import QuantConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build
from repro.serve.engine import Engine, Request, lm_schedule_from_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--quant", action="store_true")
    ap.add_argument("--planes", type=int, default=8)
    ap.add_argument("--target-rel-err", type=float, default=None,
                    help="build a per-layer PlaneSchedule from the weights")
    args = ap.parse_args()
    enable_compile_cache()
    if args.target_rel_err is not None and not args.quant:
        ap.error("--target-rel-err requires --quant (schedules drive the "
                 "mma_int8 datapath)")

    cfg = get_smoke_config(args.arch)
    if args.quant:
        cfg = cfg.replace(quant=QuantConfig(mode="mma_int8", planes=args.planes))
    mod = build(cfg)
    params = (mod.init_params(jax.random.PRNGKey(0), cfg, max_dec_pos=128)
              if cfg.family == "encdec"
              else mod.init_params(jax.random.PRNGKey(0), cfg))

    sched_desc = f"planes={args.planes}"
    if args.quant and args.target_rel_err is not None:
        sched = lm_schedule_from_params(params, cfg, args.target_rel_err)
        cfg = cfg.replace(quant=dataclasses.replace(
            cfg.quant, plane_schedule=tuple(sched.planes)))
        sched_desc = sched.describe()

    eng = Engine(cfg, params, batch=4, max_seq=64)
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=rng.integers(3, 9)),
                max_new=8)
        for i in range(6)
    ]
    done = eng.run(reqs)
    for r in sorted(done, key=lambda r: r.rid):
        print(f"req {r.rid}: prompt[{len(r.prompt)}] -> {r.out}")
    assert len(done) == len(reqs) and all(len(r.out) == 8 for r in done)
    print(f"served {len(done)} requests, quant={'mma_int8' if args.quant else 'none'}"
          f" {sched_desc}")


if __name__ == "__main__":
    main()
