"""Serving launcher: continuous-batching engine on a (smoke) config.

    PYTHONPATH=src python -m repro.launch.serve --arch yi_6b --requests 8 \
        [--quant mma_int8 --planes 6]
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.configs.base import QuantConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build
from repro.serve.engine import Engine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi_6b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--quant", default="none")
    ap.add_argument("--planes", type=int, default=8)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch)
    if args.quant != "none":
        cfg = cfg.replace(quant=QuantConfig(mode=args.quant, planes=args.planes))
    mod = build(cfg)
    key = jax.random.PRNGKey(0)
    params = (mod.init_params(key, cfg, max_dec_pos=args.max_seq)
              if cfg.family == "encdec" else mod.init_params(key, cfg))

    eng = Engine(cfg, params, batch=args.batch, max_seq=args.max_seq)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=int(rng.integers(2, 10))),
                    max_new=args.max_new) for i in range(args.requests)]
    done = eng.run(reqs)
    for r in sorted(done, key=lambda r: r.rid):
        print(f"req {r.rid}: {list(r.prompt)[:4]}... -> {r.out}")


if __name__ == "__main__":
    main()
