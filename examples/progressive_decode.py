"""MSDF dynamic precision on a real LM: per-layer plane schedules instead of
one global knob — the paper's 'future work' (early termination) plus MINT's
per-layer precision assignment, realized as a serving feature.

Builds a :class:`PlaneSchedule` from the served weights at several error
targets, installs it via ``cfg.quant.plane_schedule`` (it rides the layer
scan as data), and measures logit fidelity vs digit-serial work kept.

    PYTHONPATH=src python examples/progressive_decode.py
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.configs.base import QuantConfig
from repro.core.plane_schedule import PlaneSchedule
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build
from repro.serve.engine import lm_schedule_from_params


def main():
    enable_compile_cache()
    cfg = get_smoke_config("yi_6b")
    mod = build(cfg)
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (4, 48)), jnp.int32)

    ref = mod.forward(params, tokens, cfg).astype(jnp.float32)
    ref_top1 = jnp.argmax(ref, -1)

    def fidelity(qcfg):
        out = mod.forward(params, tokens, qcfg).astype(jnp.float32)
        agree = float((jnp.argmax(out, -1) == ref_top1).mean())
        rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
        return agree, rel

    print("== uniform schedules (the old global knob, as a schedule) ==")
    print("planes | digit work kept | top1 agreement | max rel logit err")
    for planes in (8, 6, 4, 3):
        sched = PlaneSchedule.uniform(planes, cfg.n_layers)
        qcfg = cfg.replace(
            quant=QuantConfig(mode="mma_int8", plane_schedule=tuple(sched.planes))
        )
        agree, rel = fidelity(qcfg)
        print(f"  {planes}    |      {sched.arithmetic_fraction():.2f}       "
              f"|     {agree:.3f}      | {rel:.4f}")

    print("== per-layer schedules from the served weights ==")
    print("target | schedule | digit work kept | top1 | max rel logit err")
    for tgt in (0.05, 0.01, 0.001):
        sched = lm_schedule_from_params(params, cfg, tgt)
        qcfg = cfg.replace(
            quant=dataclasses.replace(
                QuantConfig(mode="mma_int8"), plane_schedule=tuple(sched.planes)
            )
        )
        agree, rel = fidelity(qcfg)
        print(f" {tgt:<6}| {list(sched.planes)} | {sched.arithmetic_fraction():.2f} "
              f"| {agree:.3f} | {rel:.4f}")


if __name__ == "__main__":
    main()
