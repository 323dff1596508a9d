"""The system under test, built from a configuration file and a seed.

Weights are the benchmark's: drawn on the device in one jitted call from
the seed, float32 as the server takes them (it quantizes per forward).
Convs are He-normal, truncated at two standard deviations; biases are
small and non-zero so that the bias path and the zero tiles that pad a
short micro-batch both count.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import rng

_WEIGHT_TAG = 0x3E16475


def _shapes(model: dict) -> dict:
    """Conv weight shapes in the program's parameter layout."""
    c, base, depth = model["in_ch"], model["base"], model["depth"]
    per = model["convs_per_stage"]
    enc, dec, skips = [], [], []
    for d in range(depth):
        cout = base * 2**d
        enc.append([(3, 3, c, cout)] + [(3, 3, cout, cout)] * (per - 1))
        skips.append(cout)
        c = cout
    cout = base * 2**depth
    bottleneck = [(3, 3, c, cout)] + [(3, 3, cout, cout)] * (per - 1)
    c = cout
    for d in reversed(range(depth)):
        cout = skips[d]
        dec.append([(3, 3, cout + c, cout)] + [(3, 3, cout, cout)] * (per - 1))
        c = cout
    return {"enc": enc, "bottleneck": bottleneck, "dec": dec,
            "head": (1, 1, c, model["n_classes"])}


def make_params(model: dict, seed: int):
    """Weights for ``model`` from ``seed``, made on the default device."""
    shapes = _shapes(model)
    flat, tree = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda s: isinstance(s, tuple))

    def init(key):
        leaves = []
        for i, s in enumerate(flat):
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            std = np.sqrt(2.0 / (s[0] * s[1] * s[2]))
            w = jax.random.truncated_normal(kw, -2.0, 2.0, s, jnp.float32) * std
            b = jax.random.normal(kb, (s[3],), jnp.float32) * 1e-3
            leaves.append({"w": w, "b": b})
        return jax.tree_util.tree_unflatten(tree, leaves)

    h = rng.mix(seed, _WEIGHT_TAG)
    data = jnp.asarray([h >> 32, h & 0xFFFFFFFF], jnp.uint32)
    return jax.jit(lambda d: init(jax.random.wrap_key_data(d)))(data)


def unet_config(conf: dict):
    from repro.models import unet

    m, dp = conf["model"], conf["datapath"]
    return unet.UNetConfig(
        hw=conf["image"][0], in_ch=m["in_ch"], base=m["base"],
        depth=m["depth"], convs_per_stage=m["convs_per_stage"],
        n_classes=m["n_classes"], quant_mode=dp["quant_mode"],
        impl=dp["impl"], plane_schedule=tuple(conf["plane_schedule"]))


def make_engine(conf: dict, params):
    """``SegEngine`` at the configuration's tile and halo; scheduling
    settings (batch, slots, priority) stay at the engine's defaults."""
    from repro.segserve import SegEngine

    return SegEngine(unet_config(conf), params, tile=conf["tile"],
                     halo=conf["halo"], adaptive=conf["datapath"]["adaptive"])

