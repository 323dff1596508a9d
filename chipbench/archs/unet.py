"""The served U-Net: its weights, its engine, its plain reference, its tile
plan and its layer counts (the interface of ``chipbench/archs/__init__.py``).

A configuration's ``model`` holds ``in_ch``, ``base``, ``depth``,
``convs_per_stage`` and ``n_classes``; ``plane_schedule`` has one budget
per 3x3 conv in forward order.

Weights are the benchmark's: drawn on the device in one jitted call from
the seed, float32 as the server takes them (it quantizes per forward).
Convs are He-normal, truncated at two standard deviations; biases are
small and non-zero so that the bias path and the zero tiles that pad a
short micro-batch both count.

The plain reference of the served datapath is in ``jax.numpy``.  It
computes what the configuration states, with no tiles of its own and
nothing of the program: each conv quantizes its input to int8 with one
scale over the whole tensor it is given, and its weights to int8 with one
scale per output channel; the activation keeps the class's plane budget
(its top ``planes`` bits of ``q + 128``); an exact int8 x int8 -> int32
convolution follows, then the float scale, the bias and a ReLU.  Pool,
nearest upsample, skip concat and the 1x1 head are float32, the head at
``Precision.HIGHEST``.

``bits=4`` is the control: the same arithmetic with every int8 quantizer
replaced by int4 (the int4 value placed in the top four bits, so a budget
of five or more planes keeps all of it).

The shape arithmetic is copied from the program (``repro.segserve.tiling``
and ``repro.core.cycle_model.unet_conv_layers``) so that a change there
cannot move what the benchmark counts or what its reference computes: the
exact halo and the tile plan of an image, and the 3x3 convs of a forward
with their operations and bytes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import rng
from chipbench.geometry import Conv, Tile

_WEIGHT_TAG = 0x3E16475
_DN = ("NHWC", "HWIO", "NHWC")


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


# ------------------------------------------------------------ the reference


def _quant(x, bits: int, axes):
    """Symmetric quantization to ``bits`` placed in the top bits of an
    int8; returns the int32 values and the scale of one int8 step."""
    qmax = 2.0 ** (bits - 1) - 1.0
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=axes is not None)
    scale = jnp.maximum(amax, 1e-8) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int32)
    step = 2 ** (8 - bits)
    return q * step, scale / step if step > 1 else scale


def _conv3x3_int(x, w):
    """Exact SAME 3x3 convolution of int8 tensors, as the sum over the
    nine taps of int8 x int8 -> int32 products."""
    n, h, wd, _ = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    acc = None
    for i in range(3):
        for j in range(3):
            t = lax.dot_general(xp[:, i:i + h, j:j + wd, :], w[i, j],
                                (((3,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
            acc = t if acc is None else acc + t
    return acc


def _conv(p, x, planes, bits: int):
    q, xs = _quant(x, bits, None)
    u = q + 128
    keep = ~(jnp.left_shift(jnp.int32(1), 8 - planes) - 1)
    q = ((u & keep) - 128).astype(jnp.int8)
    wq, ws = _quant(p["w"], bits, (0, 1, 2))
    acc = _conv3x3_int(q, wq.astype(jnp.int8))
    out = acc.astype(jnp.float32) * (xs * jnp.squeeze(ws))
    return jax.nn.relu(out + p["b"])


@functools.partial(jax.jit, static_argnames="bits")
def forward(params, x, planes, *, bits: int = 8):
    """x: (N, H, W, C) float32 windows -> (N, H, W, classes) logits.
    ``planes``: (L,) int32, one budget per 3x3 conv in forward order."""
    li = 0

    def conv(p, h):
        nonlocal li
        out = _conv(p, h, planes[li], bits)
        li += 1
        return out

    skips, h = [], x
    for stage in params["enc"]:
        for p in stage:
            h = conv(p, h)
        skips.append(h)
        h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 2, 2, 1),
                              (1, 2, 2, 1), "VALID")
    for p in params["bottleneck"]:
        h = conv(p, h)
    for d, stage in enumerate(params["dec"]):
        h = jnp.repeat(jnp.repeat(h, 2, axis=1), 2, axis=2)
        h = jnp.concatenate([skips[-(d + 1)], h], axis=-1)
        for p in stage:
            h = conv(p, h)
    out = lax.conv_general_dilated(
        h, params["head"]["w"], (1, 1), "SAME", dimension_numbers=_DN,
        precision=lax.Precision.HIGHEST)
    return out + params["head"]["b"]


# ---------------------------------------------------------------- tile plan


def halo_for(depth: int, convs_per_stage: int) -> int:
    """Exact halo (input pixels per side) of an artificial tile edge,
    rounded up to a multiple of ``2**depth``."""
    m, skips = 0, []
    for _ in range(depth):
        m += convs_per_stage
        skips.append(m)
        m = -(-m // 2)
    m += convs_per_stage
    for level in reversed(range(depth)):
        m = max(2 * m, skips[level]) + convs_per_stage
    mult = 2**depth
    return -(-max(m, 1) // mult) * mult


def canvas_shape(h: int, w: int, conf: dict) -> tuple[int, int]:
    mult = 2 ** conf["model"]["depth"]
    return (-(-h // mult) * mult, -(-w // mult) * mult)


def plan(h: int, w: int, conf: dict) -> list[Tile]:
    """Cores of ``tile`` striding the padded canvas, each dilated by the
    halo and clipped to the canvas."""
    tile, halo = conf["tile"], conf["halo"]
    ph, pw = canvas_shape(h, w, conf)
    out = []
    for cy in range(0, ph, tile):
        ch = min(tile, ph - cy)
        for cx in range(0, pw, tile):
            cw = min(tile, pw - cx)
            out.append(Tile(max(0, cy - halo), max(0, cx - halo),
                            min(ph, cy + ch + halo), min(pw, cx + cw + halo),
                            (cy, cx, cy + ch, cx + cw)))
    return out


def canvas(image: np.ndarray, conf: dict) -> np.ndarray:
    ph, pw = canvas_shape(image.shape[0], image.shape[1], conf)
    return np.pad(image.astype(np.float32),
                  ((0, ph - image.shape[0]), (0, pw - image.shape[1]), (0, 0)))


# ------------------------------------------------------------------- layers


def layers(model: dict, n: int, h: int, w: int) -> list[Conv]:
    """The 3x3 convs of one forward over ``n`` windows of ``h x w``, in
    forward order (encoder, bottleneck, decoder)."""
    c, base, depth = model["in_ch"], model["base"], model["depth"]
    per = model["convs_per_stage"]
    out, skips = [], []
    for d in range(depth):
        cout = base * 2**d
        out.append(Conv(n, h, w, c, cout))
        out += [Conv(n, h, w, cout, cout)] * (per - 1)
        skips.append(cout)
        c, h, w = cout, h // 2, w // 2
    cout = base * 2**depth
    out.append(Conv(n, h, w, c, cout))
    out += [Conv(n, h, w, cout, cout)] * (per - 1)
    c = cout
    for d in reversed(range(depth)):
        h, w, cout = h * 2, w * 2, skips[d]
        out.append(Conv(n, h, w, cout + c, cout))
        out += [Conv(n, h, w, cout, cout)] * (per - 1)
        c = cout
    return out


def tiny(conf: dict, sizes: dict) -> dict:
    """``conf`` at a size the CPU can run: depth 2 and ``sizes``'s base
    width, image, tile and pool; its convs per stage, classes, channels,
    datapath and schedule kind kept, the halo the exact one."""
    m = {**conf["model"], "base": sizes["base"], "depth": 2}
    n_convs = 2 * m["depth"] * m["convs_per_stage"] + m["convs_per_stage"]
    return {**conf, "model": m, "plane_schedule": conf["plane_schedule"][:1] * n_convs,
            "tile": sizes["tile"], "halo": halo_for(2, m["convs_per_stage"]),
            "image": sizes["image"] + conf["image"][2:], "pool": sizes["pool"]}
