"""Plain reference of the served datapath, in ``jax.numpy``.

It computes what the configuration states, with no tiles of its own and
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
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_DN = ("NHWC", "HWIO", "NHWC")


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
