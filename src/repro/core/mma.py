"""Merged multiply-add (MMA) — the public API of the paper's technique.

``mma_dot`` computes an exact (or plane-truncated) int8 x int8 -> int32
matmul.  Truncating to the ``planes`` most significant digits of the offset
activation is folded into the operand (``bitplane.truncate_to_planes``), so
both datapaths compute the same integers as ``kernels/ref.py``'s oracle:

  impl='pallas'   the fused Pallas kernel (kernels/mma_matmul.py): the
                  plane-truncated operand in one int8 MXU pass per block,
                  accumulator held in VMEM — the TPU-native merged unit
                  (single "initial delay" = one HBM read of x and w).
                  Interpret mode off the TPU.         [paper's proposal]
  impl='xla'      one int8 ``dot_general`` on the truncated operand.

The paper's per-plane Horner recurrence and its un-merged cascade stay in
``core.bitplane`` as the algorithm and its baseline; no config selects them.

``mma_conv2d`` maps a 3x3 conv onto ``mma_dot`` the way the paper's Kernel
Processing Block groups k*k MMA units over one window.  ``mma_linear`` wraps
``mma_dot`` as a float-in/float-out quantized linear layer (dynamic
activation scale, per-channel weight scale) used by the model zoo when
``quant.mode == 'mma_int8'``; ``mma_linear_int8`` is its serving form on
pre-quantized weights.  This module is the only one that reads ``impl``.
"""
from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import ops

from . import bitplane, quant

Impl = Literal["pallas", "xla"]


def mma_dot(
    x_int8: jax.Array,
    w_int8: jax.Array,
    *,
    planes: int | jax.Array = bitplane.N_BITS,
    impl: Impl = "xla",
) -> jax.Array:
    """(..., K) int8 @ (K, N) int8 -> (..., N) int32, via the MMA datapath.

    ``planes`` is the per-call precision budget: a static int specializes
    the kernel to that many MSB planes; a traced scalar applies the same
    truncation on the data side (schedule-in-scan form, see
    ``bitplane.normalize_planes``).
    """
    x_int8, planes = bitplane.normalize_planes(x_int8, planes)
    if impl == "pallas":
        return ops.mma_matmul(x_int8, w_int8, planes=planes)
    if impl == "xla":
        if planes != bitplane.N_BITS:
            x_int8 = bitplane.truncate_to_planes(x_int8, planes)
        return jax.lax.dot_general(
            x_int8,
            w_int8,
            (((x_int8.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
    raise ValueError(f"unknown impl {impl!r}: expected 'pallas' or 'xla'")


def mma_conv2d(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    pad: int = 1,
    planes: int | jax.Array = bitplane.N_BITS,
    impl: Impl = "xla",
) -> jax.Array:
    """KPB conv: NHWC int8 x (kh, kw, Cin, Cout) int8 -> NHWC int32.

    The k*k spatial taps fold into the contraction dim exactly like the KPB
    groups k*k MMA units over one window (Eq. 1): patches (n*oh*ow, kh*kw*cin)
    @ weights (kh*kw*cin, cout), zero-padded by ``pad`` on each side.
    """
    n, h, w_, c = x.shape
    kh, kw, cin, cout = w.shape
    assert c == cin
    oh = (h + 2 * pad - kh) // stride + 1
    ow = (w_ + 2 * pad - kw) // stride + 1
    with jax.named_scope("im2col"):
        xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        patches = [
            xp[:, i : i + oh * stride : stride, j : j + ow * stride : stride, :]
            for i in range(kh)
            for j in range(kw)
        ]
        patches = jnp.concatenate(patches, axis=-1)
        wm = w.reshape(kh * kw * cin, cout)
        pm = patches.reshape(-1, kh * kw * cin)
    out = mma_dot(pm, wm, planes=planes, impl=impl)
    return out.reshape(n, oh, ow, cout)


def mma_linear(
    x: jax.Array,
    w: jax.Array,
    *,
    planes: int | jax.Array = bitplane.N_BITS,
    impl: Impl = "xla",
    batch_axis: int | None = None,
) -> jax.Array:
    """Quantized linear: float x (..., K) @ float w (K, N) -> float (..., N).

    The forward runs int8 through the MMA datapath; gradients flow via the
    straight-through estimator (the quantization is applied with
    stop_gradient so training sees the float path).  ``batch_axis`` selects
    per-row activation scales (see :func:`quant.quantize_acts`) — the
    serving path passes the batch axis so one slot's magnitudes never move
    another slot's quantization grid.
    """
    xq = quant.quantize_acts(x, batch_axis=batch_axis)
    wq = quant.quantize_weights(w, channel_axis=-1)
    out_i32 = mma_dot(xq.values, wq.values, planes=planes, impl=impl)
    out = out_i32.astype(jnp.float32) * quant.quantized_matmul_scale(xq.scale, wq.scale)
    # Straight-through estimator: forward = quantized, backward = float.
    full = x @ w
    return full + jax.lax.stop_gradient(out - full)


def mma_linear_int8(
    x: jax.Array,
    w_q: jax.Array,
    w_scale: jax.Array,
    *,
    planes: int | jax.Array = bitplane.N_BITS,
    impl: Impl = "xla",
    batch_axis: int | None = None,
) -> jax.Array:
    """Serving linear on pre-quantized weights: float x (..., K) @ int8
    ``w_q`` (K, N) with per-channel ``w_scale`` (N,) -> float32 (..., N).

    ``pallas`` dequantizes in the kernel's fused epilogue, which takes one
    activation scale per call, so it ignores ``batch_axis``; ``xla`` takes
    per-row activation scales along ``batch_axis``.
    """
    if impl == "pallas":
        xq = quant.quantize_acts(x)
        xv, planes = bitplane.normalize_planes(xq.values, planes)
        return ops.mma_matmul_scaled(xv, w_q, xq.scale, w_scale, planes=planes)
    xq = quant.quantize_acts(x, batch_axis=batch_axis)
    out_i32 = mma_dot(xq.values, w_q, planes=planes, impl=impl)
    return out_i32.astype(jnp.float32) * (xq.scale * w_scale)
