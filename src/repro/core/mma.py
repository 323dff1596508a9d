"""Merged multiply-add (MMA) — the public API of the paper's technique.

``mma_dot`` computes an exact (or plane-truncated) int8 x int8 -> int32
matmul through one of four datapaths:

  impl='pallas'   the fused Pallas kernel (kernels/mma_matmul.py): the
                  plane-truncated operand in one int8 MXU pass per block,
                  accumulator held in VMEM — the TPU-native merged unit
                  (single "initial delay" = one HBM read of x and w).
                                                          [paper's proposal]
  impl='xla'      same recurrence in pure XLA (lax.scan over planes).
  impl='cascade'  per-plane partials materialized then tree-reduced — the
                  un-merged baseline with per-stage round-trips. [baseline]
  impl='int8'     direct int8 dot_general — the bit-parallel baseline.
                                                          [baseline, Zhang'15]

``mma_linear`` wraps it as a float-in/float-out quantized linear layer
(dynamic per-tensor activation scale, per-channel weight scale) used by the
model zoo when ``quant.mode == 'mma_int8'``.
"""
from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

from . import bitplane, quant

Impl = Literal["pallas", "xla", "cascade", "int8"]


def mma_dot(
    x_int8: jax.Array,
    w_int8: jax.Array,
    *,
    planes: int | jax.Array = bitplane.N_BITS,
    impl: Impl = "xla",
    signed: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """(..., K) int8 @ (K, N) int8 -> (..., N) int32, via the MMA datapath.

    ``planes`` is the per-call precision budget: a static int specializes the
    serial datapaths to that many MSB planes; a traced scalar applies the
    same truncation on the data side (schedule-in-scan form, see
    ``bitplane.normalize_planes``).
    """
    x_int8, planes = bitplane.normalize_planes(x_int8, planes, signed=signed)
    if impl == "int8":
        if planes != bitplane.N_BITS:
            # bit-parallel hardware has no serial early exit, but the *value*
            # of a truncated result is still computable: fold the truncation
            # into the operand and run the full-width matmul.
            x_int8 = bitplane.truncate_to_planes(x_int8, planes, signed=signed)
        return jax.lax.dot_general(
            x_int8,
            w_int8,
            (((x_int8.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
    if impl == "xla":
        return bitplane.bitplane_matmul(x_int8, w_int8, planes=planes, signed=signed)
    if impl == "cascade":
        return bitplane.bitplane_matmul_cascade(
            x_int8, w_int8, planes=planes, signed=signed
        )
    if impl == "pallas":
        from repro.kernels import ops  # local import: kernels dep is optional

        return ops.mma_matmul(
            x_int8, w_int8, planes=planes, signed=signed, interpret=interpret
        )
    raise ValueError(f"unknown impl {impl!r}")


def mma_linear(
    x: jax.Array,
    w: jax.Array,
    *,
    planes: int | jax.Array = bitplane.N_BITS,
    impl: Impl = "xla",
    w_q: quant.QTensor | None = None,
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
    wq = w_q if w_q is not None else quant.quantize_weights(w, channel_axis=-1)
    out_i32 = mma_dot(xq.values, wq.values, planes=planes, impl=impl)
    out = out_i32.astype(jnp.float32) * quant.quantized_matmul_scale(xq.scale, wq.scale)
    # Straight-through estimator: forward = quantized, backward = float.
    full = x @ w
    return full + jax.lax.stop_gradient(out - full)
