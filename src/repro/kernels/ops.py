"""Public wrappers around the Pallas MMA kernels.

Handles: CPU-vs-TPU dispatch (interpret mode on CPU so the kernel body runs
everywhere), padding to MXU-aligned block shapes and arbitrary leading batch
dims.  The plane budget is a static int here; ``core.mma`` folds a traced
budget into the operand before it calls in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .mma_matmul import (
    N_BITS,
    block_shape,
    mma_matmul_pallas,
    mma_matmul_scaled_pallas,
    pad_to,
)


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def mma_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    planes: int = N_BITS,
    signed: bool = True,
    interpret: bool | None = None,
    block: tuple[int, int, int] | None = None,
) -> jax.Array:
    """(..., K) int8 @ (K, N) int8 -> (..., N) int32 via the fused kernel."""
    if interpret is None:
        interpret = _on_cpu()
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    m = 1
    for d in lead:
        m *= d

    bm, bk, bn = block_shape(m, k, n) if block is None else block
    # Shrink blocks for small problems (keeps interpret-mode tests fast);
    # int8 sublane tiling on TPU wants the second-minor dim in multiples of 32.
    bm, bk, bn = min(bm, pad_to(m, 32)), min(bk, pad_to(k, 128)), min(bn, pad_to(n, 128))
    mp, kp, np_ = pad_to(m, bm), pad_to(k, bk), pad_to(n, bn)
    with jax.named_scope("pack"):
        # Zero-padding K is exact: padded w rows are 0, so neither the dot
        # nor the unsigned path's colsum correction moves.
        x2 = jnp.pad(x.reshape(m, k), ((0, mp - m), (0, kp - k)))
        w2 = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    with jax.named_scope("mma"):
        out = mma_matmul_pallas(
            x2, w2, planes=planes, signed=signed, interpret=interpret, bm=bm, bk=bk, bn=bn
        )
    with jax.named_scope("pack"):
        return out[:m, :n].reshape(*lead, n)


def mma_matmul_scaled(
    x: jax.Array,
    w: jax.Array,
    x_scale: jax.Array,
    w_scale: jax.Array,
    *,
    planes: int = N_BITS,
    signed: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Quantized-serving matmul with the dequant epilogue fused in-kernel:
    (..., K) int8 @ (K, N) int8 -> (..., N) f32 scaled by x_scale*w_scale."""
    if interpret is None:
        interpret = _on_cpu()
    lead = x.shape[:-1]
    k, n = x.shape[-1], w.shape[-1]
    m = 1
    for d in lead:
        m *= d
    bm, bk, bn = block_shape(m, k, n)
    mp, kp, np_ = pad_to(m, bm), pad_to(k, bk), pad_to(n, bn)
    with jax.named_scope("pack"):
        x2 = jnp.pad(x.reshape(m, k), ((0, mp - m), (0, kp - k)))
        w2 = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
        ws = jnp.pad(w_scale.reshape(-1), (0, np_ - n))
    with jax.named_scope("mma"):
        out = mma_matmul_scaled_pallas(
            x2, w2, x_scale, ws, planes=planes, signed=signed, interpret=interpret,
            bm=bm, bk=bk, bn=bn,
        )
    with jax.named_scope("pack"):
        return out[:m, :n].reshape(*lead, n)
