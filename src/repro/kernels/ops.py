"""Jitted public wrappers around the Pallas MMA kernels.

Handles: CPU-vs-TPU dispatch (interpret mode on CPU so the kernel body runs
everywhere), padding to MXU-aligned block shapes, arbitrary leading batch
dims, and the KPB-style conv mapping (taps folded into the contraction dim —
the Pallas analogue of grouping k*k MMA units into a Kernel Processing
Block).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from .mma_matmul import BK, BM, BN, N_BITS, mma_matmul_pallas


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(v: int, b: int) -> int:
    return (v + b - 1) // b * b


def _normalize_planes(x, planes, *, signed: bool):
    """Static plane budgets specialize the kernel (cached variant per count,
    fewer unrolled MXU steps, validated 1..8); traced budgets fold into the
    data via the exact bit-mask identity and run the full-width variant."""
    from repro.core import bitplane  # lazy: core.mma imports this module lazily

    return bitplane.normalize_planes(x, planes, signed=signed)


def mma_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    planes: int | jax.Array = N_BITS,
    signed: bool = True,
    interpret: bool | None = None,
    block: tuple[int, int, int] | None = None,
) -> jax.Array:
    """(..., K) int8 @ (K, N) int8 -> (..., N) int32 via the fused kernel."""
    if interpret is None:
        interpret = _on_cpu()
    x, planes = _normalize_planes(x, planes, signed=signed)
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    m = 1
    for d in lead:
        m *= d

    bm, bk, bn = block if block is not None else (BM, BK, BN)
    # Shrink blocks for small problems (keeps interpret-mode tests fast);
    # int8 sublane tiling on TPU wants the second-minor dim in multiples of 32.
    bm, bk, bn = min(bm, _pad_to(m, 32)), min(bk, _pad_to(k, 128)), min(bn, _pad_to(n, 128))
    mp, kp, np_ = _pad_to(m, bm), _pad_to(k, bk), _pad_to(n, bn)
    with jax.named_scope("pack"):
        # Zero-padding K is exact: padded w rows are 0, so both the dot and
        # the signed colsum correction are unaffected (see kernel docstring).
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
    planes: int | jax.Array = N_BITS,
    signed: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Quantized-serving matmul with the dequant epilogue fused in-kernel:
    (..., K) int8 @ (K, N) int8 -> (..., N) f32 scaled by x_scale*w_scale."""
    from .mma_matmul import mma_matmul_scaled_pallas

    if interpret is None:
        interpret = _on_cpu()
    x, planes = _normalize_planes(x, planes, signed=signed)
    lead = x.shape[:-1]
    k, n = x.shape[-1], w.shape[-1]
    m = 1
    for d in lead:
        m *= d
    bm, bk, bn = min(BM, _pad_to(m, 32)), min(BK, _pad_to(k, 128)), min(BN, _pad_to(n, 128))
    mp, kp, np_ = _pad_to(m, bm), _pad_to(k, bk), _pad_to(n, bn)
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


def mma_conv2d(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    pad: int = 1,
    pad_mode: str = "zero",
    planes: int | jax.Array = N_BITS,
    signed: bool = True,
    interpret: bool | None = None,
    impl: str = "pallas",
) -> jax.Array:
    """KPB conv: NHWC int8 x (kh, kw, Cin, Cout) int8 -> NHWC int32.

    The k*k spatial taps fold into the contraction dim exactly like the KPB
    groups k*k MMA units over one window (Eq. 1): patches (n*oh*ow, kh*kw*cin)
    @ weights (kh*kw*cin, cout).  ``impl`` selects the matmul datapath:
    'pallas' (the fused kernel), or any of the ``core.mma`` paths
    ('xla' | 'cascade' | 'int8') for baselines and CPU-only runs.

    ``pad_mode`` selects what fills the ``pad`` border ring: 'zero' (the
    FBGEMM/XLA SAME convention), or 'edge' / 'reflect' (replicate /
    mirror the boundary row).  Non-zero modes serve halo-free image tiles
    (``repro.segserve``): a tile cut from a larger image has real content
    past its edge, and replicating the boundary row approximates it far
    better than a hard zero seam.
    """
    n, h, w_, c = x.shape
    kh, kw, cin, cout = w.shape
    assert c == cin
    pad_widths = ((0, 0), (pad, pad), (pad, pad), (0, 0))
    oh = (h + 2 * pad - kh) // stride + 1
    ow = (w_ + 2 * pad - kw) // stride + 1
    with jax.named_scope("im2col"):
        if pad_mode == "zero":
            xp = jnp.pad(x, pad_widths)
        elif pad_mode in ("edge", "reflect"):
            xp = jnp.pad(x, pad_widths, mode=pad_mode)
        else:
            raise ValueError(f"unknown pad_mode {pad_mode!r}")
        patches = [
            xp[:, i : i + oh * stride : stride, j : j + ow * stride : stride, :]
            for i in range(kh)
            for j in range(kw)
        ]
        patches = jnp.concatenate(patches, axis=-1)
        wm = w.reshape(kh * kw * cin, cout)
        pm = patches.reshape(-1, kh * kw * cin)
    if impl == "pallas":
        out = mma_matmul(
            pm, wm, planes=planes, signed=signed, interpret=interpret
        )
    else:
        from repro.core import mma  # lazy: core.mma imports this module lazily

        out = mma.mma_dot(pm, wm, planes=planes, signed=signed, impl=impl)
    return out.reshape(n, oh, ow, cout)
