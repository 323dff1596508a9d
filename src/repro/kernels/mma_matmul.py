"""Pallas TPU kernel: the merged multiply-add (MMA) as one fused int8 matmul.

FPGA -> TPU mapping (DESIGN.md Sec. 2).  The FPGA MMA streams activation bits
MSB-first through an AND array and keeps a left-shifted *residual* inside the
unit, so the whole inner product pays one initial delay.  The TPU analogue of
"initial delay" is an HBM round-trip: an un-fused bit-plane implementation
writes 8 plane partial products to HBM and re-reads them to reduce.  This
kernel keeps the int32 accumulator in VMEM scratch for the whole (bm, bn)
output tile: x and w are read from HBM once per tile, partial sums never
leave VMEM — the merged pipeline.

The Horner sum over the ``planes`` most significant digits of the offset
activation ``u = x + 128`` collapses into one product on a masked operand
(``core.bitplane.truncate_to_planes``):

    sum_i 2^(7-i) * plane_i(u) @ w  -  128 * colsum(w)  ==  (x & m_p) @ w,
    m_p = -(1 << (8 - planes))  as int8

so the kernel makes one MXU pass per block whatever ``planes`` is.  Datapath
per grid step (m, n, k):
    x'   = x_block & m_p                   (VPU, one AND per packed int32
                                            word of four int8 lanes; no mask
                                            at planes = 8)
    out += x' @ w_block                    (MXU, int8 x int8 -> int32)
and the int32 accumulator is written to HBM on the last k step.

Exactness: x' and w are plain int8, every product and partial sum is an
int32 integer, so the result is bit-exact for any block shape and for
K < 2^17 (|x' * w| <= 2^14).  Unsigned activations (``signed=False``, x
holds the bits of u in 0..255) take the offset form
``((u & m_p) - 128) @ w + 128 * colsum(w)``, where ``(u & m_p) - 128`` is
``x' ^ 0x80`` read as int8.  dimension_semantics marks m, n parallel and k
arbitrary (sequential accumulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N_BITS = 8

# Default MXU-aligned tile shapes for callers that give none; ``ops`` picks
# its blocks with ``block_shape``.
BM, BK, BN = 128, 512, 128
# With one int8 pass per block the kernel is bound by HBM, not the MXU: x is
# re-read once per column block and w once per row block, so blocks are as
# large as the problem and VMEM allow.  At the largest, 512 x 2048 int8 of x,
# 2048 x 512 int8 of w and 512 x 512 int32 of accumulator and output, double
# buffered, take about 7 MiB of v5e's 16 MiB.
BM_MAX, BK_WHOLE, BN_MAX = 512, 2048, 512


def pad_to(v: int, b: int) -> int:
    return (v + b - 1) // b * b


def block_shape(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(bm, bk, bn) for an (m, k) @ (k, n) problem, from its shape alone.

    Rows come in blocks of 512 (fewer re-reads of w), or the whole of a
    smaller m rounded up to int8's 32-row tile.  A contraction of at most
    2048, rounded up to the 128-lane tile, is one block, so x is read once
    for all column blocks; a longer one goes in blocks of 512.  Columns,
    rounded up to 128, are one block up to 512, so x is read once per row
    block.
    """
    bm = min(BM_MAX, pad_to(m, 32))
    bk = pad_to(k, 128) if pad_to(k, 128) <= BK_WHOLE else BK
    bn = min(pad_to(n, 128), BN_MAX)
    return bm, bk, bn


def _byte_word(byte: int) -> int:
    """``byte`` repeated in the four bytes of an int32 word (as a signed int)."""
    word = (byte & 0xFF) * 0x01010101
    return word - (1 << 32) if word >= 1 << 31 else word


def _mma_kernel(x_ref, w_ref, *refs, planes: int, signed: bool, n_k: int,
                scaled: bool):
    if scaled:
        xs_ref, ws_ref, out_ref, acc_ref = refs
    else:
        out_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    if planes < N_BITS or not signed:
        # mask on int32 words: four int8 lanes per VPU op, no widening
        words = pltpu.bitcast(x, jnp.int32)
        if planes < N_BITS:
            words = words & _byte_word(-(1 << (N_BITS - planes)))
        if not signed:
            words = words ^ _byte_word(0x80)
        x = pltpu.bitcast(words, jnp.int8)

    acc = jax.lax.dot_general(
        x, w_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    if not signed:
        acc = acc + 128 * jnp.sum(w_ref[...].astype(jnp.int32), axis=0, keepdims=True)
    acc_ref[...] += acc

    @pl.when(k == n_k - 1)
    def _flush():
        if scaled:
            # fused dequant epilogue (the OGF of the TPU datapath): the int32
            # accumulator leaves VMEM already in float form — no extra HBM
            # pass for the x_scale * w_scale[n] multiply.
            out_ref[...] = (
                acc_ref[...].astype(jnp.float32)
                * xs_ref[0] * ws_ref[...][0][None, :]
            )
        else:
            out_ref[...] = acc_ref[...]


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )


def _mma_matmul_impl(
    x: jax.Array,
    w: jax.Array,
    *,
    planes: int,
    signed: bool,
    interpret: bool,
    bm: int,
    bk: int,
    bn: int,
) -> jax.Array:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and m % bm == 0 and k % bk == 0 and n % bn == 0, (
        f"unpadded shapes {x.shape} x {w.shape} for blocks {(bm, bk, bn)}"
    )
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)

    kernel = functools.partial(
        _mma_kernel, planes=planes, signed=signed, n_k=n_k, scaled=False
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(x, w)


def _mma_matmul_scaled_impl(
    x: jax.Array,
    w: jax.Array,
    x_scale: jax.Array,
    w_scale: jax.Array,
    *,
    planes: int,
    signed: bool,
    interpret: bool,
    bm: int,
    bk: int,
    bn: int,
) -> jax.Array:
    m, k = x.shape
    _, n = w.shape
    assert m % bm == 0 and k % bk == 0 and n % bn == 0
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    kernel = functools.partial(
        _mma_kernel, planes=planes, signed=signed, n_k=n_k, scaled=True
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(x, w, x_scale.reshape(1), w_scale.reshape(1, n))


@functools.lru_cache(maxsize=None)
def plane_variant(
    planes: int,
    signed: bool = True,
    *,
    scaled: bool = False,
    interpret: bool = False,
    bm: int = BM,
    bk: int = BK,
    bn: int = BN,
):
    """Cached jitted kernel variant specialized to one plane budget.

    The plane count is a *specialization axis*: it fixes the mask applied to
    each x block before the one int8 MXU pass (none at 8 planes).  Every
    variant does the same MXU work; what a smaller budget buys on the TPU is
    a coarser operand, while the digit-serial latency and energy it saves on
    the FPGA come from the models in ``core/``.  Each distinct (planes,
    signed, scaled, block) tuple compiles once and is reused across layers
    and calls, under the trace name ``mma_matmul[_scaled]_pallas_p<planes>``
    (``u`` appended when unsigned); ``plane_variant.cache_info()`` exposes
    the variant table for tests and benchmarks.
    """
    impl = _mma_matmul_scaled_impl if scaled else _mma_matmul_impl
    fn = functools.partial(
        impl, planes=planes, signed=signed, interpret=interpret,
        bm=bm, bk=bk, bn=bn,
    )
    # name the variant so it is identifiable in HLO dumps / profiles
    fn.__name__ = (
        f"mma_matmul{'_scaled' if scaled else ''}_pallas_p{planes}"
        f"{'u' if not signed else ''}"
    )
    return jax.jit(fn)


def mma_matmul_pallas(
    x: jax.Array,
    w: jax.Array,
    *,
    planes: int = N_BITS,
    signed: bool = True,
    interpret: bool = False,
    bm: int = BM,
    bk: int = BK,
    bn: int = BN,
) -> jax.Array:
    """(M, K) int8 @ (K, N) int8 -> (M, N) int32 over the ``planes`` most
    significant digits of x, in one int8 pass per block.

    Shapes must be multiples of the block shape — ``ops.mma_matmul`` pads.
    Dispatches through the per-plane-count variant cache.
    """
    return plane_variant(
        planes, signed, interpret=interpret, bm=bm, bk=bk, bn=bn
    )(x, w)


def mma_matmul_scaled_pallas(
    x: jax.Array,
    w: jax.Array,
    x_scale: jax.Array,
    w_scale: jax.Array,
    *,
    planes: int = N_BITS,
    signed: bool = True,
    interpret: bool = False,
    bm: int = BM,
    bk: int = BK,
    bn: int = BN,
) -> jax.Array:
    """Quantized-serving form with the dequant epilogue fused into the
    flush: (M,K) int8 @ (K,N) int8 -> (M,N) f32 = acc * x_scale * w_scale[n].

    x_scale: () f32 (dynamic per-tensor); w_scale: (N,) f32 (per-channel).
    """
    return plane_variant(
        planes, signed, scaled=True, interpret=interpret, bm=bm, bk=bk, bn=bn
    )(x, w, x_scale, w_scale)
