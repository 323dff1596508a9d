"""Pallas TPU kernel: the merged multiply-add (MMA) as a fused bit-plane matmul.

FPGA -> TPU mapping (DESIGN.md Sec. 2).  The FPGA MMA streams activation bits
MSB-first through an AND array and keeps a left-shifted *residual* inside the
unit, so the whole inner product pays one initial delay.  The TPU analogue of
"initial delay" is an HBM round-trip: an un-fused bit-plane implementation
writes 8 plane partial products to HBM and re-reads them to reduce.  This
kernel keeps the Horner accumulator (the residual) in VMEM scratch for the
whole (bm, bn) output tile: x and w are read from HBM exactly once, partial
sums never leave VMEM — the merged pipeline.

Datapath per grid step (m, n, k):
    u      = x_block + 128                 (offset two's-complement -> 0..255)
    acc    = 0
    for b in MSB..(MSB-planes+1):          (static unroll, 8 iterations max)
        plane = (u >> b) & 1               (VPU)
        acc   = 2*acc + plane @ w_block    (MXU, bf16 x bf16 -> f32)
    acc   *= 2**dropped                    (early-termination rescale)
    acc   -= 128 * colsum(w_block)         (exact signed correction)
    out   += acc                           (k-accumulation in VMEM scratch,
                                            written to HBM on the last k step)

Exactness of the bf16 MXU path: plane is {0,1} (exact), |w| <= 127 needs 7
mantissa bits (bf16 has 8 -> exact), products accumulate in f32 with
|partial| <= K * 127 * 255 < 2^24 for K <= 512 per block (exact f32 ints).
The k-grid accumulation is int32.  dimension_semantics marks m, n parallel
and k arbitrary (sequential accumulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

N_BITS = 8

# Default MXU-aligned tile shapes: (bm x bk) int8 + (bk x bn) int8 + f32/i32
# accumulators comfortably fit VMEM (~16 MiB/core on v5e):
#   x: 128*512 = 64 KiB, w: 512*128 = 64 KiB, acc: 128*128*4*2 = 128 KiB.
BM, BK, BN = 128, 512, 128


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

    u = x_ref[...].astype(jnp.int32)
    if signed:
        u = u + 128
    w = w_ref[...].astype(jnp.bfloat16)

    acc = jnp.zeros(acc_ref.shape, jnp.float32)
    for i in range(planes):
        b = N_BITS - 1 - i  # MSB first — the digit-serial streaming order
        plane = ((u >> b) & 1).astype(jnp.bfloat16)
        part = jax.lax.dot_general(
            plane, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc = acc * 2.0 + part  # the left-shifted residual recurrence

    dropped = N_BITS - planes
    acc = acc * float(2**dropped)
    if signed:
        colsum = jnp.sum(w_ref[...].astype(jnp.int32), axis=0, keepdims=True)
        acc = acc - 128.0 * colsum.astype(jnp.float32)

    acc_ref[...] += acc.astype(jnp.int32)

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

    The plane count is a *specialization axis*: the kernel body unrolls
    ``planes`` Horner steps, so a 4-plane variant issues exactly half the MXU
    work of the 8-plane one — a dynamic-precision schedule that assigns a
    layer 4 planes genuinely runs a smaller kernel, not a masked full-width
    one.  Each distinct (planes, signed, block) tuple compiles once and is
    reused across layers and calls; ``plane_variant.cache_info()`` exposes
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
    """(M, K) int8 @ (K, N) int8 -> (M, N) int32, fused bit-plane Horner.

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
