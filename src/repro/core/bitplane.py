"""Bit-plane decomposition — the TPU-native form of the paper's digit-serial stream.

The FPGA design streams 8-bit activations one bit per cycle, MSB first, and
multiplies each bit against the parallel 8-bit weight via an AND gate array.
On TPU the analogue is *bit-plane decomposition*: an int8 tensor is the
Horner combination of 8 binary planes, and an inner product becomes 8 binary
(0/1) × int8 products combined MSB-first:

    acc <- 2*acc + plane_b @ w        (b = MSB .. LSB)

which is *exactly* the paper's residual recurrence (the residual is
left-shifted by one bit each cycle before the next partial products are
added, Sec. 3.2).

Signed handling: two's-complement int8 ``x`` is decomposed via the unsigned
offset form ``u = x + 128`` (planes of ``u`` are plain 0/1), and the exact
correction ``-128 * sum(w)`` is applied once at the end.  This keeps every
plane non-negative — matching the paper's unsigned activation stream (U-Net
activations are post-ReLU) — while supporting signed LM activations exactly.
"""
from __future__ import annotations

from typing import Literal

import jax
import jax.numpy as jnp

N_BITS = 8
SIGNED_OFFSET = 128  # u = x + 128 for int8 x


def decompose(x: jax.Array, *, n_bits: int = N_BITS, signed: bool = True) -> jax.Array:
    """Decompose an int tensor into MSB-first binary planes.

    Args:
      x: int8 (signed=True) or uint8-valued int32/uint8 (signed=False) tensor.
      n_bits: number of planes (8 for the paper's quantization).
      signed: apply the +128 offset trick for two's-complement input.

    Returns:
      int8 tensor of shape ``(n_bits, *x.shape)`` with planes[0] = MSB.
    """
    u = x.astype(jnp.int32)
    if signed:
        u = u + SIGNED_OFFSET
    shifts = jnp.arange(n_bits - 1, -1, -1, dtype=jnp.int32)  # MSB first
    planes = (u[None, ...] >> shifts.reshape((n_bits,) + (1,) * x.ndim)) & 1
    return planes.astype(jnp.int8)


def recombine(planes: jax.Array, *, signed: bool = True) -> jax.Array:
    """Inverse of :func:`decompose` (Horner, MSB first)."""

    def body(acc, plane):
        return acc * 2 + plane.astype(jnp.int32), None

    acc, _ = jax.lax.scan(body, jnp.zeros(planes.shape[1:], jnp.int32), planes)
    if signed:
        acc = acc - SIGNED_OFFSET
    return acc


def truncate_to_planes(
    x: jax.Array, planes: int | jax.Array, *, signed: bool = True
) -> jax.Array:
    """Data-side form of plane truncation: returns ``x'`` such that a plain
    full-precision matmul ``x' @ w`` equals ``bitplane_matmul(x, w, planes)``.

    Identity (see ``kernels/ref.py``): consuming only the ``b`` MSB planes of
    ``u = x + 128`` and Horner-rescaling equals masking off the low ``8-b``
    bits of ``u``.  Because the mask is computed with jnp shifts, ``planes``
    may be a *traced* scalar — this is what lets a per-layer
    :class:`~repro.core.plane_schedule.PlaneSchedule` ride a ``lax.scan``
    over stacked layer params while both MMA datapaths (``core.mma``) see
    ordinary int8 operands.
    """
    u = x.astype(jnp.int32)
    if signed:
        u = u + SIGNED_OFFSET
    dropped = N_BITS - jnp.asarray(planes, jnp.int32)
    mask = ~(jnp.left_shift(jnp.int32(1), dropped) - 1)
    u = u & mask
    if signed:
        return (u - SIGNED_OFFSET).astype(jnp.int8)
    return u.astype(x.dtype)


def normalize_planes(
    x: jax.Array, planes: int | jax.Array, *, signed: bool = True
) -> tuple[jax.Array, int]:
    """Resolve a per-call plane budget to (operand, static planes).

    Static Python ints are validated (1..N_BITS) and passed through — the
    kernel specializes on them (one cached variant per count).  Traced
    scalars — one entry of a PlaneSchedule riding a ``lax.scan`` — are
    folded into the *data* via :func:`truncate_to_planes`, after which the
    datapath runs its full-precision path on the pre-truncated operand:
    identical numerics, one fused matmul.
    """
    if isinstance(planes, int):
        if not (1 <= planes <= N_BITS):
            raise ValueError(f"planes {planes} outside 1..{N_BITS}")
        return x, planes
    return truncate_to_planes(x, planes, signed=signed), N_BITS


def bitplane_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    planes: int = N_BITS,
    signed: bool = True,
    correction: Literal["none", "midpoint"] = "none",
) -> jax.Array:
    """Exact (planes=8) or progressively-truncated (planes<8) int matmul.

    Computes ``x @ w`` in int32 via MSB-first bit-plane accumulation — the
    paper's digit-serial recurrence, one product per plane.  The MMA
    datapaths (``core.mma``) compute the same integers (without
    ``correction``) as one product on the plane-masked operand.

    With ``planes = b < 8`` only the ``b`` most significant planes are
    consumed — the paper's early termination.  The partial Horner sum is
    rescaled by ``2**(8-b)``; ``correction='midpoint'`` adds the expected
    value of the dropped planes (they are 0/1 each, expectation ~0.5) to
    halve the truncation bias.  The worst-case error is bounded by
    ``(2**(8-b) - 1) * sum(|w|, contraction)`` (see ``early_term.py``).

    Args:
      x: (..., K) int8 activations.
      w: (K, N) int8 weights.
      planes: number of MSB planes to consume, 1..8.
      signed: x is two's-complement int8.

    Returns:
      (..., N) int32.
    """
    n_bits = N_BITS
    pl = decompose(x, n_bits=n_bits, signed=signed)  # (8, ..., K) values 0/1
    w32 = w.astype(jnp.int32)

    # Python (unrolled) Horner loop: <= 8 iterations, keeps every plane's
    # FLOPs visible to cost analysis (a lax.scan body is counted once).
    out_shape = x.shape[:-1] + (w.shape[-1],)
    acc = jnp.zeros(out_shape, jnp.int32)
    for i in range(planes):
        plane = pl[i]
        part = jax.lax.dot_general(
            plane.astype(jnp.int8),
            w,
            (((plane.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        acc = acc * 2 + part

    dropped = n_bits - planes
    acc = acc * (2**dropped)
    colsum = jnp.sum(w32, axis=0)
    if correction == "midpoint" and dropped:
        # dropped planes contribute sum_{j<dropped} 2^j * plane_j @ w, each
        # plane entry ~ Bernoulli(1/2)  ->  E = (2^dropped - 1)/2 * colsum(w)
        acc = acc + ((2**dropped - 1) * colsum) // 2
    if signed:
        acc = acc - SIGNED_OFFSET * colsum
    return acc


def bitplane_matmul_cascade(
    x: jax.Array, w: jax.Array, *, planes: int = N_BITS, signed: bool = True
) -> jax.Array:
    """The *un-merged* baseline: per-plane partial products are materialized
    and then reduced in a separate pass — the TPU analogue of the cascaded
    MSDF multiplier + adder-tree design the paper improves on (each op is a
    separate HBM round-trip, like each FPGA unit paying its own initial
    delay).  Numerically identical to :func:`bitplane_matmul`; exists so the
    benchmark can expose the fusion win structurally (HLO bytes / op count).
    """
    pl = decompose(x, n_bits=N_BITS, signed=signed)[:planes]
    # Stage 1 (the "multipliers"): one partial-product tensor per plane.
    parts = [
        jax.lax.dot_general(
            p, w, (((p.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.int32
        )
        for p in pl
    ]
    # Stage 2 (the "adder tree"): pairwise reduction over materialized parts.
    weights = [2 ** (planes - 1 - b) for b in range(planes)]
    parts = [p * w_ for p, w_ in zip(parts, weights)]
    while len(parts) > 1:
        nxt = [a + b for a, b in zip(parts[::2], parts[1::2])]
        if len(parts) % 2:
            nxt.append(parts[-1])
        parts = nxt
    acc = parts[0] * (2 ** (N_BITS - planes))
    if signed:
        acc = acc - SIGNED_OFFSET * jnp.sum(w.astype(jnp.int32), axis=0)
    return acc
