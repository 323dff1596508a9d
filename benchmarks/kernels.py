"""MMA kernel benchmarks: (a) the merged-vs-cascaded structural claim on the
lowered HLO (HBM-materialized intermediates — the TPU analogue of the
initial-delay accounting), (b) wall time of each datapath at a
representative layer shape on the platform JAX runs on (named in every row),
(c) early-termination scaling with planes.
"""
from __future__ import annotations

import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitplane, mma


def _count_hbm_intermediates(fn, *args) -> dict:
    """Ops in the optimized HLO whose results are plausibly materialized:
    we count dots and the total bytes of dot outputs (the cascade writes one
    full (M,N) partial per plane; the merged path writes one)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    dots = re.findall(r"=\s*([a-z0-9]+)\[([\d,]*)\][^=]*\bdot\(", text)
    nbytes = 0
    for dtype, dims in dots:
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * {"f32": 4, "s32": 4, "bf16": 2, "s8": 1}.get(dtype, 4)
    return {"dot_count": len(dots), "dot_out_bytes": nbytes,
            "custom_calls": text.count("tpu_custom_call")}


def _time(fn, *args, repeats=5) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / repeats


def run() -> list[tuple[str, float, str]]:
    rng = np.random.default_rng(0)
    m, k, n = 256, 2304, 256  # one KPB-worth: k = 9 taps x 256 channels
    x = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int8)
    rows = []

    merged = jax.jit(lambda a, b: bitplane.bitplane_matmul(a, b))
    cascade = jax.jit(lambda a, b: bitplane.bitplane_matmul_cascade(a, b))
    int8 = jax.jit(lambda a, b: mma.mma_dot(a, b, impl="int8"))

    # Structural merged-vs-cascade claim: the MERGED implementation is the
    # Pallas kernel (one fused call, Horner residual in VMEM — ONE output
    # tensor ever touches HBM); the cascade materializes one full (M,N)
    # partial product per plane.  We count materialized dot outputs in the
    # optimized HLO of each.  ``ops`` runs the kernel in interpret mode off
    # the TPU, where its body is inlined and its 8 per-plane dots appear as
    # XLA dots; on the TPU the pallas_call is ONE custom call and only
    # out_specs' (M,N) int32 tile ever reaches HBM (by construction).
    from repro.kernels import ops

    platform = jax.devices()[0].platform
    pallas = jax.jit(lambda a, b: ops.mma_matmul(a, b))
    sm = _count_hbm_intermediates(pallas, x, w)
    sc = _count_hbm_intermediates(
        lambda a, b: bitplane.bitplane_matmul_cascade(a, b), x, w)
    m_out_bytes = x.shape[0] * w.shape[1] * 4  # the single fused output
    rows.append(("kernels/merged_pallas_hlo", 0.0,
                 f"xla_dots={sm['dot_count']};"
                 f"tpu_custom_calls={sm['custom_calls']};"
                 f"hbm_out_bytes={m_out_bytes} (single out_specs tile)"))
    rows.append(("kernels/cascade_hlo", _time(cascade, x, w) * 1e6,
                 f"dots={sc['dot_count']};dot_bytes={sc['dot_out_bytes']};"
                 f"hbm_bytes_ratio={sc['dot_out_bytes']/m_out_bytes:.2f}x"))
    rows.append(("kernels/merged_xla_horner", _time(merged, x, w) * 1e6,
                 "unrolled Horner (XLA fuses adds, still 8 plane dots)"))
    rows.append(("kernels/int8_direct", _time(int8, x, w) * 1e6, "bit-parallel baseline"))

    t = _time(pallas, x[:32], w[:, :128], repeats=2)
    rows.append(("kernels/pallas", t * 1e6,
                 "interpret mode" if platform != "tpu" else "Mosaic kernel"))

    # early termination: flops scale ~ planes/8
    for planes in (8, 6, 4, 2):
        fn = jax.jit(lambda a, b, p=planes: bitplane.bitplane_matmul(a, b, planes=p))
        flops = float(
            (jax.jit(lambda a, b, p=planes: bitplane.bitplane_matmul(a, b, planes=p))
             .lower(x, w).compile().cost_analysis() or {}).get("flops", 0)
        )
        rows.append((f"kernels/planes_{planes}", _time(fn, x, w) * 1e6,
                     f"hlo_flops={flops:.3e}"))
    # every time above is wall time on this platform, not the chip's
    return [(name, us, f"{derived};platform={platform}")
            for name, us, derived in rows]
