"""Quickstart: the paper's MMA datapath in five minutes.

    PYTHONPATH=src python examples/quickstart.py

1. bit-plane merged multiply-add vs exact int8 matmul (bit-exact),
2. MSDF early termination (progressive precision),
3. a quantized linear layer inside a tiny LM forward.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitplane, early_term, mma, quant
from repro.kernels import ops, ref
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(-128, 128, (64, 256)), jnp.int8)
    w = jnp.asarray(rng.integers(-128, 128, (256, 32)), jnp.int8)

    exact = ref.mma_matmul_ref(x, w)
    merged = mma.mma_dot(x, w, impl="xla")
    pallas = ops.mma_matmul(x, w)  # interpret mode only off the TPU
    print("merged == exact:", bool(jnp.array_equal(merged, exact)))
    print("pallas == exact:", bool(jnp.array_equal(pallas, exact)))

    print("\nMSDF progressive precision (planes -> max relative error):")
    for planes in range(1, 9):
        approx = mma.mma_dot(x, w, planes=planes)
        err = float(early_term.empirical_rel_err(exact, approx))
        bound = float(jnp.max(early_term.truncation_bound(w, planes, midpoint=False))
                      / jnp.maximum(jnp.max(jnp.abs(exact)), 1))
        print(f"  planes={planes}: measured={err:.4f}  worst-case-bound={bound:.4f}")

    print("\nquantized linear (float in/out through the int8 MMA path):")
    xf = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)
    wf = jnp.asarray(rng.standard_normal((256, 32)) * 0.05, jnp.float32)
    yq = mma.mma_linear(xf, wf)
    y = xf @ wf
    rel = float(jnp.max(jnp.abs(y - yq)) / jnp.max(jnp.abs(y)))
    print(f"  rel error vs float: {rel:.4f} (int8 dynamic quantization)")


if __name__ == "__main__":
    main()
