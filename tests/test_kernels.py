"""Pallas MMA kernel vs the pure-jnp oracle: shape/dtype/plane sweeps in
interpret mode, plus the XLA datapath and the KPB conv (all bit-exact)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import bitplane, mma
from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _rand_i8(shape):
    return jnp.asarray(RNG.integers(-128, 128, shape), jnp.int8)


@pytest.mark.parametrize("m,k,n", [
    (4, 32, 8), (32, 128, 32), (128, 512, 128), (37, 100, 65),
    (1, 7, 3), (256, 1024, 256), (64, 300, 90),
])
@pytest.mark.parametrize("planes", [8, 5, 2])
def test_pallas_matmul_vs_oracle(m, k, n, planes):
    x, w = _rand_i8((m, k)), _rand_i8((k, n))
    got = ops.mma_matmul(x, w, planes=planes, interpret=True)
    want = ref.mma_matmul_ref(x, w, planes=planes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", ["xla"])
def test_other_impls_vs_oracle(impl):
    x, w = _rand_i8((24, 96)), _rand_i8((96, 48))
    got = mma.mma_dot(x, w, impl=impl)
    want = ref.mma_matmul_ref(x, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_batched_leading_dims():
    x, w = _rand_i8((2, 3, 40)), _rand_i8((40, 16))
    got = ops.mma_matmul(x, w, interpret=True)
    want = ref.mma_matmul_ref(x.reshape(6, 40), w).reshape(2, 3, 16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_custom_blocks():
    x, w = _rand_i8((64, 256)), _rand_i8((256, 64))
    got = ops.mma_matmul(x, w, interpret=True, block=(32, 128, 64))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref.mma_matmul_ref(x, w)))


def test_conv2d_vs_oracle():
    x = _rand_i8((2, 12, 12, 16))
    w = _rand_i8((3, 3, 16, 24))
    got = mma.mma_conv2d(x, w, impl="pallas")
    want = ref.mma_conv2d_ref(x, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_conv2d_stride2():
    x = _rand_i8((1, 16, 16, 8))
    w = _rand_i8((3, 3, 8, 8))
    got = mma.mma_conv2d(x, w, stride=2, impl="pallas")
    want = ref.mma_conv2d_ref(x, w, stride=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(st.integers(1, 8))
@settings(max_examples=8, deadline=None)
def test_plane_truncation_matches_masked_oracle(planes):
    x, w = _rand_i8((16, 64)), _rand_i8((64, 16))
    got = ops.mma_matmul(x, w, planes=planes, interpret=True)
    want = ref.mma_matmul_ref(x, w, planes=planes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_unsigned_mode():
    x = jnp.asarray(RNG.integers(0, 256, (16, 64)), jnp.int32).astype(jnp.uint8)
    # kernel path uses int8 views; emulate unsigned via signed=False
    xi = x.astype(jnp.int32).astype(jnp.int8)  # reinterpret bits
    w = _rand_i8((64, 16))
    got = ops.mma_matmul(xi, w, signed=False, interpret=True)
    want = jax.lax.dot_general(
        x.astype(jnp.int32) % 256, w.astype(jnp.int32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,k,n", [(16, 96, 40), (64, 256, 128), (3, 50, 7)])
@pytest.mark.parametrize("planes", [8, 5])
def test_scaled_epilogue_kernel(m, k, n, planes):
    """Fused dequant epilogue == int32 kernel then scale (bit-exact in f32)."""
    x, w = _rand_i8((m, k)), _rand_i8((k, n))
    xs = jnp.float32(0.0173)
    ws = jnp.asarray(RNG.uniform(1e-3, 1e-2, n), jnp.float32)
    got = ops.mma_matmul_scaled(x, w, xs, ws, planes=planes, interpret=True)
    want = ref.mma_matmul_ref(x, w, planes=planes).astype(jnp.float32) * xs * ws
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_quantized_linear_pallas_path():
    """layers.linear dispatches w_q through the fused-scale Pallas kernel."""
    from repro.configs.base import QuantConfig
    from repro.models import layers as L

    rng = np.random.default_rng(7)
    w = jnp.asarray(rng.standard_normal((256, 320)) * 0.02, jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, 256)), jnp.float32)
    from repro.core.quant import quantize_params_int8

    p = quantize_params_int8({"w": w}, min_dim=256)
    out_p = L.linear(p, x, QuantConfig(mode="mma_int8", impl="pallas"))
    out_x = L.linear(p, x, QuantConfig(mode="mma_int8", impl="xla"))
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x), rtol=1e-5,
                               atol=1e-5)


def test_horner_equals_cascade_hlo_structure():
    """The merged path must contain ZERO intermediate HBM round-trips for
    plane partials: structurally, the cascade lowers >= 8 separate dots of
    full output size; the merged kernel is a single pallas_call."""
    x, w = _rand_i8((32, 128)), _rand_i8((128, 32))
    merged = jax.jit(lambda a, b: ops.mma_matmul(a, b, interpret=True))
    text = merged.lower(x, w).as_text()
    assert "custom_call_target" in text or "pallas" in text.lower()


# rows: the int8 extremes and the unsigned extremes (u = 255, 0) as int8 bits
_X_ROWS = (-128, 127, -1, 0)
# columns: +-127 and -128 weights, alone and mixed
_W_COLS = (127, -127, -128, "alt")


def _extreme_operands(k):
    x = jnp.asarray(np.repeat(np.array(_X_ROWS)[:, None], k, axis=1), jnp.int8)
    cols = [np.full(k, c) if c != "alt" else np.resize([127, -128], k) for c in _W_COLS]
    return x, jnp.asarray(np.stack(cols, axis=1), jnp.int8)


@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "scaled"])
@pytest.mark.parametrize("signed", [True, False], ids=["signed", "unsigned"])
@pytest.mark.parametrize("planes", range(1, 9))
@pytest.mark.parametrize("k", [9, 13824])  # a 3x3 conv on one channel; kits conv10
def test_kernel_exact_at_operand_extremes(k, planes, signed, scaled):
    """Saturated operands at every plane budget, bit for bit against the
    oracle: the one int8 pass keeps every product and sum exact at a
    contraction as long as the largest U-Net conv's."""
    x, w = _extreme_operands(k)
    # the oracle reads unsigned activations as the 0..255 values of the bits
    xr = x if signed else x.astype(jnp.int32) & 0xFF
    want = ref.mma_matmul_ref(xr, w, planes=planes, signed=signed)
    if scaled:
        # power-of-two scales: the epilogue's multiplies are exact
        xs = jnp.float32(2.0**-10)
        ws = jnp.asarray([2.0**-3, 2.0**-7, 1.0, 2.0**-1], jnp.float32)
        got = ops.mma_matmul_scaled(x, w, xs, ws, planes=planes, signed=signed,
                                    interpret=True)
        want = want.astype(jnp.float32) * xs * ws
    else:
        got = ops.mma_matmul(x, w, planes=planes, signed=signed, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _eqns(jaxpr, name):
    """Every ``name`` equation of a jaxpr, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, name)


@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "scaled"])
@pytest.mark.parametrize("planes", range(1, 9))
def test_kernel_one_int8_pass_per_block(planes, scaled):
    """The kernel body makes one int8 x int8 -> int32 MXU pass per grid step
    at every plane budget: no per-plane passes, no widening to bf16."""
    from repro.kernels import mma_matmul as mk

    x, w = _rand_i8((64, 256)), _rand_i8((256, 128))
    if scaled:
        fn = mk.plane_variant(planes, scaled=True, interpret=True, bm=32, bk=128)
        args = (x, w, jnp.float32(1.0), jnp.ones((128,), jnp.float32))
    else:
        fn = mk.plane_variant(planes, interpret=True, bm=32, bk=128)
        args = (x, w)
    calls = list(_eqns(jax.make_jaxpr(fn)(*args).jaxpr, "pallas_call"))
    assert len(calls) == 1
    dots = list(_eqns(calls[0].params["jaxpr"], "dot_general"))
    assert len(dots) == 1
    (dot,) = dots
    assert [v.aval.dtype for v in dot.invars] == [jnp.int8, jnp.int8]
    assert dot.params["preferred_element_type"] == jnp.int32
