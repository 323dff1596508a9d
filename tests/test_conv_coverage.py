"""KPB conv coverage: core.mma.mma_conv2d against two independent oracles —
the pure-jnp masked-matmul reference (kernels/ref.py) and XLA's own
conv_general_dilated — across stride / padding / kernel / channel shapes
(including non-MXU-aligned ones) on both MMA datapaths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mma
from repro.kernels import ref

RNG = np.random.default_rng(1234)


def _rand_i8(shape):
    return jnp.asarray(RNG.integers(-128, 128, shape), jnp.int8)


def _xla_conv_int(x, w, stride, pad):
    return jax.lax.conv_general_dilated(
        x.astype(jnp.int32),
        w.astype(jnp.int32),
        (stride, stride),
        ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32,
    )


CASES = [
    # (n, h, w, cin, cout, k, stride, pad)
    (1, 8, 8, 4, 8, 3, 1, 1),      # the paper's 3x3 SAME shape
    (2, 9, 7, 3, 5, 3, 1, 1),      # non-aligned everything
    (1, 8, 8, 4, 8, 3, 2, 1),      # strided downsample
    (1, 10, 10, 2, 3, 3, 2, 0),    # stride 2, VALID
    (2, 6, 6, 5, 7, 1, 1, 0),      # 1x1 conv (pointwise)
    (1, 12, 12, 3, 4, 5, 2, 2),    # 5x5, stride 2
    (1, 7, 11, 33, 65, 3, 1, 1),   # channel counts off the 32/128 tiles
]


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("case", CASES, ids=lambda c: "x".join(map(str, c)))
def test_conv_all_impls_exact(case, impl):
    n, h, w_, cin, cout, k, stride, pad = case
    x = _rand_i8((n, h, w_, cin))
    w = _rand_i8((k, k, cin, cout))
    got = mma.mma_conv2d(x, w, stride=stride, pad=pad, impl=impl)
    want_ref = ref.mma_conv2d_ref(x, w, stride=stride, pad=pad)
    want_xla = _xla_conv_int(x, w, stride, pad)
    assert got.shape == want_xla.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want_ref))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want_xla))


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("planes", [6, 3, 1])
def test_conv_truncated_all_impls(planes, impl):
    """Plane truncation agrees with the masked-matmul oracle on every
    datapath (both compute it via the data-side identity)."""
    x = _rand_i8((2, 7, 9, 5))
    w = _rand_i8((3, 3, 5, 6))
    got = mma.mma_conv2d(x, w, stride=2, pad=1, planes=planes, impl=impl)
    want = ref.mma_conv2d_ref(x, w, stride=2, pad=1, planes=planes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_conv_unsigned_path():
    """uint8-valued activations (post-ReLU streams, the paper's native
    case) read as their int8 bits go through the conv exactly; the
    unsigned kernel variants are checked in test_kernels.py."""
    x = jnp.asarray(RNG.integers(0, 256, (1, 6, 6, 3)), jnp.uint8).astype(jnp.int32)
    w = _rand_i8((3, 3, 3, 4))
    got = mma.mma_conv2d(x.astype(jnp.int8), w, impl="pallas")
    want = ref.mma_conv2d_ref(x.astype(jnp.int8), w, signed=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", ["nope", "cascade", "int8"])
def test_conv_unknown_impl_raises(impl):
    """Two datapaths: the removed 'cascade' and 'int8' values are refused
    like any unknown one."""
    x = _rand_i8((1, 4, 4, 2))
    w = _rand_i8((3, 3, 2, 2))
    with pytest.raises(ValueError, match="'pallas' or 'xla'"):
        mma.mma_conv2d(x, w, impl=impl)
