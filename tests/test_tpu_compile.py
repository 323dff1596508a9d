"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: misaligned slices,
too much fast memory, a program that cannot fit.  These tests compile the
MMA kernels and the served U-Net tile forward at the full calibrated width
(base 48, depth 3, a 128x128 window, micro-batch 4) for one chip of a
``v5e:2x2`` topology.  Nothing runs, so they say nothing of results or
time.  The topology is described inside a fixture, never at import: only
one process may load the TPU library at a time.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.models import unet

BATCH = 4  # SegEngine's default tile micro-batch
WINDOW = (128, 128)  # tile 80 + 2 x halo 24
CFG = unet.UNetConfig(quant_mode="mma_int8", impl="pallas")  # calibrated geometry
LAYERS = dataclasses.replace(CFG, hw=WINDOW).conv_layers()


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off for these tests
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


# unet64_kits512 (base 64, depth 4, two convs per stage) at its 352x352
# serving window: the largest-M convs (00, 01, 16) and the largest-K one (10)
KITS_LAYERS = unet.UNetConfig(
    in_ch=1, base=64, depth=4, convs_per_stage=2, hw=(352, 352)).conv_layers()
KITS = {f"kits352-conv{i:02d}": KITS_LAYERS[i] for i in (0, 1, 10, 16)}


@pytest.mark.parametrize("planes", [8, 5, 4, 1])
@pytest.mark.parametrize("layer", [*range(len(LAYERS)), *KITS])
@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "scaled"])
def test_mma_kernel_compiles_at_unet_conv_shape(one_chip, layer, planes, scaled):
    """Each 3x3 conv of the served tile forward as its im2col matmul:
    (batch*h*w, 9*cin) @ (9*cin, cout), through the plain int32 kernel and
    the fused-dequant kernel, at the calibrated geometry and at the kits
    window, whose shapes take the block rule's largest VMEM footprint."""
    spec = KITS[layer] if layer in KITS else LAYERS[layer]
    m, k, n = BATCH * spec.h * spec.w, 9 * spec.cin, spec.cout
    x = _sds((m, k), jnp.int8, one_chip)
    w = _sds((k, n), jnp.int8, one_chip)
    if scaled:
        fn = jax.jit(lambda a, b, xs, ws: ops.mma_matmul_scaled(
            a, b, xs, ws, planes=planes, interpret=False))
        args = (x, w, _sds((), jnp.float32, one_chip),
                _sds((n,), jnp.float32, one_chip))
    else:
        fn = jax.jit(lambda a, b: ops.mma_matmul(
            a, b, planes=planes, interpret=False))
        args = (x, w)
    assert _kernel_calls(fn.lower(*args).compile()) == 1


def test_unet_tile_forward_compiles_with_one_kernel_per_conv(one_chip,
                                                             monkeypatch):
    """The served tile forward (``impl="pallas"``, a truncated per-layer
    schedule) holds one Mosaic kernel per conv, and its ops' metadata keeps
    the named scopes a trace reader finds them by.  ``ops`` picks interpret
    mode from the backend this process sees, the CPU: steer it here."""
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    cfg = dataclasses.replace(CFG, plane_schedule=(8, 6, 5, 4, 5, 6, 8))
    params = jax.eval_shape(lambda: unet.init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), params)
    x = _sds((BATCH, *WINDOW, cfg.in_ch), jnp.float32, one_chip)
    compiled = jax.jit(unet.forward, static_argnums=2).lower(
        params, x, cfg).compile()
    assert _kernel_calls(compiled) == len(LAYERS)
    text = compiled.as_text()
    for scope in ("conv00/im2col", "conv00/pack", "conv00/mma"):
        assert f"jit(forward)/{scope}/" in text, scope
    # the tile forward fits one v5e chip's 16 GB with room to spare
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30
