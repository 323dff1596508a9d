"""Meshes.  Functions (not module constants) so importing never touches jax
device state — required for the smoke tests to see 1 device.

Every mesh in the repository goes through :func:`make_mesh`, which gives its
axes the ``Auto`` type: the sharding code (``parallel/sharding.py``) is
written GSPMD-style, with ``with_sharding_constraint`` hints that the
compiler propagates, not for explicitly typed axes.

Single pod: 16x16 = 256 chips (v5e pod), axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model) — the 'pod' axis
carries pure data parallelism across pods (DCN-connected in production;
gradient sync over 'pod' is the slice the grad-compression path targets).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with ``Auto`` axes (JAX's default is ``Explicit``)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 2):
    """(data, model) mesh over every local device — the sharding unit tests
    on forced host devices, and the four chips of one TPU host."""
    n = len(jax.devices())
    data = n // model
    return make_mesh((data, model), ("data", "model"))
