"""Shape arithmetic of the segmentation server that every architecture
shares, kept with the benchmark.

Copied from the program (``repro.segserve.tiling``, ``repro.segserve.
adaptive`` and ``repro.core.plane_schedule.PlaneSchedule.refine``) so that a
change there cannot move what the benchmark counts or what its reference
computes:

* a tile of an image's plan (each architecture plans its own,
  ``chipbench/archs/``);
* each tile's budget class and the plane schedule the class runs;
* a 3x3 conv's operations and bytes, and a whole image's operations.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

N_BITS = 8
MAX_CLASS = 6


@dataclass(frozen=True)
class Tile:
    y0: int
    x0: int
    y1: int
    x1: int
    core: tuple[int, int, int, int]  # (y0, x0, y1, x1) on the canvas

    @property
    def shape(self) -> tuple[int, int]:
        return (self.y1 - self.y0, self.x1 - self.x0)


def budget_class(window: np.ndarray, canvas_amax: float) -> int:
    """Amplitude octaves of a tile's input window below the image's."""
    if canvas_amax <= 0.0:
        return 0
    r = min(1.0, float(np.max(np.abs(window))) / float(canvas_amax))
    if r == 0.0:
        return MAX_CLASS
    return min(MAX_CLASS, max(0, int(math.floor(-math.log2(r)))))


def class_planes(base: list[int], k: int) -> tuple[int, ...]:
    """The schedule class-``k`` tiles run: ``base`` refined at ratio
    ``2**-k``, each layer dropping the extra digits its absolute error
    budget allows."""
    if k == 0:
        return tuple(base)
    r = 2.0**-k
    out = []
    for b in base:
        d = N_BITS - b
        if d == 0:
            out.append(b)
            continue
        budget = float(2**d - 1)
        d2 = d
        while d2 < N_BITS - 1 and (2 ** (d2 + 1) - 1) * r <= budget:
            d2 += 1
        out.append(N_BITS - d2)
    return tuple(out)


@dataclass(frozen=True)
class Conv:
    n: int
    h: int
    w: int
    cin: int
    cout: int

    @property
    def ops(self) -> int:
        """Operations of the 3x3 SAME conv, two per multiply-add."""
        return 2 * self.n * self.h * self.w * 9 * self.cin * self.cout

    @property
    def bytes(self) -> int:
        """Least traffic: the int8 input and weights read once, the
        32-bit output written once."""
        return (self.n * self.h * self.w * self.cin + 9 * self.cin * self.cout
                + 4 * self.n * self.h * self.w * self.cout)


def model_ops(arch, model: dict, h: int, w: int) -> int:
    """Operations of the layers of ``arch`` (a module of ``chipbench/archs``)
    over one whole ``h x w`` image: each multiply-add counted once, no halo,
    no planes, no padding."""
    return sum(layer.ops for layer in arch.layers(model, 1, h, w))
