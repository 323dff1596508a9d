"""Shape arithmetic of the served U-Net, kept with the benchmark.

Copied from the program (``repro.segserve.tiling``, ``repro.segserve.
adaptive``, ``repro.core.plane_schedule.PlaneSchedule.refine`` and
``repro.core.cycle_model.unet_conv_layers``) so that a change there cannot
move what the benchmark counts or what its reference computes:

* the exact halo and the tile plan of an image;
* each tile's budget class and the plane schedule the class runs;
* the 3x3 convs of a forward, and their operations and bytes.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

N_BITS = 8
MAX_CLASS = 6


def halo_for(depth: int, convs_per_stage: int) -> int:
    """Exact halo (input pixels per side) of an artificial tile edge,
    rounded up to a multiple of ``2**depth``."""
    m, skips = 0, []
    for _ in range(depth):
        m += convs_per_stage
        skips.append(m)
        m = -(-m // 2)
    m += convs_per_stage
    for level in reversed(range(depth)):
        m = max(2 * m, skips[level]) + convs_per_stage
    mult = 2**depth
    return -(-max(m, 1) // mult) * mult


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


def canvas_shape(h: int, w: int, depth: int) -> tuple[int, int]:
    mult = 2**depth
    return (-(-h // mult) * mult, -(-w // mult) * mult)


def plan(h: int, w: int, *, depth: int, tile: int, halo: int) -> list[Tile]:
    """Cores of ``tile`` striding the padded canvas, each dilated by the
    halo and clipped to the canvas."""
    ph, pw = canvas_shape(h, w, depth)
    out = []
    for cy in range(0, ph, tile):
        ch = min(tile, ph - cy)
        for cx in range(0, pw, tile):
            cw = min(tile, pw - cx)
            out.append(Tile(max(0, cy - halo), max(0, cx - halo),
                            min(ph, cy + ch + halo), min(pw, cx + cw + halo),
                            (cy, cx, cy + ch, cx + cw)))
    return out


def canvas(image: np.ndarray, depth: int) -> np.ndarray:
    ph, pw = canvas_shape(image.shape[0], image.shape[1], depth)
    return np.pad(image.astype(np.float32),
                  ((0, ph - image.shape[0]), (0, pw - image.shape[1]), (0, 0)))


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


def convs(model: dict, n: int, h: int, w: int) -> list[Conv]:
    """The 3x3 convs of one forward over ``n`` windows of ``h x w``, in
    forward order (encoder, bottleneck, decoder)."""
    c, base, depth = model["in_ch"], model["base"], model["depth"]
    per = model["convs_per_stage"]
    out, skips = [], []
    for d in range(depth):
        cout = base * 2**d
        out.append(Conv(n, h, w, c, cout))
        out += [Conv(n, h, w, cout, cout)] * (per - 1)
        skips.append(cout)
        c, h, w = cout, h // 2, w // 2
    cout = base * 2**depth
    out.append(Conv(n, h, w, c, cout))
    out += [Conv(n, h, w, cout, cout)] * (per - 1)
    c = cout
    for d in reversed(range(depth)):
        h, w, cout = h * 2, w * 2, skips[d]
        out.append(Conv(n, h, w, cout + c, cout))
        out += [Conv(n, h, w, cout, cout)] * (per - 1)
        c = cout
    return out


def model_ops(model: dict, h: int, w: int) -> int:
    """Operations of the 3x3 convs over one whole ``h x w`` image: each
    multiply-add counted once, no halo, no planes, no padding."""
    return sum(cv.ops for cv in convs(model, 1, h, w))
