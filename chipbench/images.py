"""Seeded phantom slices: the inputs every cell serves.

``phantom`` is ``repro.segserve.synth.phantom_image``, copied: quiet
background noise with one bright structure near the top-left, so tiles
whose window clears the structure sit orders of magnitude below the
image's amplitude (the case the server's budget classes exist for).
"""
from __future__ import annotations

import numpy as np

from chipbench import rng

_POOL_TAG = 0x5EC5EED


def phantom(h: int, w: int, c: int, seed: int) -> np.ndarray:
    gen = np.random.default_rng(seed)
    img = gen.normal(0.0, 0.01, (h, w, c))
    sh, sw = max(1, h // 5), max(1, w // 4)
    img[sh : 2 * sh, sw : 2 * sw] += gen.normal(0.0, 1.0, (sh, sw, c))
    return img.astype(np.float32)


def pool(conf: dict, seed: int) -> list[np.ndarray]:
    """The configuration's pool of distinct slices for this seed."""
    h, w, c = conf["image"]
    return [phantom(h, w, c, rng.mix(seed, _POOL_TAG, i))
            for i in range(conf["pool"])]
