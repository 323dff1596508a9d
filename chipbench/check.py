"""Whether the window's answers are right: served masks against the plain
reference, tile by tile.

The server quantizes each micro-batch's activations with one scale over
the whole micro-batch (short ones padded with zero tiles), so a tile's
logits depend on which tiles shared its batch.  The reference therefore
recomputes every micro-batch that holds a sampled image's tile, with the
tiles the server reported for it (``TileEvent.rid`` and ``.core``), from
its own copies of the images, its own tile plan and its own budget
classes, and stitches its own cores.  It takes no weights, scales or
tables from the program: only which tiles shared a batch.
"""
from __future__ import annotations

import numpy as np

from chipbench import geometry, rng
from chipbench import reference as ref

_SAMPLE_TAG = 0x5A3B1E


def sample(rids: list[int], k: int, seed: int) -> list[int]:
    """``k`` of ``rids`` drawn from the seed."""
    return sorted(sorted(rids, key=lambda r: rng.mix(seed, _SAMPLE_TAG, r))[:k])


class Reference:
    """The reference's view of one window: images, tile plans, classes."""

    def __init__(self, conf: dict, params, image_of: dict, batch: int):
        self.conf = conf
        self.params = params
        self.image_of = image_of  # rid -> (H, W, C) image
        self.batch = batch
        self.depth = conf["model"]["depth"]
        self._prep: dict = {}

    def prepared(self, rid: int):
        """(canvas, {core: Tile}, {core: class}) of one image."""
        if rid not in self._prep:
            img = self.image_of[rid]
            cv = geometry.canvas(img, self.depth)
            tiles = geometry.plan(img.shape[0], img.shape[1], depth=self.depth,
                                  tile=self.conf["tile"], halo=self.conf["halo"])
            amax = float(np.max(np.abs(cv)))
            self._prep[rid] = (
                cv, {t.core: t for t in tiles},
                {t.core: geometry.budget_class(cv[t.y0:t.y1, t.x0:t.x1], amax)
                 for t in tiles})
        return self._prep[rid]

    def canvases(self, steps, rids, *, bits: int = 8) -> dict:
        """Reference logits of each image in ``rids``, recomputed batch by
        batch as served.  A core the server never ran, or a batch whose
        tiles cannot have run together, stays NaN."""
        want = set(rids)
        n_cls = self.conf["model"]["n_classes"]
        out = {}
        for rid in rids:
            ph, pw = geometry.canvas_shape(*self.image_of[rid].shape[:2],
                                           self.depth)
            out[rid] = np.full((ph, pw, n_cls), np.nan, np.float32)
        base = self.conf["plane_schedule"]
        for step in steps:
            if not want.intersection(r for r, _ in step.tiles):
                continue
            tiles, classes = [], set()
            for rid, core in step.tiles:
                cv, by_core, cls = self.prepared(rid)
                if core not in by_core:
                    break
                tiles.append((rid, by_core[core]))
                classes.add(cls[core])
            else:
                shapes = {t.shape for _, t in tiles}
                if len(classes) != 1 or len(shapes) != 1 or len(tiles) > self.batch:
                    continue
                (h, w), = shapes
                c = self.conf["model"]["in_ch"]
                x = np.zeros((self.batch, h, w, c), np.float32)
                for b, (rid, t) in enumerate(tiles):
                    x[b] = self.prepared(rid)[0][t.y0:t.y1, t.x0:t.x1]
                planes = np.asarray(geometry.class_planes(base, classes.pop()),
                                    np.int32)
                y = np.asarray(ref.forward(self.params, x, planes, bits=bits))
                for b, (rid, t) in enumerate(tiles):
                    if rid in want:
                        cy0, cx0, cy1, cx1 = t.core
                        out[rid][cy0:cy1, cx0:cx1] = y[b, cy0 - t.y0:cy1 - t.y0,
                                                       cx0 - t.x0:cx1 - t.x0]
        return out

    def gap(self, rid: int, logits: np.ndarray, ref_canvas: np.ndarray) -> float:
        """Largest distance between ``logits`` and the reference over one
        tile core, as a share of the reference's largest logit there; the
        worst core of the image.  A missing core reads infinite."""
        h, w = logits.shape[:2]
        worst = 0.0
        for core in self.prepared(rid)[1]:
            y0, x0, y1, x1 = core
            y1, x1 = min(y1, h), min(x1, w)
            if y1 <= y0 or x1 <= x0:
                continue
            r = ref_canvas[y0:y1, x0:x1]
            if np.isnan(r).any():
                return float("inf")
            d = np.max(np.abs(logits[y0:y1, x0:x1] - r))
            if not np.isfinite(d):
                return float("inf")
            worst = max(worst, float(d / max(float(np.max(np.abs(r))), 1e-30)))
        return worst
