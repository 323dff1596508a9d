"""Streaming tiled-segmentation serving: the paper's target application
(U-Net medical-image segmentation) as a served workload.

``tiling``   — receptive-field-exact halo decomposition + stitching of
               arbitrary (H, W) images, numerically equivalent to the
               whole-image forward;
``adaptive`` — content-adaptive per-tile plane budgets (flat background
               tiles consume fewer MSB digits), layered on the certified
               per-layer :class:`~repro.core.PlaneSchedule` — with budget
               classes from fixed octaves or from a
               :class:`~repro.autotune.TunedPlan`'s calibrated thresholds;
``engine``   — request-queue + slot-table micro-batching executor with
               per-image relation-(2) cycle / GOPS/W accounting; pass a
               tuned ``plan=`` to serve a certified operating point
               (tuned tile/halo, calibrated classes, per-tile quant).
"""
from . import adaptive, engine, synth, tiling  # noqa: F401
from .adaptive import budget_class_from_thresholds  # noqa: F401
from .engine import (  # noqa: F401
    SegCounters,
    SegEngine,
    SegRequest,
    SegResult,
    TileEvent,
)
from .tiling import halo_for, plan_tiles, stitch, tiled_forward  # noqa: F401
