"""Arithmetic shared by the metric readers in ``metrics/``.

Every reader takes the run's :class:`chipbench.bench.Context` and returns
a number, or ``None`` where the run holds nothing to read.
"""
from __future__ import annotations

from chipbench import geometry
from chipbench import trace as tracing


def tiles_by_core(ctx) -> dict:
    h, w, _ = ctx.conf["image"]
    return {t.core: t for t in geometry.plan(
        h, w, depth=ctx.conf["model"]["depth"], tile=ctx.conf["tile"],
        halo=ctx.conf["halo"])}


def batch_fill(ctx) -> float | None:
    """Tiles run over tile slots offered, in the window's micro-batches."""
    steps = ctx.window_steps()
    if not steps:
        return None
    return 100.0 * sum(len(s.tiles) for s in steps) / (len(steps) * ctx.batch)


def idle_share(ctx) -> float | None:
    """Share of the traced window in which no op ran on the device."""
    s = ctx.trace_summary
    if not s or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])


def least_conv_seconds(ctx) -> float:
    """The window's 3x3 convs at the chip's peaks: for each micro-batch
    run, each conv's larger of ops over the int8 peak and bytes over the
    HBM bandwidth."""
    by_core = tiles_by_core(ctx)
    total = 0.0
    for step in ctx.window_steps():
        h, w = by_core[step.tiles[0][1]].shape
        for cv in geometry.convs(ctx.conf["model"], ctx.batch, h, w):
            total += max(cv.ops / ctx.peaks["int8_ops"],
                         cv.bytes / ctx.peaks["hbm_bytes_per_s"])
    return total


def kernel_seconds(ctx, pattern: str) -> float:
    tr = ctx.trace
    if tr is None:
        return 0.0
    ops = [op for c in sorted(tr.ops) for op in tr.ops[c]]
    return sum(tracing.op_time(ops, tr.window, pattern).values()) * 1e-9
