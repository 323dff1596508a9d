"""Model operations of the images completed in the window (the whole
image's 3x3 convs, each multiply-add counted once: no halo, no planes, no
padding) over the window's length times the chip's int8 peak."""
from chipbench import geometry


def read(ctx):
    if ctx.peaks is None:
        return None
    n = len(ctx.completed_in_window())
    h, w, _ = ctx.conf["image"]
    ops = geometry.model_ops(ctx.conf["model"], h, w) * n
    return 100.0 * ops / (ctx.window_s * ctx.peaks["int8_ops"])
