"""Device ms of the ops under an ``im2col`` scope (``kernels.ops.mma_conv2d``:
the SAME pad, the 9 taps, their concatenate and reshape) inside the window,
per image completed in the window."""
from chipbench.phases import ms_per_image


def read(ctx):
    return ms_per_image(ctx, "im2col")
