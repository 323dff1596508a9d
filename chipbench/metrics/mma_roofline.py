"""Share of the MMA kernel's device time that the window's 3x3 convs need
at the chip's peaks: summed least times (``readers.least_conv_seconds``,
ops and bytes counted per conv, not per im2col operand) over the summed
device time of the kernel's ops, matched by name."""
from chipbench.readers import kernel_seconds, least_conv_seconds

# the Mosaic kernel's op names in a v5e trace: mma_matmul_pallas_p5.13, ...
KERNEL = r"^mma_matmul(_scaled)?_pallas_p\d+u?(\.\d+)?$"


def read(ctx):
    t = kernel_seconds(ctx, KERNEL)
    if t <= 0:
        return None
    return 100.0 * least_conv_seconds(ctx) / t
