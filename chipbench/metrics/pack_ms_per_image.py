"""Device ms of the ops under a ``pack`` scope (``kernels.ops.mma_matmul``:
the pads to the kernel's 128/512/128 blocks and the output slice) inside the
window, per image completed in the window."""
from chipbench.phases import ms_per_image


def read(ctx):
    return ms_per_image(ctx, "pack")
