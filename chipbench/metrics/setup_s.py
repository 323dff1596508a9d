"""Process start to the window's first request: imports, chip start-up,
weights, images and the warm-up (from the compile cache once it holds
the cell's programs)."""


def read(ctx):
    return ctx.setup_s
