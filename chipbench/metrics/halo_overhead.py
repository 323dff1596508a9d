"""Window pixels of the tiles run for the images completed in the window,
over those images' pixels: what exact halos cost (a count)."""
from chipbench.readers import tiles_by_core


def read(ctx):
    done = {im.rid for im in ctx.completed_in_window()}
    if not done:
        return None
    by_core = tiles_by_core(ctx)
    px = sum(by_core[core].shape[0] * by_core[core].shape[1]
             for step in ctx.window.steps for rid, core in step.tiles
             if rid in done)
    h, w, _ = ctx.conf["image"]
    return px / (len(done) * h * w)
