"""Images whose whole mask was stitched inside the window, per second."""


def read(ctx):
    return len(ctx.completed_in_window()) / ctx.window_s
