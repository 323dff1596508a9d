"""Tiles run over tile slots offered (``engine.batch`` per micro-batch),
summed over the window's micro-batches: a count of the engine's batching."""
from chipbench.readers import batch_fill as read  # noqa: F401
