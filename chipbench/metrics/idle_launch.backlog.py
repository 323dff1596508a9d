"""Share of the traced window in which the device idled while the host's
innermost span was a step before the device had its micro-batch:
``segserve.gather``, ``.upload`` or ``.dispatch``."""
from chipbench.phases import idle_share


def read(ctx):
    return idle_share(ctx, "launch")
