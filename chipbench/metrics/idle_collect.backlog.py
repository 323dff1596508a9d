"""Share of the traced window in which the device idled while the host's
innermost span was a step after the device had finished: ``segserve.fetch``
(the wait and the copy to the host) or ``.stitch``."""
from chipbench.phases import idle_share


def read(ctx):
    return idle_share(ctx, "collect")
