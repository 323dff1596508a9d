"""Share of the traced window in which the device idled while the host's
innermost span was the server's admission: ``segserve.admit`` or one of
its children (``.plan``, ``.canvas``, ``.classify``)."""
from chipbench.phases import idle_share


def read(ctx):
    return idle_share(ctx, "admit")
