"""The one traffic generator: reads a cell's ``traffic`` parameters.

A traffic mix is data, never code: a new mix is a new cell file whose
``traffic`` object sets some of these keys (the rest take their default).

``keep_queued`` (default 0)
    Closed loop: before every engine event the generator tops the engine's
    queue up to this many waiting images, so it is never empty.  Each such
    image is due when it is submitted.
``rate_per_s`` (default 0)
    Open loop: arrivals per second over the window.  The window holds
    ``round(rate_per_s * seconds)`` arrivals whose gaps are the exponential
    distribution's quantiles at ``(j + 0.5) / n``: every seed gets the same
    set of gaps, in an order drawn from the seed, so seeds change the
    order and not the amount of work.
``group`` (default 1)
    Images per open-loop arrival, all due at once (a study of slices).

``name`` labels the mix and is not read.  Images come from the
configuration's pool in order.
"""
from __future__ import annotations

import math

from chipbench import rng

_ORDER_TAG = 0x9015504E
KEYS = {"name", "keep_queued", "rate_per_s", "group"}


def checked(traffic: dict) -> dict:
    """``traffic`` with its defaults; an unknown key or a mix that offers
    no load is an error."""
    unknown = set(traffic) - KEYS
    if unknown:
        raise ValueError(f"unknown traffic keys {sorted(unknown)}; known: {sorted(KEYS)}")
    out = {"keep_queued": 0, "rate_per_s": 0.0, "group": 1, **traffic}
    if out["keep_queued"] <= 0 and out["rate_per_s"] <= 0:
        raise ValueError("traffic offers no load: set keep_queued or rate_per_s")
    if out["group"] < 1:
        raise ValueError("traffic group must be at least 1")
    return out


def arrival_times(traffic: dict, seconds: float, seed: int) -> list[float]:
    """Due times, in seconds from the window's start, of the open-loop
    images, one per image; empty where the mix has no open-loop part."""
    t = checked(traffic)
    rate, group = float(t["rate_per_s"]), int(t["group"])
    n = int(round(rate * seconds))
    if n == 0:
        return []
    gaps = [-math.log(1.0 - (j + 0.5) / n) / rate for j in range(n)]
    order = sorted(range(n), key=lambda j: rng.mix(seed, _ORDER_TAG, j))
    at, out = 0.0, []
    for j in order:
        out.extend([at] * group)  # the first arrival is due as the window opens
        at += gaps[j]
    return out
