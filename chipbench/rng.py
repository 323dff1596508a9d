"""Counter-based random numbers: a pure function of (seed, counters).

SplitMix64 (Steele et al.), as in ``repro.workload.arrivals``; copied so
that the yardstick does not move when the program does.  Seeds may be any
non-negative whole number below 2**64.
"""
from __future__ import annotations

_M64 = (1 << 64) - 1


def splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & _M64
    z = x
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M64
    return (z ^ (z >> 31)) & _M64


def mix(seed: int, *counters: int) -> int:
    """64-bit hash of the seed and each counter, chained."""
    h = splitmix64(int(seed) & _M64)
    for c in counters:
        h = splitmix64(h ^ (int(c) & _M64))
    return h

