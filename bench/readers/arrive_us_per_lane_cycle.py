"""Device µs per executed lane-cycle in the cycle step's ``step.arrive``
scope: its leaf operations' time, summed over the cell's devices
(profiler trace, ``harness/scopes.py``)."""
from harness import scopes


def read(ctx):
    return scopes.us_per_lane_cycle(ctx, "step.arrive")
