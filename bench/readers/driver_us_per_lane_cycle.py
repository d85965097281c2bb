"""Device µs per executed lane-cycle in the chunked driver outside the
step: ``driver.*`` leaves (drain check, finalize) and loop-nest time
outside the per-cycle guard (profiler trace, ``harness/scopes.py``)."""
from harness import scopes


def read(ctx):
    return scopes.us_per_lane_cycle(ctx, "driver")
