"""Device µs per executed lane-cycle inside the driver's per-cycle guard
(``driver.cycle``) that no leaf operation covers: the step's op issue and
control (profiler trace, ``harness/scopes.py``)."""
from harness import scopes


def read(ctx):
    return scopes.us_per_lane_cycle(ctx, "step_gap")
