"""Percent of the window's lane-cycle budget the lanes executed: the
program's ``executed_lane_cycles`` over ``budget_lane_cycles`` counters
(Σ ``drain_cycle`` ÷ Σ ``cycles_run``, ``compute_metrics_batch``)."""
from harness import scopes


def read(ctx):
    return scopes.executed_share(ctx)
