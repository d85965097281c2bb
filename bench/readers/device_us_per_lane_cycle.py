"""Device busy microseconds, summed over the cell's devices, per simulated
lane-cycle of the traced calls' real lanes (profiler trace).  Padding
lanes of a sharded launch show up as cost."""


def read(ctx):
    if ctx.trace is None or not ctx.traced_lane_cycles:
        return None
    return ctx.trace["busy_s_total"] / ctx.traced_lane_cycles * 1e6
