"""1 - device busy time / traced window, averaged over the cell's devices
(profiler trace)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"]
