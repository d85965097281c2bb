"""Host seconds per call in ``core/metrics.compute_metrics_batch``."""


def read(ctx):
    return ctx.per_call("metrics")
