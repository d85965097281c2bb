"""Host seconds per call in ``run_batch`` before the device wait: the
program's ``run_batch.init`` and ``run_batch.dispatch`` spans."""
from harness import scopes


def read(ctx):
    return scopes.per_call(ctx, "run_batch.init", "run_batch.dispatch")
