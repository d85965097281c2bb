"""Host seconds per call waiting on the device in ``run_batch``: the
program's ``run_batch.wait`` span (``block_until_ready``)."""
from harness import scopes


def read(ctx):
    return scopes.per_call(ctx, "run_batch.wait")
