"""Host seconds per call in ``core/simulator.run_batch``: initial state,
stacking, dispatch and the device run to ``block_until_ready``."""


def read(ctx):
    return ctx.per_call("launch")
