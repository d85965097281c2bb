"""Host seconds per call in build and pack (``core/sweep._build_point``,
``core/simulator.pack``)."""


def read(ctx):
    return ctx.per_call("build", "pack")
