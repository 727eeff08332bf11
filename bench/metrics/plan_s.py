"""Host seconds of ``compile_chain`` (partition, dispatch and lowering
into steps; no XLA compilation happens in it). Benchmark host clock.
Moves ``setup_s``."""


def read(ctx):
    return ctx.plan_s
