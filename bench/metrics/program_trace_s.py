"""Seconds the engine's programs took to trace to a jaxpr and lower to an
MLIR module when they were built (``engine_trace_s`` of
``engine.metrics``, summed over its programs; the persistent compilation
cache cannot save this part). Nothing where the engine keeps no such
counter. Moves ``setup_s``."""
from bench import engine_counters


def read(ctx):
    return engine_counters.total(ctx.engine, "engine_trace_s")
