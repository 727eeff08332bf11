"""Seconds the engine's programs took in XLA and Mosaic compilation, or in
loading the executable from the persistent compilation cache, when they
were built (``engine_compile_s`` of ``engine.metrics``, summed over its
programs). Nothing where the engine keeps no such counter. Moves
``setup_s``."""
from bench import engine_counters


def read(ctx):
    return engine_counters.total(ctx.engine, "engine_compile_s")
