"""Host milliseconds per call in the engine's ``engine.launch`` span: from
calling the jitted program until it returns its unfinished outputs, with
the outputs' dict. Summed by the engine over the calls of the traced
window, as for ``engine_args_ms``. Nothing where the engine keeps no such
counters. Moves ``call_p95_ms``."""
from bench import engine_counters


def read(ctx):
    return engine_counters.span_ms_per_call(ctx.engine, "engine.launch")
