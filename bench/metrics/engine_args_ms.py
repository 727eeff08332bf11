"""Host milliseconds per call in the engine's ``engine.args`` span: the
checks of the inputs and parameters and their ``jnp.asarray``. The engine
sums the span's seconds in ``engine.metrics`` (``engine_span_s``) over
the calls it makes while a profiler session records
(``engine_timed_calls``), which in a run of the benchmark are the calls of
the traced window. Nothing where the engine keeps no such counters.
Moves ``call_p95_ms``."""
from bench import engine_counters


def read(ctx):
    return engine_counters.span_ms_per_call(ctx.engine, "engine.args")
