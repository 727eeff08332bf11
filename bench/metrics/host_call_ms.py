"""Mean host time of ``CompiledChain.__call__``, from the call until it
returns (before the benchmark waits on the output), over the measured
window. Benchmark host clock. Moves ``call_p95_ms``."""


def read(ctx):
    host = ctx.window.host_call_s
    return 1e3 * sum(host) / len(host) if host else None
