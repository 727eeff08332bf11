"""Share of the traced window in which no operation ran on the device:
1 - (union of the ``XLA Ops`` intervals) / window. Moves
``call_p95_ms``."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
