"""Share of the device's operation time, in the traced window, spent in
operations that are neither a Pallas kernel nor one of XLA's convolutions
or dots (batch norm, LRN, pools, pads, copies, concat, softmax); see
``bench/devtrace.py`` for the classes. Moves ``images_per_s``."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.op_s:
        return None
    return 100.0 * t.class_s.get("other", 0.0) / t.op_s
