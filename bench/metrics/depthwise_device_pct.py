"""Share of the device's operation time, in the traced window, spent in
the ``gconv_depthwise`` kernel (``bench/depthwise.py``). Nothing where the
plan has no ``dwconv:pallas-vpu`` step or the trace cannot tell the kernel
apart. Moves ``images_per_s``."""
from bench import depthwise


def read(ctx):
    device_s = depthwise.device_seconds(ctx)
    if not device_s or not ctx.trace.op_s:
        return None
    return 100.0 * device_s / ctx.trace.op_s
