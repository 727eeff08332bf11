"""Share of the device's operation time, in the traced window, spent in
``gconv_spatial``'s grouped kernel (``bench/grouped.py``). Nothing where
the plan has no grouped ``conv:pallas`` step or the trace cannot tell the
kernel apart. Moves ``images_per_s``."""
from bench import grouped


def read(ctx):
    device_s = grouped.device_seconds(ctx)
    if not device_s or not ctx.trace.op_s:
        return None
    return 100.0 * device_s / ctx.trace.op_s
