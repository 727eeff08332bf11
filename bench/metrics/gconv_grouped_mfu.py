"""``gconv_spatial``'s share of the chip's peak FLOP/s on its group axis:
the FLOPs of the grouped ``conv:pallas`` steps of the calls traced over
the peak (``bench/grouped.py``, ``bench/peaks.json``), over the grouped
kernel's device time in the trace. Each such step's FLOPs take longer at
peak than its unpadded bytes at peak HBM bandwidth, so this is the
kernel's roofline share. Nothing where the plan has no such step or the
trace cannot tell the kernel apart. Moves ``images_per_s``."""
from bench import grouped


def read(ctx):
    device_s = grouped.device_seconds(ctx)
    if not device_s or not ctx.trace.calls or ctx.peaks is None:
        return None
    least = grouped.least_seconds(grouped.kernel_nodes(ctx.engine),
                                  ctx.peaks)
    return 100.0 * ctx.trace.calls * least / device_s
