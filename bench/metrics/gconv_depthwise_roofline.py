"""``gconv_depthwise``'s share of its roofline: the least time of the
``dwconv:pallas-vpu`` steps of the calls traced (each step the larger of its
FLOPs over peak FLOP/s and its bytes over peak HBM bytes/s,
``bench/depthwise.py``, ``bench/peaks.json``) over the kernel's device
time in the trace. Nothing where the plan has no such step or the trace
cannot tell the kernel apart. Moves ``images_per_s``."""
from bench import depthwise


def read(ctx):
    device_s = depthwise.device_seconds(ctx)
    if not device_s or not ctx.trace.calls or ctx.peaks is None:
        return None
    least = depthwise.least_seconds(depthwise.kernel_nodes(ctx.engine),
                                    ctx.peaks)
    return 100.0 * ctx.trace.calls * least / device_s
