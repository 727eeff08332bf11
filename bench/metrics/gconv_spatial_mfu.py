"""``gconv_spatial``'s share of the chip's peak FLOP/s: the FLOPs of the
``conv:pallas`` steps of the calls traced over the peak
(``bench/work.py``, ``bench/peaks.json``), over their kernels' device time
in the trace (``bench/kernel_mfu.py``). Nothing when the plan has no such
step or the trace shows none. Moves ``images_per_s``."""
from bench import kernel_mfu


def read(ctx):
    return kernel_mfu.share(ctx, "conv:pallas")
