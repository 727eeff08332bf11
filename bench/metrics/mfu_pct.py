"""The whole call's share of the chip's bf16 peak: 2 x the conv and fc MACs
of one call (depthwise included) x calls per second of the measured
window, over ``flops_per_s`` of ``bench/peaks.json``. Moves
``images_per_s``."""
from bench import work


def read(ctx):
    if not ctx.window.calls or ctx.peaks is None:
        return None
    flops = 2 * work.model_macs(ctx.chain) * ctx.window.calls
    return 100.0 * flops / ctx.window.seconds / ctx.peaks["flops_per_s"]
