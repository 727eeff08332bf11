"""A kernel's share of the chip's peak FLOP/s over its own device time,
read from a traced window.

This is the FLOP term of the kernel's roofline alone. Its bytes term is
left out because the trace does not show the bytes that cross HBM: XLA
keeps some kernel operands in on-chip memory (the ``S(1)`` layouts), and
a bound counted from every operand's size read above 100% on the chip.
"""
from __future__ import annotations

from bench import work


def share(ctx, backend: str):
    """100 x (calls traced x FLOPs of the plan's ``backend`` steps / peak
    FLOP/s) / (their kernels' device seconds in the trace); None where there
    is nothing to read."""
    t = ctx.trace
    steps = work.step_flops(ctx.engine.chain, ctx.engine.steps, backend)
    device_s = t.class_s.get(backend, 0.0) if t is not None else 0.0
    if not steps or not device_s or not t.calls or ctx.peaks is None:
        return None
    return (100.0 * t.calls * work.least_seconds(steps, ctx.peaks)
            / device_s)
