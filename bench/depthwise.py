"""Work of the ``dwconv:pallas-vpu`` steps (``kernels/gconv_depthwise``),
and the kernel's device time in a traced window.

Counted from each node's GCONV shapes, so the count is the same whatever
implements a step: one MAC (two FLOPs) per tap of every output, and the
bytes the kernel cannot do without, in float32: the unpadded input read
once, the output written once and the taps read once. The kernel's own
padding, phase split and any re-reading are not counted.

The trace names no class for this kernel: its custom calls fall in
``bench/devtrace.py``'s ``mosaic`` class, which holds every Mosaic kernel
but ``gconv_matmul`` and ``gconv_spatial``. So the readers here read
nothing where the plan has another Pallas kernel that would land there.
"""
from __future__ import annotations

import math
from typing import List, Optional

from bench import work

BACKEND = "dwconv:pallas-vpu"
# Pallas steps whose kernels the trace tells apart from ``mosaic``
NAMED = ("matmul:pallas", "conv:pallas")
F32 = 4


def kernel_nodes(engine) -> List:
    """The fused chain's nodes of every ``dwconv:pallas-vpu`` step."""
    return [engine.chain.nodes[s.name] for s in engine.steps
            if s.backend == BACKEND]


def step_flops(node) -> int:
    return 2 * work.macs(node)


def step_bytes(node) -> int:
    """Unpadded input + output + taps, float32."""
    taps = math.prod(d.ng * d.nop * d.nks for d in node.dims)
    return F32 * (math.prod(d.in_size for d in node.dims)
                  + math.prod(node.out_shape) + taps)


def least_seconds(nodes, peaks: dict) -> float:
    """Each step's larger of FLOPs over peak FLOP/s and bytes over peak HBM
    bytes/s, summed."""
    return sum(max(step_flops(n) / peaks["flops_per_s"],
                   step_bytes(n) / peaks["hbm_bytes_per_s"])
               for n in nodes)


def device_seconds(ctx) -> Optional[float]:
    """The kernel's device seconds in the traced window: the ``mosaic``
    class, where the plan has a ``dwconv:pallas-vpu`` step and no other
    Pallas kernel that the trace would put in that class."""
    t = ctx.trace
    steps = ctx.engine.steps
    if t is None or not any(s.backend == BACKEND for s in steps):
        return None
    if any("pallas" in s.backend and s.backend not in NAMED + (BACKEND,)
           for s in steps):
        return None
    return t.class_s.get("mosaic") or None
