"""Work of the grouped ``conv:pallas`` steps (``kernels/gconv_spatial`` on
its group axis), and the kernel's device time in a traced window.

A grouped step is a conv whose channel dimension has ``Ng > 1`` groups of
``Nks > 1`` input channels (AlexNet's conv2, conv4 and conv5); a depthwise
conv (``Nks == 1``) is ``bench/depthwise.py``'s. :func:`is_grouped` is the
test of ``repro.exec.lowering.is_grouped``, written out here because the
benchmark also reads programs that lack that helper. A grouped step's
FLOPs and bytes are counted as ``bench/depthwise.py`` counts a depthwise
step's, from the node's GCONV shapes: one MAC, two FLOPs, per tap of
every output; the unpadded input, taps and output in float32, each moved
once.

The grouped kernel runs under its own jitted name (``_gconv_grouped``), so
the trace puts it in ``bench/devtrace.py``'s ``mosaic`` class, not in
``conv:pallas`` with the ungrouped convs. The readers here read nothing
where the plan has another Pallas kernel that would land in that class.
"""
from __future__ import annotations

from typing import List, Optional

from bench.depthwise import NAMED, step_bytes, step_flops  # noqa: F401

BACKEND = "conv:pallas"


def is_grouped(node) -> bool:
    return any(d.ng > 1 and d.nks > 1 and d.nopc == 1 for d in node.dims)


def kernel_nodes(engine) -> List:
    """The fused chain's nodes of every grouped ``conv:pallas`` step."""
    nodes = [engine.chain.nodes[s.name] for s in engine.steps
             if s.backend == BACKEND]
    return [n for n in nodes if is_grouped(n)]


def least_seconds(nodes, peaks: dict) -> float:
    """The steps' FLOPs over peak FLOP/s."""
    return sum(step_flops(n) for n in nodes) / peaks["flops_per_s"]


def device_seconds(ctx) -> Optional[float]:
    """The kernel's device seconds in the traced window: the ``mosaic``
    class, where the plan has a grouped ``conv:pallas`` step and no Pallas
    kernel that the trace would put in that class too."""
    t = ctx.trace
    if t is None or not kernel_nodes(ctx.engine):
        return None
    if any("pallas" in s.backend and s.backend not in NAMED
           for s in ctx.engine.steps):
        return None
    return t.class_s.get("mosaic") or None
