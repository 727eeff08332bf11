"""Work counts from GCONV shapes: MACs and FLOPs of a step.

Counted from each node's loop parameters (paper §3.1: per dimension ``Ng``,
``Nop``, ``Nopc``, ``Nks``), so the count is the same whatever implements
a step, and one MAC is two FLOPs.

A kernel's least time is its FLOPs over the chip's peak FLOP/s. The bytes
it moves set no lower bound here: XLA's memory-space assignment keeps some
kernel operands in on-chip memory (the ``S(1)`` layouts of the trace), so
the bytes that cross HBM can be fewer than the input, kernel and output of
a step, and a bound counted from those read above 100% on the chip.
"""
from __future__ import annotations

import math
from typing import Dict, List

# layers whose MACs are model work (the chain's ``meta`` layer tags)
CONV_FC_LAYERS = ("conv2d", "depthwise_conv", "fc")


def macs(node) -> int:
    """Main-operator applications: the product over dimensions of
    ``Ng * Nop * Nopc * Nks``."""
    return math.prod(d.ng * d.nop * d.nopc * d.nks for d in node.dims)


def conv_fc_nodes(chain) -> List[str]:
    return [n for n in chain.nodes
            if chain.meta.get(n, {}).get("layer") in CONV_FC_LAYERS]


def model_macs(chain) -> int:
    """MACs of every conv (depthwise included) and fc layer of a chain."""
    return sum(macs(chain.nodes[n]) for n in conv_fc_nodes(chain))


def planned_macs(chain, dispatch: Dict[str, str]) -> Dict[str, int]:
    """Conv/fc MACs by the backend tag the plan gave each layer."""
    out: Dict[str, int] = {}
    for n in conv_fc_nodes(chain):
        tag = dispatch[n]
        out[tag] = out.get(tag, 0) + macs(chain.nodes[n])
    return out


def step_flops(fused_chain, steps, backend: str) -> List[int]:
    """FLOPs of every planned step with ``backend``."""
    return [2 * macs(fused_chain.nodes[s.name]) for s in steps
            if s.backend == backend]


def least_seconds(flops: List[int], peaks: dict) -> float:
    """Least time the chip could take for these steps: FLOPs over peak
    FLOP/s (see the module docstring for why bytes set no bound)."""
    return sum(flops) / peaks["flops_per_s"]
