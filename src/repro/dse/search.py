"""Chain-level mapping search. The spec-space strategy engines (seeded
random sampling, simulated annealing, elitist genetic search) live in
:mod:`repro.dse.strategies`.

Mapping search (:func:`search_mapping`): a chain-level hill climb over
Algorithm-1 *priority variants* — per the paper (§4.4), accelerators differ
only in the parameter priorities of Lines 7-22, so re-running the mapper
under permuted spatial/temporal priorities explores alternative legal
mappings without ever constructing an invalid one. Candidates flow through
``core.costmodel.chain_mappings(overrides=...)`` (and thus
``Mapping.validate``), the climb starts from the greedy Algorithm-1 chain
cost and accepts strict improvements only — so the searched result is
*never worse* than Algorithm 1's output, by construction.
"""
from __future__ import annotations

import random
from dataclasses import replace
from typing import Dict, Tuple

from repro.core.costmodel import gconv_chain_cost
from repro.core.gconv import GConv
from repro.core.mapping import Mapping, map_gconv

from .space import PRIORITIES, TEMPORAL_PRIORITIES


# ---------------------------------------------------------------------------
# per-chain GCONV mapping search
# ---------------------------------------------------------------------------
def _variant_spec(spec, rng) -> "object":
    """A priority-permuted copy of ``spec`` (same resources — per §4.4 only
    Algorithm 1's Lines 7-22 change)."""
    spatial = tuple(replace(s, priority=rng.choice(PRIORITIES))
                    for s in spec.spatial)
    return replace(spec, spatial=spatial,
                   temporal_priority=rng.choice(TEMPORAL_PRIORITIES))


def search_mapping(chain, spec, budget: int = 48, seed: int = 0,
                   consistent: bool = True,
                   ) -> Tuple[Dict[str, Mapping], dict]:
    """Hill-climb per-node mappings of an (already fused) chain on ``spec``.

    Each trial re-maps one GCONV node under permuted Algorithm-1 priorities
    and re-scores the *whole chain* (producer/consumer alignment and the
    §4.3 loop exchange react to every per-node change, so node-local scoring
    would not be sound). A candidate override set is kept only when it
    strictly improves chain latency (energy breaks ties), starting from the
    greedy Algorithm-1 chain — the result is therefore never worse than
    Algorithm 1's output.

    Returns ``(overrides, report)``; ``overrides`` maps node name ->
    :class:`Mapping` and plugs directly into
    ``chain_mappings(chain, spec, overrides=...)``, ``gconv_chain_cost`` or
    ``repro.sim.engine.simulate_chain``.
    """
    rng = random.Random(seed)
    base = gconv_chain_cost(chain, spec, consistent=consistent)
    gnodes = [name for name, node in chain.nodes.items()
              if isinstance(node, GConv)]
    overrides: Dict[str, Mapping] = {}
    best = (base.latency, base.energy)
    accepted = 0
    for _ in range(budget if gnodes else 0):
        name = rng.choice(gnodes)
        cand_map = map_gconv(chain.nodes[name], _variant_spec(spec, rng))
        cand = dict(overrides)
        cand[name] = cand_map
        cost = gconv_chain_cost(chain, spec, consistent=consistent,
                                overrides=cand)
        if (cost.latency, cost.energy) < best:
            overrides, best = cand, (cost.latency, cost.energy)
            accepted += 1
    report = dict(
        chain=chain.name, accel=spec.name, budget=budget, seed=seed,
        greedy_latency=base.latency, searched_latency=best[0],
        greedy_energy=base.energy, searched_energy=best[1],
        improvement=base.latency / max(best[0], 1e-12),
        n_overrides=len(overrides), accepted=accepted,
    )
    assert best[0] <= base.latency, "mapping search regressed vs Algorithm 1"
    return overrides, report
