"""Whole-life-cost design-space exploration (the repo's third subsystem,
alongside ``repro.exec`` and ``repro.sim``).

Multi-fidelity search over accelerator specs and per-GCONV mappings: every
candidate is scored with the paper's analytic cost model
(``core.costmodel``), and only the Pareto-frontier survivors are promoted to
the cycle-level simulator (``repro.sim``) for validation.

    PYTHONPATH=src python -m repro.dse.run --suite zoo --budget 200 --seed 0
"""
from .evaluate import (EvalRecord, Evaluator, SUITES, area_proxy, geomean,
                       load_suite, pareto_front, suite_names)
from .search import search_mapping
from .space import (FIELDS, PRIORITIES, TEMPORAL_PRIORITIES, Point,
                    SpecSpace, baseline_points)
from .strategies import (STRATEGIES, GeneticSearch, RandomSearch,
                         SearchResult, SimulatedAnnealing)


def __getattr__(name):
    # lazy: importing .run at package-import time would shadow
    # ``python -m repro.dse.run`` (runpy double-import warning)
    if name in ("run_dse", "dominates_at_budget", "RESULTS_DIR"):
        from . import run as _run
        return getattr(_run, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "EvalRecord", "Evaluator", "SUITES", "area_proxy", "geomean",
    "load_suite", "pareto_front", "suite_names",
    "STRATEGIES", "GeneticSearch", "RandomSearch", "SearchResult",
    "SimulatedAnnealing", "search_mapping",
    "FIELDS", "PRIORITIES", "TEMPORAL_PRIORITIES", "Point", "SpecSpace",
    "baseline_points",
    "dominates_at_budget", "run_dse",
]
