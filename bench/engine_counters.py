"""Read the engine's own counters (``CompiledChain.metrics``, a
``repro.obs.metrics.Metrics``). An engine that keeps none reads as
nothing, so a metric built on them is absent, not an error."""
from __future__ import annotations

from typing import Optional


def total(engine, name: str, **labels) -> Optional[float]:
    """The sum of every series of the counter or gauge ``name`` whose
    labels include ``labels``; None where the engine has no such family."""
    metrics = getattr(engine, "metrics", None)
    if metrics is None or not hasattr(metrics, "to_dict"):
        return None
    family = metrics.to_dict()["metrics"].get(name)
    if family is None:
        return None
    return sum(s["value"] for s in family["series"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def span_ms_per_call(engine, span: str) -> Optional[float]:
    """Milliseconds of ``span`` per call the engine timed (the calls made
    while a profiler session recorded)."""
    calls = total(engine, "engine_timed_calls")
    secs = total(engine, "engine_span_s", span=span)
    if not calls or secs is None:
        return None
    return 1e3 * secs / calls
