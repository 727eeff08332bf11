"""Best-fitness-vs-evaluations trajectory recorder.

``repro.dse.run`` writes its convergence curve in this schema so strategy
benchmarks and the viz loop can overlay runs:
``{"schema": "repro.search.trajectory/v1", "metric": ...,
"trajectory": [{"n": 1, "fitness": ..., "best_fitness": ...}, ...]}``.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Sequence

SCHEMA = "repro.search.trajectory/v1"


@dataclass
class TrajectoryRecorder:
    """Running-minimum convergence curve over fitness values in evaluation
    order. Feed it scores as they happen (:meth:`record`) or all at once
    (:meth:`extend`); read the curve, the converged best and the
    evaluations-to-best count; serialize with :meth:`to_json`/:meth:`write`.
    """

    metric: str = "fitness"
    entries: List[Dict[str, float]] = field(default_factory=list)

    def record(self, fitness: float) -> None:
        best = min(fitness, self.best_fitness)
        self.entries.append(dict(n=len(self.entries) + 1, fitness=fitness,
                                 best_fitness=best))

    def extend(self, scores: Sequence[float]) -> None:
        for s in scores:
            self.record(s)

    @property
    def best_fitness(self) -> float:
        return (self.entries[-1]["best_fitness"] if self.entries
                else float("inf"))

    @property
    def evals_to_best(self) -> int:
        """1-based index of the evaluation that reached the final best
        (0 when empty)."""
        best = self.best_fitness
        return next((e["n"] for e in self.entries
                     if e["best_fitness"] == best), 0)

    def to_json(self, **header) -> dict:
        """The committed artifact: caller-supplied header fields (config,
        strategy, ...) ahead of the canonical curve fields."""
        return dict(schema=SCHEMA, **header, metric=self.metric,
                    evals_to_best=self.evals_to_best,
                    trajectory=list(self.entries))

    def write(self, path: str, **header) -> None:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_json(**header), f, indent=1, default=float)
