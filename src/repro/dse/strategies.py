"""Seeded search strategies over a :class:`~repro.dse.space.SpecSpace`.

Three strategies behind one :class:`Strategy` protocol — seeded random
sampling, simulated annealing, and a small elitist genetic search. All draw
exclusively from a ``random.Random(seed)`` stream and iterate deterministic
data structures, so a fixed seed reproduces the exact evaluation history
and best point. The engines know nothing about what a point *means*: they
call only the space's ``sample``/``mutate``/``crossover`` and the
objective (the analytic cost model's whole-life cost).
"""
from __future__ import annotations

import math
import random
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Protocol, Sequence, Tuple

from .space import Point, SpecSpace


class BudgetExhausted(Exception):
    """Raised by the scorer when the evaluation budget is spent."""


class Scorer:
    """Budget-counting, memoizing objective wrapper handed to strategies.
    Repeat evaluations of a point are free (cache hit); only unique points
    consume budget."""

    def __init__(self, objective: Callable[[Point], float], budget: int):
        self._objective = objective
        self.left = budget
        self.memo: Dict[Point, float] = {}
        self.history: List[Tuple[Point, float]] = []
        # consecutive cache hits: when a (small or tightly-budgeted) space
        # runs out of unseen valid points, proposals stop consuming budget —
        # declare exhaustion rather than letting a strategy loop forever
        self._stale = 0

    def __call__(self, point: Point) -> float:
        if point in self.memo:
            self._stale += 1
            if self._stale > 100 * max(1, self.left):
                raise BudgetExhausted
            return self.memo[point]
        if self.left <= 0:
            raise BudgetExhausted
        self._stale = 0
        self.left -= 1
        s = self._objective(point)
        self.memo[point] = s
        self.history.append((point, s))
        return s

    def best(self) -> Tuple[Point, float]:
        return min(self.history, key=lambda ps: (ps[1], ps[0]))


@dataclass
class SearchResult:
    strategy: str
    best: Point
    best_score: float
    n_evals: int
    history: List[Tuple[Point, float]] = field(default_factory=list)


class Strategy(Protocol):
    name: str

    def run(self, space: SpecSpace, objective: Callable[[Point], float],
            budget: int, seed: int = 0,
            seeds: Sequence[Point] = ()) -> SearchResult:
        """Spend up to ``budget`` unique evaluations minimizing
        ``objective``; deterministic under a fixed ``seed``."""
        ...


def _finish(name: str, scorer: Scorer) -> SearchResult:
    if not scorer.history:
        raise ValueError("search budget must allow at least 1 evaluation")
    best, best_score = scorer.best()
    return SearchResult(strategy=name, best=best, best_score=best_score,
                        n_evals=len(scorer.history),
                        history=list(scorer.history))


class RandomSearch:
    """Seeded uniform sampling — the multi-fidelity baseline strategy."""

    name = "random"

    def run(self, space, objective, budget, seed=0, seeds=()):
        rng = random.Random(seed)
        scorer = Scorer(objective, budget)
        try:
            for p in seeds:
                scorer(p)
            while True:
                scorer(space.sample(rng))
        except BudgetExhausted:
            pass
        return _finish(self.name, scorer)


class SimulatedAnnealing:
    """Single-chain Metropolis walk with a geometric temperature schedule.
    Defaults are calibrated to objectives normalized near 1.0 (the WLC
    scale, where ER == 1.0)."""

    name = "anneal"

    def __init__(self, t0: float = 0.25, t1: float = 0.005):
        self.t0, self.t1 = t0, t1

    def run(self, space, objective, budget, seed=0, seeds=()):
        rng = random.Random(seed)
        scorer = Scorer(objective, budget)
        try:
            cur = min(seeds, key=scorer) if seeds else space.sample(rng)
            cur_s = scorer(cur)
            steps = max(1, budget - len(scorer.history))
            decay = (self.t1 / self.t0) ** (1.0 / steps)
            t = self.t0
            while True:
                cand = space.mutate(cur, rng,
                                    n_fields=1 if rng.random() < 0.7 else 2)
                cand_s = scorer(cand)
                d = cand_s - cur_s
                if d <= 0 or rng.random() < math.exp(-d / max(t, 1e-9)):
                    cur, cur_s = cand, cand_s
                t *= decay
        except BudgetExhausted:
            pass
        return _finish(self.name, scorer)


class GeneticSearch:
    """Small elitist GA: tournament selection, uniform crossover with
    budget-repair, per-child mutation."""

    name = "genetic"

    def __init__(self, pop_size: int = 12, n_elite: int = 2,
                 p_mutate: float = 0.35):
        self.pop_size, self.n_elite, self.p_mutate = (
            pop_size, n_elite, p_mutate)

    def run(self, space, objective, budget, seed=0, seeds=()):
        rng = random.Random(seed)
        scorer = Scorer(objective, budget)

        def tournament(pop: List[Point]) -> Point:
            a, b = rng.choice(pop), rng.choice(pop)
            return a if scorer.memo[a] <= scorer.memo[b] else b

        try:
            pop: List[Point] = []
            for p in seeds:
                scorer(p)
                pop.append(p)
            while len(pop) < self.pop_size:
                p = space.sample(rng)
                if p not in scorer.memo:
                    scorer(p)
                    pop.append(p)
            stale = 0
            while True:
                ranked = sorted(pop, key=lambda p: (scorer.memo[p], p))
                nxt = ranked[: self.n_elite]
                while len(nxt) < self.pop_size:
                    child = space.crossover(tournament(pop), tournament(pop),
                                            rng)
                    if rng.random() < self.p_mutate:
                        child = space.mutate(child, rng)
                    # converged populations breed already-scored children
                    # (free, but no progress): push them further out
                    if child in scorer.memo:
                        child = space.mutate(child, rng, n_fields=2)
                        stale += 1
                        if stale > 50 * budget:
                            raise BudgetExhausted
                    else:
                        stale = 0
                    scorer(child)
                    nxt.append(child)
                pop = nxt
        except BudgetExhausted:
            pass
        return _finish(self.name, scorer)


STRATEGIES: Dict[str, Callable[[], Strategy]] = {
    "random": RandomSearch,
    "anneal": SimulatedAnnealing,
    "genetic": GeneticSearch,
}
