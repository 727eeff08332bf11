"""Whole-life-cost design-space exploration driver.

    PYTHONPATH=src python -m repro.dse.run --suite zoo --budget 200 --seed 0

Runs a seeded search (the three Table-4 baselines ER/TPU/EP are always in
the initial population), promotes the top-k Pareto-frontier points to
cycle-level validation (``repro.sim``), compares the best point against
every baseline *at equal-or-smaller PE/buffer budget*, hill-climbs per-node
GCONV mappings for the best point's spec, and writes three artifacts to
``results/dse/``:

  * ``evals.json``      — the run config + every per-point evaluation
    record;
  * ``frontier.json``   — the (latency, energy, area) Pareto set;
  * ``best.json``       — the best point's spec, per-workload breakdown,
    sim cross-check, baseline-domination verdicts and the mapping-search
    report;
  * ``trajectory.json`` — best-fitness-vs-evaluations convergence curve in
    the ``repro.search.trajectory/v1`` schema (``metric: "wlc"``,
    ``[{n, fitness, best_fitness}...]`` in evaluation order).

Exit status is nonzero when a promoted point violates the analytic-vs-sim
agreement contract (``repro.sim.validate``) — the searched designs must stay
inside the region where the cheap fidelity is trustworthy.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Dict, List, Optional, Sequence

from repro.core import accelerators as acc

from .evaluate import SUITES, EvalRecord, Evaluator, load_suite, pareto_front
from .search import search_mapping
from .space import SpecSpace, baseline_points
from .strategies import STRATEGIES, SearchResult
from .trajectory import TrajectoryRecorder

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dse")
BASELINES = ("ER", "TPU", "EP")


def _spec_json(spec) -> dict:
    d = dataclasses.asdict(spec)
    d["n_pes"] = spec.n_pes
    return d


def dominates_at_budget(rec: EvalRecord, base: EvalRecord) -> bool:
    """Strictly better whole-life cost while using no more PEs and no more
    buffer capacity than the baseline — the equal-budget domination claim."""
    return (rec.n_pes <= base.n_pes and rec.gb_words <= base.gb_words
            and rec.wlc < base.wlc)


def run_dse(suite: str = "zoo", budget: int = 200, seed: int = 0,
            strategy: str = "genetic", topk: int = 8,
            map_budget: int = 32, out_dir: Optional[str] = RESULTS_DIR,
            reduced: bool = False, quiet: bool = False,
            trace: Optional[str] = None) -> dict:
    """Programmatic entry point; returns the ``best.json`` payload plus the
    frontier and evaluator (used by benchmarks and tests).

    ``trace`` (required for ``--suite serve``, optional elsewhere) scores
    the promoted frontier and the baselines against a recorded serve
    trace via ``repro.syssim`` — the best point is then chosen by the
    system-under-traffic WLC, and the trace's identity + provenance are
    recorded into ``best.json``."""
    if budget < 1:
        raise ValueError(f"--budget must be >= 1, got {budget}")
    if suite == "serve" and trace is None:
        raise ValueError("--suite serve needs --trace PATH "
                         "(a launch/serve.py --trace recording)")
    t0 = time.perf_counter()
    say = (lambda *a: None) if quiet else print
    chains = load_suite(suite, reduced=reduced)
    space = SpecSpace()
    ev = Evaluator(space, chains)
    seeds = baseline_points(space)

    say(f"dse: suite={suite} ({len(chains)} chains) strategy={strategy} "
        f"budget={budget} seed={seed}")
    # points/sec is the committed search-throughput trajectory metric: time
    # the analytic search alone (not suite building, sim promotion or
    # mapping search)
    t_search = time.perf_counter()
    res: SearchResult = STRATEGIES[strategy]().run(
        space, ev.objective, budget, seed=seed,
        seeds=[seeds[b] for b in BASELINES])
    search_s = time.perf_counter() - t_search

    records = ev.records
    frontier = pareto_front(records)
    say(f"dse: {ev.n_evals} points evaluated, frontier size {len(frontier)}")

    # ---- search trajectory: best fitness vs evaluations -------------------
    # Evaluator.cache preserves insertion order, so `records` IS the
    # evaluation order; the recorder's running minimum is the
    # convergence curve the strategy benchmarks (and the archgym-style viz
    # loop) consume.
    recorder = TrajectoryRecorder(metric="wlc")
    recorder.extend([rec.wlc for rec in records])
    best_so_far = recorder.best_fitness
    evals_to_best = recorder.evals_to_best
    say(f"dse: trajectory converged to wlc {best_so_far:.4f} after "
        f"{evals_to_best}/{len(recorder.entries)} evaluations")

    # ---- multi-fidelity promotion: top-k frontier points -> repro.sim -----
    all_promoted: List[EvalRecord] = []   # every sim promotion feeds the gate
    promoted = ev.promote(frontier[:max(1, topk)])
    all_promoted += promoted
    say(f"dse: promoted {len(promoted)} frontier points to cycle-level sim")

    # ---- system-under-traffic promotion: recorded trace -> repro.syssim ---
    loaded_trace = None
    if trace is not None:
        from repro.obs.trace import load_trace

        loaded_trace = load_trace(trace)
        ev.promote_syssim(promoted, loaded_trace, reduced=reduced)
        say(f"dse: replayed {trace} on {len(promoted)} promoted points "
            f"({len(loaded_trace.serve_requests())} recorded requests)")

    def _rank(r: EvalRecord):
        # the deepest fidelity available decides: trace replay beats
        # per-chain sim beats analytic
        if r.syssim is not None:
            return (r.syssim["wlc"], r.key)
        return ((r.sim or {}).get("wlc", r.wlc), r.key)

    best = min(promoted, key=_rank)

    # ---- baselines, sim-checked the same way ------------------------------
    base_recs: Dict[str, EvalRecord] = {}
    for name in BASELINES:
        rec = ev.score_spec(acc.get(name))
        all_promoted += ev.promote([rec])
        if loaded_trace is not None:
            ev.promote_syssim([rec], loaded_trace, reduced=reduced)
        base_recs[name] = rec
    domination = {}
    for name, base in base_recs.items():
        cands = [r for r in records if dominates_at_budget(r, base)]
        winner = min(cands, key=lambda r: (r.wlc, r.key)) if cands else None
        if winner is not None and winner.fidelity != "sim":
            all_promoted += ev.promote([winner])
        domination[name] = dict(
            baseline_wlc=base.wlc,
            baseline_sim_wlc=(base.sim or {}).get("wlc"),
            dominated=winner is not None,
            by=winner.key if winner else None,
            by_wlc=winner.wlc if winner else None,
            by_sim_wlc=(winner.sim or {}).get("wlc") if winner else None,
            sim_confirmed=bool(
                winner is not None and winner.sim is not None
                and base.sim is not None
                and winner.sim["wlc"] < base.sim["wlc"]),
        )
        say(f"dse: vs {name}: wlc {base.wlc:.3f} -> "
            + (f"{winner.wlc:.3f} ({winner.key[:40]}...) "
               f"sim_confirmed={domination[name]['sim_confirmed']}"
               if winner else "not dominated"))

    agree_ok = all((r.sim or {}).get("within_tolerance")
                   for r in all_promoted)
    say(f"dse: analytic-vs-sim agreement over {len(all_promoted)} promoted "
        f"points: {'ok' if agree_ok else 'VIOLATED'}")

    # ---- mapping search on the best point's spec --------------------------
    best_spec = space.to_spec(best.point)
    mapping_reports = []
    for name, chain in chains:
        _, rep = search_mapping(chain, best_spec, budget=map_budget,
                                seed=seed)
        mapping_reports.append(rep)
    map_gain = max(r["improvement"] for r in mapping_reports)
    say(f"dse: mapping search (budget {map_budget}/chain): max chain "
        f"improvement {map_gain:.4f}x over Algorithm 1")

    wall_s = time.perf_counter() - t0
    payload = dict(
        config=dict(suite=suite, budget=budget, seed=seed, strategy=strategy,
                    topk=topk, map_budget=map_budget, reduced=reduced,
                    trace=trace),
        n_evals=ev.n_evals, wall_s=round(wall_s, 3),
        search_s=round(search_s, 3),
        points_per_sec=round(ev.n_evals / max(search_s, 1e-9), 2),
        best=best.to_json(), best_spec=_spec_json(best_spec),
        baselines={k: v.to_json() for k, v in base_recs.items()},
        domination=domination,
        agreement_ok=bool(agree_ok),
        mapping_search=mapping_reports,
        frontier_size=len(frontier),
        search=dict(strategy=res.strategy, best_score=res.best_score,
                    n_evals=res.n_evals),
        trajectory=dict(points=len(recorder.entries), best_wlc=best_so_far,
                        evals_to_best=evals_to_best),
    )
    if loaded_trace is not None:
        # the served-traffic claim is only as good as the trace it was
        # scored on: stamp the trace's identity (path + content hash +
        # recorded meta) and this run's provenance into best.json
        import hashlib

        from repro.obs import provenance

        with open(trace, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()
        payload["trace"] = dict(
            path=os.path.abspath(trace), sha256=digest,
            meta=dict(loaded_trace.meta),
            requests=len(loaded_trace.serve_requests()),
            provenance=provenance())

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "evals.json"), "w") as f:
            json.dump(dict(config=payload["config"],
                           records=[r.to_json() for r in records]),
                      f, indent=1, default=float)
        with open(os.path.join(out_dir, "frontier.json"), "w") as f:
            json.dump(dict(config=payload["config"],
                           frontier=[r.to_json() for r in frontier]),
                      f, indent=1, default=float)
        with open(os.path.join(out_dir, "best.json"), "w") as f:
            json.dump(payload, f, indent=1, default=float)
        recorder.write(os.path.join(out_dir, "trajectory.json"),
                       config=payload["config"], strategy=res.strategy)
        say(f"dse: wrote {os.path.abspath(out_dir)}/"
            f"{{evals,frontier,best,trajectory}}.json")

    payload["_frontier"] = frontier
    payload["_evaluator"] = ev
    return payload


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--suite", choices=sorted(SUITES), default="zoo")
    ap.add_argument("--budget", type=int, default=200,
                    help="unique analytic point evaluations")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--strategy", choices=sorted(STRATEGIES),
                    default="genetic")
    ap.add_argument("--topk", type=int, default=8,
                    help="frontier points promoted to cycle-level sim "
                         "(clamped to >= 1: the best point is always "
                         "sim-cross-checked)")
    ap.add_argument("--map-budget", type=int, default=32,
                    help="mapping-search trials per chain on the best spec")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--reduced", action="store_true",
                    help="test-scale chain variants (CI smoke)")
    ap.add_argument("--trace", default=None,
                    help="recorded serve trace (launch/serve.py --trace); "
                         "scores the promoted frontier against the "
                         "recorded traffic via repro.syssim and records "
                         "the trace's provenance into best.json "
                         "(required for --suite serve)")
    args = ap.parse_args(argv)
    if args.suite == "serve" and args.trace is None:
        ap.error("--suite serve requires --trace PATH")
    payload = run_dse(suite=args.suite, budget=args.budget, seed=args.seed,
                      strategy=args.strategy, topk=args.topk,
                      map_budget=args.map_budget, out_dir=args.out,
                      reduced=args.reduced, trace=args.trace)
    # the headline claim counts only sim-confirmed domination (the analytic
    # verdict alone could flip inside the sim agreement tolerance)
    dominated = [k for k, v in payload["domination"].items()
                 if v["sim_confirmed"]]
    print(f"dse: best wlc={payload['best']['wlc']:.4f} "
          f"(sim {payload['best'].get('sim', {}).get('wlc', float('nan')):.4f}) "
          f"dominates at equal budget (sim-confirmed): "
          f"{', '.join(dominated) or 'none'}")
    return 0 if payload["agreement_ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
