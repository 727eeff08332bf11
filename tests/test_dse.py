"""Tests for the whole-life-cost design-space explorer (repro.dse) and its
core hooks (Mapping.from_entries / chain_mappings overrides)."""
import json
import os
import random
import subprocess
import sys

import pytest

from repro.core import accelerators as acc
from repro.core.costmodel import chain_mappings, gconv_chain_cost
from repro.core.gconv import DimSpec, GConv
from repro.core.mapping import Entry, Mapping, MappingError, map_gconv
from repro.dse import (Evaluator, EvalRecord, SpecSpace, baseline_points,
                       load_suite, pareto_front, search_mapping)
from repro.dse.strategies import STRATEGIES


@pytest.fixture(scope="module")
def space():
    return SpecSpace()


@pytest.fixture(scope="module")
def reduced_suite():
    return load_suite("zoo", reduced=True)


@pytest.fixture(scope="module")
def evaluator(space, reduced_suite):
    return Evaluator(space, reduced_suite)


# ---------------------------------------------------------------------------
# space: encode/decode, validity, generation
# ---------------------------------------------------------------------------
def test_encode_decode_roundtrip(space):
    rng = random.Random(7)
    for _ in range(50):
        p = space.sample(rng)
        enc = space.encode(p)
        assert space.decode(enc) == p
        spec = space.to_spec(p)
        assert spec.n_pes <= space.max_pes
        assert sum(spec.gb.values()) <= space.max_gb_words


def test_decode_rejects_garbage(space):
    with pytest.raises(ValueError):
        space.decode("ax0=999999")           # missing fields + off-grid
    with pytest.raises(ValueError):
        space.decode("nonsense")
    good = space.encode(space.sample(random.Random(0)))
    with pytest.raises(ValueError):
        space.decode(good + ",bogus=1")      # unknown extra field


def test_sampling_and_mutation_stay_valid(space):
    rng = random.Random(3)
    p = space.sample(rng)
    for _ in range(100):
        q = space.mutate(p, rng)
        assert space.is_valid(q)
        child = space.crossover(p, q, rng)
        assert space.is_valid(child)
        p = q


def test_baseline_seeds_match_table4(space):
    pts = baseline_points(space)
    assert set(pts) == {"ER", "TPU", "EP"}
    for name, p in pts.items():
        real = acc.get(name)
        spec = space.to_spec(p)
        assert spec.n_pes == real.n_pes
        assert spec.gb == real.gb
        assert spec.ls == real.ls
        assert spec.gb_bandwidth == real.gb_bandwidth
        assert spec.has_overlap_primitive == real.has_overlap_primitive
        for enc_dim, real_dim in zip(spec.spatial, real.spatial):
            assert enc_dim.size == real_dim.size
            assert enc_dim.reduce == real_dim.reduce
            assert enc_dim.overlap == real_dim.overlap
            assert enc_dim.priority == real_dim.priority


# ---------------------------------------------------------------------------
# evaluator: objective + Pareto
# ---------------------------------------------------------------------------
def test_wlc_normalized_to_er(evaluator):
    assert evaluator.score_spec(acc.get("ER")).wlc == pytest.approx(1.0)


def test_pareto_toy_correctness():
    def rec(key, lat, energy, area):
        return EvalRecord(key=key, spec_name=key, point=None, lat=lat,
                          energy=energy, area=area, n_pes=1, gb_words=1,
                          wlc=lat + energy + area)

    a = rec("a", 1.0, 2.0, 3.0)          # frontier
    b = rec("b", 2.0, 1.0, 3.0)          # frontier (trades lat for energy)
    c = rec("c", 2.0, 2.0, 3.0)          # dominated by both a and b
    d = rec("d", 1.0, 2.0, 2.0)          # dominates a
    e = rec("e", 1.0, 2.0, 2.0)          # duplicate of d -> collapsed
    front = pareto_front([a, b, c, d, e])
    assert sorted(r.key for r in front) == ["b", "d"]


def test_baselines_in_space_score_identically(space, evaluator):
    """The encoded Table-4 seed points must cost exactly like the real
    specs they mirror (same resources + priorities => same mappings)."""
    for name, p in baseline_points(space).items():
        enc = evaluator.score_point(p)
        real = evaluator.score_spec(acc.get(name))
        assert enc.lat == pytest.approx(real.lat)
        assert enc.energy == pytest.approx(real.energy)


# ---------------------------------------------------------------------------
# strategies: determinism + budget
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("strategy", sorted(STRATEGIES))
def test_search_deterministic_under_seed(strategy, space, reduced_suite):
    def once():
        ev = Evaluator(space, reduced_suite)
        seeds = list(baseline_points(space).values())
        res = STRATEGIES[strategy]().run(space, ev.objective, budget=15,
                                         seed=11, seeds=seeds)
        frontier = pareto_front(ev.records)
        return (res.best, res.best_score, [r.key for r in frontier],
                [(p, s) for p, s in res.history])

    assert once() == once()


@pytest.mark.parametrize("strategy", sorted(STRATEGIES))
def test_search_respects_budget(strategy, space, evaluator):
    before = evaluator.n_evals
    res = STRATEGIES[strategy]().run(space, evaluator.objective, budget=9,
                                     seed=2)
    assert res.n_evals <= 9
    assert evaluator.n_evals - before <= 9
    assert res.best_score == min(s for _, s in res.history)


def test_seeded_search_never_worse_than_seeds(space, evaluator):
    """Seeding the baselines guarantees the search result is at least as
    good as the best hand-designed configuration."""
    seeds = list(baseline_points(space).values())
    seed_best = min(evaluator.objective(p) for p in seeds)
    res = STRATEGIES["anneal"]().run(space, evaluator.objective, budget=12,
                                     seed=5, seeds=seeds)
    assert res.best_score <= seed_best


# ---------------------------------------------------------------------------
# Mapping.from_entries / validate (the shared resource-limit path)
# ---------------------------------------------------------------------------
def _toy_gconv():
    return GConv(name="g", input="x", kernel="w", main="mul", reduce="add",
                 dims=(DimSpec("C", nop=8, nks=16),
                       DimSpec("W", nopc=32, nks=3, pad=1)))


def test_from_entries_reconstructs_algorithm1():
    g = _toy_gconv()
    spec = acc.get("ER")
    m = map_gconv(g, spec)
    rebuilt = Mapping.from_entries(g, spec, spatial=m.spatial,
                                   temporal=m.temporal)
    assert rebuilt.cycles() == m.cycles()
    assert rebuilt.movement() == m.movement()


def test_from_entries_rejects_axis_overflow():
    g = _toy_gconv()
    spec = acc.get("ER")                      # py axis has 12 PEs
    with pytest.raises(MappingError):
        Mapping.from_entries(g, spec,
                             spatial=[Entry("ks", "C", 16, "py")],
                             temporal=[Entry("op", "C", 8, "T"),
                                       Entry("opc", "W", 32, "T"),
                                       Entry("ks", "W", 3, "T")])


def test_from_entries_rejects_under_coverage():
    g = _toy_gconv()
    spec = acc.get("ER")
    with pytest.raises(MappingError):
        Mapping.from_entries(g, spec, spatial=[],
                             temporal=[Entry("op", "C", 8, "T")])


def test_from_entries_rejects_bad_placement():
    g = _toy_gconv()
    spec = acc.get("ER")
    full = [Entry("ks", "C", 16, "T"), Entry("op", "C", 8, "T"),
            Entry("opc", "W", 32, "T"), Entry("ks", "W", 3, "T")]
    with pytest.raises(MappingError):     # unknown spatial axis
        Mapping.from_entries(g, spec,
                             spatial=[Entry("ks", "C", 4, "nope")],
                             temporal=full)
    with pytest.raises(MappingError):     # temporal must be @T
        Mapping.from_entries(g, spec, spatial=[],
                             temporal=full[:-1] + [Entry("ks", "W", 3, "py")])
    with pytest.raises(MappingError):     # only opc entries slide
        Mapping.from_entries(
            g, spec, spatial=[],
            temporal=full[:-1] + [Entry("ks", "W", 3, "T", sliding=True)])


def test_chain_mappings_overrides_flow_through(reduced_suite):
    """An override replaces Algorithm 1's mapping for that node in both the
    mapping table and the chain cost — and the caller's object is cloned,
    not mutated, by the §4.3 loop exchange."""
    name, chain = reduced_suite[0]
    spec = acc.get("ER")
    node = next(n for n, g in chain.nodes.items() if isinstance(g, GConv))
    ov = map_gconv(chain.nodes[node], spec)
    before = [e for e in ov.temporal]
    mappings, _ = chain_mappings(chain, spec, overrides={node: ov})
    assert mappings[node] is not ov
    assert ov.temporal == before
    cost = gconv_chain_cost(chain, spec, overrides={node: ov})
    assert cost.latency > 0


# ---------------------------------------------------------------------------
# mapping search: never worse than Algorithm 1
# ---------------------------------------------------------------------------
def test_chain_mappings_overrides_reject_foreign_resources(reduced_suite):
    """A mapping built for a different accelerator's resources must not be
    injectable (it would smuggle that accelerator's scratchpads/bandwidth
    into the chain cost); priority-variant specs with identical resources
    are fine (that is how the mapping search works)."""
    import dataclasses

    name, chain = reduced_suite[0]
    spec = acc.get("ER")
    node = next(n for n, g in chain.nodes.items() if isinstance(g, GConv))
    foreign = map_gconv(chain.nodes[node], acc.get("EP"))
    with pytest.raises(MappingError):
        chain_mappings(chain, spec, overrides={node: foreign})
    variant = dataclasses.replace(
        spec, spatial=tuple(
            dataclasses.replace(s, priority=("op", "opc", "ks", "g"))
            for s in spec.spatial))
    ok = map_gconv(chain.nodes[node], variant)
    mappings, _ = chain_mappings(chain, spec, overrides={node: ok})
    assert mappings[node].cycles() == ok.cycles()


def test_chain_mappings_overrides_reject_unknown_node(reduced_suite):
    name, chain = reduced_suite[0]
    spec = acc.get("ER")
    node = next(n for n, g in chain.nodes.items() if isinstance(g, GConv))
    ov = map_gconv(chain.nodes[node], spec)
    with pytest.raises(MappingError):
        chain_mappings(chain, spec, overrides={"no_such_node": ov})


def test_search_mapping_never_worse_reduced(reduced_suite):
    spec = acc.get("ER")
    for name, chain in reduced_suite[:3]:
        ov, rep = search_mapping(chain, spec, budget=12, seed=0)
        assert rep["searched_latency"] <= rep["greedy_latency"]
        # overrides must reproduce the reported latency through the public
        # chain_mappings/gconv_chain_cost path
        cost = gconv_chain_cost(chain, spec, overrides=ov)
        assert cost.latency == pytest.approx(rep["searched_latency"])


@pytest.mark.slow
def test_search_mapping_never_worse_zoo_fullsize():
    """The ISSUE's regression: searched mappings are never worse than
    Algorithm 1's greedy output across the full-size zoo."""
    suite = load_suite("zoo")
    for accel in ("ER", "TPU", "EP"):
        spec = acc.get(accel)
        for name, chain in suite:
            ov, rep = search_mapping(chain, spec, budget=16, seed=0)
            assert rep["searched_latency"] <= rep["greedy_latency"], (
                f"{name}@{accel} regressed")


# ---------------------------------------------------------------------------
# driver: promotion, domination, artifacts
# ---------------------------------------------------------------------------
def test_run_dse_promotion_and_artifacts(tmp_path):
    from repro.dse import run_dse

    payload = run_dse(suite="zoo", budget=18, seed=0, strategy="anneal",
                      topk=2, map_budget=4, out_dir=str(tmp_path),
                      reduced=True, quiet=True)
    assert payload["frontier_size"] > 0
    best = payload["best"]
    assert best["fidelity"] == "sim"
    assert best["sim"]["within_tolerance"]
    assert best["sim"]["movement_drift"] <= 1e-9
    for fn in ("evals.json", "frontier.json", "best.json"):
        with open(tmp_path / fn) as f:
            json.load(f)
    # equal-budget domination claims carry the sim cross-check flag
    for name, verdict in payload["domination"].items():
        if verdict["dominated"]:
            assert verdict["by_wlc"] < verdict["baseline_wlc"]


@pytest.mark.slow
def test_dse_cli_end_to_end(tmp_path):
    """The module CLI writes artifacts and exits 0 (agreement holds)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.dse.run", "--suite", "zoo",
         "--budget", "30", "--seed", "0", "--strategy", "genetic",
         "--topk", "3", "--map-budget", "4", "--reduced",
         "--out", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr
    with open(tmp_path / "best.json") as f:
        best = json.load(f)
    assert best["agreement_ok"]
    assert best["config"]["budget"] == 30


# ---------------------------------------------------------------------------
# satellite: hillclimb import hygiene
# ---------------------------------------------------------------------------
def test_hillclimb_import_is_side_effect_free():
    proc = subprocess.run(
        [sys.executable, "-c",
         "import os, sys; before = os.environ.get('XLA_FLAGS');"
         "sys.path.insert(0, 'src');"
         "import repro.launch.hillclimb as hc;"
         "assert os.environ.get('XLA_FLAGS') == before;"
         "assert 'jax' not in sys.modules;"
         "assert not hasattr(hc, 'OUT')"],
        capture_output=True, text=True,
        cwd=os.path.join(os.path.dirname(__file__), ".."), timeout=120)
    assert proc.returncode == 0, proc.stderr
