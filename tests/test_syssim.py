"""System-simulator tests: degenerate exactness vs repro.sim,
heterogeneous overlap, serve-trace replay, and the arbitration
invariants (word conservation, monotone latency under contention)."""
import pytest
from _hypothesis_compat import example, given, settings, st

from repro.core import accelerators as acc
from repro.sim.validate import DEFAULT_ACCELS, DRIFT_TOL
from repro.syssim import (ChainJob, RoutedChain, SystemSpec, Task,
                          hetero, hetero_utilization_gain, maxmin_fair,
                          replay_trace, route_chain, simulate_system,
                          single_array, validate_degenerate)
from repro.syssim.system import ArrayUnit, VectorUnit

FAST_NETS = ("MN", "AN")


# ---------------------------------------------------------------------------
# degenerate contract: 1 unit + no contention == repro.sim
# ---------------------------------------------------------------------------
def test_degenerate_single_unit_matches_sim_reduced():
    rows, summary = validate_degenerate(nets=FAST_NETS,
                                        accels=DEFAULT_ACCELS, reduced=True)
    assert summary["pairs"] == len(FAST_NETS) * len(DEFAULT_ACCELS)
    for r in rows:
        assert r["exact"], r
        assert r["contention_stall_cycles"] == 0.0
        assert r["cycles_drift"] <= DRIFT_TOL
    assert summary["all_within_tolerance"]


@pytest.mark.slow
def test_degenerate_single_unit_matches_sim_full_zoo():
    rows, summary = validate_degenerate(reduced=False)
    assert summary["all_exact"], \
        [r for r in rows if not r["exact"]]
    assert summary["all_within_tolerance"]


def test_degenerate_report_reproduces_sim_breakdown():
    """Movement/energy/compute agree per-unit, not just in aggregate."""
    from repro.models import cnn

    chain = cnn.build("MN", reduced=True)
    system = single_array("ER")
    routed = route_chain(chain, system)
    report = simulate_system([ChainJob(routed=routed)], system)
    (u,) = report.units
    sim = routed.sim
    assert u.energy == pytest.approx(sim.energy, rel=1e-12)
    assert u.offered_words == pytest.approx(sim.movement_words, rel=1e-12)
    assert u.injected_words == pytest.approx(u.offered_words, rel=1e-9)
    assert report.word_conservation_err <= 1e-9
    assert report.makespan == pytest.approx(sim.total_cycles, rel=1e-12)
    # credits only apply to back-to-back same-unit tasks, and they did:
    assert report.handoff_overlap_cycles == pytest.approx(
        sim.handoff_overlap_cycles, rel=1e-12)


# ---------------------------------------------------------------------------
# heterogeneous routing + overlap
# ---------------------------------------------------------------------------
def test_routing_follows_plan_backend_tags():
    from repro.models import cnn

    chain = cnn.build("MN", reduced=True)
    system = hetero("ER")
    routed = route_chain(chain, system)
    kinds = {t.unit: system.unit(t.unit).kind for t in routed.tasks}
    assert set(kinds.values()) == {"array", "vector"}
    for t in routed.tasks:
        if system.unit(t.unit).kind == "vector":
            assert t.backend.startswith(
                ("elementwise", "reduce", "concat", "movement",
                 "segment:norm", "segment:softmax")), t.backend
    # forcing the array keeps every group on the array
    homo = route_chain(chain, system, use_vector=False)
    assert {t.unit for t in homo.tasks} == {"array0"}


def test_hetero_two_unit_overlap_beats_array_only():
    g = hetero_utilization_gain("MN", accel="ER", n_jobs=2, reduced=True)
    assert g["vector_tasks"] > 0
    assert g["strictly_higher"]
    assert g["hetero_utilization"] > g["array_only_utilization"]
    assert g["hetero_makespan"] < g["array_only_makespan"]


# ---------------------------------------------------------------------------
# serve-trace replay
# ---------------------------------------------------------------------------
def _recorded_trace(tmp_path, n=3, max_new=3):
    from benchmarks.serve_bench import _workload
    from repro.launch.serve import Server
    from repro.obs import Tracer

    tr = Tracer()
    srv = Server("tinyllama-1.1b", smoke=True, slots=2, max_len=64,
                 tracer=tr)
    srv.run_workload(_workload(n, srv.cfg.vocab, max_new=max_new),
                     stagger_ticks=1)
    path = str(tmp_path / "serve_trace.json")
    tr.write(path)
    return path


def test_replay_recorded_trace_no_dropped_requests(tmp_path):
    path = _recorded_trace(tmp_path)
    res = replay_trace(path, hetero("ER"), reduced=True)
    assert res.requests_recorded == 3
    assert res.requests_simulated == 3 and res.dropped == 0
    rep = res.report
    assert rep.goodput > 0 and rep.energy > 0
    assert rep.word_conservation_err <= 1e-9
    assert {j.rid for j in rep.jobs} == {0, 1, 2}
    # staggered submits -> distinct arrivals spaced by tick_cycles
    arrivals = sorted(j.arrival for j in rep.jobs)
    assert arrivals[0] == 0.0 and arrivals[1] > 0.0
    summ = res.summary()
    assert summ["dropped"] == 0 and summ["requests_recorded"] == 3


def test_replay_fixed_tick_cycles_is_comparable(tmp_path):
    """An explicit tick_cycles (the dse cross-candidate mode) is honored
    and scales arrivals linearly."""
    path = _recorded_trace(tmp_path)
    a = replay_trace(path, hetero("ER"), reduced=True, tick_cycles=100.0)
    b = replay_trace(path, hetero("ER"), reduced=True, tick_cycles=200.0)
    assert a.tick_cycles == 100.0 and b.tick_cycles == 200.0
    arr_a = sorted(j.arrival for j in a.report.jobs)
    arr_b = sorted(j.arrival for j in b.report.jobs)
    for x, y in zip(arr_a, arr_b):
        assert y == pytest.approx(2 * x)


def test_replay_rejects_requestless_trace(tmp_path):
    from repro.obs import Tracer

    tr = Tracer()
    tr.counter("slots", {"active": 0, "queued": 0})
    path = str(tmp_path / "empty.json")
    tr.write(path)
    with pytest.raises(ValueError, match="no 'request'"):
        replay_trace(path, single_array("ER"), reduced=True)


# ---------------------------------------------------------------------------
# arbitration invariants (property tests)
# ---------------------------------------------------------------------------
demand_list = st.lists(st.floats(min_value=0.0, max_value=64.0,
                                 allow_nan=False), min_size=1, max_size=8)


@given(demand_list, st.floats(min_value=0.01, max_value=256.0,
                              allow_nan=False))
@settings(max_examples=80, deadline=None)
def test_maxmin_fair_is_feasible_and_work_conserving(ds, capacity):
    demands = {f"u{i}": d for i, d in enumerate(ds)}
    alloc = maxmin_fair(demands, capacity)
    assert set(alloc) == set(demands)
    for u, a in alloc.items():
        assert -1e-9 <= a <= demands[u] + 1e-9         # never over-granted
    total = sum(alloc.values())
    want = min(capacity, sum(demands.values()))
    assert total == pytest.approx(want, rel=1e-9, abs=1e-9)  # no idle waste
    # max-min fairness: an unsatisfied unit's share is >= any other share
    for u, a in alloc.items():
        if a < demands[u] - 1e-6:
            assert a >= max(alloc.values()) - 1e-6


def _toy_system(n_tasks_bw):
    spec = acc.get("ER")
    return SystemSpec(name="toy", units=(ArrayUnit(spec=spec),),
                      interconnect_bw=n_tasks_bw)


def _toy_jobs(task_params, arrivals):
    """Synthetic single-unit jobs: (work, words) per task."""
    jobs = []
    for j, (tasks, arr) in enumerate(zip(task_params, arrivals)):
        tl = [Task(chain=f"job{j}", name=f"t{i}", unit="array0",
                   backend="oracle", work=w, compute=w * 0.5,
                   bus_words=words, movement={"I": words}, energy=1.0)
              for i, (w, words) in enumerate(tasks)]
        routed = RoutedChain(name=f"job{j}", tasks=tl, dispatch={},
                             sim=None)
        jobs.append(ChainJob(routed=routed, arrival=arr, name=f"job{j}"))
    return jobs


task_strategy = st.lists(
    st.tuples(st.floats(min_value=1.0, max_value=100.0, allow_nan=False),
              st.floats(min_value=0.0, max_value=500.0, allow_nan=False)),
    min_size=1, max_size=4)


@given(st.lists(task_strategy, min_size=1, max_size=3),
       st.floats(min_value=0.5, max_value=32.0, allow_nan=False))
@settings(max_examples=60, deadline=None)
@example(jobs_params=[[(1.0, 3.467560678637506e-296)]], bw=1.0)
def test_engine_conserves_words_under_contention(jobs_params, bw):
    arrivals = [3.0 * i for i in range(len(jobs_params))]
    jobs = _toy_jobs(jobs_params, arrivals)
    report = simulate_system(jobs, _toy_system(bw))
    offered = sum(words for tasks in jobs_params for _, words in tasks)
    assert report.movement_words == pytest.approx(offered, rel=1e-9,
                                                  abs=1e-9)
    assert report.interconnect.forwarded_words == pytest.approx(
        offered, rel=1e-9, abs=1e-6)
    injected = sum(u.injected_words for u in report.units)
    assert injected == pytest.approx(offered, rel=1e-9, abs=1e-6)
    assert len(report.jobs) == len(jobs_params)
    for j in report.jobs:
        assert j.finish >= j.arrival - 1e-9


@given(task_strategy, st.floats(min_value=1.0, max_value=32.0,
                                allow_nan=False),
       st.floats(min_value=1.1, max_value=8.0, allow_nan=False))
@settings(max_examples=60, deadline=None)
@example(tasks=[(1.0, 1.7710721796340165e-189)], bw=1.0, squeeze=2.0)
def test_engine_latency_monotone_in_capacity(tasks, bw, squeeze):
    wide = simulate_system(_toy_jobs([tasks], [0.0]), _toy_system(bw))
    narrow = simulate_system(_toy_jobs([tasks], [0.0]),
                             _toy_system(bw / squeeze))
    assert narrow.makespan >= wide.makespan - 1e-6
    # every lost cycle is attributed to arbitration stall
    slip = narrow.makespan - wide.makespan
    assert narrow.contention_stall_cycles >= slip - 1e-6


@given(st.lists(task_strategy, min_size=1, max_size=2), task_strategy,
       st.floats(min_value=0.5, max_value=16.0, allow_nan=False))
@settings(max_examples=40, deadline=None)
@example(base_jobs=[[(1.0, 4.271146188712234e-43)]], extra=[(1.0, 0.0)],
         bw=1.0)
def test_engine_latency_monotone_in_load(base_jobs, extra, bw):
    arrivals = [0.0] * len(base_jobs)
    before = simulate_system(_toy_jobs(base_jobs, arrivals),
                             _toy_system(bw))
    after = simulate_system(_toy_jobs(base_jobs + [extra],
                                      arrivals + [0.0]), _toy_system(bw))
    # adding a concurrent job never speeds the shared system up
    assert after.makespan >= before.makespan - 1e-6


def test_engine_rejects_bad_jobs():
    jobs = _toy_jobs([[(10.0, 5.0)]], [-1.0])
    with pytest.raises(ValueError, match="negative arrival"):
        simulate_system(jobs, _toy_system(8.0))
    stray = _toy_jobs([[(10.0, 5.0)]], [0.0])
    stray[0].routed.tasks[0].unit = "nope"
    with pytest.raises(KeyError):
        simulate_system(stray, _toy_system(8.0))


# ---------------------------------------------------------------------------
# system spec validation
# ---------------------------------------------------------------------------
def test_system_spec_validation():
    spec = acc.get("ER")
    with pytest.raises(ValueError, match="at least one unit"):
        SystemSpec(name="x", units=())
    with pytest.raises(ValueError, match="ArrayUnit"):
        SystemSpec(name="x", units=(VectorUnit(),))
    with pytest.raises(ValueError, match="duplicate"):
        SystemSpec(name="x", units=(ArrayUnit(spec=spec, name="u"),
                                    VectorUnit(name="u")))
    with pytest.raises(ValueError, match="capacity"):
        SystemSpec(name="x", units=(ArrayUnit(spec=spec),),
                   interconnect_bw=0.0)
    sys2 = hetero(spec)
    assert sys2.capacity == pytest.approx(
        sum(u.link_bw for u in sys2.units))
    assert sys2.unit("vec0").kind == "vector"
    with pytest.raises(KeyError):
        sys2.unit("nope")
