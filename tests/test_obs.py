"""Observability regressions: repro.obs trace/metrics/report + the
profiled compiled engine.

Pins the layer's three contracts: the export schema round-trips through
both formats and the report CLI; a disabled tracer costs nothing (no
per-call allocation beyond a flag check — tracemalloc-verified); and
``compile_chain(profile=True)`` records the engine's call phases and
program builds while running the same fused program, outputs
bit-identical to the unprofiled engine."""
import json
import time
import tracemalloc

import numpy as np
import pytest

from repro.obs import Metrics, Tracer, exp_buckets, load_trace, percentile
from repro.obs import trace as trace_mod
from repro.obs.metrics import Histogram
from repro.obs.report import summarize


# ---------------------------------------------------------------------------
# tracer: nesting, ring buffer, export round-trip
# ---------------------------------------------------------------------------
def test_nested_span_parenting():
    tr = Tracer()
    with tr.span("outer", cat="t") as outer:
        with tr.span("inner", cat="t") as inner:
            with tr.span("leaf", cat="t") as leaf:
                pass
        with tr.span("inner2", cat="t") as inner2:
            pass
    by = {e["name"]: e for e in tr.events}
    assert by["outer"]["parent"] is None
    assert by["inner"]["parent"] == outer.id
    assert by["leaf"]["parent"] == inner.id
    assert by["inner2"]["parent"] == outer.id
    assert inner2.id != inner.id
    # children are contained in the parent's [ts, ts+dur] window
    for child in ("inner", "inner2"):
        assert by[child]["ts"] >= by["outer"]["ts"]
        assert (by[child]["ts"] + by[child]["dur"]
                <= by["outer"]["ts"] + by["outer"]["dur"] + 1e-6)


def test_add_span_explicit_endpoints_and_parenting():
    tr = Tracer()
    t0 = time.perf_counter()
    t1 = t0 + 0.25
    pid = tr.add_span("request", "request", t0, t1, attrs={"rid": 7})
    cid = tr.add_span("queue", "request", t0, t0 + 0.1, parent=pid)
    assert pid is not None and cid == pid + 1
    spans = [e for e in tr.events if e["type"] == "span"]
    req = next(s for s in spans if s["name"] == "request")
    assert req["dur"] == pytest.approx(0.25e6, rel=1e-6)
    assert next(s for s in spans
                if s["name"] == "queue")["parent"] == pid
    # out-of-order endpoints clamp to zero duration, never negative
    assert tr.add_span("x", "t", t1, t0) is not None
    assert [e for e in tr.events if e["name"] == "x"][0]["dur"] == 0.0


def test_ring_buffer_keeps_most_recent_events():
    tr = Tracer(capacity=10)
    for i in range(25):
        tr.instant(f"e{i}")
    assert len(tr.events) == 10
    assert [e["name"] for e in tr.events] == [f"e{i}" for i in range(15, 25)]


@pytest.mark.parametrize("suffix", [".json", ".jsonl"])
def test_export_round_trip_both_formats(tmp_path, suffix):
    tr = Tracer()
    tr.meta["kind"] = "test"
    tr.meta["slots"] = 2
    with tr.span("work", cat="chain", attrs={"signature": "sig0"}):
        with tr.span("step0", cat="execute", attrs={"backend": "pallas"}):
            pass
    tr.instant("marker", cat="serve", attrs={"tick": 3})
    tr.counter("slots", {"active": 2, "queued": 1})
    path = tmp_path / f"trace{suffix}"
    tr.write(str(path))
    got = load_trace(str(path))
    assert got.version == trace_mod.SCHEMA_VERSION
    assert got.meta == {"kind": "test", "slots": 2}
    assert [s["name"] for s in got.spans] == ["step0", "work"]
    step, work = got.spans
    assert step["parent"] == work["id"]
    assert step["args"]["backend"] == "pallas"
    assert got.instants[0]["args"] == {"tick": 3}
    assert got.counters[0]["values"] == {"active": 2, "queued": 1}


def test_chrome_export_is_perfetto_shaped(tmp_path):
    """The .json flavor is literal Chrome trace-event JSON: ph X/i/C
    events under traceEvents plus the schema header in otherData."""
    tr = Tracer()
    with tr.span("s"):
        pass
    tr.counter("c", {"v": 1})
    path = tmp_path / "t.json"
    tr.write(str(path))
    doc = json.loads(path.read_text())
    phs = sorted(e["ph"] for e in doc["traceEvents"])
    assert phs == ["C", "X"]
    x = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(x)
    assert doc["otherData"]["schema"] == trace_mod.SCHEMA
    assert doc["otherData"]["version"] == trace_mod.SCHEMA_VERSION


def test_load_trace_rejects_wrong_schema_and_version(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"schema": "other", "version": 1}) + "\n")
    with pytest.raises(ValueError, match="schema"):
        load_trace(str(bad))
    bad.write_text(json.dumps(
        {"schema": trace_mod.SCHEMA, "version": 99}) + "\n")
    with pytest.raises(ValueError, match="version"):
        load_trace(str(bad))
    bad.write_text(json.dumps(
        {"schema": trace_mod.SCHEMA,
         "version": trace_mod.SCHEMA_VERSION}) + "\n"
        + json.dumps({"type": "span", "name": "x"}) + "\n")
    with pytest.raises(ValueError, match="missing fields"):
        load_trace(str(bad))


# ---------------------------------------------------------------------------
# disabled tracer: provably free
# ---------------------------------------------------------------------------
def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("s", cat="t", attrs=None):
        pass
    tr.instant("i")
    tr.counter("c", {"v": 1})
    assert tr.add_span("a", "t", 0.0, 1.0) is None
    assert not tr.events


def test_disabled_span_allocates_nothing():
    """span() on a disabled tracer is a flag check returning a module
    singleton — zero allocations attributable to trace.py per call."""
    tr = Tracer(enabled=False)
    for _ in range(16):                    # warm any lazy interpreter state
        with tr.span("warm"):
            pass
    tracemalloc.start()
    try:
        snap0 = tracemalloc.take_snapshot()
        for _ in range(1000):
            with tr.span("hot"):
                pass
        snap1 = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    flt = (tracemalloc.Filter(True, trace_mod.__file__),)
    stats = snap1.filter_traces(flt).compare_to(
        snap0.filter_traces(flt), "lineno")
    # per-call allocation over 1000 calls would show count_diff ~ 1000
    # (a _Span or attrs dict each time); a couple of live one-off
    # interpreter-state blocks are fine
    grown = [s for s in stats if s.size_diff > 0]
    assert sum(s.count_diff for s in grown) < 10, [str(s) for s in grown]
    assert sum(s.size_diff for s in grown) < 1024, [str(s) for s in grown]


# ---------------------------------------------------------------------------
# metrics: percentile, histogram buckets, registry schema
# ---------------------------------------------------------------------------
def test_percentile_degenerate_and_numpy_agreement():
    assert percentile([], 50) == 0.0
    assert percentile([], 99) == 0.0
    assert percentile([3.25], 50) == 3.25
    assert percentile([3.25], 99) == 3.25
    rng = np.random.default_rng(0)
    xs = rng.exponential(size=37).tolist()
    for q in (0, 25, 50, 90, 99, 100):
        assert percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)), abs=1e-12)


def test_histogram_bucket_boundaries():
    h = Histogram([1.0, 2.0, 4.0])
    for v in (0.0, 1.0):                  # le convention: bound inclusive
        h.observe(v)
    h.observe(1.5)
    h.observe(2.0)
    h.observe(4.0)
    h.observe(4.0001)                     # overflow bucket
    assert h.counts == [2, 2, 1, 1]
    assert h.count == 6
    assert h.sum == pytest.approx(12.5001)
    assert h.mean == pytest.approx(12.5001 / 6)
    with pytest.raises(ValueError, match="strictly increasing"):
        Histogram([1.0, 1.0, 2.0])
    bs = exp_buckets(1e-3, 1.0, 4)
    assert bs[0] == pytest.approx(1e-3) and bs[-1] == pytest.approx(1.0)
    assert len(bs) == 4


def test_metrics_schema_round_trip_snapshot_merge_diff():
    reg = Metrics()
    reg.counter("reqs", kind="a").inc(3)
    reg.gauge("active").set(2.5)
    reg.histogram("lat", [0.1, 1.0], kind="a").observe(0.05)
    d = reg.to_dict()
    assert d["schema"] == "repro.obs.metrics" and d["version"] == 1
    back = Metrics.from_dict(json.loads(json.dumps(d)))
    assert back.to_dict() == d

    snap = reg.snapshot()
    reg.counter("reqs", kind="a").inc(2)
    reg.histogram("lat", kind="a").observe(0.5)
    delta = reg.diff(snap)
    assert delta.value("reqs", kind="a") == 2.0
    (s,) = delta.to_dict()["metrics"]["lat"]["series"]
    assert s["count"] == 1 and s["counts"] == [0, 1, 0]

    merged = Metrics().merge(snap).merge(delta)
    assert merged.to_dict() == reg.to_dict()

    with pytest.raises(ValueError, match="counter"):
        reg.gauge("reqs")                 # family type is sticky
    with pytest.raises(ValueError, match="declare buckets"):
        Metrics().histogram("fresh")


# ---------------------------------------------------------------------------
# report: synthetic trace
# ---------------------------------------------------------------------------
def _synthetic_serve_trace():
    tr = Tracer()
    tr.meta.update(kind="serve", slots=2)
    base = time.perf_counter()
    for rid, (qw, ttft, lat) in enumerate(
            [(0.1, 0.2, 1.0), (0.0, 0.1, 0.5), (0.3, 0.5, 2.0)]):
        t0 = base + rid
        pid = tr.add_span("request", "request", t0, t0 + lat,
                          attrs={"rid": rid, "out_len": 4,
                                 "queue_wait_s": qw, "ttft_s": ttft,
                                 "latency_s": lat})
        tr.add_span("queue", "request", t0, t0 + qw, parent=pid)
        tr.add_span("prefill", "request", t0 + qw, t0 + ttft, parent=pid)
        tr.add_span("decode", "request", t0 + ttft, t0 + lat, parent=pid)
    for active in (1, 2, 1, 0):
        tr.counter("slots", {"active": active, "queued": 0})
    return tr


def test_report_summarize_synthetic_serve_trace():
    tr = _synthetic_serve_trace()
    out = summarize(trace_mod.Trace(dict(tr.meta), list(tr.events),
                                    trace_mod.SCHEMA_VERSION))
    assert out["requests"] == 3
    assert out["p50_ttft_s"] == percentile([0.2, 0.1, 0.5], 50)
    assert out["p99_latency_s"] == percentile([1.0, 0.5, 2.0], 99)
    assert out["tokens_out"] == 12
    assert out["slot_utilization"] == pytest.approx(1.0 / 2, abs=1e-4)
    assert set(out["phases"]) == {"queue", "prefill", "decode"}
    assert out["phases"]["decode"]["count"] == 3
    # request spans have children, so self-time ranks the phases on top
    assert out["top_spans"][0]["name"] != "request" or \
        out["top_spans"][0]["self_us"] < out["top_spans"][0]["total_us"]


def test_report_cli_exit_codes(tmp_path):
    from repro.obs.report import main
    tr = _synthetic_serve_trace()
    path = tmp_path / "t.json"
    tr.write(str(path))
    assert main([str(path)]) == 0
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    assert main([str(bad)]) == 1
    assert main([str(tmp_path / "missing.json")]) == 1


def test_report_cli_text_format(tmp_path, capsys):
    from repro.obs.report import main
    tr = _synthetic_serve_trace()
    path = tmp_path / "t.json"
    tr.write(str(path))
    assert main([str(path), "--format", "json"]) == 0
    out = json.loads(capsys.readouterr().out)     # default stays machine-readable
    assert out["requests"] == 3
    assert main([str(path), "--format", "text"]) == 0
    text = capsys.readouterr().out
    assert "requests" in text and "p50" in text
    with pytest.raises(SystemExit):
        main([str(path), "--format", "yaml"])


# ---------------------------------------------------------------------------
# serve-schema iterators (shared by report + syssim replay)
# ---------------------------------------------------------------------------
def _ticked_serve_trace():
    """Synthetic trace carrying the full tick-stamped lifecycle schema."""
    tr = Tracer()
    tr.meta.update(kind="serve", slots=2)
    base = time.perf_counter()
    lifecycle = [  # rid, submit, admit, done, prompt, out
        (1, 0, 0, 4, 8, 4),
        (0, 0, 1, 3, 6, 2),
        (2, 2, 2, 2, 4, 3),   # done == admit -> service_ticks floors at 1
    ]
    for rid, sub, adm, done, plen, out in lifecycle:
        t0 = base + rid
        pid = tr.add_span("request", "request", t0, t0 + 1.0,
                          attrs={"rid": rid, "prompt_len": plen,
                                 "out_len": out, "max_new": 8,
                                 "submit_tick": sub, "admit_tick": adm,
                                 "done_tick": done, "ttft_s": 0.1,
                                 "latency_s": 1.0, "queue_wait_s": 0.05})
        tr.add_span("queue", "request", t0, t0 + 0.25, parent=pid)
        tr.add_span("decode", "request", t0 + 0.25, t0 + 1.0, parent=pid)
    for i, (active, queued) in enumerate([(1, 2), (2, 1), (2, 0), (1, 0)]):
        tr.counter("slots", {"active": active, "queued": queued, "tick": i})
    return trace_mod.Trace(dict(tr.meta), list(tr.events),
                           trace_mod.SCHEMA_VERSION)


def test_serve_requests_iterator_schema_and_order():
    reqs = _ticked_serve_trace().serve_requests()
    assert [r.rid for r in reqs] == [0, 1, 2]   # (submit_tick, rid) order
    r0 = reqs[0]
    assert r0.submit_tick == 0 and r0.admit_tick == 1 and r0.done_tick == 3
    assert r0.tokens == 6 + 2                   # prompt + recorded out_len
    assert r0.service_ticks == 2
    assert r0.phases["queue"] == pytest.approx(0.25, rel=1e-6)
    assert r0.phases["decode"] == pytest.approx(0.75, rel=1e-6)
    assert reqs[2].service_ticks == 1           # floored, never zero
    # out_len falls back to the max_new budget when not recorded
    partial = trace_mod.ServeRequest(
        rid=9, prompt_len=4, max_new=8, out_len=None, submit_tick=None,
        admit_tick=None, done_tick=None, queue_wait_s=None, ttft_s=None,
        latency_s=None)
    assert partial.tokens == 12 and partial.service_ticks is None


def test_serve_ticks_iterator():
    ticks = _ticked_serve_trace().serve_ticks()
    assert [t.index for t in ticks] == [0, 1, 2, 3]
    assert [t.active for t in ticks] == [1, 2, 2, 1]
    assert [t.queued for t in ticks] == [2, 1, 0, 0]
    # pre-tick-stamp traces fall back to sample order
    legacy = _synthetic_serve_trace()
    lt = trace_mod.Trace(dict(legacy.meta), list(legacy.events),
                         trace_mod.SCHEMA_VERSION).serve_ticks()
    assert [t.index for t in lt] == [0, 1, 2, 3]
    assert [t.active for t in lt] == [1, 2, 1, 0]


def test_recorded_server_trace_round_trips_iterators(tmp_path):
    """A real Server run carries the tick-stamped schema end to end."""
    from benchmarks.serve_bench import _workload
    from repro.launch.serve import Server

    tr = Tracer()
    srv = Server("tinyllama-1.1b", smoke=True, slots=2, max_len=64,
                 tracer=tr)
    srv.run_workload(_workload(3, srv.cfg.vocab, max_new=3),
                     stagger_ticks=1)
    path = tmp_path / "serve.json"
    tr.write(str(path))
    trace = load_trace(str(path))
    reqs = trace.serve_requests()
    assert len(reqs) == 3
    for r in reqs:
        assert r.submit_tick is not None and r.done_tick is not None
        assert r.service_ticks >= 1 and r.tokens > 0
    ticks = trace.serve_ticks()
    assert ticks and [t.index for t in ticks] == list(range(len(ticks)))
    assert max(t.active for t in ticks) <= 2


# ---------------------------------------------------------------------------
# profiled compiled engine
# ---------------------------------------------------------------------------
def _mn_case():
    import jax

    from repro.core.interpreter import init_chain_params
    from repro.models import cnn

    chain = cnn.build("MN", reduced=True, batch=1)
    params = init_chain_params(chain, jax.random.PRNGKey(0))
    return chain, cnn.random_inputs(chain), params


@pytest.mark.slow
def test_profile_mode_coverage_and_attribution(tmp_path):
    from repro.exec import compile_chain

    chain, inputs, params = _mn_case()
    plain = compile_chain(chain)
    eng = compile_chain(chain, profile=True)
    assert eng.tracer is not None and eng.tracer.enabled
    spans = [e for e in eng.tracer.events if e["type"] == "span"]
    assert [s["name"] for s in spans] == ["compile.partition",
                                          "compile.plan", "compile.lint"]
    assert {s["cat"] for s in spans} == {"compile"}

    first = eng(inputs, params)            # cold: the program is built
    spans = [e for e in eng.tracer.events if e["type"] == "span"][3:]
    by_name = {s["name"]: s for s in spans}
    assert [s["name"] for s in spans] == ["engine.call", "engine.args",
                                          "engine.launch", "engine.compile"]
    call = by_name["engine.call"]
    assert call["parent"] is None and call["cat"] == "engine"
    assert call["args"]["signature"] == eng.signature
    assert by_name["engine.args"]["parent"] == call["id"]
    assert by_name["engine.launch"]["parent"] == call["id"]
    built = by_name["engine.compile"]
    assert built["parent"] == by_name["engine.launch"]["id"]
    assert built["args"]["programs"] == 1
    assert built["args"]["trace_s"] > 0 and built["args"]["compile_s"] > 0

    got = eng(inputs, params)              # warm: nothing is built
    spans = [e for e in eng.tracer.events if e["type"] == "span"]
    assert [s["name"] for s in spans[-3:]] == ["engine.call", "engine.args",
                                               "engine.launch"]
    assert sum(s["name"] == "engine.compile" for s in spans) == 1
    ref = plain(inputs, params)
    for o in ref:                          # the same fused program
        np.testing.assert_array_equal(np.asarray(first[o]),
                                      np.asarray(ref[o]))
        np.testing.assert_array_equal(np.asarray(got[o]),
                                      np.asarray(ref[o]))

    path = str(tmp_path / "engine.json")
    eng.tracer.write(path)
    prof = summarize(load_trace(path))["profile"]
    assert prof["span"] == "engine.call"
    assert prof["children"] == ["engine.args", "engine.launch"]
    assert prof["coverage"] >= 0.95, prof
    assert eng.metrics.value("engine_timed_calls") == 2


def test_profile_disabled_is_default_and_matches():
    from repro.exec import compile_chain

    chain, inputs, params = _mn_case()
    eng = compile_chain(chain)
    assert eng.tracer is None and not eng.options.profile
    off = compile_chain(chain, profile=True, tracer=Tracer(enabled=False))
    got, ref = off(inputs, params), eng(inputs, params)
    for o in ref:
        np.testing.assert_array_equal(np.asarray(got[o]),
                                      np.asarray(ref[o]))
    assert not off.tracer.events
    # no profiler session and no tracer: no call is timed
    assert "engine_timed_calls" not in off.metrics.families()
    assert "engine_timed_calls" not in eng.metrics.families()
