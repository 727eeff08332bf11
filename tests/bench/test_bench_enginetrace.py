"""The engine's spans, steps and counters as the benchmark reads them.

- On the trace the benchmark already had (``gln_b32_trace``, recorded
  before the engine carried spans), every number of ``devtrace``'s
  ``Summary`` and every trace-read metric is pinned, and
  ``bench/enginetrace.py`` reads what ``devtrace`` reads.
- On a ``googlenet.b1`` window recorded on a TPU v5e with the engine's
  spans (``gln_b1_engine``, with the program's ``op_steps()`` beside it),
  ``enginetrace`` reads the spans, every instruction's device time and
  the gap labels.
- The counter-read metrics read an engine's own counters, and nothing
  from an engine that keeps none.
"""
import gzip
import json
import os
from types import SimpleNamespace

import pytest

from bench import devtrace, engine_counters, enginetrace, spec

DATA = os.path.join(spec.BENCH_DIR, "testdata")


def _planes(tmp_path_factory, name):
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(os.path.join(DATA, name), "rb") as f:
        path.write_bytes(f.read())
    return list(ProfileData.from_file(str(path)).planes)


@pytest.fixture(scope="module")
def old(tmp_path_factory):
    return _planes(tmp_path_factory, "gln_b32_trace.xplane.pb.gz")


@pytest.fixture(scope="module")
def b1(tmp_path_factory):
    with open(os.path.join(DATA, "gln_b1_engine.steps.json")) as f:
        steps = json.load(f)
    return _planes(tmp_path_factory, "gln_b1_engine.xplane.pb.gz"), steps


def test_the_old_trace_reads_as_it_did(old):
    s = devtrace.reduce(old)
    assert (s.annotated_calls, s.devices) == (3, 1)
    assert s.window_s == pytest.approx(0.058195577000000005, rel=1e-12)
    assert s.busy_s == pytest.approx(0.05040490400000008, rel=1e-12)
    assert s.op_s == pytest.approx(0.05040490400000008, rel=1e-12)
    assert s.class_s == pytest.approx({
        "conv:pallas": 0.010814046000000008,
        "matmul:pallas": 0.0008037030000000583,
        "other": 0.036994854999999965,
        "xla_mac": 0.0017923000000000452}, rel=1e-12)
    assert [k for k, _v in s.top_ops] == [
        "fusion.3 fusion:kCustom", "reduce.29 reduce",
        "fusion fusion:kCustom", "pad.116 pad", "pad.123.clone pad",
        "fusion.2 fusion:kCustom", "copy.374 copy", "copy.222 copy",
        "reduce.28 reduce", "reduce.26 reduce"]
    assert [v for _k, v in s.top_ops] == pytest.approx([
        0.004970675000000008, 0.004928974000000003, 0.00281082299999999,
        0.0018738910000000095, 0.0015976430000000166,
        0.0013530310000000045, 0.0012943029999999897,
        0.001276303999999985, 0.0012310660000000029,
        0.0012242929999999805], rel=1e-12)
    assert [k for k, _v in s.idle_gaps] == \
        ["host in CompiledChain.__call__"] * 3 \
        + ["host waiting on the result"] * 7
    assert [v for _k, v in s.idle_gaps[:4]] == pytest.approx([
        0.002733788000000001, 0.0025512260000000037,
        0.0016276520000000003, 0.0008747419999999978], rel=1e-12)


def test_the_old_trace_reads_the_same_metrics(old, monkeypatch):
    from bench.spec import metric_reader
    from repro.exec import compile_chain
    from repro.models import cnn

    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")   # plan as on the chip
    chain = cnn.build("GLN", batch=32)
    eng = compile_chain(chain, backend="auto", lint="off")
    s = devtrace.reduce(old)
    s.calls = s.annotated_calls
    ctx = SimpleNamespace(trace=s, engine=eng, chain=chain,
                          peaks={"flops_per_s": 197e12})
    want = {"gconv_matmul_mfu": 28.356942598897604,
            "gconv_spatial_mfu": 9.864117252109478,
            "non_mac_device_pct": 73.39534859544601,
            "device_idle_pct": 13.387053452532871}
    for name, v in want.items():
        assert metric_reader(name)(ctx) == pytest.approx(v, rel=1e-12), name


def test_enginetrace_reads_the_old_trace_as_devtrace_does(old):
    s = devtrace.reduce(old)
    inst = enginetrace.inst_seconds(old)
    assert len(inst) > 10 * len(s.top_ops)         # every instruction
    assert sum(inst.values()) == pytest.approx(s.op_s, rel=1e-9)
    for key, v in s.top_ops:
        assert inst[key.split()[0]] == pytest.approx(v, rel=1e-9)
    assert enginetrace.engine_spans(old) == {}
    gaps = enginetrace.idle_gaps(old)      # with no engine span, the
    assert [(lab, g) for lab, g, _a in gaps[:10]] == \
        pytest.approx(s.idle_gaps)          # labels are devtrace's
    assert sum(g for _l, g, _a in gaps) == \
        pytest.approx(s.window_s - s.busy_s, rel=1e-9)


def test_the_engine_spans_nest_once_per_call(b1):
    planes, steps = b1
    spans = enginetrace.engine_spans(planes)
    calls = steps["calls"]
    assert {k: len(v) for k, v in spans.items()} == {
        "engine.call": calls, "engine.args": calls, "engine.launch": calls}
    for (c0, c1), (a0, a1), (l0, l1) in zip(spans["engine.call"],
                                             spans["engine.args"],
                                             spans["engine.launch"]):
        assert c0 <= a0 < a1 <= l0 < l1 <= c1
    assert devtrace.reduce(planes).annotated_calls == calls


def test_every_instruction_and_every_step_of_the_b1_window(b1):
    planes, steps = b1
    s = devtrace.reduce(planes)
    inst = enginetrace.inst_seconds(planes)
    assert sum(inst.values()) == pytest.approx(s.op_s, rel=1e-9)
    for key, v in s.top_ops:
        assert inst[key.split()[0]] == pytest.approx(v, rel=1e-9)
    # op_steps() of the program that ran puts every instruction the trace
    # names down to a step
    by_step = enginetrace.step_seconds(inst, steps["op_steps"])
    assert by_step.get(None, 0.0) <= 0.05 * s.op_s
    assert set(by_step) - {None} <= set(steps["backends"])


def test_the_b1_gaps_are_the_host_in_engine_args(b1):
    planes, steps = b1
    # the device's clock runs 1.30-1.66 ms behind the host's here (bounded by
    # the runtime's enqueue and completion events)
    assert 1.30e-3 < enginetrace.clock_offset(planes) < 1.66e-3
    gaps = enginetrace.idle_gaps(planes)
    _w0, w1 = enginetrace.window(planes)
    # the last gap runs from the device's last operation, on its own clock,
    # to the end of the last wait, on the host's: the window's end
    long = [(lab, g) for lab, g, a in gaps
            if g > 1e-3 and a + g < w1 - 1e-9]
    assert len(long) >= steps["calls"] - 2
    assert {lab for lab, _g in long} == {"host in engine.args"}
    r = enginetrace.report(planes, steps["op_steps"], steps["backends"],
                           steps["calls"])
    assert r["bench_call_covered_by_args_and_launch"] >= 0.9
    ms = r["span_ms_per_call"]
    assert ms["engine.args"] > ms["engine.launch"] > 0
    assert ms["engine.args"] + ms["engine.launch"] <= ms["engine.call"]
    assert r["attributed_share"] >= 0.95
    idle = sum(r["idle_s_by_label"].values())
    assert idle == pytest.approx(r["window_s"] - s_busy(planes), rel=1e-6)
    assert r["idle_s_by_label"]["host in engine.args"] > 0.9 * idle
    assert sum(r["device_share_by_tag"].values()) == pytest.approx(1.0)


def s_busy(planes):
    return devtrace.reduce(planes).busy_s


def test_a_gap_takes_the_innermost_span_covering_most_of_it():
    spans = {"engine.call": [(0.0, 10.0)], "engine.args": [(0.0, 4.0)],
             "engine.launch": [(4.0, 9.0)]}
    assert enginetrace.label(1.0, 3.5, spans) == "host in engine.args"
    assert enginetrace.label(3.0, 8.0, spans) == "host in engine.launch"
    # neither phase covers most of it: the call does
    assert enginetrace.label(2.0, 6.0, spans) == "host in engine.call"
    assert enginetrace.label(10.5, 12.0, spans) == "host between calls"


class _Engine:
    def __init__(self):
        from repro.obs import Metrics
        self.metrics = Metrics()


def test_the_counter_metrics_read_the_engine():
    from bench.spec import metric_reader

    eng = _Engine()
    m = eng.metrics
    m.counter("engine_timed_calls").inc(4)
    m.counter("engine_span_s", span="engine.args").inc(0.004)
    m.counter("engine_span_s", span="engine.launch").inc(0.006)
    m.gauge("engine_trace_s", program="exact").set(3.0)
    m.gauge("engine_trace_s", program="bucket=2").set(0.5)
    m.gauge("engine_compile_s", program="exact").set(2.0)
    ctx = SimpleNamespace(engine=eng)
    assert metric_reader("engine_args_ms")(ctx) == pytest.approx(1.0)
    assert metric_reader("engine_launch_ms")(ctx) == pytest.approx(1.5)
    assert metric_reader("program_trace_s")(ctx) == pytest.approx(3.5)
    assert metric_reader("program_compile_s")(ctx) == pytest.approx(2.0)


@pytest.mark.parametrize("engine", [_Engine(), SimpleNamespace()],
                         ids=["no_counters_yet", "no_metrics_at_all"])
def test_the_counter_metrics_read_nothing_where_there_is_nothing(engine):
    from bench.spec import metric_reader

    ctx = SimpleNamespace(engine=engine)
    for name in ("engine_args_ms", "engine_launch_ms", "program_trace_s",
                 "program_compile_s"):
        assert metric_reader(name)(ctx) is None, name
    assert engine_counters.total(engine, "engine_timed_calls") is None


def test_a_real_engine_feeds_the_counter_metrics():
    import jax
    from jax.profiler import TraceAnnotation

    from bench.spec import metric_reader
    from repro.core.interpreter import init_chain_params
    from repro.exec import compile_chain
    from repro.models import cnn

    chain = cnn.build("GLN", reduced=True, batch=1)
    params = init_chain_params(chain, jax.random.PRNGKey(0))
    eng = compile_chain(chain, lint="off")
    eng(cnn.random_inputs(chain), params)
    ctx = SimpleNamespace(engine=eng)
    assert metric_reader("program_trace_s")(ctx) > 0
    assert metric_reader("program_compile_s")(ctx) > 0
    # no call was made while a profiler session recorded
    assert metric_reader("engine_args_ms")(ctx) is None
    assert not TraceAnnotation.is_enabled()


def test_a_profiler_session_records_the_engine_spans(tmp_path):
    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    from bench.spec import metric_reader
    from repro.core.interpreter import init_chain_params
    from repro.exec import compile_chain
    from repro.models import cnn

    chain = cnn.build("GLN", reduced=True, batch=1)
    params = init_chain_params(chain, jax.random.PRNGKey(0))
    inputs = cnn.random_inputs(chain)
    eng = compile_chain(chain, lint="off")
    jax.block_until_ready(eng(inputs, params))
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            with TraceAnnotation(devtrace.CALL_SPAN):
                out = eng(inputs, params)
            with TraceAnnotation(devtrace.WAIT_SPAN):
                jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    planes = list(ProfileData.from_file(
        devtrace.find_xplane(str(tmp_path))).planes)
    spans = enginetrace.engine_spans(planes)
    assert {k: len(v) for k, v in spans.items()} == {
        "engine.call": 3, "engine.args": 3, "engine.launch": 3}
    for (c0, c1), (a0, a1), (l0, l1) in zip(spans["engine.call"],
                                             spans["engine.args"],
                                             spans["engine.launch"]):
        assert c0 <= a0 < a1 <= l0 < l1 <= c1
    assert eng.metrics.value("engine_timed_calls") == 3
    assert metric_reader("engine_launch_ms")(SimpleNamespace(engine=eng)) > 0
