"""The trace reduction (bench/devtrace.py) on a small trace recorded on a
TPU v5e: googlenet at batch 32, three calls of the benchmark's traced loop
(bench/testdata/gln_b32_trace.xplane.pb.gz)."""
import gzip
import os
from types import SimpleNamespace

import pytest

from bench import devtrace, spec

TRACE = os.path.join(os.path.dirname(spec.BENCH_DIR), "bench", "testdata",
                     "gln_b32_trace.xplane.pb.gz")


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(TRACE, "rb") as f:
        path.write_bytes(f.read())
    return devtrace.reduce_file(str(path))


def test_parse_op_reads_instruction_and_opcode():
    name = ('%_gconv_spatial.19 = f32[32,56,56,256]{3,2,1,0:T(8,128)S(1)} '
            'custom-call(f32[32,58,58,64]{3,2,1,0:T(8,128)} %pad.121), '
            'custom_call_target="tpu_custom_call"')
    assert devtrace.parse_op(name) == ("_gconv_spatial.19", "custom-call")
    assert devtrace.op_class(name) == "conv:pallas"
    fusion = ('%fusion.33 = f32[32,64,112,112]{1,0,2,3:T(8,128)} fusion('
              'bf16[32,3,224,224]{0,1,3,2:T(4,128)(2,1)S(1)} %copy.214), '
              'kind=kOutput, calls=%fused_computation.34')
    assert devtrace.parse_op(fusion) == ("fusion.33", "fusion:kOutput")
    assert devtrace.op_class(fusion) == "xla_mac"
    tup = ('%copy-start.58 = (f32[1,192,7,7]{1,0,3,2:T(1,128)S(1)}, '
           'u32[]{:S(2)}) copy-start(f32[1,192,7,7]{1,0,3,2:T(1,128)} '
           '%params__conv1_w__.1)')
    assert devtrace.parse_op(tup) == ("copy-start.58", "copy-start")
    assert devtrace.op_class(tup) == "other"
    assert devtrace.op_class(
        '%_gconv_matmul.3 = f32[1,8,128]{2,1,0} custom-call(f32[1,8,128]'
        '{2,1,0} %x), custom_call_target="tpu_custom_call"') == \
        "matmul:pallas"


def test_union_merges_overlaps():
    assert devtrace._union([(0, 2), (1, 3), (5, 6)]) == 4
    assert devtrace._union([]) == 0


def test_recorded_trace_reduces_to_its_calls(summary):
    assert summary.annotated_calls == 3
    assert summary.devices == 1
    assert 0 < summary.busy_s <= summary.window_s
    # one TensorCore runs its ops one after another: the sum of their
    # durations is their union
    assert summary.op_s == pytest.approx(summary.busy_s, rel=1e-6)
    cls = summary.class_s
    assert cls["conv:pallas"] > cls["matmul:pallas"] > 0
    assert sum(cls.values()) == pytest.approx(summary.op_s)
    b = summary.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(g[0].startswith("host") for g in b["idle_gaps"])


def test_trace_readers_stay_inside_their_bounds(summary, monkeypatch):
    from bench import work
    from bench.spec import metric_reader
    from repro.exec import compile_chain
    from repro.models import cnn

    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")   # plan as on the chip
    chain = cnn.build("GLN", batch=32)
    eng = compile_chain(chain, backend="auto", lint="off")
    summary.calls = summary.annotated_calls
    peaks = {"flops_per_s": 197e12}
    ctx = SimpleNamespace(trace=summary, engine=eng, chain=chain,
                          peaks=peaks)
    for name in ("gconv_matmul_mfu", "gconv_spatial_mfu",
                 "non_mac_device_pct", "device_idle_pct"):
        v = metric_reader(name)(ctx)
        assert v is not None and 0 < v < 100, (name, v)
    assert work.step_flops(eng.chain, eng.steps, "conv:pallas")
