"""The configuration ``mobilenet-v1-f32dw`` against the program on the CPU:
its reference (bench/reference/mobilenet-v1-f32dw.py) on seeded random
weights at full width, its cell run end to end at a small batch, and the
readers of the depthwise kernel's metrics (bench/depthwise.py)."""
import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, control, depthwise, run, spec, weights
from bench.devtrace import Summary
from bench.run import _check_against_reference
from repro.exec import CompiledChain, compile_chain
from repro.models import cnn

CONFIG = "mobilenet-v1-f32dw"
CELL = "mobilenet-v1-f32dw.b32"
# float32 on both sides, summed in different orders (see
# test_bench_reference.py): a wrong layer reads > 1e-2
TOL = 1e-5
# Howard et al. 2017, Table 1: (input side, channels, stride) of the 13
# depthwise 3x3 convs
DEPTHWISE = ((112, 32, 1), (112, 64, 2), (56, 128, 1), (56, 128, 2),
             (28, 256, 1), (28, 256, 2), (14, 512, 1), (14, 512, 1),
             (14, 512, 1), (14, 512, 1), (14, 512, 1), (14, 512, 2),
             (7, 1024, 1))


@pytest.mark.parametrize("batch", [32, 2])
def test_the_chain_takes_the_references_inputs_and_parameters(batch):
    _check_against_reference(cnn.build("MN", batch=batch),
                             spec.reference(CONFIG), batch)


def test_the_cell_is_the_configuration_under_b32_traffic():
    cell = spec.cell(CELL)
    assert (cell.config["net"], cell.traffic_name, cell.chips) == ("MN",
                                                                   "b32", 1)
    assert cell.config["reduced"] == []
    assert set(spec.cell_limits(CELL)) == {"logit_rel_rms"}
    names = {m["name"] for m in cell.per_layer}
    assert {"gconv_depthwise_roofline", "depthwise_device_pct"} <= names
    assert not names & {"gconv_matmul_mfu", "gconv_spatial_mfu"}


def _seeded(batch, seed):
    cfg = spec.config(CONFIG)
    ref = spec.reference(CONFIG)
    params = weights.make_params(ref.param_specs(), cfg["weights"], seed)
    inputs = {"x": weights.make_images((batch, 3, 224, 224), 1, seed)[0]}
    return cnn.build(cfg["net"], batch=batch), ref, params, inputs


def test_reference_agrees_with_the_program_at_full_width():
    chain, ref, params, inputs = _seeded(2, 5)
    with jax.default_matmul_precision("highest"):
        eng = compile_chain(chain, backend="jnp", lint="off")
        probs = next(iter(eng(inputs, params).values()))
        logits = jax.jit(functools.partial(ref.logits,
                                           precision="highest"))(params,
                                                                 inputs)
    assert {eng.dispatch[f"dw{i}"] for i in range(13)} == {"conv:lax"}
    r = check.readings(np.asarray(probs), np.asarray(logits))
    assert r["logit_rel_rms"] < TOL
    # the recipe spreads the logits (std about 0.3) without saturating
    # the softmax
    assert float(np.std(np.asarray(logits))) > 0.2
    assert float(np.max(np.asarray(probs))) < 0.5


@pytest.fixture(scope="module")
def full_width_steps():
    """The jnp plan's environment at full width, batch 2, and the kernel
    plan's depthwise steps."""
    chain, _ref, params, inputs = _seeded(2, 7)
    with jax.default_matmul_precision("highest"):
        env = compile_chain(chain, backend="jnp", lint="off")(
            inputs, params, keep_all=True)
    eng = compile_chain(chain, backend="pallas", lint="off")
    return eng, env


@pytest.mark.parametrize("i", range(13))
def test_each_kernel_step_agrees_with_lax_at_full_width(full_width_steps,
                                                        i):
    """Step by step, on the same inputs: at batch 2 the batch-statistics
    norms amplify a change of summation order about threefold a layer, so
    the whole network cannot be compared this way."""
    from repro.exec import lowering

    eng, env = full_width_steps
    node = eng.chain.nodes[f"dw{i}"]
    assert eng.dispatch[node.name] == depthwise.BACKEND
    plan = lowering.match_conv(node, lowering.dim_classes(node),
                               tuple(eng.chain.shape_of(node.kernel)))
    args = (env[node.input], env[node.kernel], lambda op: env[op.operand])
    got = lowering.lower_depthwise_pallas(node, plan)(*args)
    want = lowering.lower_conv(node, plan)(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5 * float(
                                   np.max(np.abs(np.asarray(want)))))


@pytest.mark.parametrize("precision", ["default", None])
def test_the_reference_keeps_its_depthwise_convs_at_highest(precision):
    """Whatever the precision asked of the other convs and the fc."""
    _chain, ref, params, inputs = _seeded(2, 6)
    jaxpr = jax.make_jaxpr(functools.partial(ref.logits,
                                             precision=precision))(
        params, inputs)
    convs = [e.params for e in jaxpr.eqns
             if e.primitive.name == "conv_general_dilated"]
    highest = (jax.lax.Precision.HIGHEST,) * 2
    depthwise_convs = [c for c in convs if c["feature_group_count"] > 1]
    assert len(depthwise_convs) == 13 and len(convs) == 27
    assert all(c["precision"] == highest for c in depthwise_convs)
    assert not any(c["precision"] == highest for c in convs
                   if c["feature_group_count"] == 1)


def _small():
    cell = spec.cell(CELL)
    cell.traffic = dict(cell.traffic, batch=2, pool=2, sample=4)
    return cell


def test_a_cpu_run_of_the_cell_is_correct():
    out = run.run_cell(_small(), 2 ** 31 + 21, 0.5, False,
                       require_chip=False)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"images_per_s", "call_p95_ms",
                                   "setup_s"}


def test_the_bfloat16_control_is_not_correct(monkeypatch):
    cell = _small()
    ctl = control.control_engine(spec.reference(cell.config_name))
    monkeypatch.setattr(CompiledChain, "__call__",
                        lambda self, inputs, params=None, keep_all=False:
                        ctl(inputs, params))
    out = run.run_cell(cell, 2 ** 31 + 22, 0.5, False, require_chip=False)
    assert out["correct"] is False


# ---------------------------------------------------------------------------
# the depthwise kernel's readers
# ---------------------------------------------------------------------------
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _chip_plan(monkeypatch, net="MN", batch=32):
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")   # plan as on the chip
    return compile_chain(cnn.build(net, batch=batch), backend="auto",
                         lint="off")


def _summary(mosaic_s, op_s, calls):
    s = Summary(window_s=1.0, busy_s=0.8, op_s=op_s,
                class_s={"mosaic": mosaic_s, "other": op_s - mosaic_s},
                top_ops=[], idle_gaps=[])
    s.calls = calls
    return s


def _ctx(engine, trace):
    return SimpleNamespace(engine=engine, chain=engine.chain, trace=trace,
                           peaks=PEAKS)


def _least_seconds_b32():
    """The 13 steps' bytes at b32 (input, output and taps, float32) over
    the HBM peak; their FLOPs bound no step."""
    total = 0
    for h, c, s in DEPTHWISE:
        o = h // s
        total += 4 * (32 * c * h * h + 32 * c * o * o + 9 * c)
        assert 2 * 9 * 32 * c * o * o / 197e12 < total / 819e9
    return total / 819e9


def test_the_readers_read_the_kernel_in_a_summary(monkeypatch):
    eng = _chip_plan(monkeypatch)
    assert len(depthwise.kernel_nodes(eng)) == 13
    ctx = _ctx(eng, _summary(mosaic_s=0.05, op_s=0.4, calls=50))
    roof = spec.metric_reader("gconv_depthwise_roofline")(ctx)
    assert roof == pytest.approx(100 * 50 * _least_seconds_b32() / 0.05)
    assert 0 < roof < 100
    assert spec.metric_reader("depthwise_device_pct")(ctx) == \
        pytest.approx(12.5)


@pytest.mark.parametrize("case", ["no kernel step", "other mosaic kernel",
                                  "no mosaic time"])
def test_the_readers_read_nothing_where_there_is_nothing(monkeypatch, case):
    trace = _summary(mosaic_s=0.05, op_s=0.4, calls=50)
    if case == "no kernel step":         # the parent's plan: conv:lax
        eng = _chip_plan(monkeypatch)
        eng = SimpleNamespace(chain=eng.chain, steps=[
            SimpleNamespace(name=s.name, backend="conv:lax"
                            if s.backend == depthwise.BACKEND else s.backend)
            for s in eng.steps])
    elif case == "other mosaic kernel":
        eng = _chip_plan(monkeypatch)
        eng = SimpleNamespace(chain=eng.chain, steps=list(eng.steps) + [
            SimpleNamespace(name="norm", backend="segment:norm:pallas")])
    else:
        eng = _chip_plan(monkeypatch)
        trace.class_s.pop("mosaic")
    for name in ("gconv_depthwise_roofline", "depthwise_device_pct"):
        assert spec.metric_reader(name)(_ctx(eng, trace)) is None


def test_the_byte_count_is_the_unpadded_operands(monkeypatch):
    eng = _chip_plan(monkeypatch, batch=2)
    node = depthwise.kernel_nodes(eng)[1]                # dw1: 112 -> 56
    assert depthwise.step_bytes(node) == 4 * (2 * 64 * 112 * 112
                                              + 2 * 64 * 56 * 56 + 9 * 64)
    assert depthwise.step_flops(node) == 2 * 9 * 2 * 64 * 56 * 56
