"""The configuration ``alexnet`` against the program on the CPU: its
reference (bench/reference/alexnet.py) on seeded random weights at full
width, its cell run end to end at a small batch, the plan's share of MACs
on the Pallas kernels, and the readers of the grouped kernel's metrics
(bench/grouped.py)."""
import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, control, grouped, run, spec, weights
from bench.devtrace import Summary
from bench.run import _check_against_reference
from repro.exec import CompiledChain, compile_chain
from repro.models import cnn

CONFIG = "alexnet"
CELL = "alexnet.b32"
# float32 on both sides, summed in different orders (see
# test_bench_reference.py): a wrong layer reads > 1e-2
TOL = 1e-5
GROUPED = ("conv2", "conv4", "conv5")
# the grouped convs the chip plan puts on the kernel: the one a max pool
# feeds (conv4 and conv5 stay on XLA: lowering.lower_conv_pallas)
ON_KERNEL = ("conv2",)
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("batch", [32, 1])
def test_the_chain_takes_the_references_inputs_and_parameters(batch):
    _check_against_reference(cnn.build("AN", batch=batch),
                             spec.reference(CONFIG), batch)


def test_the_cell_is_the_configuration_under_b32_traffic():
    cell = spec.cell(CELL)
    assert (cell.config["net"], cell.traffic_name, cell.chips) == ("AN",
                                                                   "b32", 1)
    assert cell.config["reduced"] == []
    assert set(cell.config["fills"]) == {"dropout.mask", "dropout_1.mask"}
    assert set(spec.cell_limits(CELL)) == {"logit_rel_rms"}
    names = {m["name"] for m in cell.per_layer}
    assert {"gconv_grouped_mfu", "grouped_device_pct"} <= names
    assert not names & {"gconv_matmul_mfu", "gconv_spatial_mfu",
                        "gconv_depthwise_roofline"}


def _seeded(batch, seed):
    cfg = spec.config(CONFIG)
    ref = spec.reference(CONFIG)
    chain = cnn.build(cfg["net"], batch=batch)
    params = weights.make_params(ref.param_specs(), cfg["weights"], seed)
    inputs = {n: jnp.full(chain.inputs[n].shape, v, jnp.float32)
              for n, v in cfg["fills"].items()}
    inputs["x"] = weights.make_images((batch, 3, 227, 227), 1, seed)[0]
    return chain, ref, params, inputs


def test_reference_agrees_with_the_jnp_plan_at_full_width():
    chain, ref, params, inputs = _seeded(1, 3)
    with jax.default_matmul_precision("highest"):
        eng = compile_chain(chain, backend="jnp", lint="off")
        probs = next(iter(eng(inputs, params).values()))
        logits = jax.jit(functools.partial(ref.logits,
                                           precision="highest"))(params,
                                                                 inputs)
    r = check.readings(np.asarray(probs), np.asarray(logits))
    assert r["logit_rel_rms"] < TOL
    # the recipe spreads the logits without saturating the softmax
    assert float(np.std(np.asarray(logits))) > 0.3
    assert float(np.max(np.asarray(probs))) < 0.5


def test_the_reference_writes_a_grouped_conv_as_convs_over_slices():
    """No conv of the reference groups its features: conv2, conv4 and
    conv5 are two convs each over half the channels, then a concat."""
    _chain, ref, params, inputs = _seeded(1, 4)
    jaxpr = jax.make_jaxpr(ref.logits)(params, inputs)
    convs = [e.params for e in jaxpr.eqns
             if e.primitive.name == "conv_general_dilated"]
    assert len(convs) == 2 + 2 * len(GROUPED)
    assert all(c["feature_group_count"] == 1 for c in convs)
    concats = [e for e in jaxpr.eqns if e.primitive.name == "concatenate"]
    assert len(concats) == len(GROUPED)


@pytest.fixture(scope="module")
def full_width_b1():
    """The jnp plan's environment at full width, batch 1, and the kernel
    plan."""
    chain, _ref, params, inputs = _seeded(1, 7)
    with jax.default_matmul_precision("highest"):
        env = compile_chain(chain, backend="jnp", lint="off")(
            inputs, params, keep_all=True)
    return compile_chain(chain, backend="pallas", lint="off"), env


@pytest.mark.parametrize("name", ON_KERNEL)
def test_each_grouped_kernel_step_agrees_with_lax_at_full_width(
        full_width_b1, name):
    """Step by step on the same inputs, the kernel (interpret mode) against
    ``lax.conv_general_dilated(feature_group_count=2)``, both in
    float32."""
    from repro.exec import lowering

    eng, env = full_width_b1
    node = eng.chain.nodes[name]
    assert eng.dispatch[name] == "conv:pallas" and grouped.is_grouped(node)
    plan = lowering.match_conv(node, lowering.dim_classes(node),
                               tuple(eng.chain.shape_of(node.kernel)))
    args = (env[node.input], env[node.kernel], lambda op: env[op.operand])
    got = np.asarray(lowering.lower_conv_pallas(node, plan,
                                                after_pool=True)(*args))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(lowering.lower_conv(node, plan)(*args))
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * float(np.max(np.abs(want))))


def _small():
    cell = spec.cell(CELL)
    cell.traffic = dict(cell.traffic, batch=2, pool=2, sample=4)
    return cell


def test_a_cpu_run_of_the_cell_is_correct():
    out = run.run_cell(_small(), 2 ** 31 + 31, 0.5, False,
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
    out = run.run_cell(cell, 2 ** 31 + 32, 0.5, False, require_chip=False)
    assert out["correct"] is False


# ---------------------------------------------------------------------------
# the chip plan and the grouped kernel's readers
# ---------------------------------------------------------------------------
def _chip_plan(monkeypatch, net="AN", batch=32):
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")   # plan as on the chip
    return compile_chain(cnn.build(net, batch=batch), backend="auto",
                         lint="off")


def test_pallas_mac_pct_counts_the_grouped_conv_on_the_kernel(monkeypatch):
    """Of the conv and fc MACs, conv1 (stride 4, 14.6%) and the grouped
    conv4 and conv5 (25.8%) stay on XLA."""
    eng = _chip_plan(monkeypatch)
    ctx = SimpleNamespace(chain=eng.chain, engine=eng)
    assert spec.metric_reader("pallas_mac_pct")(ctx) == pytest.approx(
        59.648, abs=5e-4)


def _summary(mosaic_s, op_s, calls):
    s = Summary(window_s=1.0, busy_s=0.8, op_s=op_s,
                class_s={"mosaic": mosaic_s, "conv:pallas": 0.01,
                         "other": op_s - mosaic_s - 0.01},
                top_ops=[], idle_gaps=[])
    s.calls = calls
    return s


def _ctx(engine, trace):
    return SimpleNamespace(engine=engine, chain=engine.chain, trace=trace,
                           peaks=PEAKS)


def test_each_grouped_step_is_bound_by_its_flops(monkeypatch):
    """At b32 each grouped step's FLOPs at peak take longer than its
    unpadded bytes at peak HBM bandwidth (conv2: 14.3 GFLOP in 73 us,
    34 MB in 42 us), so the FLOP share is the roofline share."""
    eng = _chip_plan(monkeypatch)
    assert [n.name for n in grouped.kernel_nodes(eng)] == list(ON_KERNEL)
    nodes = [eng.chain.nodes[name] for name in GROUPED]
    assert all(grouped.is_grouped(n) for n in nodes)
    for n in nodes:
        assert (grouped.step_flops(n) / PEAKS["flops_per_s"]
                > grouped.step_bytes(n) / PEAKS["hbm_bytes_per_s"])
    conv2 = nodes[0]
    assert grouped.step_flops(conv2) == 2 * 32 * 256 * 27 * 27 * 48 * 25
    assert grouped.step_bytes(conv2) == 4 * (32 * 96 * 27 * 27
                                             + 32 * 256 * 27 * 27
                                             + 256 * 48 * 25)


def test_the_readers_read_the_kernel_in_a_summary(monkeypatch):
    eng = _chip_plan(monkeypatch)
    ctx = _ctx(eng, _summary(mosaic_s=0.05, op_s=0.4, calls=50))
    flops = 2 * 32 * 256 * 27 * 27 * 48 * 25            # conv2
    mfu = spec.metric_reader("gconv_grouped_mfu")(ctx)
    assert mfu == pytest.approx(100 * 50 * flops / 197e12 / 0.05)
    assert 0 < mfu < 100
    assert spec.metric_reader("grouped_device_pct")(ctx) == \
        pytest.approx(12.5)


@pytest.mark.parametrize("case", ["parent plan", "other mosaic kernel",
                                  "no mosaic time"])
def test_the_readers_read_nothing_where_there_is_nothing(monkeypatch, case):
    trace = _summary(mosaic_s=0.05, op_s=0.4, calls=50)
    eng = _chip_plan(monkeypatch)
    if case == "parent plan":            # grouped convs on conv:lax
        eng = SimpleNamespace(chain=eng.chain, steps=[
            SimpleNamespace(name=s.name, backend="conv:lax"
                            if s.name in GROUPED else s.backend)
            for s in eng.steps])
    elif case == "other mosaic kernel":
        eng = SimpleNamespace(chain=eng.chain, steps=list(eng.steps) + [
            SimpleNamespace(name="dw", backend="dwconv:pallas-vpu")])
    else:
        trace.class_s.pop("mosaic")
    for name in ("gconv_grouped_mfu", "grouped_device_pct"):
        assert spec.metric_reader(name)(_ctx(eng, trace)) is None


def test_other_cells_plan_no_grouped_step(monkeypatch):
    for net in ("GLN", "MN"):
        assert grouped.kernel_nodes(_chip_plan(monkeypatch, net)) == []
