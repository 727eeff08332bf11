"""The depthwise GCONV kernel (``kernels.gconv_depthwise``) in interpret
mode against XLA's grouped conv at ``Precision.HIGHEST``, its refusal rule,
and how the chain compiler plans, lowers and counts depthwise convs."""
import dataclasses
import functools
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.interpreter import ChainExecutor
from repro.exec import compile_chain, lowering
from repro.kernels.gconv_depthwise import (gconv_depthwise, geometry,
                                           mosaic_refusal, planes,
                                           step_bytes)
from repro.models import cnn

DW = "dwconv:pallas-vpu"


def _lax_depthwise(x, w, stride, pad):
    """x (B, H, W, C), w (K, K, C) through lax's grouped conv, in float32."""
    C = x.shape[-1]
    y = jax.lax.conv_general_dilated(
        jnp.transpose(x, (0, 3, 1, 2)), jnp.transpose(w, (2, 0, 1))[:, None],
        (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"), feature_group_count=C,
        precision=jax.lax.Precision.HIGHEST)
    return jnp.transpose(y, (0, 2, 3, 1))


def _operands(shape, k, seed):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kx, shape, jnp.float32),
            jax.random.normal(kw, (k, k, shape[-1]), jnp.float32))


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("c", [8, 32, 128, 512])
@pytest.mark.parametrize("hw", [7, 14, 15, 56])
def test_kernel_matches_lax_at_highest(stride, c, hw):
    batch = 1 if hw * hw * c > 14 * 14 * 512 else 2
    x, w = _operands((batch, hw, hw, c), 3, hw * 1000 + c + stride)
    got = gconv_depthwise(x, w, stride=stride, pad=1, interpret=True)
    want = _lax_depthwise(x, w, stride, 1)
    assert got.shape == want.shape == (batch, (hw - 1) // stride + 1,
                                       (hw - 1) // stride + 1, c)
    # float32 on both sides, nine products summed in different orders
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k,pad,stride", [(5, 2, 1), (5, 2, 2), (3, 0, 1),
                                          (1, 0, 2)])
def test_kernel_takes_other_windows(k, pad, stride):
    x, w = _operands((2, 13, 11, 16), k, 7 * k + pad + stride)
    got = gconv_depthwise(x, w, stride=stride, pad=pad, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_lax_depthwise(x, w, stride, pad)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("c", [8, 128])                 # planes, channels
@pytest.mark.parametrize("stride", [1, 2])
def test_kernel_arithmetic_is_float32(c, stride):
    """Operands that bfloat16 would round come out as float32 computes
    them: ``1 + 2**-10`` times ``1 + 2**-12`` at one tap."""
    x = jnp.full((1, 4, 4, c), 1 + 2.0 ** -10, jnp.float32)
    w = jnp.zeros((3, 3, c), jnp.float32).at[1, 1].set(1 + 2.0 ** -12)
    got = gconv_depthwise(x, w, stride=stride, pad=1, interpret=True)
    want = np.float32(1 + 2.0 ** -10) * np.float32(1 + 2.0 ** -12)
    assert np.all(np.asarray(got) == want)


def test_the_geometry_puts_every_tap_inside_the_scratch():
    for h, k, s, p in [(112, 3, 2, 1), (7, 3, 1, 1), (15, 3, 2, 1),
                       (13, 5, 2, 2), (11, 1, 2, 0)]:
        g = geometry(h, h, k, s, p)
        assert g.oh == (h + 2 * p - k) // s + 1
        for e, d in g.shift:
            assert 0 <= e < s and d >= 0 and d + g.hs <= g.rows
        assert g.oh + (k - 1) // s <= g.rows and g.cols % 8 == 0
        pg = planes(h, h, k, s, p)
        assert (pg.oh, pg.ow) == (g.oh, g.ow)
        assert pg.hp >= max(h + 2 * p, s * pg.oh + k - 1)
        assert pg.lanes % 128 == 0 and pg.lanes >= 128 + pg.owf + k - 1 - p


@pytest.mark.parametrize("geom,why", [
    ((14, 14, 64, 3, 3, 1), "stride 3"),
    ((14, 14, 64, 3, 1, 3), "pad 3"),
    ((1, 1, 64, 5, 1, 1), "smaller than the window"),
    ((224, 224, 96, 3, 1, 1), "VMEM"),
])
def test_refused_geometries_name_their_reason(geom, why):
    h, w, c, k, stride, pad = geom
    assert why in mosaic_refusal(h, w, c, k, stride=stride, pad=pad)
    x, wt = _operands((1, h, w, c), k, 0)
    with pytest.raises(ValueError, match="refuses"):
        gconv_depthwise(x, wt, stride=stride, pad=pad, interpret=True)


def test_every_mobilenet_depthwise_geometry_is_taken():
    h, c = 112, 32
    for out_c, stride in cnn._MOBILENET_CFG:
        assert mosaic_refusal(h, h, c, 3, stride=stride, pad=1) is None
        assert step_bytes(h, h, c, 3, stride=stride, pad=1) < 20 * 2**20
        h, c = h // stride, out_c


# ---------------------------------------------------------------------------
# planning, lowering and counting
# ---------------------------------------------------------------------------
def _tags(eng):
    return dict(sorted(Counter(s.backend for s in eng.steps).items()))


def _depthwise_steps(eng):
    return [s for s in eng.steps
            if eng.chain.meta.get(s.name, {}).get("layer")
            == "depthwise_conv"]


def test_mobilenet_plans_its_depthwise_convs_on_the_kernel():
    eng = compile_chain(cnn.build("MN", batch=32), backend="pallas",
                        lint="off")
    dw = _depthwise_steps(eng)
    assert len(dw) == 13 and {s.backend for s in dw} == {DW}
    assert eng.dispatch["conv1"] == "conv:lax"          # the strided stem
    assert _tags(eng) == {DW: 13, "conv:lax": 1, "matmul:pallas": 14,
                          "movement": 1, "reduce": 55,
                          "segment:softmax": 1}


def test_the_chip_plan_of_mobilenet_takes_the_kernel(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")    # plan as on a TPU
    eng = compile_chain(cnn.build("MN", batch=32), backend="auto",
                        lint="off")
    assert {s.backend for s in _depthwise_steps(eng)} == {DW}


def test_interpret_mode_auto_plans_depthwise_as_lax_at_highest():
    eng = compile_chain(cnn.build("MN", batch=2), backend="auto", lint="off")
    assert {s.backend for s in _depthwise_steps(eng)} == {"conv:lax"}


# the parent plan's tags of the zoo nets without depthwise convs, planned as
# on a TPU (auto) and with every kernel asked for (pallas)
UNCHANGED = {
    ("GLN", "auto"): {"concat": 9, "conv:lax": 1, "conv:pallas": 19,
                      "elementwise": 37, "matmul:jnp": 21,
                      "matmul:pallas": 17, "movement": 1, "reduce": 16,
                      "segment:softmax": 1},
    ("GLN", "pallas"): {"concat": 9, "conv:lax": 1, "conv:pallas": 19,
                        "elementwise": 37, "matmul:pallas": 38,
                        "movement": 1, "reduce": 16, "segment:softmax": 1},
    ("AN", "auto"): {"conv:lax": 3, "conv:pallas": 2, "matmul:pallas": 3,
                     "movement": 1, "reduce": 5, "segment:softmax": 1},
    ("AN", "pallas"): {"conv:lax": 3, "conv:pallas": 2, "matmul:pallas": 3,
                       "movement": 1, "reduce": 5, "segment:softmax": 1},
}


@pytest.mark.parametrize("net,backend", list(UNCHANGED))
def test_nets_without_depthwise_convs_keep_their_plan(monkeypatch, net,
                                                      backend):
    """GoogLeNet, and AlexNet's grouped convs (``icg > 1``), plan exactly as
    with no depthwise branch at all."""
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")
    chain = cnn.build(net, batch=32)
    eng = compile_chain(chain, backend=backend, lint="off")
    assert _tags(eng) == UNCHANGED[(net, backend)]
    monkeypatch.setattr(lowering, "is_depthwise", lambda node, plan: False)
    assert compile_chain(chain, backend=backend,
                         lint="off").dispatch == eng.dispatch


def _counts(eng):
    fam = eng.metrics.to_dict()["metrics"]["engine_depthwise_steps"]
    return {s["labels"]["backend"]: s["value"] for s in fam["series"]}


@pytest.mark.parametrize("net,backend,want", [
    ("MN", "pallas", {DW: 13, "conv:lax": 0}),
    ("MN", "jnp", {DW: 0, "conv:lax": 13}),
    ("GLN", "pallas", {DW: 0, "conv:lax": 0}),
])
def test_the_engine_counts_depthwise_steps_by_backend(net, backend, want):
    eng = compile_chain(cnn.build(net, batch=32), backend=backend,
                        lint="off")
    assert _counts(eng) == want


def _conv_node_and_plan(net, name):
    """A conv node of the fused chain with no pre/post sequence, and its
    ``match_conv`` plan."""
    chain = compile_chain(cnn.build(net, batch=2), backend="jnp",
                          lint="off").chain
    node = dataclasses.replace(chain.nodes[name], pre=(), post=())
    k_shape = tuple(chain.shape_of(node.kernel))
    return node, lowering.match_conv(node, lowering.dim_classes(node),
                                     k_shape), k_shape


def _conv_precisions(node, plan, k_shape):
    fn = functools.partial(lowering.lower_conv(node, plan), lookup=None)
    jaxpr = jax.make_jaxpr(fn)(jnp.zeros(node.in_shape, jnp.float32),
                               jnp.zeros(k_shape, jnp.float32))
    return [e.params["precision"] for e in jaxpr.eqns
            if e.primitive.name == "conv_general_dilated"]


def test_lax_fallback_states_highest_for_depthwise_only():
    node, plan, k_shape = _conv_node_and_plan("MN", "dw1")
    assert lowering.is_depthwise(node, plan)
    highest = jax.lax.Precision.HIGHEST
    assert _conv_precisions(node, plan, k_shape) == [(highest, highest)]
    for net, name in (("AN", "conv2"), ("MN", "conv1")):   # grouped, stem
        node, plan, k_shape = _conv_node_and_plan(net, name)
        assert not lowering.is_depthwise(node, plan)
        assert _conv_precisions(node, plan, k_shape) == [None]


def test_the_lowering_refuses_asymmetric_padding():
    node, plan, _k = _conv_node_and_plan("MN", "dw2")
    assert lowering.depthwise_refusal(node, plan) is None
    h = plan[1][0]
    dims = list(node.dims)
    dims[h] = dataclasses.replace(dims[h], pad_r=2)
    odd = dataclasses.replace(node, dims=tuple(dims))
    assert "padding" in lowering.depthwise_refusal(odd, plan)
    assert lowering.lower_depthwise_pallas(odd, plan) is None


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_reduced_mobilenet_matches_the_oracle(backend):
    chain = cnn.build("MN", reduced=True, batch=2)
    eng = compile_chain(chain, backend=backend)
    want = DW if backend == "pallas" else "conv:lax"
    assert eng.dispatch["dw0"] == want
    params = eng.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), chain.inputs["x"].shape)
    with jax.default_matmul_precision("highest"):
        got = eng({"x": x}, params)
        ref = ChainExecutor(chain)({"x": x}, params)
    for name, y in got.items():
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref[name]),
                                   rtol=1e-4, atol=1e-5)
