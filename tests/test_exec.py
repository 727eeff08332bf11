"""Differential tests: the compiled chain engine (repro.exec) vs the oracle
interpreter, across the CNN zoo, the LM chain segments, fusion-group
execution, the fused-segment dispatch targets and randomized GCONVs.

The oracle stays the semantic reference; here it runs under one jax.jit so
the reference cost is a single compile of the oracle's own (deliberately
expansion-heavy) program rather than per-op eager dispatch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.chain import Chain
from repro.core.fusion import fuse_chain
from repro.core.gconv import DimSpec, GConv, Op
from repro.core.interpreter import ChainExecutor, eval_gconv
from repro.core import layers as L
from repro.exec import compile_chain, dispatch_gconv, execute_gconv
from repro.exec import lowering as low
from repro.models import cnn, lm_chain
from repro.models.common import ModelConfig

TOL = dict(rtol=1e-4, atol=1e-4)


def _inputs_and_params(chain, seed=0):
    ex = ChainExecutor(chain)
    params = ex.init_params(jax.random.PRNGKey(seed))
    return ex, cnn.random_inputs(chain, seed + 1), params


def _oracle(ex, inputs, params, **kw):
    return jax.jit(lambda i, p: ex(i, p, **kw))(inputs, params)


def _assert_allclose(got, ref):
    assert set(got) == set(ref)
    for o in ref:
        np.testing.assert_allclose(np.asarray(got[o]), np.asarray(ref[o]),
                                   err_msg=o, **TOL)


# ---------------------------------------------------------------------------
# the seven zoo networks + the training (FP+BP) chain
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", list(cnn.ZOO))
@pytest.mark.slow
def test_zoo_compiled_matches_oracle(name):
    chain = cnn.build(name, reduced=True, batch=2)
    ex, inputs, params = _inputs_and_params(chain)
    ref = _oracle(ex, inputs, params)
    got = compile_chain(chain)(inputs, params)
    _assert_allclose(got, ref)


@pytest.mark.slow
def test_training_block_compiled_matches_oracle():
    chain = cnn.training_block_chain(batch=4, ch=8, hw=8)
    ex = ChainExecutor(chain)
    params = ex.init_params(jax.random.PRNGKey(0))
    ins = {"x": jax.random.normal(jax.random.PRNGKey(1), (4, 8, 8, 8)),
           "gO": jax.random.normal(jax.random.PRNGKey(2), (4, 8, 8, 8))}
    ref = _oracle(ex, ins, params, keep_all=True)
    got = compile_chain(chain)(ins, params, keep_all=True)
    for o in got:          # every surviving node, node-for-node
        np.testing.assert_allclose(np.asarray(got[o]), np.asarray(ref[o]),
                                   err_msg=o, **TOL)


# ---------------------------------------------------------------------------
# LM chain segments (dense + MoE)
# ---------------------------------------------------------------------------
def _tiny_cfg(**kw):
    base = dict(name="tiny", family="dense", n_layers=1, d_model=16,
                n_heads=2, n_kv_heads=2, d_ff=32, vocab=64)
    base.update(kw)
    return ModelConfig(**base)


def test_lm_block_compiled_matches_oracle():
    ch = lm_chain.block_chain(_tiny_cfg(), 2, 8)
    ex, inputs, params = _inputs_and_params(ch)
    ref = _oracle(ex, inputs, params)
    for fuse in (True, False):
        got = compile_chain(ch, fuse=fuse)(inputs, params)
        _assert_allclose(got, ref)


def test_lm_moe_block_compiled_matches_oracle():
    cfg = _tiny_cfg(name="tiny-moe", family="moe", n_experts=4, top_k=2)
    ch = lm_chain.block_chain(cfg, 2, 8)
    ex, inputs, params = _inputs_and_params(ch)
    ref = _oracle(ex, inputs, params)
    eng = compile_chain(ch)
    _assert_allclose(eng(inputs, params), ref)
    # the expert FFN must hit the grouped-matmul backend (Ng = n_experts)
    assert eng.dispatch["e_gate"].startswith("matmul")
    assert eng.dispatch["e_up"].startswith("matmul")
    assert eng.dispatch["e_down"].startswith("matmul")


# ---------------------------------------------------------------------------
# fused segments: the hand-fused paths are now dispatch targets
# ---------------------------------------------------------------------------
def test_segments_dispatch_to_hand_fused_paths():
    ch = lm_chain.block_chain(_tiny_cfg(), 2, 8)
    eng = compile_chain(ch, fuse=False)          # unfused form of the chain
    tags = set(eng.dispatch.values())
    assert "segment:norm:jnp" in tags            # models.common.norm
    assert "segment:attention:jnp" in tags       # models.common.attention_naive
    ex, inputs, params = _inputs_and_params(ch)
    _assert_allclose(eng(inputs, params), _oracle(ex, inputs, params))


def test_segments_dispatch_to_pallas_kernels():
    """backend='pallas' routes the same segments through chain_norm /
    flash_attention / gconv_matmul (interpret mode on CPU)."""
    ch = lm_chain.block_chain(_tiny_cfg(), 1, 4)
    eng = compile_chain(ch, fuse=False, backend="pallas")
    tags = set(eng.dispatch.values())
    assert "segment:norm:pallas" in tags
    assert "segment:attention:pallas" in tags
    assert "matmul:pallas" in tags
    ex, inputs, params = _inputs_and_params(ch)
    _assert_allclose(eng(inputs, params), _oracle(ex, inputs, params))


def test_pallas_matmul_runs_fused_sequences_in_register():
    """fuse=True + backend='pallas': the rmsnorm that fusion folded into
    the linears' pre sequence rides the gconv_matmul prologue (and the
    softmax-into-values pre likewise), still allclose to the oracle."""
    ch = lm_chain.block_chain(_tiny_cfg(), 1, 4)
    eng = compile_chain(ch, fuse=True, backend="pallas")
    assert "matmul:pallas" in set(eng.dispatch.values())
    ex, inputs, params = _inputs_and_params(ch)
    _assert_allclose(eng(inputs, params), _oracle(ex, inputs, params))


def test_softmax_segment_detected_in_zoo_chain():
    chain = cnn.build("AN", reduced=True, batch=2)
    eng = compile_chain(chain)
    assert "segment:softmax" in set(eng.dispatch.values())


def test_segment_honors_out_dtype():
    """Segment lowerings must keep the oracle's out_dtype contract."""
    import dataclasses

    c = Chain("sm")
    xin = c.add_input("x", (2, 3, 5))
    y = L.softmax(c, xin, axis=-1)
    c.nodes[y] = dataclasses.replace(c.nodes[y], out_dtype="bfloat16")
    c.mark_output(y)
    eng = compile_chain(c)
    assert "segment:softmax" in set(eng.dispatch.values())
    xv = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 5))
    got = eng({"x": xv}, {})[y]
    ref = ChainExecutor(c)({"x": xv}, {})[y]
    assert got.dtype == ref.dtype == jnp.bfloat16

    # interior out_dtype: the oracle quantizes the intermediate, so the
    # f32 segment must refuse and fall back to per-node dispatch
    c2 = Chain("sm2")
    xin2 = c2.add_input("x", (2, 3, 5))
    y2 = L.softmax(c2, xin2, axis=-1)
    c2.nodes[f"{y2}.exp"] = dataclasses.replace(
        c2.nodes[f"{y2}.exp"], out_dtype="bfloat16")
    c2.mark_output(y2)
    eng2 = compile_chain(c2)
    assert "segment:softmax" not in set(eng2.dispatch.values())
    got2 = eng2({"x": xv}, {})[y2]
    ref2 = ChainExecutor(c2)({"x": xv}, {})[y2]
    np.testing.assert_allclose(np.asarray(got2), np.asarray(ref2), **TOL)


# ---------------------------------------------------------------------------
# fusion-group execution == unfused execution, node for node
# ---------------------------------------------------------------------------
def _bn_block_chain(c=4, hw=6):
    chain = Chain("fuseblk")
    x = chain.add_input("x", (2, c, hw, hw))
    y = L.conv2d(chain, x, out_c=c, k=1, bias=False)
    y, _ = L.batch_norm_fp(chain, y)
    y = L.relu(chain, y)
    y = L.scale_layer(chain, y)
    chain.mark_output(y)
    return chain


def test_fusion_group_execution_matches_unfused_node_for_node():
    chain = _bn_block_chain()
    fused, report = fuse_chain(chain)
    assert report.groups                          # something actually fused
    ex = ChainExecutor(chain)
    params = ex.init_params(jax.random.PRNGKey(3))
    ins = {"x": jax.random.normal(jax.random.PRNGKey(4), (2, 4, 6, 6))}
    ref_all = _oracle(ex, ins, params, keep_all=True)
    got_all = compile_chain(chain, fuse=True)(ins, params, keep_all=True)
    # every surviving (host) node's value equals its unfused oracle value
    for name in got_all:
        np.testing.assert_allclose(np.asarray(got_all[name]),
                                   np.asarray(ref_all[name]),
                                   err_msg=name, **TOL)
    # and the unfused compile agrees on every original node
    got_unfused = compile_chain(chain, fuse=False)(ins, params, keep_all=True)
    for name in got_unfused:
        np.testing.assert_allclose(np.asarray(got_unfused[name]),
                                   np.asarray(ref_all[name]),
                                   err_msg=name, **TOL)


def test_execution_partitions_cover_fused_chain():
    chain = _bn_block_chain()
    eng = compile_chain(chain)
    hosts = [g.host for g in eng.partitions]
    assert hosts == list(eng.chain.nodes)
    members = [m for g in eng.partitions for m in g.members]
    expected = {m for ms in eng.fusion_report.groups.values() for m in ms}
    assert set(members) == expected
    # fused members are reported in the dispatch table, not executed
    for m in members:
        assert eng.dispatch[m].startswith("fused:")


# ---------------------------------------------------------------------------
# randomized GCONVs across main/reduce/pre/post combinations
# ---------------------------------------------------------------------------
dim_strategy = st.builds(
    dict,
    ng=st.integers(1, 3), nop=st.integers(1, 3), nopc=st.integers(1, 4),
    nks=st.integers(1, 3), stride=st.integers(1, 2))

PRES = [(), (Op("square"),), (Op("abs"),)]
POSTS = [(), (Op("relu"),), (Op("scale", const=0.5),)]


@given(dim_strategy, dim_strategy,
       st.sampled_from(["none", "mul", "add", "sub", "max"]),
       st.sampled_from(["none", "add", "max"]),
       st.integers(0, len(PRES) - 1), st.integers(0, len(POSTS) - 1),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=40, deadline=None)
def test_compiled_gconv_matches_oracle_random(d1, d2, main, reduce,
                                              pre_i, post_i, seed):
    if reduce == "none":                  # no taps without a reduce
        d1 = dict(d1, nks=1)
        d2 = dict(d2, nks=1)
    if main == "none":                    # no Nop replication without a kernel
        d1 = dict(d1, nop=1)              # (the oracle defines no semantics
        d2 = dict(d2, nop=1)              #  for kernel-less replication)
    g = GConv(name="g", dims=(DimSpec("A", **d1), DimSpec("B", **d2)),
              input="x", kernel=None if main == "none" else "k",
              main=main, reduce=reduce,
              pre=PRES[pre_i], post=POSTS[post_i])
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, g.in_shape)
    kk = (jax.random.normal(k2, g.k_shape) if main != "none" else None)
    want = np.asarray(eval_gconv(g, x, kk))
    got = np.asarray(execute_gconv(g, x, kk))
    np.testing.assert_allclose(got, want, **TOL)


def test_compiled_gconv_broadcast_kernel():
    """Kernel with broadcast (size-1) axes — the chain's Table-2 usage."""
    g = GConv(name="g",
              dims=(DimSpec("A", ng=3), DimSpec("B", nop=2, nks=4)),
              input="x", kernel="k", main="mul", reduce="add")
    x = jax.random.normal(jax.random.PRNGKey(0), g.in_shape)
    kk = jax.random.normal(jax.random.PRNGKey(1), (1, 8))  # bcast over A
    want = np.asarray(eval_gconv(g, x, kk))
    got = np.asarray(execute_gconv(g, x, kk))
    np.testing.assert_allclose(got, want, **TOL)


# ---------------------------------------------------------------------------
# window reduces (pools, LRN's channel window): reduce_window and slices
# ---------------------------------------------------------------------------
def _win(name, nopc, nks, stride=1, pad=0, pad_r=None):
    return DimSpec(name, nopc=nopc, nks=nks, stride=stride, pad=pad,
                   pad_r=pad_r)


_BC = (DimSpec("B", ng=2), DimSpec("C", ng=3))
_LRN_POST = (Op("scale", const=1e-4 / 5), Op("add_const", const=2.0),
             Op("pow", const=-0.75))
# (reduce, dims, pre, post): padding, ceil mode (padr > pad), crops
# (padr < 0), strides 1, 2 and > nks, 3-D, LRN's C window, and one
# stride-1 window dim on both sides of the slice fold's tap bound
WINDOW_CASES = {
    "max-pad1-s1": ("max", _BC + (_win("H", 6, 3, 1, 1),
                                  _win("W", 5, 3, 1, 1)), (), ()),
    "max-pad1-s2": ("max", _BC + (_win("H", 4, 3, 2, 1),
                                  _win("W", 4, 3, 2, 1)), (), ()),
    "max-ceil": ("max", _BC + (_win("H", 4, 3, 2, 0, 1),
                               _win("W", 3, 3, 2, 0, 1)), (), ()),
    "max-crop": ("max", _BC + (_win("H", 3, 3, 2, 0, -1),
                               _win("W", 3, 3, 2, 1, -1)), (), ()),
    "max-stride-gt-nks": ("max", _BC + (_win("H", 3, 2, 3),
                                        _win("W", 2, 2, 3, 0, -2)), (), ()),
    "min-ceil-pad1": ("min", _BC + (_win("H", 4, 3, 2, 1, 2),
                                    _win("W", 4, 3, 2, 1)), (), ()),
    "add-avg-pad1": ("add", _BC + (_win("H", 5, 3, 1, 1),
                                   _win("W", 3, 3, 2, 1, 0)),
                     (), (Op("scale", const=1.0 / 9),)),
    "add-ceil-crop": ("add", _BC + (_win("H", 4, 3, 2, 0, 1),
                                    _win("W", 3, 3, 2, 0, -1)), (), ()),
    "max-3d": ("max", _BC + (_win("T", 2, 2, 2, 0, -1), _win("H", 3, 3, 2, 1),
                             _win("W", 3, 3, 2, 1, 2)), (), ()),
    "max-window-and-contract": ("max", (DimSpec("B", ng=2),
                                        DimSpec("C", ng=2, nks=3),
                                        _win("H", 4, 3, 2, 1)), (), ()),
    "lrn": ("add", (DimSpec("B", ng=2), _win("C", 7, 5, 1, 2),
                    DimSpec("H", ng=3), DimSpec("W", ng=4)),
            (Op("square"),), _LRN_POST),
    "max-1d-s1-crop": ("max", _BC + (_win("T", 5, 3, 1, 1, -1),), (), ()),
    "min-1d-s1-ceil": ("min", _BC + (_win("T", 4, 3, 1, 0, 2),), (), ()),
    "add-1d-9-taps": ("add", _BC + (_win("T", 6, 9, 1, 4),), (), ()),
}


@pytest.mark.parametrize("batched", [False, True], ids=["exact", "vmapped"])
@pytest.mark.parametrize("case", list(WINDOW_CASES))
def test_window_reduce_matches_oracle(case, batched):
    reduce, dims, pre, post = WINDOW_CASES[case]
    g = GConv(name="g", dims=dims, input="x", main="none", reduce=reduce,
              pre=pre, post=post)
    tag, _ = dispatch_gconv(g, None)
    assert tag == "reduce"
    xs = jax.random.normal(jax.random.PRNGKey(len(case)), (3,) + g.in_shape)
    if batched:
        got = jax.jit(jax.vmap(lambda x: execute_gconv(g, x)))(xs)
    else:
        got = jnp.stack([jax.jit(lambda x: execute_gconv(g, x))(x)
                         for x in xs])
    want = np.stack([np.asarray(eval_gconv(g, x, None)) for x in xs])
    if reduce == "add":                 # the taps sum in another order
        np.testing.assert_allclose(np.asarray(got), want, **TOL)
    else:                               # max and min are exact
        np.testing.assert_array_equal(np.asarray(got), want)


def _primitives(jaxpr):
    """Every primitive name in ``jaxpr``, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)     # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    yield from _primitives(sub)


@pytest.mark.parametrize("reduced", [True, False], ids=["reduced", "full"])
def test_window_reduces_fold_without_gather(reduced):
    """Each window-reduce step of a GoogLeNet chain (batch 2) folds in one
    reduce_window (pools) or as shifted slices (LRN), with no transpose or
    gather around it, and the engine counts each kind. Traced on shapes
    alone: nothing runs."""
    eng = compile_chain(cnn.build("GLN", reduced=reduced, batch=2))
    chain = eng.chain

    def spec(infos):
        return {n: jax.ShapeDtypeStruct(i.shape, jnp.dtype(i.dtype))
                for n, i in infos.items()}

    inputs, params = spec(chain.inputs), spec(chain.params)
    whole = jax.make_jaxpr(lambda i, p: eng._execute(i, p, False))(
        inputs, params).jaxpr
    n_windows = sum(p.startswith("reduce_window") for p in _primitives(whole))
    steps = {s.name: s for s in eng.steps if s.backend == "reduce"
             and low.window_fold(chain.nodes[s.name]) is not None}
    folds = {n: low.window_fold(chain.nodes[n]) for n in steps}
    n_rw = sum(f == "reduce_window" for f in folds.values())
    assert n_rw and n_windows == n_rw
    assert eng.metrics.value("engine_reduce_window_steps") == n_rw
    assert eng.metrics.value("engine_slice_window_steps") == len(folds) - n_rw
    env = dict(inputs, **params)
    env.update((n, jax.ShapeDtypeStruct(chain.shape_of(n), jnp.float32))
               for n in chain.nodes)
    for name, fold in folds.items():
        prims = list(_primitives(jax.make_jaxpr(steps[name].run)(env).jaxpr))
        n = sum(p.startswith("reduce_window") for p in prims)
        assert n == (fold == "reduce_window"), name
        assert not {"gather", "transpose"} & set(prims), name
        if fold == "reduce_window":
            assert "pad" not in prims, name


@pytest.mark.parametrize("name,rw,sl", [("GLN", 13, 2), ("AN", 3, 2)])
def test_reduce_window_step_count(name, rw, sl):
    """Full-size plans (planning traces nothing). GoogLeNet: 4 stride-2
    max pools and 9 inception pools fold in reduce_window, its 2 LRN
    windows as slices; AlexNet: 3 pools, 2 LRN windows. Global average
    pools are contractions, not windows."""
    eng = compile_chain(cnn.build(name))
    assert eng.metrics.value("engine_reduce_window_steps") == rw
    assert eng.metrics.value("engine_slice_window_steps") == sl


# ---------------------------------------------------------------------------
# kernels.common satellites
# ---------------------------------------------------------------------------
def test_pick_block_invariants():
    from repro.kernels.common import cdiv, pick_block, round_up

    for n in list(range(1, 40)) + [100, 127, 128, 129, 130, 255, 300, 513]:
        for target in (8, 64, 128, 256, 512):
            for align in (8, 128):
                b = pick_block(n, target, align)
                assert b >= 1
                # a grid of cdiv(n, b) blocks always covers the axis: the
                # remainder is never silently dropped
                assert cdiv(n, b) * b >= n, (n, target, align, b)
                assert b <= round_up(n, align), (n, target, align, b)
                if n > align:
                    assert b % align == 0, (n, target, align, b)


def test_gconv_matmul_remainder_blocks():
    """n just above the 128 alignment (e.g. 130) must not drop the
    remainder: the padded grid covers it and results match the oracle."""
    from repro.kernels import ref
    from repro.kernels.gconv_matmul import gconv_matmul

    x = jax.random.normal(jax.random.PRNGKey(0), (1, 130, 130))
    w = jax.random.normal(jax.random.PRNGKey(1), (1, 130, 130))
    got = gconv_matmul(x, w, interpret=True)       # default (big) targets
    np.testing.assert_allclose(got, ref.gconv_matmul_ref(x, w),
                               rtol=1e-4, atol=1e-4)


def test_use_interpret_env_override(monkeypatch):
    from repro.kernels import common

    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")
    assert common.use_interpret() is False
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    assert common.use_interpret() is True
    monkeypatch.delenv("REPRO_FORCE_INTERPRET")
    assert common.use_interpret() is common._backend_wants_interpret()


# ---------------------------------------------------------------------------
# the chip-side kernel choice: dispatch_gconv's static rule
# ---------------------------------------------------------------------------
def _matmul_plan(ch, name):
    node = ch.nodes[name]
    classes = low.dim_classes(node)
    kshape = tuple(ch.shape_of(node.kernel))
    return node, low.match_grouped_matmul(node, classes, kshape)


def test_prefer_pallas_rejects_tiny_m_huge_k(monkeypatch):
    """(1, 4096) @ (4096, 4096) is a matvec: its Pallas grid degenerates
    to one padded M-row, so the heuristic must keep jnp even though K
    and N dwarf mxu_min (the pre-fix code only looked at K/N)."""
    from repro.exec.dispatch import _prefer_pallas_matmul
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")
    ch = Chain("matvec")
    x = ch.add_input("x", (1, 4096))
    ch.mark_output(L.fc(ch, x, out_f=4096, name="fc1"))
    node, plan = _matmul_plan(ch, "fc1")
    assert plan is not None
    assert not _prefer_pallas_matmul("auto", 128, plan, node)


def test_prefer_pallas_accepts_aligned_m(monkeypatch):
    from repro.exec.dispatch import _prefer_pallas_matmul
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")
    ch = Chain("fat")
    x = ch.add_input("x", (8, 512))
    ch.mark_output(L.fc(ch, x, out_f=512, name="fc1"))
    node, plan = _matmul_plan(ch, "fc1")
    assert _prefer_pallas_matmul("auto", 128, plan, node)
    # forced pallas bypasses the heuristic; small K/N still fails auto
    assert _prefer_pallas_matmul("pallas", 128, plan, node)
    ch2 = Chain("thin")
    x2 = ch2.add_input("x", (8, 64))
    ch2.mark_output(L.fc(ch2, x2, out_f=64, name="fc1"))
    node2, plan2 = _matmul_plan(ch2, "fc1")
    assert not _prefer_pallas_matmul("auto", 128, plan2, node2)


# Each full-width zoo net's backend histogram under backend="auto" on the
# chip. A change to the rule (dispatch_gconv, _prefer_pallas_matmul, the
# lowerings' refusal rules) that moves a step shows here and must update
# these numbers on purpose.
CHIP_PLAN = {
    "AN": {"conv:lax": 4, "conv:pallas": 1, "fused": 14, "matmul:pallas": 3,
           "movement": 1, "reduce": 5, "segment:softmax": 1},
    "GLN": {"concat": 9, "conv:lax": 1, "conv:pallas": 19,
            "elementwise": 37, "fused": 26, "matmul:jnp": 21,
            "matmul:pallas": 17, "movement": 1, "reduce": 16,
            "segment:softmax": 1},
    "MN": {"conv:lax": 1, "dwconv:pallas-vpu": 13, "fused": 111,
           "matmul:jnp": 2, "matmul:pallas": 12, "movement": 1,
           "reduce": 55, "segment:softmax": 1},
    "DN": {"concat": 58, "conv:lax": 1, "conv:pallas": 58, "fused": 487,
           "matmul:jnp": 2, "matmul:pallas": 60, "movement": 1,
           "reduce": 247, "segment:softmax": 1},
    "ZFFR": {"conv:lax": 2, "conv:pallas": 4, "elementwise": 1, "fused": 16,
             "matmul:jnp": 4, "matmul:pallas": 2, "movement": 4,
             "reduce": 5, "segment:softmax": 2},
    "C3D": {"conv:lax": 8, "fused": 15, "matmul:pallas": 3, "movement": 1,
            "reduce": 5, "segment:softmax": 1},
    "CapNN": {"conv:lax": 1, "conv:pallas": 1, "elementwise": 14,
              "fused": 12, "matmul:jnp": 6, "movement": 13, "reduce": 8,
              "segment:softmax": 1},
}


@pytest.mark.parametrize("name", list(cnn.ZOO))
def test_chip_rule_plan(name, monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")
    eng = compile_chain(cnn.build(name), backend="auto", lint="off")
    assert eng.backend_histogram() == CHIP_PLAN[name]
