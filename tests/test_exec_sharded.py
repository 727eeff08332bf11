"""Mesh-aware compiled execution (compile_chain(mesh=...), ServeEngine
data-parallel mode).

Two layers:
  * in-process: ShardPlan derivation (column/row/replicate decisions, dp
    guards, step wrapping) on fake meshes, plus end-to-end execution on a
    1x1 debug mesh — no extra devices needed;
  * subprocess (slow): the real 8-fake-device differential checks via
    ``python -m repro.exec.shardcheck`` — the device count locks at the
    first jax initialization, so multi-device runs need their own process
    (same pattern as the dry-run tests).
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core.interpreter import ChainExecutor
from repro.exec import compile_chain, derive_plan
from repro.exec.shardplan import wrap_steps
from repro.launch.mesh import make_debug_mesh
from repro.models import cnn, lm_chain
from repro.models.common import ModelConfig

TOL = dict(rtol=1e-4, atol=1e-4)


class FakeMesh:
    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)
        self.empty = False


def _tiny_cfg(**kw):
    base = dict(name="tiny", family="dense", n_layers=1, d_model=16,
                n_heads=2, n_kv_heads=2, d_ff=32, vocab=64)
    base.update(kw)
    return ModelConfig(**base)


def _compiled(chain):
    eng = compile_chain(chain)
    return eng


# ---------------------------------------------------------------------------
# ShardPlan derivation (pure policy, no devices)
# ---------------------------------------------------------------------------
def test_plan_column_splits_divisible_matmuls():
    ch = lm_chain.block_chain(_tiny_cfg(), 2, 8)
    eng = _compiled(ch)
    plan = derive_plan(eng.chain, eng.dispatch, FakeMesh(data=4, model=2))
    # d_ff = 32 and d_model = 16 divide model=2: the projection matmuls
    # column-split (no collective)
    assert plan.step_tp.get("w_gate") == "column"
    assert plan.step_tp.get("wq") == "column"
    assert plan.tp == "model" and plan.dp == ("data",)


def test_plan_no_tp_without_model_axis_or_at_size_one():
    ch = lm_chain.block_chain(_tiny_cfg(), 2, 8)
    eng = _compiled(ch)
    assert derive_plan(eng.chain, eng.dispatch,
                       FakeMesh(data=8, model=1)).step_tp == {}
    plan = derive_plan(eng.chain, eng.dispatch, FakeMesh(replica=8))
    assert plan.step_tp == {} and plan.tp is None
    assert plan.dp == ("replica",)


def test_plan_row_splits_when_only_k_divides():
    # Cout = 7 (odd), K = 32: the column split is impossible, the row
    # split (explicit psum) takes over
    from repro.core.chain import Chain
    from repro.core.gconv import DimSpec, GConv

    c = Chain("rowsplit")
    c.add_input("x", (5, 32))
    c.add_param("w", (1, 32 * 7))
    c.add(GConv("y", dims=(DimSpec("b", ng=5), DimSpec("c", nks=32, nop=7)),
                input="x", kernel="w", main="mul", reduce="add"))
    c.outputs = ["y"]
    eng = _compiled(c)
    assert eng.dispatch["y"] == "matmul:jnp"
    plan = derive_plan(eng.chain, eng.dispatch, FakeMesh(data=4, model=2))
    assert plan.step_tp == {"y": "row"}
    # neither divides (model=13): replication fallback
    plan13 = derive_plan(eng.chain, eng.dispatch, FakeMesh(data=1, model=13))
    assert plan13.step_tp == {}


def test_plan_input_specs_guarded():
    ch = lm_chain.block_chain(_tiny_cfg(), 2, 8)
    eng = _compiled(ch)
    plan = derive_plan(eng.chain, eng.dispatch, FakeMesh(data=2, model=1))
    for name, spec in plan.in_specs.items():
        shape = eng.chain.inputs[name].shape
        if shape and shape[0] % 2 == 0:
            assert spec[0] == "data", name
        else:
            assert tuple(spec) == (None,) * len(spec), name


def test_wrap_steps_tags_tp_modes():
    ch = lm_chain.block_chain(_tiny_cfg(), 2, 8)
    eng = _compiled(ch)
    plan = derive_plan(eng.chain, eng.dispatch, FakeMesh(data=4, model=2))
    wrapped = wrap_steps(eng.chain, eng.steps, plan)
    tags = {s.name: s.backend for s in wrapped}
    assert tags["w_gate"] == "matmul:jnp+tp:column"
    # non-matmul steps pass through untouched
    plain = {s.name: s.backend for s in eng.steps}
    for name, tag in tags.items():
        if name not in plan.step_tp:
            assert tag == plain[name]


# ---------------------------------------------------------------------------
# end-to-end on the 1x1 debug mesh (sharded machinery, single device)
# ---------------------------------------------------------------------------
def test_sharded_engine_runs_on_debug_mesh():
    mesh = make_debug_mesh(1, 1)
    ch = lm_chain.block_chain(_tiny_cfg(), 2, 8)
    params = ChainExecutor(ch).init_params(jax.random.PRNGKey(0))
    inputs = cnn.random_inputs(ch, 1)
    ref = compile_chain(ch)(inputs, params)
    eng = compile_chain(ch, mesh=mesh)
    assert eng.shard_plan is not None and eng.mesh is mesh
    got = eng(inputs, params)
    for o in ref:
        np.testing.assert_allclose(np.asarray(got[o]), np.asarray(ref[o]),
                                   err_msg=o, **TOL)
    # batched mode through the sharded in-shardings path
    import jax.numpy as jnp
    batched = {k: jnp.stack([v, v, v]) for k, v in inputs.items()}
    got_b = eng(batched, params)
    for o in ref:
        np.testing.assert_allclose(np.asarray(got_b[o][1]),
                                   np.asarray(ref[o]), err_msg=o, **TOL)


def test_sharded_signature_distinct_from_plain():
    mesh = make_debug_mesh(1, 1)
    ch = lm_chain.block_chain(_tiny_cfg(), 2, 8)
    plain = compile_chain(ch)
    sharded = compile_chain(ch, mesh=mesh)
    assert plain.signature != sharded.signature
    assert "mesh=data1xmodel1" in sharded.signature
    again = compile_chain(lm_chain.block_chain(_tiny_cfg(), 2, 8),
                          mesh=make_debug_mesh(1, 1))
    assert again.signature == sharded.signature


def test_serve_engine_debug_mesh_matches_unsharded():
    from repro.exec.serving import ServeEngine
    from repro.models import api

    from repro import configs

    cfg = configs.get("tinyllama-1.1b", smoke=True)
    model = api.build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    plain = ServeEngine(model, slots=2, max_len=32)
    sharded = ServeEngine(model, slots=2, max_len=32,
                          mesh=make_debug_mesh(1, 1))
    params_sh = sharded.shard_params(params)
    logits_p, rows_p, _ = plain.prefill(params, [[1, 2, 3], [4, 5]])
    logits_s, rows_s, _ = sharded.prefill(params_sh, [[1, 2, 3], [4, 5]])
    np.testing.assert_array_equal(np.asarray(logits_p),
                                  np.asarray(logits_s))
    cache_p = plain.splice_many(plain.init_state(), [0, 1], rows_p)
    cache_s = sharded.splice_many(sharded.init_state(), [0, 1], rows_s)
    import jax.numpy as jnp
    toks = jnp.asarray([[7], [9]], jnp.int32)
    lg_p, cache_p = plain.decode(params, toks, cache_p)
    lg_s, cache_s = sharded.decode(params_sh, toks, cache_s)
    np.testing.assert_array_equal(np.asarray(lg_p), np.asarray(lg_s))
    for k in cache_p:
        np.testing.assert_array_equal(np.asarray(cache_p[k]),
                                      np.asarray(cache_s[k]), err_msg=k)


# ---------------------------------------------------------------------------
# the real multi-device checks (subprocess: 8 faked host devices)
# ---------------------------------------------------------------------------
def _shardcheck(*args, devices=8, timeout=1500):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count"
                          f"={devices}")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.exec.shardcheck", *args],
        capture_output=True, text=True, env=env, timeout=timeout)
    assert proc.stdout.strip(), proc.stderr[-2000:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 0, (report, proc.stderr[-2000:])
    return report


@pytest.mark.slow
def test_sharded_zoo_allclose_on_8_devices():
    report = _shardcheck("--mesh", "4x2", "--nets", "all")
    assert report["devices"] >= 8
    assert len(report["rows"]) == len(cnn.ZOO)
    for row in report["rows"]:
        assert row["ok"], row


@pytest.mark.slow
def test_sharded_lm_blocks_allclose_on_8_devices():
    report = _shardcheck("--mesh", "4x2", "--lm")
    rows = {r["check"]: r for r in report["rows"]}
    assert rows["lm:dense"]["ok"] and rows["lm:moe"]["ok"], rows
    # tensor-parallel splits actually engaged on the 4x2 mesh
    assert rows["lm:dense"]["tp_steps"] > 0
    assert rows["lm:dense"]["batched_max_err"] <= 1e-4


@pytest.mark.slow
def test_sharded_serve_byte_identical_on_8_devices():
    report = _shardcheck("--mesh", "8x1", "--serve")
    (row,) = report["rows"]
    assert row["identical_to_sequential"], row
    assert row["slots"] == 8
