"""Launch-layer tests: sharding rules, train step on a multi-device debug
mesh (subprocess with virtual devices), serving driver, dry-run machinery."""
import json
import os
import subprocess
import sys

import jax
import pytest

from repro import configs
from repro.launch.mesh import dp_axes, make_debug_mesh


def test_dp_axes_and_debug_mesh():
    mesh = make_debug_mesh(1, 1)
    assert dp_axes(mesh) == ("data",)


def test_param_sharding_rules_guarded():
    """Divisibility guards: hymba vocab 32001 must fall back to replicated
    vocab dim; dense dims shard 2-D."""
    from jax.sharding import PartitionSpec as P
    from repro.launch import sharding as shlib
    from repro.models import api

    # single-device mesh but with axis sizes (1,1): everything divides -> all
    # rules apply; check the specs structurally instead of axis sizes
    mesh = make_debug_mesh(1, 1)
    cfg = configs.get("hymba-1.5b", smoke=False)
    model = api.build(cfg)
    ps = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    sh = shlib.param_shardings(cfg, mesh, ps)
    assert sh["embed"].spec == P("model", "data")     # 32001 % 1 == 0 here
    assert sh["layers"]["wq"].spec == P(None, "data", "model")
    assert sh["layers"]["ln1"].spec == P()


def test_guard_drops_nondivisible_axes():
    from jax.sharding import PartitionSpec as P
    from repro.launch.sharding import guard

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    # vocab 32001 not divisible by 16 -> replicated; 32000 divisible
    assert guard(FakeMesh, ("model", "data"), (32001, 2048)) == \
        P(None, "data")
    assert guard(FakeMesh, ("model", "data"), (32000, 2048)) == \
        P("model", "data")


@pytest.mark.slow
def test_train_smoke_loss_falls(tmp_path):
    from repro.launch.train import train

    report = train("tinyllama-1.1b", steps=40, smoke=True, batch=4, seq=32,
                   peak_lr=2e-3, ckpt_dir=str(tmp_path))
    losses = report["losses"]
    assert len(losses) == 40
    # random-token data: compare window means (single steps are noise)
    first = sum(losses[:8]) / 8
    last = sum(losses[-8:]) / 8
    assert last < first, (first, last)


@pytest.mark.slow
def test_train_survives_injected_failure(tmp_path):
    from repro.launch.train import train

    boom = {"armed": True}

    def fault(step):
        if step == 12 and boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("node died")

    report = train("tinyllama-1.1b", steps=20, smoke=True, batch=2, seq=16,
                   ckpt_dir=str(tmp_path), ckpt_every=5, fault_hook=fault)
    assert report["restarts"] == 1
    assert report["final_step"] == 20


@pytest.mark.slow
def test_serve_continuous_batching():
    from repro.launch.serve import Request, Server

    srv = Server("tinyllama-1.1b", smoke=True, slots=2, max_len=48)
    for i in range(3):
        srv.submit(Request(rid=i, prompt=[1, 2, 3], max_new=4))
    report = srv.run_until_drained()
    assert report["requests"] == 3
    assert report["tokens_out"] >= 12
    outs = [r.out for r in srv.finished]
    assert all(len(o) == 4 for o in outs)


def test_compile_cache_keeps_the_env_dir(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it; the helper reports
    it and places no other directory."""
    from repro.runtime.compile_cache import enable_compile_cache

    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    from repro.runtime.compile_cache import enable_compile_cache

    was = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        got = enable_compile_cache()
        assert got == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_collective_parser():
    from repro.analysis.roofline import collective_bytes

    hlo = """
  %ag = f32[128,256]{1,0} all-gather(%x), replica_groups={}
  %ar.1 = bf16[64]{0} all-reduce(%y), to_apply=%add
  %ag2-start = (f32[8], f32[16]) all-gather-start(%z)
  %ag2-done = f32[16]{0} all-gather-done(%ag2-start)
  %rs = f32[32,32]{1,0} reduce-scatter(%w), dimensions={0}
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 128 * 256 * 4 + (8 + 16) * 4
    assert out["all-reduce"] == 64 * 2
    assert out["reduce-scatter"] == 32 * 32 * 4


def test_dryrun_import_is_side_effect_free():
    """Importing launch.dryrun must not mutate XLA_FLAGS (the hillclimb
    env-purity contract, extended to the dry-run: the fake-device flag is
    set in main(), before the first jax INITIALIZATION — module-level jax
    imports do not lock the device count)."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import os, sys; before = os.environ.get('XLA_FLAGS');"
         "sys.path.insert(0, 'src');"
         "import jax;"          # jax first, as in any test process
         "import repro.launch.dryrun as dr;"
         "assert os.environ.get('XLA_FLAGS') == before, 'env mutated';"
         "assert callable(dr.main)"],
        capture_output=True, text=True,
        cwd=os.path.join(os.path.dirname(__file__), ".."), timeout=240)
    assert proc.returncode == 0, proc.stderr


@pytest.mark.slow
def test_dryrun_smoke_cell_subprocess():
    """End-to-end dry-run of one small cell in a subprocess (own XLA_FLAGS),
    asserting the JSON record has the roofline terms."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out_dir = os.path.join(os.path.dirname(__file__), "..", "results",
                           "dryrun")
    target = os.path.join(out_dir,
                          "tinyllama-1.1b__decode_32k__single.json")
    if not os.path.exists(target):
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch",
             "tinyllama-1.1b", "--shape", "decode_32k", "--mesh", "single"],
            env=env, capture_output=True, text=True, timeout=1200)
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    with open(target) as f:
        rec = json.load(f)
    assert rec["status"] == "ok"
    assert rec["chips"] == 256
    roof = rec["roofline"]
    assert roof["compute_s"] > 0 and roof["memory_s"] > 0
    assert roof["dominant"] in ("compute", "memory", "collective")


def test_input_specs_cover_all_cells():
    for arch, shape, ok, why in configs.all_cells(include_skipped=True):
        cfg = configs.get(arch)
        spec = configs.input_specs(arch, shape, cfg)
        assert spec, (arch, shape)
        for leaf in jax.tree.leaves(spec):
            assert hasattr(leaf, "shape") and hasattr(leaf, "dtype")
