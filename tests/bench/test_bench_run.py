"""The harness end to end on the CPU: it refuses to measure without a chip,
and with the look for a chip skipped, a run whose timed path is broken
underneath, or whose program is replaced by the lower-precision control,
comes out not correct."""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import control, run, spec
from repro.exec.engine import CompiledChain

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "googlenet.b1",
         "--seed", "3", "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_a_cpu_run_exits_nonzero_with_no_result_line():
    p = _cli(ROOT)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "not 'tpu'" in p.stderr


def test_the_benchmark_files_alone_do_not_run(tmp_path):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


def test_an_unknown_device_kind_is_an_error(monkeypatch):
    import repro.kernels.common as common

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(common, "use_interpret", lambda: False)
    with pytest.raises(run.NoChip, match="not in bench/peaks.json"):
        run.device_peaks(1, True)


def _small(cell_name, batch):
    cell = spec.cell(cell_name)
    cell.traffic = dict(cell.traffic, batch=batch, pool=2, sample=4)
    return cell


def _first_output(out):
    (name, probs), = out.items()
    return name, probs


def _alter_one_answer(call):
    def broken(self, inputs, params=None, keep_all=False):
        name, p = _first_output(call(self, inputs, params, keep_all))
        return {name: p.at[0].set(jnp.roll(p[0], 1))}
    return broken


def _half_batch_left_out(call):
    def broken(self, inputs, params=None, keep_all=False):
        name, p = _first_output(call(self, inputs, params, keep_all))
        h = p.shape[0] // 2
        return {name: jnp.concatenate([p[:h], p[:p.shape[0] - h]])}
    return broken


def _stale_answer(call):
    last = {}

    def broken(self, inputs, params=None, keep_all=False):
        out = call(self, inputs, params, keep_all)
        prev = last.get("out", out)
        last["out"] = out
        return prev
    return broken


def _one_layer_wrong(call):
    def broken(self, inputs, params=None, keep_all=False):
        params = dict(params)
        params["loss3.w"] = params["loss3.w"] * 1.01    # logits 1% high
        return call(self, inputs, params, keep_all)
    return broken


FAULTS = [None, _alter_one_answer, _stale_answer, _one_layer_wrong]


# a batch of one has no half to leave out
@pytest.mark.parametrize("cell_name,batch,fault",
                         [("googlenet.b32", 2, f)
                          for f in FAULTS + [_half_batch_left_out]]
                         + [("googlenet.b1", 1, f) for f in FAULTS])
def test_a_broken_timed_path_is_not_correct(monkeypatch, cell_name, batch,
                                            fault):
    if fault is not None:
        monkeypatch.setattr(CompiledChain, "__call__",
                            fault(CompiledChain.__call__))
    out = run.run_cell(_small(cell_name, batch), 2 ** 31 + 11, 0.5, False,
                       require_chip=False)
    assert out["correct"] is (fault is None)
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 1
    assert set(out["metrics"]) == {"images_per_s", "call_p95_ms",
                                   "setup_s"}
    if fault is not None:
        assert out["failed"] >= 1


@pytest.mark.parametrize("cell_name,batch", [("googlenet.b32", 2),
                                             ("googlenet.b1", 1)])
def test_the_bfloat16_control_is_not_correct(monkeypatch, cell_name, batch):
    cell = _small(cell_name, batch)
    ctl = control.control_engine(spec.reference(cell.config_name))
    monkeypatch.setattr(CompiledChain, "__call__",
                        lambda self, inputs, params=None, keep_all=False:
                        ctl(inputs, params))
    out = run.run_cell(cell, 2 ** 31 + 12, 0.5, False, require_chip=False)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
