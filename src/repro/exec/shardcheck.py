"""Sharded-vs-single-device checks for the mesh-aware compiled engine.

The one driver behind tests/test_exec_sharded.py, the ``exec_sharded``
benchmark cell and the ``exec_sharded_micro`` FAST CI gate: compile each
requested program twice — single-device and against a mesh — and compare.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m repro.exec.shardcheck \\
        --mesh 8x1 --nets MN --lm --serve --bench 0

With enough devices the checks run in this process (one process per
chip: a child could not open devices its parent holds). On a CPU backend
with too few devices, :func:`run` re-execs in a subprocess with the
fake-device flag and ``JAX_PLATFORMS=cpu`` (the device count locks at the
first jax initialization, so it cannot be raised in-process); on an
accelerator with too few devices it fails.

Checks (each a row in the JSON report printed as the last stdout line):

  * ``net:<name>``  — zoo chain, sharded exact-mode outputs vs the
                      single-device engine, allclose rtol 1e-4;
  * ``lm:dense`` / ``lm:moe`` — the LM block chains, same comparison,
                      plus the dense block in batched (leading-batch)
                      mode against single-device per-sample rows;
  * ``serve``       — staggered continuous batching on a data-parallel
                      mesh vs the sequential single-slot reference,
                      byte-identical token streams required;
  * ``bench``       — steady-state batched throughput, single vs sharded
                      (items/s and the scaling ratio; smoke scale).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

RTOL = 1e-4
# the scaling bench needs enough per-device work to amortize multi-device
# dispatch; these smoke-scale shapes give >1.2x on a 2-core CI host
BENCH_D_MODEL, BENCH_SEQ, BENCH_BATCH = 128, 64, 128


def _mesh_devices(spec: str) -> int:
    from repro.shardpolicy import parse_mesh_spec

    d, m = parse_mesh_spec(spec)
    return d * m


def _reexec_on_fake_cpus(argv, devices: int) -> dict:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "..")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={devices}")
    proc = subprocess.run([sys.executable, "-m", "repro.exec.shardcheck",
                           *argv], capture_output=True, text=True, env=env)
    if not proc.stdout.strip():
        raise RuntimeError(f"shardcheck produced no output: "
                           f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _tiny_cfg(**kw):
    from repro.models.common import ModelConfig

    base = dict(name="tiny", family="dense", n_layers=1, d_model=16,
                n_heads=2, n_kv_heads=2, d_ff=32, vocab=64)
    base.update(kw)
    return ModelConfig(**base)


def _compare(chain, mesh):
    """(max_err, ok, tp_steps) of sharded vs single-device exact mode."""
    import jax
    import jax.numpy as jnp

    from repro.core.interpreter import ChainExecutor
    from repro.exec import compile_chain
    from repro.models import cnn

    params = ChainExecutor(chain).init_params(jax.random.PRNGKey(0))
    inputs = cnn.random_inputs(chain, 1)
    ref = compile_chain(chain)(inputs, params)
    eng = compile_chain(chain, mesh=mesh)
    got = eng(inputs, params)
    err = 0.0
    ok = True
    for o in ref:
        r = jnp.asarray(ref[o], jnp.float32)
        g = jnp.asarray(got[o], jnp.float32)
        err_o = float(jnp.max(jnp.abs(g - r)))
        tol_o = RTOL * float(jnp.max(jnp.abs(r))) + RTOL
        err = max(err, err_o)
        ok = ok and err_o <= tol_o        # each output vs its OWN scale
    return err, ok, len(eng.shard_plan.step_tp)


def check_net(name, mesh):
    from repro.models import cnn

    chain = cnn.build(name, reduced=True, batch=2)
    err, ok, tp = _compare(chain, mesh)
    return {"check": f"net:{name}", "max_err": err, "tp_steps": tp,
            "ok": ok}


def check_lm(kind, mesh):
    import jax
    import jax.numpy as jnp

    from repro.core.interpreter import ChainExecutor
    from repro.exec import compile_chain
    from repro.models import cnn, lm_chain

    cfg = (_tiny_cfg() if kind == "dense"
           else _tiny_cfg(name="tiny-moe", family="moe", n_experts=4,
                          top_k=2))
    chain = lm_chain.block_chain(cfg, 2, 8)
    err, ok, tp = _compare(chain, mesh)
    row = {"check": f"lm:{kind}", "max_err": err, "tp_steps": tp, "ok": ok}
    if kind == "dense":
        # batched mode: sharded leading-batch rows vs single-device
        # per-sample execution
        params = ChainExecutor(chain).init_params(jax.random.PRNGKey(0))
        ins = cnn.random_inputs(chain, 1)
        n = 2 * mesh.devices.size
        key = jax.random.PRNGKey(7)
        batched = {k: jax.random.normal(jax.random.fold_in(key, i),
                                        (n,) + tuple(v.shape))
                   for i, (k, v) in enumerate(sorted(ins.items()))}
        e1 = compile_chain(chain)
        e8 = compile_chain(chain, mesh=mesh)
        got = e8(batched, params)
        berr = 0.0
        for j in range(n):
            one = e1({k: v[j] for k, v in batched.items()}, params)
            for o in one:
                berr = max(berr, float(jnp.max(jnp.abs(
                    got[o][j] - one[o]))))
        row["batched_max_err"] = berr
        row["batched_buckets"] = e8.batch_buckets
        row["ok"] = bool(row["ok"] and berr <= RTOL)
    return row


def check_serve(mesh):
    """Staggered DP-mesh serving vs the sequential single-slot reference."""
    from repro.launch.serve import Request, Server, sequential_reference
    import numpy as np

    slots = mesh.devices.size
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, 256, rng.integers(2, 6)).tolist(),
                    max_new=6)
            for i in range(slots + 4)]
    srv = Server("tinyllama-1.1b", smoke=True, slots=slots, max_len=48,
                 mesh=mesh)
    srv.run_workload([Request(rid=r.rid, prompt=list(r.prompt),
                              max_new=r.max_new) for r in reqs],
                     stagger_ticks=2)
    got = {r.rid: r.out for r in srv.finished}
    ref = sequential_reference(
        "tinyllama-1.1b",
        [Request(rid=r.rid, prompt=list(r.prompt), max_new=r.max_new)
         for r in reqs], max_len=48)
    identical = all(got[r.rid] == ref[i] for i, r in enumerate(reqs))
    return {"check": "serve", "slots": slots, "requests": len(reqs),
            "identical_to_sequential": bool(identical),
            "ok": bool(identical)}


def bench_scaling(iters=3):
    """Steady-state batched throughput: single device vs data-parallel.

    Benches a pure data-parallel mesh over ALL devices (not the check
    mesh — its model axis is deliberately ignored): the scaling story at
    smoke scale is DP replicas — tensor-splitting matmuls this small only
    adds dispatch overhead, which the correctness checks tolerate but a
    throughput gate must not."""
    import jax
    import jax.numpy as jnp

    from repro.core.interpreter import ChainExecutor
    from repro.exec import compile_chain
    from repro.launch.mesh import make_debug_mesh
    from repro.models import cnn, lm_chain

    mesh = make_debug_mesh(len(jax.devices()), 1)

    cfg = _tiny_cfg(d_model=BENCH_D_MODEL, n_heads=4, n_kv_heads=4,
                    d_ff=2 * BENCH_D_MODEL, vocab=256)
    chain = lm_chain.block_chain(cfg, 2, BENCH_SEQ)
    params = ChainExecutor(chain).init_params(jax.random.PRNGKey(0))
    ins = cnn.random_inputs(chain, 1)
    batched = {k: jnp.stack([v] * BENCH_BATCH) for k, v in ins.items()}

    def best(eng):
        t = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(eng(batched, params))
            t = min(t, time.perf_counter() - t0)
        return t

    e1 = compile_chain(chain)
    en = compile_chain(chain, mesh=mesh)
    jax.block_until_ready(e1(batched, params))            # compile+warm
    jax.block_until_ready(en(batched, params))
    # interleaved rounds, gate on the best: scheduling noise on a small
    # shared CI host (8 device threads on ~2 cores) swings single-round
    # ratios by +-30%, and a flaky throughput gate is worse than a
    # slightly lenient one — a genuinely broken sharded path stays below
    # 1.0 in every round
    t1 = tn = float("inf")
    scaling = 0.0
    for _ in range(3):
        t1 = min(t1, best(e1))
        tn = min(tn, best(en))
        scaling = t1 / tn
        if scaling > 1.0:
            break
    return {"check": "bench", "devices": mesh.devices.size,
            "batch": BENCH_BATCH,
            "single_items_per_s": round(BENCH_BATCH / t1, 1),
            "sharded_items_per_s": round(BENCH_BATCH / tn, 1),
            "scaling": round(scaling, 3),
            "ok": bool(scaling > 1.0)}


def _parse(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="8x1", help="'D' or 'DxM'")
    ap.add_argument("--nets", default="",
                    help="comma list of zoo nets, or 'all'")
    ap.add_argument("--lm", action="store_true",
                    help="check the LM dense + MoE blocks")
    ap.add_argument("--serve", action="store_true",
                    help="check staggered DP serving vs sequential")
    ap.add_argument("--bench", type=int, default=-1, metavar="ITERS",
                    help="scaling bench iters (0 = default 3, -1 = skip)")
    return ap.parse_args(argv)


def _run_here(args) -> dict:
    import jax

    from repro.launch.mesh import mesh_from_spec
    from repro.models import cnn

    mesh = mesh_from_spec(args.mesh)
    rows = []
    nets = (list(cnn.ZOO) if args.nets == "all"
            else [n for n in args.nets.split(",") if n])
    for name in nets:
        rows.append(check_net(name, mesh))
    if args.lm:
        rows.append(check_lm("dense", mesh))
        rows.append(check_lm("moe", mesh))
    if args.serve:
        rows.append(check_serve(mesh))
    if args.bench >= 0:
        rows.append(bench_scaling(iters=args.bench or 3))
    return {"mesh": args.mesh, "devices": len(jax.devices()),
            "rows": rows, "ok": bool(rows) and all(r["ok"] for r in rows)}


def run(argv) -> dict:
    """The checks' JSON report: computed in this process when it has the
    mesh's devices, else on faked CPU devices in a child (CPU only)."""
    args = _parse(argv)
    need = _mesh_devices(args.mesh)
    import jax                       # first init locks the device count

    have = len(jax.devices())
    if have >= need:
        return _run_here(args)
    if jax.default_backend() != "cpu":
        raise RuntimeError(f"--mesh {args.mesh} needs {need} devices; this "
                           f"{jax.default_backend()} host has {have}")
    return _reexec_on_fake_cpus(argv, need)


def main(argv=None):
    report = run(sys.argv[1:] if argv is None else argv)
    print(json.dumps(report))
    raise SystemExit(0 if report["ok"] else 1)


if __name__ == "__main__":
    main()
