"""Interpreter-vs-compiled execution benchmarks (the perf trajectory).

``exec``       — per-zoo-network wall time: the eager oracle interpreter
                 (``core.interpreter.ChainExecutor``) vs the compiled engine
                 (``repro.exec``), steady-state (post-warmup), plus the
                 allclose divergence between the two. Seeds the
                 ``results/benchmarks.json`` perf trajectory.
``exec_micro`` — one smoke network, run by the FAST CI tier;
                 ``benchmarks.run`` exits nonzero if the compiled engine is
                 not faster than the interpreter.
``exec_sharded``       — mesh-aware engine (``compile_chain(mesh=...)``) in
                 this process when it has the mesh's devices, else on
                 faked CPU devices in a child (the device count locks at
                 first jax init): full zoo + LM blocks sharded-vs-
                 single-device divergence, and 1-device vs N-device
                 batched throughput scaling. Rides
                 ``repro.exec.shardcheck.run``.
``exec_sharded_micro`` — FAST CI gate: one zoo net + the LM blocks + the
                 scaling bench; ``benchmarks.run`` exits nonzero when the
                 sharded program diverges (allclose, rtol 1e-4) or loses
                 its >1 scaling over one device.
"""
from __future__ import annotations

import time


def _bench_pair(chain, inputs, params, iters=3):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.interpreter import ChainExecutor
    from repro.exec import compile_chain

    ex = ChainExecutor(chain)
    eng = compile_chain(chain)

    t0 = time.perf_counter()
    got = jax.block_until_ready(eng(inputs, params))
    compile_s = time.perf_counter() - t0
    ref = jax.block_until_ready(ex(inputs, params))       # eager warmup
    err = 0.0
    for o in ref:
        err = max(err, float(jnp.max(jnp.abs(
            jnp.asarray(got[o], jnp.float32)
            - jnp.asarray(ref[o], jnp.float32)))))

    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(ex(inputs, params))
    oracle_us = (time.perf_counter() - t0) / iters * 1e6
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(eng(inputs, params))
    compiled_us = (time.perf_counter() - t0) / iters * 1e6
    speedup = oracle_us / max(compiled_us, 1e-9)
    return dict(
        oracle_us=round(oracle_us),
        compiled_us=round(compiled_us, 1),
        speedup=round(speedup, 1),
        _speedup_raw=speedup,        # unrounded, for gates; stripped below
        compile_us=round(compile_s * 1e6),
        max_err=round(err, 6),
        backends=eng.backend_histogram(),
    )


def _zoo_case(name, batch=2):
    import jax

    from repro.core.interpreter import init_chain_params
    from repro.models import cnn

    chain = cnn.build(name, reduced=True, batch=batch)
    params = init_chain_params(chain, jax.random.PRNGKey(0))
    return chain, cnn.random_inputs(chain), params


def exec_speedup():
    """Fig.-style interpreter-vs-compiled sweep over the seven zoo CNNs."""
    import numpy as np

    from repro.models import cnn

    rows = []
    for name in cnn.ZOO:
        chain, inputs, params = _zoo_case(name)
        r = _bench_pair(chain, inputs, params)
        r["net"] = name
        rows.append(r)
    # gates use the unrounded ratios (rounding 1.04 -> 1.0 must not fail CI)
    speedups = [r.pop("_speedup_raw") for r in rows]
    geomean = float(np.exp(np.mean(np.log(np.maximum(speedups, 1e-9)))))
    summary = dict(
        networks=len(rows),
        geomean_speedup=round(geomean, 1),
        min_speedup=round(min(speedups), 1),
        all_faster=bool(min(speedups) > 1.0),
        worst_err=max(r["max_err"] for r in rows),
        target="geomean >= 3x over the oracle interpreter at test scale",
        met=bool(geomean >= 3.0),
    )
    return rows, summary


def exec_micro():
    """FAST-tier smoke: one network; fails CI when compiled is slower."""
    chain, inputs, params = _zoo_case("MN", batch=1)
    r = _bench_pair(chain, inputs, params)
    r["net"] = "MN"
    raw = r.pop("_speedup_raw")
    summary = dict(
        speedup=r["speedup"],
        max_err=r["max_err"],
        # gate both speed (unrounded: 1.04 must pass) and correctness —
        # the zoo differential tests are @slow and absent from FAST CI
        compiled_faster=bool(raw > 1.0 and r["max_err"] <= 1e-3),
    )
    return [r], summary


# ---------------------------------------------------------------------------
# mesh-aware engine: sharded-vs-single-device + throughput scaling
# ---------------------------------------------------------------------------
def _sharded_summary(report):
    rows = report["rows"]
    errs = [r["max_err"] for r in rows if "max_err" in r]
    bench = next((r for r in rows if r["check"] == "bench"), None)
    return dict(
        mesh=report["mesh"],
        devices=report["devices"],
        checks=len(rows),
        worst_err=max(errs) if errs else None,
        all_allclose=all(r["ok"] for r in rows if "max_err" in r),
        scaling=bench["scaling"] if bench else None,
        scaling_gt_1=bool(bench and bench["ok"]),
        ok=bool(report["ok"]),
    )


def exec_sharded(mesh: str = "4x2"):
    """Full sweep: all zoo nets + LM blocks sharded on faked devices, plus
    the data-parallel throughput-scaling bench (1 device vs all)."""
    from repro.exec import shardcheck

    report = shardcheck.run(["--mesh", mesh, "--nets", "all", "--lm",
                             "--bench", "0"])
    return report["rows"], _sharded_summary(report)


def exec_sharded_micro(mesh: str = "4x2"):
    """FAST-tier gate: one zoo net + the LM blocks + the scaling bench;
    nonzero exit from benchmarks.run on divergence or scaling <= 1."""
    from repro.exec import shardcheck

    report = shardcheck.run(["--mesh", mesh, "--nets", "MN", "--lm",
                             "--bench", "0"])
    return report["rows"], _sharded_summary(report)
