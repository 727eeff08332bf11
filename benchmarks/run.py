"""Benchmark harness: one entry per paper table/figure + kernel
microbenchmarks + the roofline table from the dry-run artifacts.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig14,...]
                                            [--engine analytic|sim]

Two evaluation engines cover the zoo x accelerator grid:
  * ``analytic`` (default) — the paper's closed-form cost model
    (Eqs. 6-10, repro.core.costmodel); runs every table/figure.
  * ``sim`` — the cycle-level tiled simulator (repro.sim); runs the
    analytic-vs-sim cross-validation and writes per-node
    stall/utilization breakdowns to results/sim/.

Prints ``name,us_per_call,derived`` CSV lines per benchmark plus a summary
block comparing each reproduced number to the paper's claim.
"""
from __future__ import annotations

import argparse
import json
import os
import time

# "simval" (the cycle-level sim sweep) is not in ALL: the default analytic
# run stays pure closed-form; select it with --engine sim or --only simval.
# "exec_micro" / "dse_micro" / "serve_micro" / "exec_sharded_micro" /
# "obs_micro" (the FAST-tier smokes) likewise only run via --only.
ALL = ("table1", "fig12", "fig13", "fig14", "fig15", "fusion", "fig18",
       "fig20", "kernels", "roofline", "exec", "exec_sharded", "dse",
       "serve", "syssim", "lint")

MICRO = ("exec_micro", "dse_micro", "serve_micro", "exec_sharded_micro",
         "obs_micro", "chaos_micro", "syssim_micro", "lint_micro")


def _run(name, fn):
    t0 = time.perf_counter()
    rows, summary = fn()
    dt = (time.perf_counter() - t0) * 1e6
    print(f"\n=== {name} ===")
    for r in rows[:12]:
        print("  " + json.dumps(r))
    if len(rows) > 12:
        print(f"  ... ({len(rows)} rows total)")
    print(f"  summary: {json.dumps(summary)}")
    print(f"{name},{dt:.0f},{json.dumps(summary)}")
    return rows, summary


def bench_kernels():
    """Kernel wall-times (interpret mode on CPU -> correctness-scale only;
    the derived column is max |err| vs the jnp oracle)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.chain_norm import chain_norm
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.gconv_matmul import gconv_matmul
    from repro.kernels.gconv_spatial import gconv_spatial

    rows = []

    def one(name, fn, fn_ref, *args):
        y = fn(*args)
        jax.block_until_ready(y)
        t0 = time.perf_counter()
        for _ in range(3):
            y = fn(*args)
        jax.block_until_ready(y)
        us = (time.perf_counter() - t0) / 3 * 1e6
        err = float(jnp.max(jnp.abs(
            jnp.asarray(y, jnp.float32)
            - jnp.asarray(fn_ref(*args), jnp.float32))))
        rows.append(dict(kernel=name, us_per_call=round(us),
                         max_err=round(err, 6)))

    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (4, 64, 64))
    w = jax.random.normal(k, (4, 64, 64))
    one("gconv_matmul(4x64x64x64)",
        lambda a, b: gconv_matmul(a, b, block_m=32, block_n=32, block_k=32,
                                  interpret=True),
        ref.gconv_matmul_ref, x, w)
    xs = jax.random.normal(k, (2, 16, 16, 8))
    ws = jax.random.normal(k, (3, 3, 8, 16))
    one("gconv_spatial(2x16x16x8)",
        lambda a, b: gconv_spatial(a, b, pad=1, interpret=True),
        lambda a, b: ref.gconv_spatial_ref(a, b, pad=1), xs, ws)
    xn = jax.random.normal(k, (128, 256))
    g = jnp.ones((256,))
    one("chain_norm(128x256)",
        lambda a, b: chain_norm(a, b, block_t=64, interpret=True),
        ref.chain_norm_ref, xn, g)
    q = jax.random.normal(k, (2, 64, 32))
    one("flash_attention(2x64x32)",
        lambda a: flash_attention(a, a, a, block_q=32, block_k=32,
                                  interpret=True),
        lambda a: ref.flash_attention_ref(a, a, a), q)
    worst = max(r["max_err"] for r in rows)
    return rows, {"kernels": len(rows), "worst_err": worst,
                  "all_match_oracle": bool(worst < 5e-2)}


def bench_roofline():
    """Roofline table from the dry-run JSON cache (run launch/dryrun first)."""
    out_dir = os.path.join(os.path.dirname(__file__), "..", "results",
                           "dryrun")
    rows = []
    if not os.path.isdir(out_dir):
        return [], {"note": "no dry-run results yet "
                            "(python -m repro.launch.dryrun --all)"}
    for fn in sorted(os.listdir(out_dir)):
        if not fn.endswith(".json"):
            continue
        with open(os.path.join(out_dir, fn)) as f:
            rec = json.load(f)
        if rec.get("status") != "ok":
            rows.append(dict(cell=fn[:-5], status=rec.get("status"),
                             reason=str(rec.get("reason",
                                                rec.get("error", "")))[:60]))
            continue
        r = rec["roofline"]
        rows.append(dict(
            cell=fn[:-5], status="ok", dominant=r["dominant"],
            compute_ms=round(r["compute_s"] * 1e3, 3),
            memory_ms=round(r["memory_s"] * 1e3, 3),
            collective_ms=round(r["collective_s"] * 1e3, 3),
            useful=round(r["useful_ratio"], 3),
            per_dev_gb=rec.get("per_device_gb")))
    ok = [r for r in rows if r.get("status") == "ok"]
    doms = {}
    for r in ok:
        doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
    return rows, {"cells_ok": len(ok), "dominant_histogram": doms}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--engine", choices=("analytic", "sim"),
                    default="analytic",
                    help="analytic: closed-form cost model over every "
                         "table/figure; sim: cycle-level tiled simulator "
                         "cross-validated against the analytic model")
    ap.add_argument("--mesh", default="4x2",
                    help="mesh for the exec_sharded cells, 'D' or 'DxM' "
                         "(the devices are faked in a subprocess via "
                         "--xla_force_host_platform_device_count)")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.only:
        want = args.only.split(",")
        if args.engine == "sim" and set(want) != {"simval"}:
            ap.error("--engine sim only runs the 'simval' benchmark; "
                     "drop --only or use --only simval")
    elif args.engine == "sim":
        want = ["simval"]
    else:
        want = list(ALL)

    from benchmarks import (chaos_bench, dse_bench, exec_bench, lint_bench,
                            obs_bench, serve_bench, syssim_bench)
    from benchmarks import paper_tables as pt
    from repro.obs import Metrics, provenance

    table = {
        "table1": pt.table1_layers, "fig12": pt.fig12_breakdown,
        "fig13": pt.fig13_conv_speedup, "fig14": pt.fig14_speedup,
        "fig15": pt.fig15_code_density, "fusion": pt.fusion_gains,
        "fig18": pt.fig18_energy, "fig20": pt.fig20_wholelife,
        "kernels": bench_kernels, "roofline": bench_roofline,
        "simval": pt.sim_validation,
        "exec": exec_bench.exec_speedup, "exec_micro": exec_bench.exec_micro,
        "exec_sharded": lambda: exec_bench.exec_sharded(mesh=args.mesh),
        "exec_sharded_micro":
            lambda: exec_bench.exec_sharded_micro(mesh=args.mesh),
        "dse": dse_bench.dse_search, "dse_micro": dse_bench.dse_micro,
        "serve": serve_bench.serve_bench,
        "serve_micro": serve_bench.serve_micro,
        "obs_micro": obs_bench.obs_micro,
        "chaos_micro": chaos_bench.chaos_micro,
        "syssim": syssim_bench.syssim_bench,
        "syssim_micro": syssim_bench.syssim_micro,
        "lint": lint_bench.lint_scan,
        "lint_micro": lint_bench.lint_micro,
    }
    # harness wall-times go through the unified metrics registry so the
    # committed artifact carries the same schema every other subsystem emits
    reg = Metrics()
    results = {}
    for name in want:
        t0 = time.perf_counter()
        results[name] = _run(name, table[name])
        reg.histogram("bench_wall_s", buckets=[0.1, 1, 10, 60, 600],
                      bench=name).observe(time.perf_counter() - t0)
        reg.counter("bench_runs", bench=name).inc()
    out = os.path.join(os.path.dirname(__file__), "..", "results",
                       "benchmarks.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    # merge into the existing artifact so partial runs (--only, --engine
    # sim) update their entries without destroying the others
    merged = {}
    if os.path.exists(out):
        try:
            with open(out) as f:
                merged = json.load(f)
        except (OSError, ValueError):
            merged = {}
    # the *_micro benchmarks are per-machine CI smoke gates: keep their wall
    # times out of the committed perf-trajectory artifact (every FAST CI run
    # would otherwise clobber the curated rows with laptop numbers)
    merged.update({k: {"rows": v[0], "summary": v[1]}
                   for k, v in results.items() if k not in MICRO})
    # provenance + harness metrics are stamped once per invocation that
    # contributes rows, so every committed number is attributable to a git
    # SHA / jax version / device; micro-only (FAST CI) runs leave the
    # stamp alone for the same reason their rows are excluded — a smoke
    # box's identity must not masquerade as the curated rows' origin
    if any(k not in MICRO for k in results):
        merged["provenance"] = provenance()
        merged["metrics"] = reg.to_dict()
    with open(out, "w") as f:
        json.dump(merged, f, indent=1, default=str)
    print(f"\nwrote {os.path.abspath(out)}")

    # CI gates (scripts/ci.sh FAST tier): the compiled engine must beat the
    # oracle interpreter on the smoke network, and the design-space smoke
    # must produce a frontier whose best point passes the analytic-vs-sim
    # agreement contract
    if "exec_micro" in results and not results["exec_micro"][1].get(
            "compiled_faster"):
        raise SystemExit("exec_micro: compiled engine slower than the "
                         "oracle interpreter")
    if "dse_micro" in results and not results["dse_micro"][1].get("ok"):
        raise SystemExit("dse_micro: no frontier or the best point's "
                         "analytic cost disagrees with its sim promotion")
    if "serve_micro" in results and not results["serve_micro"][1].get("ok"):
        raise SystemExit(
            "serve_micro: continuous-batching outputs diverge from "
            "sequential single-slot decode (cache corruption) or batched "
            "serving lost its throughput edge over per-request execution")
    if "exec_sharded_micro" in results and not results[
            "exec_sharded_micro"][1].get("ok"):
        raise SystemExit(
            "exec_sharded_micro: the sharded compiled engine diverged "
            "from the single-device engine (allclose, rtol 1e-4) on the "
            "zoo net / LM blocks, or lost its >1 data-parallel throughput "
            "scaling over one device")
    if "obs_micro" in results and not results["obs_micro"][1].get("ok"):
        raise SystemExit(
            "obs_micro: serve trace failed schema validation, the report "
            "CLI disagrees with Server.stats() on request count or "
            "p50/p99 TTFT, or disabled-mode tracing overhead on the exec "
            "micro cell exceeded the 2% budget")
    if "chaos_micro" in results and not results["chaos_micro"][1].get("ok"):
        raise SystemExit(
            "chaos_micro: recovered outputs diverged byte-for-byte from "
            "the fault-free sequential reference under the fixed fault "
            "spec, a spec'd fault never fired, a request landed in the "
            "wrong terminal status, or the resilience layer cost more "
            "than 5% on the fault-free serve path")
    if "syssim_micro" in results and not results["syssim_micro"][1].get(
            "ok"):
        raise SystemExit(
            "syssim_micro: the degenerate 1-unit uncontended system "
            "diverged from repro.sim (movement/energy/cycles drift or "
            "analytic agreement out of tolerance), or the serve-trace "
            "replay dropped recorded requests")
    if "lint_micro" in results and not results["lint_micro"][1].get("ok"):
        raise SystemExit(
            "lint_micro: the static-verifier CLI failed its exit-code "
            "contract — the clean reduced sweep must exit 0 with zero "
            "error findings, and the --mutants run must exit nonzero "
            "with every seeded mutant caught by its intended rule and "
            "no false positives on the clean bases")


if __name__ == "__main__":
    main()
