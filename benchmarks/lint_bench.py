"""Static-analysis benchmark cells.

``lint_scan`` (full tier) sweeps the paper-scale zoo + LM chains through
every ``repro.lint`` pass layer in-process and lands the per-chain
severity counts — plus the ``lint_findings``/``dispatch_oracle_nodes``
metrics — in results/benchmarks.json, so regressions in the static
health of the corpus show up in the committed artifact's trajectory.

``lint_micro`` (FAST CI gate) exercises the actual ``python -m
repro.lint`` entry point twice in subprocesses: the clean reduced sweep
must exit 0 with zero errors, and the ``--mutants`` run must exit
nonzero (the seeded corpus is present) with every mutant caught by its
intended rule and no false positives on the clean bases.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time


def lint_scan():
    from repro.lint import fake_mesh, lint_chain
    from repro.lint.cli import corpus_chains
    from repro.obs import Metrics

    reg = Metrics()
    rows = []
    for chain in corpus_chains("full"):
        for backend in ("auto", "pallas"):
            for spec in (None, "4x2"):
                t0 = time.perf_counter()
                mesh = fake_mesh(spec) if spec else None
                rep = lint_chain(chain, backend=backend, mesh=mesh)
                rep.to_metrics(reg)
                c = rep.counts()
                rows.append(dict(
                    chain=chain.name, backend=backend,
                    mesh=spec or "none", errors=c["error"],
                    warns=c["warn"], infos=c["info"],
                    oracle_nodes=rep.oracle_nodes(),
                    us_per_lint=round((time.perf_counter() - t0) * 1e6)))
    errors = sum(r["errors"] for r in rows)
    summary = dict(
        chains=len(rows), errors=errors,
        warns=sum(r["warns"] for r in rows),
        oracle_nodes=max(r["oracle_nodes"] for r in rows),
        zero_errors=errors == 0,
        metrics=reg.to_dict())
    return rows, summary


def _run_cli(*extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"        # host-only child: leave the chip
    proc = subprocess.run(
        [sys.executable, "-m", "repro.lint", "--scale", "reduced",
         "--format", "json", *extra],
        capture_output=True, text=True, env=env)
    summary = None
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            summary = json.loads(line)
            break
        except ValueError:
            continue
    return proc.returncode, summary or {}


def lint_micro():
    """FAST gate: the CLI exits nonzero iff a mutant is present."""
    rows = []
    rc_clean, s_clean = _run_cli()
    rows.append(dict(run="clean", rc=rc_clean,
                     errors=s_clean.get("counts", {}).get("error", -1),
                     clean=s_clean.get("clean")))
    rc_mut, s_mut = _run_cli("--mutants")
    mut = s_mut.get("mutants") or {}
    rows.append(dict(run="mutants", rc=rc_mut,
                     caught=mut.get("caught"), total=mut.get("total"),
                     false_positives=mut.get("false_positives")))
    ok = (rc_clean == 0 and s_clean.get("clean") is True
          and s_clean.get("counts", {}).get("error") == 0
          and rc_mut == 1 and mut.get("all_caught") is True
          and mut.get("false_positives") == 0)
    return rows, dict(ok=bool(ok), rc_clean=rc_clean, rc_mutants=rc_mut,
                      mutants_caught=mut.get("caught"),
                      mutants_total=mut.get("total"))
