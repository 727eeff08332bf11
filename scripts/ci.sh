#!/usr/bin/env bash
# CI entry point.
#
#   ./scripts/ci.sh                 tier-1: full suite (the ROADMAP verify)
#   FAST=1 ./scripts/ci.sh          smoke tier: skip @slow tests, then run
#                                   the compiled-engine smoke benchmark
#                                   (fails if the compiled engine is slower
#                                   than the oracle interpreter), the
#                                   design-space-explorer smoke (fails if no
#                                   frontier is produced or the best point
#                                   violates the analytic-vs-sim agreement),
#                                   the serving smoke (drains a small
#                                   staggered workload through the compiled
#                                   serving programs; fails on cache
#                                   corruption — outputs diverging from
#                                   sequential single-slot decode — or on a
#                                   throughput regression vs per-request
#                                   execution) and the sharded-engine smoke
#                                   (8 faked host devices in a subprocess;
#                                   fails if the mesh-compiled program
#                                   diverges from the single-device engine
#                                   on a zoo net / the LM blocks, or loses
#                                   its >1 data-parallel scaling) and the
#                                   observability smoke (traced serve
#                                   workload round-tripped through the
#                                   trace schema + report CLI; fails if
#                                   the report disagrees with
#                                   Server.stats() or disabled-mode
#                                   tracing overhead exceeds 2%) and the
#                                   chaos smoke (staggered workload served
#                                   through a fixed fault-injection spec;
#                                   fails if recovered outputs diverge
#                                   byte-for-byte from the fault-free
#                                   reference or the resilience layer
#                                   costs >5% on the fault-free path) and
#                                   the system-simulator smoke (fails if
#                                   the degenerate 1-unit uncontended
#                                   system diverges from repro.sim or the
#                                   serve-trace replay drops recorded
#                                   requests)
#   CI_INSTALL=1 ./scripts/ci.sh    pip install -e '.[dev]' first (networked
#                                   CI; the dev extras declare pytest and
#                                   hypothesis — without them the property
#                                   tests self-skip)
#
# Extra arguments are passed through to pytest.
set -euo pipefail
cd "$(dirname "$0")/.."

if [ "${CI_INSTALL:-0}" = "1" ]; then
  python -m pip install -e '.[dev]'
fi

marker_args=()
if [ "${FAST:-0}" = "1" ]; then
  marker_args=(-m "not slow")
fi

PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m pytest -x -q ${marker_args[@]+"${marker_args[@]}"} "$@"

if [ "${FAST:-0}" = "1" ]; then
  # smoke gates: benchmarks.run exits nonzero when the compiled engine does
  # not beat the interpreter (exec_micro), when the design-space explorer
  # produces no frontier / fails the analytic-vs-sim agreement (dse_micro),
  # when continuous-batching serving corrupts caches / regresses below
  # per-request throughput (serve_micro), or when the mesh-sharded engine
  # diverges from the single-device one / loses >1 data-parallel scaling
  # on faked host devices (exec_sharded_micro), or when the observability
  # layer breaks — serve trace failing schema validation, the report CLI
  # disagreeing with Server.stats(), or disabled-mode tracing overhead
  # above 2% on the exec micro cell (obs_micro), or when serving through
  # the fixed chaos spec loses byte-identity with the fault-free
  # reference / the resilience layer costs >5% fault-free (chaos_micro),
  # or when the system simulator's degenerate 1-unit case diverges from
  # repro.sim / the serve-trace replay drops requests (syssim_micro)
  # ... and the static-analysis smoke: the repro.lint CLI must exit 0
  # with zero error findings on the clean reduced corpus, and exit
  # nonzero on the seeded mutation corpus with every mutant caught by
  # its intended rule (lint_micro)
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.run \
    --only exec_micro,dse_micro,serve_micro,exec_sharded_micro,obs_micro,chaos_micro,syssim_micro,lint_micro
fi

# pyflakes-class static checks (config in pyproject [tool.ruff]); the
# runtime container does not ship ruff (no-install constraint), so this
# gate only arms where the dev extras are installed
if command -v ruff >/dev/null 2>&1; then
  ruff check .
else
  echo "ruff not installed; skipping static check (pip install -e '.[dev]')"
fi
