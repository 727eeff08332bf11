"""``repro.obs`` — unified tracing, metrics and profiling substrate.

Dependency-free (stdlib only at import time) so every layer can emit
through it: the compiled engine's spans and build counters (its
``profile=True`` mode records the spans ``engine.call`` > ``engine.args``
(the call's inputs and params taken, ``jnp.asarray`` only on values that
are not yet ``jax.Array``s) / ``engine.launch`` > ``engine.compile`` of
each call of the one fused program, and ``compile.*`` of
``compile_chain``, into a tracer), the
serving driver's ``--trace`` request-lifecycle trace, the simulator's
stats and the benchmark harness's provenance-stamped artifacts.

  * :mod:`repro.obs.trace`   — ring-buffered span tracer, Chrome/JSONL
    export (:data:`~repro.obs.trace.SCHEMA_VERSION`), :func:`load_trace`.
  * :mod:`repro.obs.metrics` — labeled counters/gauges/histograms with
    ``snapshot``/``merge``/``diff`` and one versioned ``to_dict`` schema;
    the shared :func:`~repro.obs.metrics.percentile`.
  * :mod:`repro.obs.compiles` — the process's JAX program builds (trace
    and compile seconds, programs, persistent-cache hits) from one
    ``jax.monitoring`` listener; imports JAX only when installed.
  * :mod:`repro.obs.report`  — ``python -m repro.obs.report TRACE``
    (top spans by self-time, backend time share, slot utilization,
    request-latency breakdown, profile coverage).
  * :func:`provenance` — git SHA / dirty flag / jax version / device kind
    stamp for result artifacts.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

from .metrics import Metrics, exp_buckets, percentile  # noqa: F401
from .trace import Trace, Tracer, load_trace  # noqa: F401


def provenance(repo_root: str = None) -> dict:
    """One attribution stamp per artifact-writing invocation: git SHA +
    dirty flag, jax version, device kind, timestamp. Every field degrades
    to ``None`` rather than raising — provenance must never break the run
    it describes."""
    root = repo_root or os.path.join(os.path.dirname(__file__), "..", "..",
                                     "..")
    sha, dirty = None, None
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=root, capture_output=True,
            text=True, timeout=10).stdout.strip() or None
        status = subprocess.run(
            ["git", "status", "--porcelain"], cwd=root, capture_output=True,
            text=True, timeout=10)
        dirty = bool(status.stdout.strip()) if status.returncode == 0 \
            else None
    except (OSError, subprocess.SubprocessError):
        pass
    jax_version, device = None, None
    try:
        import jax
        jax_version = jax.__version__
        dev = jax.devices()[0]
        device = f"{dev.platform}:{getattr(dev, 'device_kind', '?')}"
    except Exception:
        pass
    return {
        "git_sha": sha,
        "git_dirty": dirty,
        "jax": jax_version,
        "device": device,
        "python": sys.version.split()[0],
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    }
