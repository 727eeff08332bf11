"""Trace summarizer: ``python -m repro.obs.report TRACE[.json|.jsonl]``.

Reconstructs, from any trace written by :class:`repro.obs.trace.Tracer`:

  * **top spans by self-time** — per span name, call count, total and
    self time (duration minus nested children), the profiler's headline;
  * **per-backend time share** — spans attributed with a ``backend``
    arg aggregated into a time-share map;
  * **request latency breakdown** — ``request``-category lifecycle spans:
    request count plus queue-wait/TTFT/latency p50/p99 recomputed from
    the per-request args through the same :func:`repro.obs.metrics.
    percentile` the serving driver's ``Server.stats()`` uses, so the two
    agree bit for bit;
  * **slot utilization** — the serving driver's per-tick ``slots``
    counter track averaged against the slot capacity in the trace meta;
  * **profile coverage** — for profiled engine runs, the fraction of the
    latest ``engine.call`` span's wall time covered by its child spans
    (``engine.args``, ``engine.launch``);
  * **fault timeline** — ``chaos``/``resilience``-category instants
    (injected faults, retries, quarantines, sheds, degrade/recover
    transitions) in tick order, with per-event counts.

Prints one JSON object; exits nonzero on unreadable/invalid traces.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Sequence

from .metrics import percentile
from .trace import Trace, load_trace


def _span_children(spans: List[dict]) -> Dict[object, List[dict]]:
    kids: Dict[object, List[dict]] = {}
    for s in spans:
        p = s.get("parent")
        if p is not None:
            kids.setdefault(p, []).append(s)
    return kids


def top_spans(trace: Trace, n: int = 15) -> List[dict]:
    spans = trace.spans
    kids = _span_children(spans)
    agg: Dict[str, dict] = {}
    for s in spans:
        child_t = sum(c["dur"] for c in kids.get(s.get("id"), ()))
        a = agg.setdefault(s["name"], dict(name=s["name"], cat=s["cat"],
                                           calls=0, total_us=0.0,
                                           self_us=0.0))
        a["calls"] += 1
        a["total_us"] += s["dur"]
        a["self_us"] += max(0.0, s["dur"] - child_t)
    out = sorted(agg.values(), key=lambda a: -a["self_us"])[:n]
    for a in out:
        a["total_us"] = round(a["total_us"], 1)
        a["self_us"] = round(a["self_us"], 1)
    return out


def backend_share(trace: Trace) -> Dict[str, float]:
    """Time share per ``backend`` arg over backend-attributed spans."""
    by: Dict[str, float] = {}
    for s in trace.spans:
        b = s["args"].get("backend")
        if b is not None:
            by[b] = by.get(b, 0.0) + s["dur"]
    total = sum(by.values())
    return ({b: round(v / total, 4) for b, v in sorted(by.items())}
            if total > 0 else {})


def request_stats(trace: Trace) -> dict:
    """Request count + latency percentiles from the stable
    :meth:`Trace.serve_requests` lifecycle iterator (shared with the
    ``repro.syssim`` replay frontend). Keys are well-formed for zero and
    one finished request (percentile() contract)."""
    reqs = trace.serve_requests()
    qw = [r.queue_wait_s for r in reqs if r.queue_wait_s is not None]
    ttft = [r.ttft_s for r in reqs if r.ttft_s is not None]
    lat = [r.latency_s for r in reqs if r.latency_s is not None]
    return {
        "requests": len(reqs),
        "p50_queue_wait_s": percentile(qw, 50),
        "p99_queue_wait_s": percentile(qw, 99),
        "p50_ttft_s": percentile(ttft, 50),
        "p99_ttft_s": percentile(ttft, 99),
        "p50_latency_s": percentile(lat, 50),
        "p99_latency_s": percentile(lat, 99),
        "tokens_out": sum(int(r.out_len or 0) for r in reqs),
    }


def phase_breakdown(trace: Trace) -> Dict[str, dict]:
    """p50/total seconds per request-lifecycle phase (queue/prefill/
    decode child spans folded into each ``ServeRequest``)."""
    phases: Dict[str, List[float]] = {}
    for r in trace.serve_requests():
        for name, secs in r.phases.items():
            phases.setdefault(name, []).append(secs)
    return {name: {"count": len(xs), "p50_s": percentile(xs, 50),
                   "total_s": round(sum(xs), 6)}
            for name, xs in sorted(phases.items())}


def slot_utilization(trace: Trace) -> Optional[float]:
    ticks = trace.serve_ticks()
    if not ticks:
        return None
    slots = trace.meta.get("slots")
    mean_active = sum(t.active for t in ticks) / len(ticks)
    return round(mean_active / slots, 4) if slots else round(mean_active, 4)


def profile_coverage(trace: Trace) -> Optional[dict]:
    """Fraction of the latest ``engine.call`` span covered by its child
    spans — how much of a profiled engine call its phases explain."""
    calls = [s for s in trace.spans if s["name"] == "engine.call"]
    if not calls:
        return None
    last = calls[-1]
    kids = _span_children(trace.spans).get(last.get("id"), [])
    child_t = sum(c["dur"] for c in kids)
    cov = child_t / last["dur"] if last["dur"] > 0 else 0.0
    return {"span": last["name"], "span_us": round(last["dur"], 1),
            "children": sorted({c["name"] for c in kids}),
            "attributed_us": round(child_t, 1),
            "coverage": round(min(cov, 1.0), 4),
            "signature": last["args"].get("signature")}


def fault_timeline(trace: Trace) -> Optional[dict]:
    """Resilience timeline from ``chaos``/``resilience``-category instants
    (injected faults, retries, quarantines, sheds, degrade/recover
    transitions, snapshots). ``events`` is the chronological list (tick,
    event name, site/kind detail); ``counts`` aggregates per event name.
    None when the trace carries no fault activity — fault-free traces
    keep their summary unchanged."""
    marks = [e for e in trace.instants
             if e["cat"] in ("chaos", "resilience")]
    if not marks:
        return None
    marks.sort(key=lambda e: e["ts"])
    counts: Dict[str, int] = {}
    events = []
    for e in marks:
        counts[e["name"]] = counts.get(e["name"], 0) + 1
        a = e["args"]
        detail = {k: a[k] for k in ("site", "kind", "status", "rid",
                                    "slot", "error", "index")
                  if k in a}
        events.append({"ts_us": round(e["ts"], 1), "event": e["name"],
                       "tick": a.get("tick"), **detail})
    return {"counts": dict(sorted(counts.items())), "events": events}


def summarize(trace: Trace, top: int = 15) -> dict:
    out = {"schema_version": trace.version, "meta": trace.meta,
           "events": len(trace.events), "spans": len(trace.spans)}
    out.update(request_stats(trace))
    out["phases"] = phase_breakdown(trace)
    out["slot_utilization"] = slot_utilization(trace)
    out["backend_share"] = backend_share(trace)
    out["profile"] = profile_coverage(trace)
    out["faults"] = fault_timeline(trace)
    out["top_spans"] = top_spans(trace, top)
    return out


def render_text(out: dict) -> str:
    """Terminal-friendly rendering of a :func:`summarize` dict."""
    lines = [f"trace: schema v{out['schema_version']}, "
             f"{out['events']} events, {out['spans']} spans",
             f"meta: {json.dumps(out['meta'], default=str)}",
             f"requests: {out['requests']}  "
             f"tokens_out: {out['tokens_out']}"]
    for k in ("queue_wait", "ttft", "latency"):
        p50, p99 = out[f"p50_{k}_s"], out[f"p99_{k}_s"]
        if p50 is not None:
            lines.append(f"  {k}: p50 {p50:.6f}s  p99 {p99:.6f}s")
    for name, ph in (out.get("phases") or {}).items():
        lines.append(f"  phase {name}: x{ph['count']} "
                     f"p50 {ph['p50_s']:.6f}s total {ph['total_s']:.6f}s")
    if out.get("slot_utilization") is not None:
        lines.append(f"slot_utilization: {out['slot_utilization']}")
    if out.get("backend_share"):
        lines.append("backend_share: " + ", ".join(
            f"{b}={v:.2%}" for b, v in out["backend_share"].items()))
    if out.get("profile"):
        pr = out["profile"]
        lines.append(f"profile: {pr['span']} coverage {pr['coverage']:.2%}"
                     f" by {', '.join(pr['children'])}")
    if out.get("faults"):
        lines.append("faults: " + json.dumps(out["faults"]["counts"]))
    lines.append(f"top spans (self time, top {len(out['top_spans'])}):")
    for a in out["top_spans"]:
        lines.append(f"  {a['self_us']:>12.1f}us self "
                     f"{a['total_us']:>12.1f}us total x{a['calls']:<6} "
                     f"{a['name']} [{a['cat']}]")
    return "\n".join(lines)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs.report",
        description="Summarize a repro.obs trace (Chrome JSON or JSONL).")
    ap.add_argument("trace", help="path written by Tracer.write / --trace")
    ap.add_argument("--top", type=int, default=15,
                    help="span-name rows in the self-time table")
    ap.add_argument("--format", choices=("json", "text"), default="json",
                    help="json (default): one machine-readable object, "
                         "consumed by benchmark cells and syssim tooling; "
                         "text: terminal rendering of the same summary")
    args = ap.parse_args(argv)
    try:
        trace = load_trace(args.trace)
    except (OSError, ValueError) as e:
        print(f"report: invalid trace {args.trace!r}: {e}", file=sys.stderr)
        return 1
    out = summarize(trace, top=args.top)
    try:
        if args.format == "text":
            print(render_text(out))
        else:
            print(json.dumps(out, indent=1, default=float))
    except BrokenPipeError:            # | head etc. closed stdout
        pass
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
