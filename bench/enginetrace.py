"""The engine's own spans and steps in a profiler trace.

``bench/devtrace.py`` reduces a trace to what the benchmark's own
annotations (``bench.call``, ``bench.wait``) and the HLO op names can say.
The engine (``repro.exec.engine``) adds, on the same clock, host spans
``engine.call`` > ``engine.args`` / ``engine.launch`` around each call, and
one ``jax.named_scope`` per fusion-group step in the program, which
``CompiledChain.op_steps()`` maps to the compiled program's instructions.
This module reads them:

- :func:`engine_spans`: the intervals of every host event named
  ``engine.*``;
- :func:`inst_seconds`: device seconds per HLO instruction, every one of
  them, averaged over the device planes as ``devtrace`` averages;
- :func:`clock_offset`: how far the device's clock is from the host's;
- :func:`label`: names an idle gap, moved onto the host's clock, by the
  innermost ``engine.*`` span that covers most of it ("host in
  engine.args"), else as ``devtrace`` does;
- :func:`step_seconds`: the instructions' seconds summed by step.

Run on the chip, it takes a short traced window of one cell, prints what
these read as one JSON object and can keep the trace:

    python bench/enginetrace.py --workload googlenet.b1 --seed 5 \\
        --seconds 0.2 [--save chiprun_out/gln_b1_engine]
"""
from __future__ import annotations

import argparse
import bisect
import gzip
import json
import os
import shutil
import sys
import tempfile
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import devtrace, engine_counters  # noqa: E402

PREFIX = "engine."
ENQUEUE = "DoEnqueueProgram"      # the TPU runtime's host events
READ = "ReadSyncFlag"
Interval = Tuple[float, float]


def engine_spans(planes) -> Dict[str, List[Interval]]:
    """``{name: [(start_s, end_s)]}`` of the host events named
    ``engine.*``, each list sorted."""
    spans: Dict[str, List[Interval]] = {}
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9))
    return {k: sorted(v) for k, v in spans.items()}


def window(planes) -> Interval:
    """The traced window as ``devtrace.reduce`` bounds it: from the first
    ``bench.call`` to the end of the last ``bench.wait``, else the device
    operations' own extent."""
    host = devtrace._host_spans(planes)
    if host[devtrace.CALL_SPAN] and host[devtrace.WAIT_SPAN]:
        return (min(a for a, _ in host[devtrace.CALL_SPAN]),
                max(b for _, b in host[devtrace.WAIT_SPAN]))
    ops = [iv for dev in _per_device(planes) for _n, iv in dev]
    return min(a for a, _ in ops), max(b for _, b in ops)


def _per_device(planes) -> List[List[Tuple[str, Interval]]]:
    """``[(op name, (start_s, end_s))]`` of each device plane's
    ``XLA Ops`` line, for the planes that ran operations."""
    out = []
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = [(e.name, (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9))
               for line in plane.lines if line.name == "XLA Ops"
               for e in line.events]
        if ops:
            out.append(ops)
    return out


def inst_seconds(planes, w: Optional[Interval] = None) -> Dict[str, float]:
    """Device seconds of each HLO instruction inside the window ``w``
    (default :func:`window`), averaged over the device planes that ran
    operations."""
    planes = list(planes)
    w0, w1 = window(planes) if w is None else w
    devices = _per_device(planes)
    out: Dict[str, float] = {}
    memo: Dict[str, str] = {}
    for ops in devices:
        for name, (a, b) in ops:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            inst = memo.get(name)
            if inst is None:
                inst = memo[name] = devtrace.parse_op(name)[0]
            out[inst] = out.get(inst, 0.0) + (b - a) / len(devices)
    return out


def clock_offset(planes) -> Optional[float]:
    """Seconds to add to a device timestamp to put it on the host's clock;
    None where the trace cannot bound it.

    The device plane and the host plane of a TPU v5e trace keep clocks that
    differ by a few tenths of a millisecond to more than one (between 0.30
    and 0.82 ms in ``gln_b32_trace``, 1.30 and 1.66 ms in
    ``gln_b1_engine``): without the correction a gap of a few milliseconds
    is put down to the wrong host span. Each execution of a program (an
    event of the device's ``XLA Modules`` line) starts after the host has
    enqueued it (the end of the runtime's ``DoEnqueueProgram``) and ends
    before the host has read its completion (the end of ``ReadSyncFlag``;
    the read can start before the device is done). Paired in order, they
    bound the offset from below and above; the middle of the bounds is
    taken.
    """
    modules: List[Interval] = []
    enqueued: List[float] = []
    read: List[float] = []
    for plane in planes:
        if plane.name.startswith("/device:TPU:") and not modules:
            modules = [(e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9)
                       for line in plane.lines
                       if line.name == "XLA Modules" for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == ENQUEUE:
                        enqueued.append((e.start_ns + e.duration_ns) * 1e-9)
                    elif e.name == READ:
                        read.append((e.start_ns + e.duration_ns) * 1e-9)
    if not modules or not len(modules) == len(enqueued) == len(read):
        return None
    modules.sort()
    lo = max(e - m0 for e, (m0, _m1) in zip(sorted(enqueued), modules))
    hi = min(r - m1 for r, (_m0, m1) in zip(sorted(read), modules))
    return (lo + hi) / 2 if lo <= hi else None


def _overlap(ivs: List[Interval], a: float, b: float):
    """The intervals of sorted, non-overlapping ``ivs`` that meet
    ``[a, b]``, each with the length it covers."""
    i = bisect.bisect_left(ivs, (a,))
    if i and ivs[i - 1][1] > a:
        i -= 1
    while i < len(ivs) and ivs[i][0] < b:
        x, y = ivs[i]
        c = min(b, y) - max(a, x)
        if c > 0:
            yield (x, y), c
        i += 1


def label(a: float, b: float, spans: Dict[str, List[Interval]],
          host=None) -> str:
    """What the host was doing over most of the gap ``[a, b]``: the
    innermost (shortest) ``engine.*`` span that covers more than half of
    it, else ``devtrace``'s label from the benchmark's own spans."""
    best = None
    for name, ivs in spans.items():
        for (x, y), c in _overlap(ivs, a, b):
            if 2 * c > b - a and (best is None or y - x < best[0]):
                best = (y - x, name)
    if best is not None:
        return f"host in {best[1]}"
    return devtrace._label(a, b, host) if host is not None \
        else "host between calls"


def idle_gaps(planes, w: Optional[Interval] = None,
              offset: Optional[float] = None) -> List[Tuple[str, float,
                                                            float]]:
    """Every idle gap of the first device that ran operations, in the
    window, as ``(label, seconds, start_s)``, longest first; each gap is
    labelled after moving it by ``offset`` (default :func:`clock_offset`,
    0 where that finds none) onto the host's clock."""
    planes = list(planes)
    devices = _per_device(planes)
    if not devices:
        return []
    w0, w1 = window(planes) if w is None else w
    if offset is None:
        offset = clock_offset(planes) or 0.0
    ivs = sorted((max(a, w0), min(b, w1)) for _name, (a, b) in devices[0]
                 if min(b, w1) > max(a, w0))
    spans, host = engine_spans(planes), devtrace._host_spans(planes)
    gaps, end = [], w0
    for a, b in ivs + [(w1, w1)]:
        if a > end:
            gaps.append((label(end + offset, a + offset, spans, host),
                         a - end, end))
        end = max(end, b)
    return sorted(gaps, key=lambda g: -g[1])


def step_seconds(inst_s: Dict[str, float],
                 op_steps: Dict[str, str]) -> Dict[Optional[str], float]:
    """Device seconds by step; ``None`` holds the instructions that
    ``op_steps`` puts down to no step."""
    out: Dict[Optional[str], float] = {}
    for inst, s in inst_s.items():
        step = op_steps.get(inst)
        out[step] = out.get(step, 0.0) + s
    return out


def _covered(outer: List[Interval], inner: List[Interval]) -> float:
    """Seconds of ``outer`` that the intervals of ``inner`` cover."""
    total = 0.0
    for a, b in outer:
        total += sum(c for _iv, c in _overlap(inner, a, b))
    return total


def report(planes, op_steps: Dict[str, str], backends: Dict[str, str],
           calls: int) -> dict:
    """What the engine's spans and steps read in one traced window:
    milliseconds per call of each ``engine.*`` span, the share of
    ``bench.call`` that ``engine.args`` and ``engine.launch`` cover, the
    idle time by label, the longest gaps, and the device time by step and
    by the steps' dispatch tag."""
    planes = list(planes)
    w = window(planes)
    spans = engine_spans(planes)
    host = devtrace._host_spans(planes)
    inside = {k: [(a, b) for a, b in v if a >= w[0] and b <= w[1]]
              for k, v in spans.items()}
    bench_call = host[devtrace.CALL_SPAN]
    call_s = sum(b - a for a, b in bench_call)
    phases = sorted(inside.get("engine.args", [])
                    + inside.get("engine.launch", []))
    inst_s = inst_seconds(planes, w)
    op_s = sum(inst_s.values())
    by_step = step_seconds(inst_s, op_steps)
    by_tag: Dict[str, float] = {}
    for step, s in by_step.items():
        tag = backends.get(step, "none") if step is not None else "none"
        by_tag[tag] = by_tag.get(tag, 0.0) + s
    offset = clock_offset(planes)
    gaps = idle_gaps(planes, w, offset or 0.0)
    idle_by: Dict[str, float] = {}
    for lab, g, _a in gaps:
        idle_by[lab] = idle_by.get(lab, 0.0) + g
    return {
        "window_s": w[1] - w[0],
        "clock_offset_ms": 1e3 * offset if offset is not None else None,
        "calls": calls,
        "span_ms_per_call": {k: 1e3 * sum(b - a for a, b in v) / calls
                             for k, v in sorted(inside.items())},
        "bench_call_covered_by_args_and_launch":
            _covered(bench_call, phases) / call_s if call_s else None,
        "idle_s_by_label": dict(sorted(idle_by.items(),
                                       key=lambda kv: -kv[1])),
        "idle_gaps": [[lab, g] for lab, g, _a in gaps[:10]],
        "op_s": op_s,
        "attributed_share": 1.0 - by_step.get(None, 0.0) / op_s
        if op_s else None,
        "device_share_by_tag": {t: s / op_s for t, s in sorted(
            by_tag.items(), key=lambda kv: -kv[1])} if op_s else {},
        "top_steps": [[st, s] for st, s in sorted(
            by_step.items(), key=lambda kv: -kv[1])[:15]],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.2)
    ap.add_argument("--save", metavar="PREFIX",
                    help="keep the trace as PREFIX.xplane.pb.gz and the "
                         "program's steps as PREFIX.steps.json")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import run, spec

    run._paths()
    import jax

    cell = spec.cell(args.workload)
    b = run.build(cell)
    eng = b.engine
    params, pool = run.make_inputs(b, args.seed)
    for p in pool:
        jax.block_until_ready(eng(p, params))
    op_steps = eng.op_steps()
    tdir = tempfile.mkdtemp(prefix="engine_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            w = run.closed_loop(eng, params, pool, args.seconds,
                                run.sample_rng(args.seed), 0, annotate=True)
        finally:
            jax.profiler.stop_trace()
        path = devtrace.find_xplane(tdir)
        from jax.profiler import ProfileData

        planes = list(ProfileData.from_file(path).planes)
        if args.save:
            with open(path, "rb") as f, \
                    gzip.open(args.save + ".xplane.pb.gz", "wb") as g:
                shutil.copyfileobj(f, g)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    backends = {s.name: s.backend for s in eng.steps}
    if args.save:
        with open(args.save + ".steps.json", "w") as f:
            json.dump({"calls": w.calls, "op_steps": op_steps,
                       "backends": backends}, f, indent=0, sort_keys=True)
    out = report(planes, op_steps, backends, w.calls)
    out["workload"] = cell.name
    out["op_steps"] = len(op_steps)
    # builds over the whole run: 1 where the window built nothing
    out["programs_compiled"] = engine_counters.total(
        eng, "engine_programs_compiled")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
