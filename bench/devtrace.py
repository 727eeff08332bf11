"""Reduction of a profiler trace (``.xplane.pb``) to the device numbers the
per-layer metrics read.

The device plane (``/device:TPU:<n>``) has an ``XLA Ops`` line whose events
are the operations the TensorCore ran, one after another, each named by its
HLO text (``%<instruction> = <shape> <opcode>(...)``). The host plane
(``/host:CPU``) holds the benchmark's own annotations around each call
(``bench.call``: ``CompiledChain.__call__`` until it returns;
``bench.wait``: ``block_until_ready`` on its output). Both are on one clock.

Each operation falls in one class:

- ``matmul:pallas`` / ``conv:pallas``: the Mosaic kernels of
  ``gconv_matmul`` / ``gconv_spatial``, told apart by the instruction name
  that the kernel's jitted wrapper gives (``_gconv_matmul.<n>``,
  ``_gconv_spatial.<n>``);
- ``mosaic``: any other Mosaic kernel (``tpu_custom_call``);
- ``xla_mac``: XLA's own convolutions and dots (``convolution``, ``dot``, or
  an output fusion, ``kind=kOutput``, which is how XLA fuses a convolution
  or dot with its elementwise neighbours);
- ``other``: everything else (reductions, pools, pads, copies, loops).
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Tuple

CALL_SPAN = "bench.call"
WAIT_SPAN = "bench.wait"
_HLO = re.compile(r"%(\S+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")
_KERNELS = (("_gconv_matmul", "matmul:pallas"),
            ("_gconv_spatial", "conv:pallas"))


def parse_op(name: str) -> Tuple[str, str]:
    """``(instruction, opcode)`` of an HLO op event name; a fusion's opcode
    carries its kind (``fusion:kOutput``)."""
    m = _HLO.match(name)
    if not m:
        return name, "?"
    rest = name[m.end() - 1:]
    o = _OPCODE.search(rest)
    opcode = o.group(1) if o else "?"
    if opcode == "fusion":
        k = _KIND.search(rest)
        opcode = f"fusion:{k.group(1) if k else '?'}"
    return m.group(1), opcode


def op_class(name: str) -> str:
    inst, opcode = parse_op(name)
    if opcode == "custom-call" and 'custom_call_target="tpu_custom_call"' \
            in name:
        for prefix, cls in _KERNELS:
            if inst.split(".")[0] == prefix:
                return cls
        return "mosaic"
    if opcode in ("convolution", "dot", "fusion:kOutput"):
        return "xla_mac"
    return "other"


def _union(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


@dataclass
class Summary:
    """Device time of a traced window, in seconds, averaged over the
    device planes that ran operations."""
    window_s: float
    busy_s: float
    op_s: float                                   # sum of op durations
    class_s: Dict[str, float]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    calls: int = 0
    annotated_calls: int = 0
    devices: int = 1

    def breakdown(self) -> dict:
        return {"device_ops": [list(x) for x in self.top_ops[:10]],
                "idle_gaps": [list(x) for x in self.idle_gaps[:10]]}


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def _host_spans(planes) -> Dict[str, List[Tuple[float, float]]]:
    spans: Dict[str, List[Tuple[float, float]]] = {CALL_SPAN: [],
                                                   WAIT_SPAN: []}
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in spans:
                    spans[e.name].append((e.start_ns * 1e-9,
                                          (e.start_ns + e.duration_ns) * 1e-9))
    return spans


def _label(a: float, b: float, spans) -> str:
    """What the host was doing over most of ``[a, b]``."""
    best, label = 0.0, "host between calls"
    for name in (CALL_SPAN, WAIT_SPAN):
        cover = sum(max(0.0, min(b, y) - max(a, x)) for x, y in spans[name])
        if cover > best:
            best, label = cover, ("host in CompiledChain.__call__"
                                  if name == CALL_SPAN
                                  else "host waiting on the result")
    return label


def reduce(planes) -> Summary:
    """Reduce the planes of one trace (``ProfileData.planes``)."""
    planes = list(planes)
    spans = _host_spans(planes)
    calls = spans[CALL_SPAN]
    waits = spans[WAIT_SPAN]
    per_dev = []
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
               for line in plane.lines if line.name == "XLA Ops"
               for e in line.events]
        if ops:
            per_dev.append(ops)
    if not per_dev:
        raise RuntimeError("the trace holds no device operation")
    if calls and waits:
        w0, w1 = min(a for a, _ in calls), max(b for _, b in waits)
    else:
        w0 = min(a for ops in per_dev for a, _, _ in ops)
        w1 = max(b for ops in per_dev for _, b, _ in ops)
    n = len(per_dev)
    class_s: Dict[str, float] = {}
    by_op: Dict[str, float] = {}
    gaps: List[Tuple[float, float, float]] = []
    busy = op_total = 0.0
    memo: Dict[str, Tuple[str, str]] = {}
    for ops in per_dev:
        ivs = []
        for a, b, name in ops:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            if name not in memo:
                inst, opcode = parse_op(name)
                memo[name] = (op_class(name), f"{inst} {opcode}")
            cls, short = memo[name]
            d = (b - a) / n
            class_s[cls] = class_s.get(cls, 0.0) + d
            by_op[short] = by_op.get(short, 0.0) + d
            op_total += d
        busy += _union(ivs) / n
        end = w0
        for a, b in sorted(ivs):
            if a > end:
                gaps.append((a - end, end, a))
            end = max(end, b)
        if w1 > end:
            gaps.append((w1 - end, end, w1))
    gaps = sorted(gaps, reverse=True)[:10]
    top = sorted(by_op.items(), key=lambda kv: -kv[1])
    return Summary(window_s=w1 - w0, busy_s=busy, op_s=op_total,
                   class_s=class_s, top_ops=top[:10],
                   idle_gaps=[(_label(a, b, spans), g) for g, a, b in gaps],
                   annotated_calls=len(calls), devices=n)


def reduce_file(path: str) -> Summary:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path).planes)
