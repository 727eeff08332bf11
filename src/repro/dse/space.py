"""Declarative design space over accelerator specs and mapping priorities.

A design *point* is a tuple of indices into per-field choice lists
(:data:`FIELDS`) — hashable, totally ordered, and trivially reproducible.
:meth:`SpecSpace.encode` / :meth:`SpecSpace.decode` give the canonical
``field=value,...`` string form used in artifacts; :meth:`SpecSpace.to_spec`
materializes the :class:`~repro.core.accelerators.AcceleratorSpec` that both
evaluation engines (``core.costmodel``, ``repro.sim``) score directly.

The parameterization covers everything the paper's Table 4 varies between
accelerators (§4.4): the two PE-array axes (sizes, reduce-link placement on
axis 0, overlap-reuse primitives), per-PE scratchpad words, per-type global
buffer capacity and bandwidth, and — because "different accelerators only
change the priorities and resources" of Algorithm 1 — the per-axis and
temporal parameter priorities that steer the mapper. Choice grids include
the exact Table-4 values so ER / TPU / EP are encodable as seed points
(:func:`baseline_points`).

Validity (:meth:`SpecSpace.is_valid`) enforces the *equal-budget* frame the
whole-life-cost comparison needs: PE count and total buffer capacity are
capped at the largest Table-4 baseline budget, so a searched point never
wins by simply spending more silicon than the baselines it is compared to.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Dict, List, Tuple

from repro.core.accelerators import AcceleratorSpec, SpatialDim

# A point is an index tuple: hashable (the strategies' memo table) and
# totally ordered (``min(..., key=lambda ps: (score, point))`` tie-breaks
# are deterministic under a fixed seed).
Point = Tuple[int, ...]

# Algorithm-1 parameter priorities offered to the search (spatial axes).
PRIORITIES: Tuple[Tuple[str, ...], ...] = (
    ("ks", "opc", "op", "g"),       # Table-4 reduce-axis default
    ("opc", "op", "ks", "g"),       # ER px
    ("op", "opc", "ks", "g"),       # TPU cols / EP sub
    ("op", "ks", "opc", "g"),
    ("opc", "ks", "op", "g"),
    ("g", "op", "opc", "ks"),       # group-first (MoE-style workloads)
)
# temporal unrolling priorities (scratchpad fill order)
TEMPORAL_PRIORITIES: Tuple[Tuple[str, ...], ...] = (
    ("op", "ks", "opc", "g"),       # AcceleratorSpec default
    ("ks", "op", "opc", "g"),
    ("opc", "op", "ks", "g"),
    ("g", "op", "ks", "opc"),
)

_K = 1024
# (name, choices) — choice grids deliberately include the odd Table-4 sizes
# (12x14 ER array, 11 words/cycle TPU kernel bus, ER's 0.05 MB buffers) so
# the paper baselines are exact members of the space.
FIELDS: Tuple[Tuple[str, Tuple], ...] = (
    ("ax0", (2, 4, 8, 12, 16, 32, 64, 128, 256, 512)),   # reduce-link axis
    ("ax1", (1, 2, 4, 8, 14, 16, 32, 64)),
    ("overlap", (0, 1, 2)),      # overlap primitives: none / ax0 only / both
    ("ls_i", (1, 4, 12, 24, 64, 224, 256)),              # per-PE words
    ("ls_k", (1, 4, 12, 24, 64, 224, 256)),
    ("ls_o", (1, 4, 12, 24, 64, 224, 256)),
    ("gb_i", (4 * _K, 16 * _K, 26214, 64 * _K, 131072, 262144, 393216,
              786432)),                                  # GB words per type
    ("gb_k", (4194, 4 * _K, 16 * _K, 64 * _K, 131072, 262144, 393216,
              786432)),
    ("gb_o", (4 * _K, 16 * _K, 26214, 64 * _K, 131072, 262144, 393216,
              786432)),
    ("bw_i", (4, 8, 16, 32, 64, 128, 256)),              # words/cycle
    ("bw_k", (4, 8, 11, 16, 32, 64, 128, 256)),
    ("bw_o", (4, 8, 16, 32, 64, 128, 256)),
    ("prio0", tuple(range(len(PRIORITIES)))),
    ("prio1", tuple(range(len(PRIORITIES)))),
    ("tprio", tuple(range(len(TEMPORAL_PRIORITIES)))),
)

_INDEX = {name: i for i, (name, _) in enumerate(FIELDS)}


@dataclass(frozen=True)
class SpecSpace:
    """Budget-constrained accelerator + mapping-priority search space.

    The default budgets are the largest Table-4 baseline budgets: 4096 PEs
    (TPU 64x64), 3 x 0.75 MB-words of global buffer (EP), and EP-scale total
    scratchpad capacity — the "equal PE/buffer budget" envelope of the
    whole-life-cost comparison.
    """

    max_pes: int = 4096
    max_gb_words: int = 3 * 786432
    max_ls_words: int = 512 * _K          # sum(ls per PE) * n_pes

    # ------------------------------------------------------------------
    @property
    def n_fields(self) -> int:
        return len(FIELDS)

    def values(self, point: Point) -> Dict[str, object]:
        """Decode a point into its ``{field: value}`` dict."""
        self._check_shape(point)
        return {name: choices[i]
                for (name, choices), i in zip(FIELDS, point)}

    def _check_shape(self, point: Point):
        if len(point) != len(FIELDS):
            raise ValueError(f"point has {len(point)} fields, "
                             f"expected {len(FIELDS)}")
        for (name, choices), i in zip(FIELDS, point):
            if not (0 <= i < len(choices)):
                raise ValueError(f"field {name!r}: index {i} out of range")

    # ---- budgets / validity ------------------------------------------
    def budget(self, point: Point) -> Tuple[int, int]:
        """(PE count, total GB words) — the equal-budget comparison pair."""
        v = self.values(point)
        return (v["ax0"] * v["ax1"], v["gb_i"] + v["gb_k"] + v["gb_o"])

    def is_valid(self, point: Point) -> bool:
        v = self.values(point)
        pes = v["ax0"] * v["ax1"]
        gb = v["gb_i"] + v["gb_k"] + v["gb_o"]
        ls = (v["ls_i"] + v["ls_k"] + v["ls_o"]) * pes
        return (pes <= self.max_pes and gb <= self.max_gb_words
                and ls <= self.max_ls_words)

    # ---- canonical string form ---------------------------------------
    def encode(self, point: Point) -> str:
        v = self.values(point)
        return ",".join(f"{name}={v[name]}" for name, _ in FIELDS)

    def decode(self, s: str) -> Point:
        vals: Dict[str, str] = {}
        for part in s.split(","):
            name, _, raw = part.partition("=")
            if not _:
                raise ValueError(f"malformed field {part!r}")
            vals[name] = raw
        point: List[int] = []
        for name, choices in FIELDS:
            if name not in vals:
                raise ValueError(f"missing field {name!r}")
            want = int(vals.pop(name))
            for i, c in enumerate(choices):
                if int(c) == want:
                    point.append(i)
                    break
            else:
                raise ValueError(f"field {name!r}: {want} not in grid "
                                 f"{choices}")
        if vals:
            raise ValueError(f"unknown fields {sorted(vals)}")
        return tuple(point)

    # ---- materialization ---------------------------------------------
    def to_spec(self, point: Point) -> AcceleratorSpec:
        v = self.values(point)
        enc = self.encode(point)
        digest = hashlib.sha1(enc.encode()).hexdigest()[:8]
        ov = v["overlap"]
        spatial = (
            SpatialDim("d0", v["ax0"], reduce=True, overlap=ov >= 1,
                       priority=PRIORITIES[v["prio0"]]),
            SpatialDim("d1", v["ax1"], reduce=False, overlap=ov >= 2,
                       priority=PRIORITIES[v["prio1"]]),
        )
        return AcceleratorSpec(
            name=f"DSE-{digest}", kind="DSE", spatial=spatial,
            ls={"I": v["ls_i"], "K": v["ls_k"], "O": v["ls_o"]},
            gb={"I": v["gb_i"], "K": v["gb_k"], "O": v["gb_o"]},
            gb_bandwidth={"I": v["bw_i"], "K": v["bw_k"], "O": v["bw_o"]},
            temporal_priority=TEMPORAL_PRIORITIES[v["tprio"]],
            offload=False, has_overlap_primitive=ov >= 1)

    # ---- point generation --------------------------------------------
    def sample(self, rng, max_tries: int = 10_000) -> Point:
        for _ in range(max_tries):
            p = tuple(rng.randrange(len(choices)) for _, choices in FIELDS)
            if self.is_valid(p):
                return p
        raise RuntimeError("could not sample a valid point "
                           "(budgets too tight for the grid?)")

    def mutate(self, point: Point, rng, n_fields: int = 1,
               max_tries: int = 1000) -> Point:
        """Resample ``n_fields`` random fields; retries until valid."""
        self._check_shape(point)
        for _ in range(max_tries):
            p = list(point)
            for f in rng.sample(range(len(FIELDS)), n_fields):
                p[f] = rng.randrange(len(FIELDS[f][1]))
            p = tuple(p)
            if p != point and self.is_valid(p):
                return p
        return point

    def crossover(self, a: Point, b: Point, rng) -> Point:
        """Uniform crossover; falls back to mutation-repair when the child
        breaks a budget (e.g. one parent's big array with the other's big
        buffers), and to a parent when even repair cannot restore validity
        (``mutate`` returns its input unchanged after ``max_tries``)."""
        self._check_shape(a)
        self._check_shape(b)
        child = tuple(x if rng.random() < 0.5 else y for x, y in zip(a, b))
        if self.is_valid(child):
            return child
        child = self.mutate(child, rng, n_fields=2)
        return child if self.is_valid(child) else a


def _point_from_values(space: SpecSpace, **values) -> Point:
    return space.decode(",".join(f"{name}={values[name]}"
                                 for name, _ in FIELDS))


def baseline_points(space: SpecSpace) -> Dict[str, Point]:
    """The three paper baselines (ER / TPU / EP, Table 4) encoded as design
    points — exact members of the grid, used to seed every search so the
    explorer always starts from (and therefore never loses to) the
    hand-designed configurations' neighborhoods."""
    pts = {
        "ER": _point_from_values(
            space, ax0=12, ax1=14, overlap=2,
            ls_i=12, ls_k=224, ls_o=24,
            gb_i=26214, gb_k=4194, gb_o=26214,
            bw_i=16, bw_k=16, bw_o=16,
            prio0=0, prio1=1, tprio=0),
        "TPU": _point_from_values(
            space, ax0=64, ax1=64, overlap=0,
            ls_i=1, ls_k=1, ls_o=1,
            gb_i=393216, gb_k=131072, gb_o=393216,
            bw_i=64, bw_k=11, bw_o=64,
            prio0=0, prio1=2, tprio=0),
        "EP": _point_from_values(
            space, ax0=512, ax1=4, overlap=1,
            ls_i=64, ls_k=1, ls_o=1,
            gb_i=786432, gb_k=786432, gb_o=786432,
            bw_i=128, bw_k=128, bw_o=128,
            prio0=0, prio1=2, tprio=0),
    }
    for name, p in pts.items():
        if not space.is_valid(p):
            raise ValueError(f"baseline seed {name} violates space budgets")
    return pts
