"""Compile-time kernel autotuner: measured (backend, block) selection.

`exec.dispatch` picks backends with a fixed ``mxu_min`` threshold and
``kernels.common.pick_block`` is a static formula — neither ever consults a
measurement. This module adds the measurement: for every tunable step of a
compiled plan (grouped matmuls, convs, and their einsum-expressible
alternatives), enumerate the candidate (backend, block-shape) points whose
materialized blocks satisfy ``block_contract_ok``, time each candidate
on-device (``block_until_ready``-timed runs, warmup + interquartile mean
over repeats), and re-lower the step to the winner.

Decisions persist in a tuning database under ``results/tune/`` keyed by
``device kind | heuristic plan signature | step name`` — the signature
already encodes the chain name, input shapes and every heuristic dispatch
decision, so any change to shapes, fusion or the heuristic invalidates the
key and the group re-tunes. Subsequent compiles are pure lookups (the
in-process cache makes a warm-cache compile a dict hit per group; the
<5% compile-overhead bound is gated by ``benchmarks/tune_bench.py``).
Entries that fail structural validation are *quarantined* on load — a
corrupted DB can cost a re-measure, never a crash and never a bogus plan
(the ``plan.tuned-contract`` lint rule audits every applied decision).

The search itself is a second consumer of the shared :mod:`repro.search`
engines (the DSE is the first): a :class:`KernelSpace` over candidate
indices, the same seeded strategies, the same budget accounting, the same
trajectory records.

Modes (``compile_chain(tune=...)``):

  * ``"off"``      — heuristic dispatch only (the default).
  * ``"readonly"`` — apply DB hits, keep the heuristic for misses; never
                     measures (the serving/production path).
  * ``"auto"``     — apply DB hits, measure + persist misses.
  * ``"force"``    — re-measure every group and overwrite the DB.
"""
from __future__ import annotations

import json
import os
import time
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.gconv import GConv
from ..kernels.common import block_contract_ok, pick_block, use_interpret
from ..kernels.gconv_matmul import (BLOCK_K, BLOCK_M, BLOCK_N, K_ALIGN,
                                    M_ALIGN, N_ALIGN)
from ..search import STRATEGIES, TrajectoryRecorder
from . import lowering as low

SCHEMA = "repro.tune/v1"
WARMUP = 2          # un-timed runs per candidate (compile + cache warm)
REPEATS = 5         # timed runs per candidate (IQM taken)
MARGIN = 1.25       # a switch must beat the heuristic by this factor
                    # standalone; marginal wins routinely invert inside
                    # the fused whole-chain program (XLA fuses/layouts
                    # the step differently in context)
DEFAULT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "tune")

# dispatch tags a tuned decision may carry (chain-plan groups); serve-level
# groups use "attn:*" / "flags:*" tags — validation is structural, not
# enumerated, so both vocabularies share one DB format
TUNABLE = ("matmul:jnp", "matmul:pallas", "conv:lax", "conv:pallas",
           "einsum")


def default_db_path() -> str:
    return os.path.join(DEFAULT_DIR, "tune_db.json")


def device_key() -> str:
    """DB partition key for the measuring device: the JAX device kind,
    plus the interpret-mode flag — interpret-mode Pallas timings must
    never masquerade as real-kernel timings of the same device."""
    kind = jax.devices()[0].device_kind.replace("|", ";")
    return kind + ("+interpret" if use_interpret() else "")


# ---------------------------------------------------------------------------
# tuning database
# ---------------------------------------------------------------------------
def entry_ok(entry) -> bool:
    """Structural validation of one DB entry; failures are quarantined.
    Geometry-aware validation (does the block satisfy the pick_block
    contract *for this node*?) happens at apply time and is additionally
    audited by the ``plan.tuned-contract`` lint rule."""
    if not isinstance(entry, dict):
        return False
    if not (isinstance(entry.get("backend"), str) and entry["backend"]):
        return False
    block = entry.get("block")
    if block is not None:
        if not isinstance(block, dict) or not block:
            return False
        for a, v in block.items():
            if a not in ("m", "n", "k", "o"):
                return False
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                return False
    lat = entry.get("latency_us")
    if not isinstance(lat, (int, float)) or isinstance(lat, bool):
        return False
    if not (lat > 0 and lat == lat and lat != float("inf")):
        return False
    return True


class TuneDB:
    """Persisted (backend, block) decisions, one JSON file per results
    tree. Load is tolerant by construction: an unreadable file starts an
    empty DB; an entry failing :func:`entry_ok` moves to ``quarantined``
    (kept in the file for inspection) and reads as a miss — the caller
    falls back to the heuristic or re-measures, it never raises."""

    def __init__(self, path: str, entries: Optional[Dict[str, dict]] = None,
                 quarantined: Optional[Dict[str, dict]] = None):
        self.path = path
        self.entries: Dict[str, dict] = dict(entries or {})
        self.quarantined: Dict[str, dict] = dict(quarantined or {})

    @classmethod
    def load(cls, path: str) -> "TuneDB":
        try:
            with open(path) as f:
                raw = json.load(f)
        except (OSError, ValueError):
            return cls(path)
        if not isinstance(raw, dict) or raw.get("schema") != SCHEMA:
            # unknown schema: quarantine wholesale (re-tune, don't guess)
            return cls(path, quarantined={"__file__": {
                "reason": f"unrecognized schema {raw.get('schema')!r}"
                if isinstance(raw, dict) else "non-object DB file"}})
        entries, quarantined = {}, dict(raw.get("quarantined") or {})
        src = raw.get("entries")
        for key, entry in (src.items() if isinstance(src, dict) else ()):
            if entry_ok(entry):
                entries[key] = entry
            else:
                quarantined[key] = {"entry": entry,
                                    "reason": "failed entry validation"}
        return cls(path, entries, quarantined)

    def lookup(self, key: str) -> Optional[dict]:
        entry = self.entries.get(key)
        return entry if entry is not None and entry_ok(entry) else None

    def record(self, key: str, entry: dict) -> None:
        assert entry_ok(entry), entry
        self.entries[key] = entry
        self.quarantined.pop(key, None)

    def save(self) -> None:
        os.makedirs(os.path.dirname(os.path.abspath(self.path)),
                    exist_ok=True)
        with open(self.path, "w") as f:
            json.dump(dict(schema=SCHEMA, entries=self.entries,
                           quarantined=self.quarantined),
                      f, indent=1, sort_keys=True, default=float)


# warm-cache compiles must not re-read JSON per compile: one in-process
# cache keyed by (path, mtime), refreshed by save()
_DB_CACHE: Dict[str, Tuple[Optional[float], TuneDB]] = {}

# ... nor re-lower a switched step per compile: lowered run closures are
# cached per (DB key, decision) and reused when the node is structurally
# identical (GConv dataclass equality covers dims, operand names, ops and
# dtype — everything the lowering reads)
_RUN_CACHE: Dict[Tuple[str, str, str], Tuple[object, Callable]] = {}
_RUN_CACHE_MAX = 512


def load_db(path: Optional[str] = None) -> TuneDB:
    path = path or default_db_path()
    try:
        mtime: Optional[float] = os.path.getmtime(path)
    except OSError:
        mtime = None
    hit = _DB_CACHE.get(path)
    if hit is not None and hit[0] == mtime:
        return hit[1]
    db = TuneDB.load(path)
    _DB_CACHE[path] = (mtime, db)
    return db


def save_db(db: TuneDB) -> None:
    db.save()
    try:
        mtime: Optional[float] = os.path.getmtime(db.path)
    except OSError:
        mtime = None
    _DB_CACHE[db.path] = (mtime, db)


# ---------------------------------------------------------------------------
# candidate space (a repro.search PointSpace over candidate indices)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class KernelSpace:
    """Index space over a group's candidate list — points are ``(i,)``.
    Index 0 is always the heuristic's own choice, so the scorer's
    deterministic tie-break (``min`` over ``(score, point)``) resolves a
    measured tie in the heuristic's favor."""

    n: int

    def sample(self, rng) -> Tuple[int, ...]:
        return (rng.randrange(self.n),)

    def mutate(self, point, rng, n_fields: int = 1) -> Tuple[int, ...]:
        if self.n <= 1:
            return point
        j = rng.randrange(self.n - 1)
        if j >= point[0]:
            j += 1
        return (j,)

    def crossover(self, a, b, rng) -> Tuple[int, ...]:
        return a if rng.random() < 0.5 else b


def measured_select(n: int, measure: Callable[[int], float], *,
                    budget: int, seed: int = 0,
                    strategy: str = "random") -> Tuple[int, float, "object"]:
    """Pick the candidate index minimizing ``measure(i)`` (seconds) with a
    shared-strategy search over :class:`KernelSpace`; returns
    ``(winner_index, winner_seconds, SearchResult)``. ``budget`` is capped
    at ``n`` — a full enumeration when affordable, a seeded subset when
    not. Index 0 (the heuristic) is always measured first."""
    space = KernelSpace(n)
    res = STRATEGIES[strategy]().run(
        space, lambda p: measure(p[0]), min(max(1, budget), n),
        seed=seed, seeds=[(0,)])
    return res.best[0], res.best_score, res


# ---------------------------------------------------------------------------
# per-group candidates + measured objective
# ---------------------------------------------------------------------------
def _matmul_blocks(M: int, N: int, K: int) -> List[Dict[str, int]]:
    """Materialized (bm, bn, bk) candidates around the static defaults —
    every emitted block satisfies ``block_contract_ok`` by construction
    (same ``min(target, pick_block(...))`` form the lint audit uses)."""
    out, seen = [], set()
    for tm in (128, BLOCK_M):
        for tn in (128, BLOCK_N):
            for tk in (256, BLOCK_K):
                bm = min(tm, pick_block(M, tm, M_ALIGN))
                bn = min(tn, pick_block(N, tn, N_ALIGN))
                bk = min(tk, pick_block(K, tk, K_ALIGN))
                if (bm, bn, bk) not in seen:
                    seen.add((bm, bn, bk))
                    out.append(dict(m=bm, n=bn, k=bk))
    return out


def _conv_blocks(O: int) -> List[Dict[str, int]]:
    out, seen = [], set()
    for to in (64, 128, 256):
        bo = max(1, min(to, O))
        if bo not in seen:
            seen.add(bo)
            out.append(dict(o=bo))
    return out


@dataclass
class _Group:
    """One tunable step: classification + lowering plans, with the
    candidate list built lazily — the warm-compile (DB hit) path only
    needs :meth:`legal` and :meth:`lower`, never the enumeration."""

    name: str
    node: GConv
    heuristic: str
    classes: Tuple[str, ...] = ()
    mplan: object = None
    cplan: object = None
    einsum_ok: bool = False
    pallas_ok: bool = False
    _cands: Optional[List[Tuple[str, Optional[Dict[str, int]]]]] = None

    @property
    def geometry(self) -> Tuple[int, ...]:
        """(M, N, K) for matmul groups, (O,) for conv groups."""
        if self.mplan is not None:
            g_ix, m_ix, c_ix = self.mplan
            dims = self.node.dims
            M = (int(np.prod([dims[i].in_size for i in m_ix]))
                 if m_ix else 1)
            K = int(np.prod([dims[i].nks for i in c_ix])) if c_ix else 1
            N = int(np.prod([dims[i].nop for i in c_ix])) if c_ix else 1
            return M, N, K
        return (self.node.dims[self.cplan[0]].nop,)

    @property
    def candidates(self) -> List[Tuple[str, Optional[Dict[str, int]]]]:
        if self._cands is not None:
            return self._cands
        cands: List[Tuple[str, Optional[Dict[str, int]]]] = []
        if self.mplan is not None:
            M, N, K = self.geometry
            cands.append(("matmul:jnp", None))
            if self.pallas_ok:
                cands += [("matmul:pallas", b)
                          for b in _matmul_blocks(M, N, K)]
            if self.einsum_ok:
                cands.append(("einsum", None))
        elif self.cplan is not None:
            cands.append(("conv:lax", None))
            if self.pallas_ok:
                cands += [("conv:pallas", b)
                          for b in _conv_blocks(self.geometry[0])
                          if low.lower_conv_pallas(self.node, self.cplan,
                                                   block_o=b["o"])
                          is not None]
            if self.einsum_ok:
                cands.append(("einsum", None))
        # heuristic first: measured ties resolve to the incumbent
        h_ix = next((i for i, (t, _b) in enumerate(cands)
                     if t == self.heuristic), 0)
        if cands:
            cands.insert(0, cands.pop(h_ix))
        self._cands = cands
        return cands

    def legal(self, tag: str, block: Optional[Dict[str, int]]) -> bool:
        """Is a (possibly DB-recalled) decision still a sound lowering of
        this node here? Cheap direct checks — no candidate enumeration —
        mirroring what the ``plan.tuned-contract`` lint rule audits."""
        if tag == "matmul:jnp":
            return self.mplan is not None and block is None
        if tag == "matmul:pallas":
            if self.mplan is None or not self.pallas_ok:
                return False
            if block is None:
                return True
            if sorted(block) != ["k", "m", "n"]:
                return False
            M, N, K = self.geometry
            return (block_contract_ok(M, block["m"], M_ALIGN)
                    and block_contract_ok(N, block["n"], N_ALIGN)
                    and block_contract_ok(K, block["k"], K_ALIGN))
        if tag == "conv:lax":
            return self.cplan is not None and block is None
        if tag == "conv:pallas":
            if self.cplan is None or not self.pallas_ok:
                return False
            if block is not None and not (sorted(block) == ["o"]
                                          and 1 <= block["o"]):
                return False
            return low.lower_conv_pallas(
                self.node, self.cplan,
                block_o=block["o"] if block else None) is not None
        if tag == "einsum":
            return self.einsum_ok and block is None
        return False

    def lower(self, tag: str, block: Optional[Dict[str, int]]) -> Callable:
        if tag == "matmul:jnp":
            return low.lower_grouped_matmul(self.node, self.mplan)
        if tag == "matmul:pallas":
            blk = (block["m"], block["n"], block["k"]) if block else None
            return low.lower_grouped_matmul(self.node, self.mplan,
                                            pallas=True, block=blk)
        if tag == "conv:lax":
            return low.lower_conv(self.node, self.cplan)
        if tag == "conv:pallas":
            fn = low.lower_conv_pallas(self.node, self.cplan,
                                       block_o=block["o"] if block else None)
            assert fn is not None, "conv:pallas candidate without geometry"
            return fn
        if tag == "einsum":
            return low.lower_einsum(self.node, self.classes)
        raise ValueError(f"untunable tag {tag!r}")


def _group_for(step, chain) -> Optional[_Group]:
    """Build the group for one plan step, or None when the step is not
    tunable (non-GConv, segment, or no alternative lowering exists).

    Pallas candidates are only offered where the kernels actually compile
    to Mosaic — in interpret mode (any non-TPU backend) they are a
    correctness tool, never a performance candidate."""
    if step.backend not in TUNABLE:
        return None
    node = chain.nodes.get(step.name)
    if not isinstance(node, GConv):
        return None
    classes = low.dim_classes(node)
    k_shape = (tuple(chain.shape_of(node.kernel))
               if node.kernel is not None else None)
    g = _Group(step.name, node, step.backend, classes,
               einsum_ok=low.GENERAL not in classes,
               pallas_ok=not use_interpret())
    if step.backend.startswith("matmul:"):
        g.mplan = low.match_grouped_matmul(node, classes, k_shape)
        if g.mplan is None:
            return None
    elif step.backend.startswith("conv:"):
        g.cplan = low.match_conv(node, classes, k_shape)
        if g.cplan is None:
            return None
    else:                                # einsum heuristic: need a plan to
        g.mplan = low.match_grouped_matmul(node, classes, k_shape)
        g.cplan = (low.match_conv(node, classes, k_shape)
                   if g.mplan is None else None)
        if g.mplan is None and g.cplan is None:
            return None
    return g


def _synth_names(chain, names, seed: int = 0):
    """Deterministic measurement operands at the chain's declared shapes
    (inputs, params and intermediate producers all resolve through
    ``chain.shape_of``)."""
    rng = np.random.default_rng(seed)
    env = {}
    for name in names:
        if name in env:
            continue
        shape = tuple(chain.shape_of(name))
        info = chain.inputs.get(name) or chain.params.get(name)
        if info is not None:
            dtype = info.dtype
        else:
            src = chain.nodes.get(name)
            dtype = (getattr(src, "out_dtype", None) or "float32")
        if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
            env[name] = jnp.zeros(shape, dtype)
        else:
            env[name] = jnp.asarray(
                0.1 * rng.standard_normal(shape), dtype)
    return env


def _synth_env(chain, group: _Group, seed: int = 0):
    """Measurement operands for one group's step in isolation."""
    node = group.node
    names = [node.input]
    if node.kernel is not None:
        names.append(node.kernel)
    for op in tuple(node.pre) + tuple(node.post):
        if op.operand is not None:
            names.append(op.operand)
    return _synth_names(chain, names, seed)


def _iqm(ts: List[float]) -> float:
    ts = sorted(ts)
    q = len(ts) // 4
    mid = ts[q:len(ts) - q] or ts
    return sum(mid) / len(mid)


def measure_callable(fn: Callable, *args, warmup: int = WARMUP,
                     repeats: int = REPEATS) -> float:
    """Device-synced wall seconds for one jitted callable: ``warmup``
    un-timed runs (trace + XLA compile + cache warm), then the
    interquartile mean over ``repeats`` ``block_until_ready``-timed
    runs."""
    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return _iqm(ts)


# ---------------------------------------------------------------------------
# plan tuning driver
# ---------------------------------------------------------------------------
def _tuned_meta(tag: str, block, source: str, group: str,
                latency_us: float, heuristic_us: Optional[float]) -> dict:
    return dict(backend=tag, block=dict(block) if block else None,
                source=source, group=group,
                latency_us=latency_us, heuristic_us=heuristic_us)


def _blk_token(block) -> str:
    return "" if not block else repr(sorted(block.items()))


def _cache_run(key: str, tag: str, block, node, run) -> None:
    if len(_RUN_CACHE) >= _RUN_CACHE_MAX:
        _RUN_CACHE.clear()
    _RUN_CACHE[(key, tag, _blk_token(block))] = (node, run)


def _apply(step, group: _Group, tag: str, block, meta: dict, dispatch):
    if tag != step.backend or block is not None:
        from .dispatch import _gconv_step
        step.run = _gconv_step(group.node, group.lower(tag, block))
        step.backend = tag
    step.meta = dict(step.meta or {})
    step.meta["tuned"] = meta
    dispatch[group.name] = tag


def _validate_e2e(chain, plan, orig_runs: Dict[str, Callable], *,
                  seed: int, warmup: int,
                  repeats: int) -> Tuple[bool, float, float]:
    """Whole-plan arbitration for this compile's measured switches.

    Per-group wall times are blind to cross-step fusion and layout
    effects — a backend that wins standalone can lose once XLA sees the
    step inside the full program. Measure the tuned plan against the
    heuristic plan (switched steps restored from ``orig_runs``)
    end-to-end on synthetic operands; the caller reverts every switch
    when the tuned plan is not faster. Returns
    ``(keep, heuristic_us, tuned_us)``."""
    env = _synth_names(chain, list(chain.inputs) + list(chain.params),
                       seed)
    outs = chain.outputs or [list(chain.nodes)[-1]]

    def runner(use_orig: bool):
        def run(e):
            e = dict(e)
            for st in plan.steps:
                fn = (orig_runs.get(st.name, st.run) if use_orig
                      else st.run)
                e[st.name] = fn(e)
            return [e[o] for o in outs]
        return jax.jit(run)

    tuned_s = measure_callable(runner(False), env, warmup=warmup,
                               repeats=repeats)
    heur_s = measure_callable(runner(True), env, warmup=warmup,
                              repeats=repeats)
    return (tuned_s <= heur_s, round(heur_s * 1e6, 3),
            round(tuned_s * 1e6, 3))


def _signature(plan, chain) -> str:
    """The heuristic signature with tuned block choices appended to the
    per-step backend tokens — equal-signature engines run the same tuned
    program."""
    base = plan.signature.rsplit("|", 1)[0]
    toks = []
    for s in plan.steps:
        tok = f"{s.name}={s.backend}"
        tuned = (s.meta or {}).get("tuned")
        if tuned and tuned.get("block"):
            tok += "@" + ",".join(f"{a}{v}" for a, v
                                  in sorted(tuned["block"].items()))
        toks.append(tok)
    return f"{base}|{';'.join(toks)}"


def tune_plan(chain, plan, *, mode: str = "auto",
              db_path: Optional[str] = None, budget: int = 16,
              seed: int = 0, strategy: str = "random",
              backend: str = "auto", warmup: int = WARMUP,
              repeats: int = REPEATS, tracer=None) -> Tuple[object, dict]:
    """Tune a compiled plan in place (steps re-lowered to the winning
    (backend, block), ``Step.meta['tuned']`` recorded, signature extended)
    and return ``(plan, report)``.

    ``chain`` is the FUSED chain the plan was built from. ``backend``
    forwards the compile option: a forced backend restricts candidates to
    that backend's family (block-only tuning); ``"auto"`` tunes across
    backends. Measurement spans land on ``tracer`` (`repro.obs`) when one
    is given."""
    if mode not in ("readonly", "auto", "force"):
        raise ValueError(f"tune mode {mode!r}: want readonly|auto|force")
    from ..obs import Metrics
    reg = Metrics()
    db = load_db(db_path)
    dev = device_key()
    base_sig = plan.signature
    report = dict(mode=mode, device=dev, db_path=db.path, groups={},
                  measured=0, from_db=0, kept_heuristic=0)
    dirty = False
    # freshly-measured switches pending whole-plan validation:
    # (step, group, db key, db entry, original run, original backend)
    switched: List[tuple] = []
    fam = {"jnp": ("matmul:jnp", "conv:lax", "einsum"),
           "pallas": ("matmul:pallas", "conv:pallas")}.get(backend)
    for step in plan.steps:
        if step.backend not in TUNABLE:
            continue
        key = f"{dev}|{base_sig}|{step.name}"
        entry = db.lookup(key) if mode != "force" else None
        if (entry is not None and entry["backend"] == step.backend
                and entry.get("block") is None
                and (fam is None or entry["backend"] in fam)):
            # kept-heuristic decision (the warm path's common case): the
            # step is already lowered exactly this way, so no group
            # geometry or legality probe is needed — annotate and move on
            meta = _tuned_meta(entry["backend"], None, "db", step.name,
                               entry["latency_us"],
                               entry.get("heuristic_us"))
            step.meta = dict(step.meta or {})
            step.meta["tuned"] = meta
            plan.dispatch[step.name] = step.backend
            report["from_db"] += 1
            report["groups"][step.name] = meta
            continue
        if entry is not None and (fam is None or entry["backend"] in fam):
            # switched decision already lowered this process for a
            # structurally identical node: reuse the run closure (the
            # decision was legality-checked when the cache was filled)
            cached = _RUN_CACHE.get((key, entry["backend"],
                                     _blk_token(entry.get("block"))))
            if cached is not None and cached[0] == chain.nodes.get(
                    step.name):
                meta = _tuned_meta(entry["backend"], entry.get("block"),
                                   "db", step.name, entry["latency_us"],
                                   entry.get("heuristic_us"))
                step.run = cached[1]
                step.backend = entry["backend"]
                step.meta = dict(step.meta or {})
                step.meta["tuned"] = meta
                plan.dispatch[step.name] = entry["backend"]
                report["from_db"] += 1
                report["groups"][step.name] = meta
                continue
        group = _group_for(step, chain)
        if group is None:
            continue
        if entry is not None:
            tag_ok = fam is None or entry["backend"] in fam
            if not tag_ok or not group.legal(entry["backend"],
                                             entry.get("block")):
                entry = None          # decision no longer a legal lowering
        if entry is not None:
            meta = _tuned_meta(entry["backend"], entry.get("block"), "db",
                               step.name, entry["latency_us"],
                               entry.get("heuristic_us"))
            _apply(step, group, entry["backend"], entry.get("block"), meta,
                   plan.dispatch)
            _cache_run(key, entry["backend"], entry.get("block"),
                       group.node, step.run)
            report["from_db"] += 1
            report["groups"][step.name] = meta
            continue
        if mode == "readonly":
            report["kept_heuristic"] += 1
            continue
        # ---- measure -----------------------------------------------------
        if fam is not None:           # forced backend: family-only tuning
            group._cands = [c for c in group.candidates if c[0] in fam]
        if len(group.candidates) < 2:
            continue
        env = _synth_env(chain, group, seed=seed)
        from .dispatch import _gconv_step
        times: Dict[int, float] = {}

        def _measure(i: int, _g=group, _env=env, _times=times) -> float:
            tag, block = _g.candidates[i]
            run = jax.jit(_gconv_step(_g.node, _g.lower(tag, block)))
            s = measure_callable(run, _env, warmup=warmup, repeats=repeats)
            _times[i] = s
            reg.counter("tune_measurements", group=_g.name).inc()
            reg.histogram("tune_candidate_us",
                          buckets=[10, 100, 1000, 10000, 100000],
                          backend=tag).observe(s * 1e6)
            return s

        span = (tracer.span(f"tune:{step.name}", cat="tune",
                            attrs={"candidates": len(group.candidates)})
                if tracer is not None else nullcontext())
        with span:
            win, win_s, res = measured_select(
                len(group.candidates), _measure, budget=budget, seed=seed,
                strategy=strategy)
        tag, block = group.candidates[win]
        heur_s = times.get(0)
        rejected = None
        if win != 0 and heur_s is not None and heur_s < win_s * MARGIN:
            # not a decisive standalone win: keep the incumbent (see
            # MARGIN — marginal wins tend to invert in fused context)
            rejected = dict(backend=tag,
                            block=dict(block) if block else None,
                            latency_us=round(win_s * 1e6, 3),
                            reason="margin")
            win, win_s = 0, heur_s
            tag, block = group.candidates[0]
        recorder = TrajectoryRecorder(metric="latency_us")
        recorder.extend([s * 1e6 for _p, s in res.history])
        from ..obs import provenance
        entry = dict(backend=tag, block=dict(block) if block else None,
                     latency_us=round(win_s * 1e6, 3),
                     heuristic_us=(round(heur_s * 1e6, 3)
                                   if heur_s is not None else None),
                     heuristic_backend=group.heuristic,
                     n_candidates=len(group.candidates),
                     n_evals=res.n_evals, strategy=res.strategy,
                     trajectory=recorder.to_json(group=step.name),
                     provenance=provenance())
        if rejected is not None:
            entry["rejected"] = rejected
        if tag != step.backend or block is not None:
            switched.append((step, group, key, entry, step.run,
                             step.backend))
        db.record(key, entry)
        dirty = True
        meta = _tuned_meta(tag, block, "measured", step.name,
                           entry["latency_us"], entry["heuristic_us"])
        _apply(step, group, tag, block, meta, plan.dispatch)
        _cache_run(key, tag, block, group.node, step.run)
        report["measured"] += 1
        report["groups"][step.name] = meta
    if switched:
        keep, heur_us, tuned_us = _validate_e2e(
            chain, plan, {st.name: run for st, _g, _k, _e, run, _b
                          in switched},
            seed=seed, warmup=warmup, repeats=max(repeats, 7))
        report["e2e"] = dict(heuristic_us=heur_us, tuned_us=tuned_us,
                             kept=keep)
        if not keep:
            for step, group, key, entry, orig_run, orig_backend \
                    in switched:
                step.run = orig_run
                step.backend = orig_backend
                plan.dispatch[group.name] = orig_backend
                lat = entry["heuristic_us"] or entry["latency_us"]
                meta = _tuned_meta(orig_backend, None, "e2e-reject",
                                   step.name, lat, entry["heuristic_us"])
                step.meta["tuned"] = meta
                report["groups"][step.name] = meta
                db.record(key, dict(
                    entry, backend=orig_backend, block=None,
                    latency_us=lat,
                    rejected=dict(backend=entry["backend"],
                                  block=entry["block"],
                                  latency_us=entry["latency_us"],
                                  reason="e2e",
                                  heuristic_e2e_us=heur_us,
                                  tuned_e2e_us=tuned_us)))
    if dirty:
        save_db(db)
    plan.signature = _signature(plan, chain)
    report["signature"] = plan.signature
    report["metrics"] = reg.to_dict()
    return plan, report
