"""The compiled GCONV-chain execution engine.

``compile_chain`` turns a :class:`~repro.core.chain.Chain` into a
:class:`CompiledChain`: §4.3 fusion partitions the chain into fusion
groups (``exec.partition``), each group is dispatched to its best backend
(``exec.dispatch`` / ``exec.lowering``) and the whole program is emitted as
ONE jitted function — Movement/Concat nodes lower to metadata-only
reshape/transpose inside the same XLA program, so intermediates never make
the per-node round trip the oracle interpreter pays for.

The engine is differentially tested allclose against
:class:`~repro.core.interpreter.ChainExecutor` on the full CNN zoo and the
LM chain segments (tests/test_exec.py), and benchmarked against it per zoo
network (``python -m benchmarks.run --only exec``).

Usage mirrors the oracle::

    eng = compile_chain(chain)
    params = eng.init_params(jax.random.PRNGKey(0))
    outs = eng(inputs, params)            # dict of chain outputs
    eng.dispatch                          # node -> backend table

Mesh-aware mode: ``compile_chain(chain, mesh=mesh)`` derives a per-chain
:class:`~repro.exec.shardplan.ShardPlan` (data-parallel leading batch
axis, tensor-parallel grouped matmuls, divisibility-guarded fallback to
replication — the same policy as ``launch/sharding.py`` via
``repro.shardpolicy``) and compiles the SAME program against the mesh:
exact-shape calls jit with the plan's in-shardings and run the
tensor-parallel-wrapped steps; batched calls shard the leading bucket
axis over the data bundle (the bucket floor rises to the data-axis size
so every bucket divides). Differentially tested against the single-device
engine on faked host devices (tests/test_exec_sharded.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional

import jax
import jax.numpy as jnp

from ..core.chain import Chain
from ..core.fusion import ExecGroup, FusionReport
from .batch import BucketedCache, batch_bucket, pad_leading, unpad_leading
from .dispatch import Plan, plan_chain
from .partition import partition_chain


@dataclass(frozen=True)
class CompileOptions:
    fuse: bool = True            # run §4.3 operation fusion first
    segments: bool = True        # recognize softmax/norm/attention segments
    backend: str = "auto"        # auto | jnp | pallas
    mxu_min: int = 128           # min K/N to prefer the Pallas matmul (auto)
    jit: bool = True
    profile: bool = False        # per-step timed spans into a repro.obs
                                 # tracer (see CompiledChain docstring)
    lint: Optional[str] = None   # off|info|warn|error: run the repro.lint
                                 # passes post-compile and raise LintError
                                 # at/above that severity. None reads the
                                 # REPRO_LINT env var (tests default it to
                                 # "error" in conftest.py; "off" elsewhere)
    tune: str = "off"            # off|readonly|auto|force: measured
                                 # (backend, block) selection per tunable
                                 # step against the persisted tuning DB
                                 # (repro.exec.tune; "readonly" never
                                 # measures, "force" always re-measures)
    tune_db: Optional[str] = None    # DB path; None -> results/tune/
    tune_budget: int = 16        # max measured candidates per step


class CompiledChain:
    """A chain compiled to one jitted function (plus introspection)."""

    def __init__(self, source: Chain, chain: Chain, report: FusionReport,
                 partitions: List[ExecGroup], plan: Plan,
                 options: CompileOptions, shard_plan=None, tracer=None):
        self.source = source
        self.chain = chain                   # the fused chain actually run
        self.fusion_report = report
        self.partitions = partitions
        self._plan = plan
        self.steps = plan.steps
        self.dispatch: Dict[str, str] = plan.dispatch
        self.options = options
        self.lint_report = None          # set by compile_chain when linted
        self.tune_report = None          # set by compile_chain when tuned
        # mesh-aware mode: the ShardPlan plus the step list with the
        # tensor-parallel matmuls re-lowered to their column/row split
        self.shard_plan = shard_plan
        self.mesh = shard_plan.mesh if shard_plan is not None else None
        if shard_plan is not None:
            from .shardplan import wrap_steps
            self._steps_sharded = wrap_steps(chain, self.steps, shard_plan)
            self._min_bucket = shard_plan.dp_size
        else:
            self._steps_sharded = self.steps
            self._min_bucket = 1
        self._fns: Dict[bool, object] = {}
        # leading-batch execution: one vmapped program per (keep_all,
        # batch bucket), cached per engine (exec.batch.BucketedCache)
        self._batched = BucketedCache(self._build_batched)
        # profiling (repro.obs): per-step jitted programs so each fusion-
        # group step can be timed device-synced. The DISABLED path costs
        # exactly one flag check in __call__ — no tracer object, span or
        # dict is ever allocated unless profiling is live.
        self._profile = options.profile
        self.tracer = None
        if options.profile:
            from ..obs.trace import Tracer
            self.tracer = tracer if tracer is not None else Tracer()
            self._step_fns: Dict[str, object] = {}

    # -- parameter init (the oracle's own recipe, shared) ---------------
    def init_params(self, key, scale: float = 0.1) -> Dict[str, jnp.ndarray]:
        from ..core.interpreter import init_chain_params
        return init_chain_params(self.chain, key, scale)

    # -- execution ------------------------------------------------------
    def _execute(self, inputs, params, keep_all: bool, steps=None):
        """``keep_all`` mirrors the oracle's contract (the whole
        environment: inputs, params and every produced node) — except
        that §4.3-fused members and segment-interior nodes do not exist
        in the compiled program and therefore have no entry (that is the
        point of fusing them; see ``dispatch`` for the ``fused:`` tags)."""
        env: Dict[str, jnp.ndarray] = dict(inputs)
        env.update(params)
        for step in (self.steps if steps is None else steps):
            env[step.name] = step.run(env)
        if keep_all:
            return env
        outs = self.chain.outputs or [list(self.chain.nodes)[-1]]
        return {o: env[o] for o in outs}

    def _fn(self, keep_all: bool):
        fn = self._fns.get(keep_all)
        if fn is None:
            if self.shard_plan is not None:
                run = (lambda inputs, params, _k=keep_all:
                       self._execute(inputs, params, _k,
                                     self._steps_sharded))
                if self.options.jit:
                    run = jax.jit(run, in_shardings=(
                        self.shard_plan.input_shardings(),
                        self.shard_plan.param_shardings()))
                fn = run
            elif self.options.jit:
                fn = jax.jit(
                    lambda inputs, params, _k=keep_all:
                    self._execute(inputs, params, _k))
            else:
                fn = (lambda inputs, params, _k=keep_all:
                      self._execute(inputs, params, _k))
            self._fns[keep_all] = fn
        return fn

    def _build_batched(self, key):
        keep_all, bucket = key           # bucket fixes the traced shape;
        run = (lambda ins, ps, _k=keep_all:   # one compile per cache entry
               self._execute(ins, ps, _k))
        fn = jax.vmap(run, in_axes=(0, None))
        if not self.options.jit:
            return fn
        if self.shard_plan is not None:
            # data-parallel replicas over the bucket axis: the tensor-
            # parallel step rewrites stay out of the vmapped program — the
            # mesh's contribution here is the leading-axis sharding (the
            # bucket floor is the dp size, so the axis always divides)
            return jax.jit(fn, in_shardings=(
                self.shard_plan.batched_input_shardings(self.chain, bucket),
                self.shard_plan.param_shardings()))
        return jax.jit(fn)

    def _batch_size(self, ins: Dict[str, jnp.ndarray]) -> Optional[int]:
        """None for exact chain shapes; N when every input carries one
        extra leading batch axis of the same size N (the batched mode)."""
        exact = all(tuple(a.shape) == self.chain.inputs[n].shape
                    for n, a in ins.items())
        if exact:
            return None
        sizes = set()
        for name, arr in ins.items():
            want = self.chain.inputs[name].shape
            if arr.ndim != len(want) + 1 or tuple(arr.shape[1:]) != want:
                raise ValueError(
                    f"input {name!r}: got {arr.shape}, want {want} or "
                    f"batch-extended (N,)+{want}")
            sizes.add(arr.shape[0])
        if len(sizes) != 1:
            raise ValueError(
                f"inconsistent leading batch sizes {sorted(sizes)}")
        return sizes.pop()

    # -- profiled execution (repro.obs) ---------------------------------
    def _step_fn(self, step):
        """Per-step jitted program (profile mode runs steps one by one so
        each can be block_until_ready-timed; the single fused program of
        the fast path cannot attribute time to its interior)."""
        fn = self._step_fns.get(step.name)
        if fn is None:
            run = step.run
            fn = jax.jit(run) if self.options.jit else run
            self._step_fns[step.name] = fn
        return fn

    def _profiled(self, ins, ps, keep_all):
        """Exact-shape execution with one device-synced span per fusion-
        group step, attributed with the step's backend tag and the plan
        signature. The first run of a step is recorded under cat
        ``compile`` (trace + XLA compile + execute), steady-state runs
        under cat ``execute`` — so compile time never pollutes the
        execute-time attribution. The loop keeps only two clock reads of
        bookkeeping per step and defers event construction until after
        the enclosing chain span closes, so >= 95% of the chain span's
        wall time is attributed to named steps (the report CLI's
        ``profile.coverage``)."""
        import time as _time

        tr = self.tracer
        sig = self._plan.signature
        env: Dict[str, jnp.ndarray] = dict(ins)
        env.update(ps)
        steps = self._steps_sharded
        step_fns = self._step_fns
        marks = []
        with tr.span(f"chain:{self.chain.name}", cat="chain",
                     attrs={"signature": sig,
                            "steps": len(steps)}) as chain_span:
            for step in steps:
                compiled = step.name in step_fns
                fn = step_fns[step.name] if compiled else self._step_fn(step)
                t0 = _time.perf_counter()
                out = jax.block_until_ready(fn(env))
                t1 = _time.perf_counter()
                env[step.name] = out
                marks.append((step, compiled, t0, t1))
        parent = getattr(chain_span, "id", None)
        for step, compiled, t0, t1 in marks:
            tr.add_span(step.name, "execute" if compiled else "compile",
                        t0, t1, parent=parent,
                        attrs={"backend": step.backend, "signature": sig})
        if keep_all:
            return env
        outs = self.chain.outputs or [list(self.chain.nodes)[-1]]
        return {o: env[o] for o in outs}

    def __call__(self,
                 inputs: Mapping[str, jnp.ndarray],
                 params: Optional[Mapping[str, jnp.ndarray]] = None,
                 keep_all: bool = False) -> Dict[str, jnp.ndarray]:
        params = params or {}
        ins = {}
        for name in self.chain.inputs:
            if name not in inputs:
                raise ValueError(f"missing chain input {name!r}")
            ins[name] = jnp.asarray(inputs[name])
        ps = {}
        for name in self.chain.params:
            if name not in params:
                raise ValueError(f"missing chain param {name!r}")
            ps[name] = jnp.asarray(params[name])
        n = self._batch_size(ins)
        profiling = self._profile and self.tracer.enabled
        if n is None:
            if profiling:
                return self._profiled(ins, ps, keep_all)
            return dict(self._fn(keep_all)(ins, ps))
        bucket = batch_bucket(n, self._min_bucket)
        if profiling:
            # batched programs are one fused vmap: attribute the call as a
            # whole (per-step attribution is an exact-shape-mode feature)
            with self.tracer.span(f"batched:{self.chain.name}", cat="chain",
                                  attrs={"backend": "batched", "n": n,
                                         "bucket": bucket,
                                         "signature":
                                             self._plan.signature}):
                fn = self._batched.get((keep_all, bucket))
                out = jax.block_until_ready(fn(pad_leading(ins, bucket), ps))
            return dict(unpad_leading(out, n))
        fn = self._batched.get((keep_all, bucket))
        out = fn(pad_leading(ins, bucket), ps)
        return dict(unpad_leading(out, n))

    # -- batched-mode introspection -------------------------------------
    @property
    def batch_compiles(self) -> int:
        """Distinct batched programs compiled so far (== #buckets seen)."""
        return self._batched.compiles

    @property
    def batch_buckets(self):
        return sorted({b for _k, b in self._batched.keys()})

    @property
    def signature(self) -> str:
        """Stable program identity (chain name + input shapes + dispatch
        decisions, plus the mesh and tensor-parallel splits when sharded);
        introspection/reporting metadata — equal-signature engines run the
        same program."""
        sig = self._plan.signature
        if self.shard_plan is not None:
            mesh_s = "x".join(f"{a}{n}"
                              for a, n in self.shard_plan.mesh.shape.items())
            tp_s = ",".join(f"{n}={m}"
                            for n, m in sorted(self.shard_plan.step_tp.items()))
            sig += f"|mesh={mesh_s}|tp={tp_s}"
        return sig

    # -- introspection --------------------------------------------------
    def backend_histogram(self) -> Dict[str, int]:
        hist: Dict[str, int] = {}
        for tag in self.dispatch.values():
            key = tag.split(":")[0] if tag.startswith("fused") else tag
            hist[key] = hist.get(key, 0) + 1
        return hist

    def pretty(self) -> str:
        lines = [f"CompiledChain {self.chain.name!r}: "
                 f"{len(self.steps)} steps from {len(self.source.nodes)} "
                 f"nodes (fusion {self.fusion_report.before_len}->"
                 f"{self.fusion_report.after_len})"]
        for name, tag in self.dispatch.items():
            lines.append(f"  {name}: {tag}")
        return "\n".join(lines)


def compile_chain(chain: Chain, mesh=None, tracer=None,
                  **options) -> CompiledChain:
    """Compile a chain for execution. See :class:`CompileOptions`.

    ``mesh``: a ``jax.sharding.Mesh`` to compile a SHARDED program against
    (see the module docstring); ``None`` keeps the single-device engine.

    ``profile=True``: wrap each fusion-group step in a device-synced timed
    span recorded into ``engine.tracer`` (a fresh ``repro.obs.trace.
    Tracer`` unless ``tracer=`` is given) — backend + plan-signature
    attributed, compile events separate from execute events; export with
    ``engine.tracer.write(path)`` and summarize with ``python -m
    repro.obs.report``. With the default ``profile=False`` the hot path
    is untouched beyond one flag check per call.

    ``lint="error"``: run the `repro.lint` static passes over the compiled
    artifacts (chain + plan + shard plan) and raise
    :class:`~repro.lint.LintError` on findings at/above the given
    severity; the full report lands on ``engine.lint_report`` either way.
    ``lint=None`` (default) reads the ``REPRO_LINT`` env var ("off" when
    unset; conftest.py defaults it to "error" so every test-compiled
    chain is verified).

    ``tune="auto"``: after heuristic planning, re-lower each tunable step
    to the measured-fastest (backend, block) candidate — DB hits under
    ``results/tune/`` are pure lookups, misses are measured on-device and
    persisted (see :mod:`repro.exec.tune`). ``tune="readonly"`` applies
    hits but never measures; ``tune="force"`` re-measures everything. The
    decisions land in ``Step.meta['tuned']`` (audited by the
    ``plan.tuned-contract`` lint rule), the per-group report on
    ``engine.tune_report``.
    """
    import os

    from .shardplan import plan_backend

    opts = CompileOptions(**options)
    chain.validate()
    fused, report, parts = partition_chain(chain, fuse=opts.fuse)
    backend = plan_backend(opts.backend, mesh)
    plan = plan_chain(fused, backend=backend, mxu_min=opts.mxu_min,
                      segments=opts.segments)
    tune_report = None
    if opts.tune != "off":
        from .tune import tune_plan
        plan, tune_report = tune_plan(
            fused, plan, mode=opts.tune, db_path=opts.tune_db,
            budget=opts.tune_budget, backend=backend, tracer=tracer)
    shard_plan = None
    if mesh is not None and not mesh.empty:
        from .shardplan import derive_plan
        shard_plan = derive_plan(fused, plan.dispatch, mesh)
    # §4.3-fused nodes no longer exist in the fused chain; record them in
    # the dispatch table so every ORIGINAL node has an entry
    for host, members in report.groups.items():
        for m in members:
            plan.dispatch.setdefault(m, f"fused:{host}")
    eng = CompiledChain(chain, fused, report, parts, plan, opts,
                        shard_plan, tracer)
    eng.tune_report = tune_report
    level = opts.lint if opts.lint is not None \
        else os.environ.get("REPRO_LINT", "off")
    if level and level != "off":
        from ..lint import LintError, lint_compiled
        eng.lint_report = lint_compiled(eng)
        if eng.lint_report.at_least(level):
            raise LintError(eng.lint_report, level)
    return eng
