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

import re
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass
from typing import Dict, List, Mapping, Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from ..core.chain import Chain
from ..core.fusion import ExecGroup, FusionReport
from ..core.gconv import GConv
from ..obs import compiles
from ..obs.metrics import Metrics
from .batch import BucketedCache, batch_bucket, pad_leading, unpad_leading
from .dispatch import Plan, plan_chain
from .lowering import dim_classes, is_depthwise, match_conv, window_fold
from .partition import partition_chain


@dataclass(frozen=True)
class CompileOptions:
    fuse: bool = True            # run §4.3 operation fusion first
    segments: bool = True        # recognize softmax/norm/attention segments
    backend: str = "auto"        # auto | jnp | pallas
    mxu_min: int = 128           # min K/N to prefer the Pallas matmul (auto)
    jit: bool = True
    profile: bool = False        # record the engine's spans into a
                                 # repro.obs tracer (see compile_chain)
    lint: Optional[str] = None   # off|info|warn|error: run the repro.lint
                                 # passes post-compile and raise LintError
                                 # at/above that severity. None reads the
                                 # REPRO_LINT env var (tests default it to
                                 # "error" in conftest.py; "off" elsewhere)


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
        # observability (repro.obs): the spans of __call__ go into
        # ``tracer`` while it is enabled; ``metrics`` holds each program's
        # build counters and the phase seconds of timed calls, and, counted
        # once here, the reduce steps by how their window dims fold
        # (lowering.window_fold), the depthwise convs by backend and the
        # spatial-kernel convs whose output width the kernel pads
        self.tracer = tracer
        self.metrics = Metrics()
        folds = [window_fold(chain.nodes[s.name]) for s in self.steps
                 if s.backend == "reduce"]
        self.metrics.counter("engine_reduce_window_steps").inc(
            folds.count("reduce_window"))
        self.metrics.counter("engine_slice_window_steps").inc(
            folds.count("slice"))
        dw = [s.backend for s in self.steps if _is_depthwise(chain, s.name)]
        for backend in sorted(set(dw) | {"dwconv:pallas-vpu",
                                         "conv:lax"}):
            self.metrics.counter("engine_depthwise_steps",
                                 backend=backend).inc(dw.count(backend))
        self.metrics.counter("engine_spatial_width_padded_steps").inc(
            sum(_spatial_width_padded(chain, s.name) for s in self.steps
                if s.backend == "conv:pallas"))
        self._builds = compiles.install()
        # what a call takes from the caller's dicts, and how many of those
        # values were not yet jax.Arrays (counted only when some were)
        self._input_names = tuple(chain.inputs)
        self._param_names = tuple(chain.params)
        self._converted = self.metrics.counter("engine_args_converted")

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
            with jax.named_scope(step.name):
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

    def _args(self, inputs, params):
        """The chain's inputs and parameters as arrays, and the leading
        batch size (None for exact shapes). A value that is already a
        ``jax.Array`` (a tracer too) is passed on as it is, which is what
        ``jnp.asarray`` would return; any other goes through it."""
        ins, n_in = _take(inputs, self._input_names, "input")
        ps, n_ps = _take(params or {}, self._param_names, "param")
        if n_in or n_ps:
            self._converted.inc(n_in + n_ps)
        return ins, ps, self._batch_size(ins)

    def _launch(self, ins, ps, n, keep_all):
        """Run the program (exact-shape, or the batch bucket's) up to its
        unfinished outputs; returns them and what the call built, if it
        built anything (a :class:`repro.obs.compiles.BuildTotals`)."""
        builds = self._builds
        if n is None:
            fn, bucket = self._fn(keep_all), None
        else:
            bucket = batch_bucket(n, self._min_bucket)
            fn = self._batched.get((keep_all, bucket))
            ins = pad_leading(ins, bucket)
        before = builds.totals
        out = fn(ins, ps)
        built = None
        if builds.totals is not before:
            built = builds.totals - before
            self._record_build(keep_all, bucket, built)
        out = dict(out) if n is None else dict(unpad_leading(out, n))
        return out, built

    def _record_build(self, keep_all, bucket, built):
        program = ("exact" if bucket is None else f"bucket={bucket}") \
            + ("+all" if keep_all else "")
        m = self.metrics
        m.counter("engine_programs_compiled", program=program).inc(
            built.programs)
        m.counter("engine_compile_cache_hits", program=program).inc(
            built.cache_hits)
        for name, secs in (("engine_trace_s", built.trace_s),
                           ("engine_compile_s", built.compile_s)):
            g = m.gauge(name, program=program)
            g.set(g.value + secs)

    def __call__(self,
                 inputs: Mapping[str, jnp.ndarray],
                 params: Optional[Mapping[str, jnp.ndarray]] = None,
                 keep_all: bool = False) -> Dict[str, jnp.ndarray]:
        tr = self.tracer
        if (tr is not None and tr.enabled) or TraceAnnotation.is_enabled():
            return self._timed_call(inputs, params, keep_all)
        # nothing records: annotations would be dropped, so none are made
        ins, ps, n = self._args(inputs, params)
        return self._launch(ins, ps, n, keep_all)[0]

    def _timed_call(self, inputs, params, keep_all):
        """``__call__`` while a profiler session or the tracer records:
        annotations ``engine.call`` > ``engine.args`` / ``engine.launch``,
        the phases' seconds summed into ``metrics`` (``engine_span_s`` by
        span, ``engine_timed_calls``) and, with an enabled tracer, the same
        spans (and ``engine.compile`` under a launch that built its
        program) in its ring."""
        t0 = time.perf_counter()
        with TraceAnnotation("engine.call"):
            with TraceAnnotation("engine.args"):
                ins, ps, n = self._args(inputs, params)
            t1 = time.perf_counter()
            with TraceAnnotation("engine.launch"):
                out, built = self._launch(ins, ps, n, keep_all)
            t2 = time.perf_counter()
        m = self.metrics
        m.counter("engine_timed_calls").inc()
        m.counter("engine_span_s", span="engine.args").inc(t1 - t0)
        m.counter("engine_span_s", span="engine.launch").inc(t2 - t1)
        tr = self.tracer
        if tr is not None and tr.enabled:
            call = tr.add_span("engine.call", "engine", t0, t2,
                               attrs={"signature": self.signature,
                                      "n": n})
            tr.add_span("engine.args", "engine", t0, t1, parent=call)
            launch = tr.add_span("engine.launch", "engine", t1, t2,
                                 parent=call)
            if built is not None:
                tr.add_span("engine.compile", "compile", t1, t2,
                            parent=launch, attrs=built._asdict())
        return out

    def op_steps(self) -> Dict[str, str]:
        """``{HLO instruction: step name}`` of the exact-shape program, as
        compiled for the default device: the instructions a profiler trace
        names, each put down to the fusion-group step whose
        ``jax.named_scope`` its ``metadata`` carries. XLA's layout copies
        carry no metadata; each takes the step of its first user that has
        one. Instructions outside every step scope are left out."""
        def spec(infos):
            return {n: jax.ShapeDtypeStruct(i.shape, jnp.dtype(i.dtype))
                    for n, i in infos.items()}

        text = self._fn(False).lower(
            spec(self.chain.inputs), spec(self.chain.params)
        ).compile().as_text()
        return hlo_op_steps(text, [s.name for s in self.steps])

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


def _take(given, names, kind):
    """``{name: array}`` of ``names`` from ``given`` and how many of them
    ``jnp.asarray`` had to convert; a missing name raises ValueError."""
    out, converted = {}, 0
    for name in names:
        if name not in given:
            raise ValueError(f"missing chain {kind} {name!r}")
        value = given[name]
        if not isinstance(value, jax.Array):
            value = jnp.asarray(value)
            converted += 1
        out[name] = value
    return out, converted


_HLO_INST = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = ")
_HLO_REF = re.compile(r"%([^\s,(){}=]+)")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLEE = re.compile(r"\b(?:calls|to_apply)=%([^\s,(){}]+)")


def hlo_op_steps(text: str, steps) -> Dict[str, str]:
    """``{instruction: step}`` of a compiled HLO module's text (see
    :meth:`CompiledChain.op_steps`): an instruction's step is the first
    scope of its ``op_name`` (the last part is the operation) that names
    one of ``steps``; one without takes its first user's. Instructions of
    fusion bodies and reduction regions, which no trace names, are left
    out."""
    steps = set(steps)
    comps: List[List[tuple]] = []
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split()[1] if line.startswith("ENTRY") \
                else line.split()[0]
            comps.append([name.lstrip("%")])
            continue
        m = _HLO_INST.match(line)
        if m and comps:
            comps[-1].append((m.group(1), line))
    callees = {c for comp in comps for _n, line in comp[1:]
               for c in _HLO_CALLEE.findall(line)}
    out: Dict[str, str] = {}
    for comp in comps:
        if comp[0] in callees:
            continue
        insts = comp[1:]
        users: Dict[str, List[str]] = {}
        own: Dict[str, Optional[str]] = {}
        for name, line in insts:
            body = line.split(" = ", 1)[1]
            for ref in _HLO_REF.findall(body):
                users.setdefault(ref, []).append(name)
            m = _HLO_OP_NAME.search(line)
            scopes = m.group(1).split("/")[:-1] if m else ()
            own[name] = next((x for x in scopes if x in steps), None)
        for name, line in reversed(insts):
            step = own[name]
            if step is None and " parameter(" not in line:
                step = next((own[u] for u in users.get(name, ())
                             if own.get(u) is not None), None)
                own[name] = step
            if step is not None:
                out[name] = step
    return out


def _conv_plan(chain: Chain, name: str):
    """``lowering.match_conv``'s plan of the step ``name``, or None when it
    is not a conv."""
    node = chain.nodes.get(name)
    if not isinstance(node, GConv) or node.kernel is None:
        return None
    return match_conv(node, dim_classes(node),
                      tuple(chain.shape_of(node.kernel)))


def _is_depthwise(chain: Chain, name: str) -> bool:
    """Is the step ``name`` a depthwise conv (``lowering.is_depthwise``)?"""
    plan = _conv_plan(chain, name)
    return plan is not None and is_depthwise(chain.nodes[name], plan)


def _spatial_width_padded(chain: Chain, name: str) -> bool:
    """Does ``gconv_spatial`` compute the ``conv:pallas`` step ``name``
    wider than its output (``gconv_spatial.block_width``)?"""
    from ..kernels.gconv_spatial import block_width
    _, windows, _ = _conv_plan(chain, name)
    ow = chain.nodes[name].dims[windows[1]].nopc      # (H, W) windows
    return block_width(ow) != ow


@contextmanager
def _phase(name: str, tracer):
    """One set-up phase of ``compile_chain``: a profiler annotation, and a
    span in ``tracer`` unless it is None."""
    span = tracer.span(name, cat="compile") if tracer is not None \
        else nullcontext()
    with TraceAnnotation(name), span:
        yield


def compile_chain(chain: Chain, mesh=None, tracer=None,
                  **options) -> CompiledChain:
    """Compile a chain for execution. See :class:`CompileOptions`.

    ``mesh``: a ``jax.sharding.Mesh`` to compile a SHARDED program against
    (see the module docstring); ``None`` keeps the single-device engine.

    Tracing (``repro.obs``): every call runs the one fused program, with
    one ``jax.named_scope`` per fusion-group step (``engine.op_steps()``
    maps the compiled program's HLO instructions to steps), inside the
    host spans ``engine.call`` > ``engine.args`` (the chain's inputs and
    params taken from the caller's dicts, ``jnp.asarray`` on those that are
    not yet ``jax.Array``s, the input shapes checked) / ``engine.launch``
    (the jitted program, until it returns its unfinished outputs), written
    as ``jax.profiler`` annotations while a profiler session records, so
    that they land in its trace beside the device's operations. A call
    that builds a program counts it in ``engine.metrics`` under its
    program key:
    ``engine_programs_compiled``, ``engine_compile_cache_hits`` and the
    seconds ``engine_trace_s`` (tracing and lowering) and
    ``engine_compile_s`` (XLA/Mosaic compile or persistent-cache load).
    ``engine_reduce_window_steps`` and ``engine_slice_window_steps``
    count, once at construction, the ``reduce`` steps whose window dims
    fold in one ``lax.reduce_window`` or as shifted slices
    (``lowering.window_fold``); ``engine_depthwise_steps`` counts the
    depthwise conv steps (``lowering.is_depthwise``) by ``backend``
    (``dwconv:pallas-vpu`` where the kernel takes them, else ``conv:lax``).
    ``engine_args_converted`` counts the values a call had to convert
    (NumPy arrays, Python scalars, lists); device arrays count nothing.
    ``compile_chain``'s own phases are annotated ``compile.partition``,
    ``compile.plan`` and ``compile.lint``.

    ``profile=True`` (or an enabled ``tracer=``) also records those spans
    into ``engine.tracer`` (a fresh ``repro.obs.trace.Tracer`` unless
    ``tracer=`` is given), and an ``engine.compile`` span under the launch
    that built a program; export with ``engine.tracer.write(path)`` and
    summarize with ``python -m repro.obs.report``. While a profiler
    session or the tracer records, each call's phase seconds are also
    summed into ``engine.metrics`` (``engine_span_s`` by ``span``, over
    ``engine_timed_calls``). Without either, a call pays two flag checks.

    ``lint="error"``: run the `repro.lint` static passes over the compiled
    artifacts (chain + plan + shard plan) and raise
    :class:`~repro.lint.LintError` on findings at/above the given
    severity; the full report lands on ``engine.lint_report`` either way.
    ``lint=None`` (default) reads the ``REPRO_LINT`` env var ("off" when
    unset; conftest.py defaults it to "error" so every test-compiled
    chain is verified).
    """
    import os

    from .shardplan import plan_backend

    opts = CompileOptions(**options)
    if opts.profile and tracer is None:
        from ..obs.trace import Tracer
        tracer = Tracer()
    spans = tracer if tracer is not None and tracer.enabled else None
    chain.validate()
    with _phase("compile.partition", spans):
        fused, report, parts = partition_chain(chain, fuse=opts.fuse)
    backend = plan_backend(opts.backend, mesh)
    with _phase("compile.plan", spans):
        plan = plan_chain(fused, backend=backend, mxu_min=opts.mxu_min,
                          segments=opts.segments)
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
    level = opts.lint if opts.lint is not None \
        else os.environ.get("REPRO_LINT", "off")
    if level and level != "off":
        from ..lint import LintError, lint_compiled
        with _phase("compile.lint", spans):
            eng.lint_report = lint_compiled(eng)
        if eng.lint_report.at_least(level):
            raise LintError(eng.lint_report, level)
    return eng
