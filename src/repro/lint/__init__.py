"""`repro.lint` — static verifier over chain IR, execution plans, and
shard plans.

Three pass layers (see the README rule catalog):

  * **chain** — whole-chain re-validation beyond the add-time checks:
    dangling outputs, dead nodes, unused inputs/params, no-op Movements,
    out_dtype quantization points fusion refuses to absorb, and an
    interval-liveness peak checked against each Table-4 accelerator's
    global buffer.
  * **plan** — the compiled plan vs the fused chain: dispatch coverage,
    step consistency, §4.3 fusion-group legality, Pallas
    ``pick_block``/``mxu_min`` preconditions, and the oracle-fallback
    detector (a hot node on the O(macs) oracle is an ``error``).
  * **shard** — the ShardPlan without devices: TP split divisibility,
    row splits carry their explicit psum, replication pinned by sharding
    constraints (the PR 5 bug class as a compile-time ``error``), input
    spec divisibility/policy, params-replicate contract.

Entry points::

    lint_chain(chain)                       # build artifacts + run passes
    lint_chain(chain, mesh=fake_mesh("4x2"))
    compile_chain(chain, lint="error")      # gate at compile time
    python -m repro.lint                    # zoo + LM sweep CLI

The shard layer needs only ``mesh.shape``/``mesh.axis_names``
(`repro.shardpolicy` is duck-typed), so :func:`fake_mesh` fakes an
8-device mesh with no devices, subprocesses, or XLA flags.
"""
from __future__ import annotations

from typing import Mapping, Optional

from .findings import Finding, LintError, LintReport, severity_rank
from .registry import (LintContext, RULES, Rule, make_finding, run_passes)
from . import chain_passes, plan_passes, shard_passes  # noqa: F401  (register passes)
from .plan_passes import R_COMPILE


class FakeMesh:
    """Duck-typed stand-in for ``jax.sharding.Mesh``: carries only the
    axis geometry (``shape`` mapping + ``axis_names``), which is all the
    shard-plan derivation and the lint passes consult. Executing a
    program against it is impossible by design."""

    def __init__(self, shape: Mapping[str, int]):
        self.shape = dict(shape)

    @property
    def axis_names(self):
        return tuple(self.shape)

    @property
    def empty(self) -> bool:
        return not self.shape

    @property
    def size(self) -> int:
        n = 1
        for s in self.shape.values():
            n *= s
        return n

    def __repr__(self):
        return f"FakeMesh({self.shape})"


def fake_mesh(spec: str = "4x2") -> FakeMesh:
    """A deviceless mesh from the ``--mesh`` grammar (``"8"`` or
    ``"4x2"`` = (data, model))."""
    from ..shardpolicy import parse_mesh_spec
    d, m = parse_mesh_spec(spec)
    shape = {"data": d}
    if m > 1:
        shape["model"] = m
    return FakeMesh(shape)


def build_context(chain, *, backend: str = "auto", mxu_min: int = 128,
                  mesh=None, fuse: bool = True, segments: bool = True,
                  config: str = "") -> LintContext:
    """Compile the chain's static artifacts (fused chain, plan, shard
    plan) exactly as ``compile_chain`` would, without building an
    engine — ``mesh`` may be a :class:`FakeMesh`."""
    from ..exec.dispatch import plan_chain
    from ..exec.partition import partition_chain
    from ..exec.shardplan import plan_backend
    fused, report, parts = partition_chain(chain, fuse=fuse)
    plan = plan_chain(fused, backend=plan_backend(backend, mesh),
                      mxu_min=mxu_min, segments=segments)
    for host, members in report.groups.items():
        for m in members:
            plan.dispatch.setdefault(m, f"fused:{host}")
    shard_plan = sharded_steps = None
    if mesh is not None and not mesh.empty:
        from ..exec.shardplan import derive_plan, wrap_steps
        shard_plan = derive_plan(fused, plan.dispatch, mesh)
        sharded_steps = wrap_steps(fused, plan.steps, shard_plan)
    return LintContext(source=chain, fused=fused, fusion=report,
                       partitions=parts, plan=plan, backend=backend,
                       mxu_min=mxu_min, shard_plan=shard_plan,
                       sharded_steps=sharded_steps, config=config)


def lint_chain(chain, *, backend: str = "auto", mxu_min: int = 128,
               mesh=None, fuse: bool = True, segments: bool = True,
               config: str = "") -> LintReport:
    """Lint a chain end to end: compile the static artifacts and run all
    applicable passes. A chain too broken to compile gets the chain-layer
    report (plus ``plan.compile-failed`` if no chain finding explains the
    failure)."""
    if not config:
        parts = [f"backend={backend}"]
        if mesh is not None:
            parts.append("mesh=" + "x".join(str(s)
                                            for s in mesh.shape.values()))
        config = " ".join(parts)
    try:
        ctx = build_context(chain, backend=backend, mxu_min=mxu_min,
                            mesh=mesh, fuse=fuse, segments=segments,
                            config=config)
    except Exception as e:
        ctx = LintContext(source=chain, config=config)
        rep = run_passes(ctx, layers=("chain",))
        if not rep.errors():
            rep.add(make_finding(ctx, R_COMPILE, error=repr(e),
                                 message=f"chain failed to compile: {e}"))
        return rep
    return run_passes(ctx)


def lint_compiled(engine) -> LintReport:
    """Lint a :class:`~repro.exec.engine.CompiledChain` in place — the
    artifacts it already built are audited, nothing is recompiled."""
    opts = engine.options
    shard_plan = engine.shard_plan
    config = f"backend={opts.backend}"
    if shard_plan is not None:
        config += " mesh=" + "x".join(str(s)
                                      for s in shard_plan.mesh.shape.values())
    ctx = LintContext(
        source=engine.source, fused=engine.chain,
        fusion=engine.fusion_report, partitions=engine.partitions,
        plan=engine._plan, backend=opts.backend, mxu_min=opts.mxu_min,
        shard_plan=shard_plan,
        sharded_steps=(engine._steps_sharded
                       if shard_plan is not None else None),
        config=config)
    return run_passes(ctx)


__all__ = ["Finding", "LintReport", "LintError", "LintContext", "Rule",
           "RULES", "FakeMesh", "fake_mesh", "build_context", "lint_chain",
           "lint_compiled", "run_passes", "severity_rank"]
