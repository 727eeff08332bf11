"""Per-chain sharding plans: the compiled engine joins the mesh world.

Before this module, ``repro.exec`` was strictly single-device while the
full mesh machinery lived in ``repro.launch`` — two disjoint subsystems.
A :class:`ShardPlan` is derived once at ``compile_chain(mesh=...)`` time
and applies the SAME divisibility-guarded policy as the launch-layer model
sharder (both import :mod:`repro.shardpolicy`; nothing is duplicated):

  * **data parallel** — the leading batch axis of every chain input shards
    over the mesh's "data" axis bundle when it divides
    (:func:`repro.shardpolicy.guard`); in the batched/vmapped mode the
    *bucket* axis shards instead, and the engine raises the bucket floor
    to the data-axis size so every bucket divides by construction.
  * **tensor parallel** — grouped-matmul fusion groups split their
    ``(G, M, K) @ (G, K, N)`` contraction over the "model" axis:
    column-split (kernel sharded on N = the Cout/channel GCONV axis, no
    collective) when N divides; otherwise row-split (both operands sharded
    on K) with an **explicit psum** inside a ``shard_map`` — the one place
    the chain program needs a collective; otherwise replicate.
  * **replication fallback** — any axis that doesn't divide falls back to
    replication for that dim, exactly as in ``launch/sharding.py``.

Everything not pinned by the plan is left to GSPMD propagation, so the
sharded program is allclose to the single-device one by construction (the
only numerical difference is reduction order inside the psum).
Differentially tested on 8 faked host devices in
``tests/test_exec_sharded.py`` / ``python -m repro.exec.shardcheck``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import shardpolicy as policy
from ..core.chain import Chain
from ..core.gconv import GConv
from . import lowering as low

COLUMN, ROW = "column", "row"


@dataclass(frozen=True)
class ShardPlan:
    """How one compiled chain maps onto a mesh (derived, never mutated)."""

    mesh: Mesh
    dp: tuple                            # data-parallel axis bundle
    tp: Optional[str]                    # tensor-parallel axis name or None
    in_specs: Dict[str, P] = field(default_factory=dict)
    param_specs: Dict[str, P] = field(default_factory=dict)
    step_tp: Dict[str, str] = field(default_factory=dict)  # node -> col/row

    @property
    def dp_size(self) -> int:
        return policy.axis_size(self.mesh, self.dp)

    @property
    def tp_size(self) -> int:
        return policy.axis_size(self.mesh, self.tp)

    # -- NamedSharding trees matching the engine's (inputs, params) args --
    def input_shardings(self):
        return {n: NamedSharding(self.mesh, s)
                for n, s in self.in_specs.items()}

    def param_shardings(self):
        return {n: NamedSharding(self.mesh, s)
                for n, s in self.param_specs.items()}

    def batched_input_shardings(self, chain: Chain, bucket: int):
        """Leading-bucket-axis data parallelism for the vmapped mode."""
        dp = self.dp if bucket % self.dp_size == 0 else None
        return {n: NamedSharding(self.mesh, P(dp, *([None] * len(i.shape))))
                for n, i in chain.inputs.items()}

    def describe(self) -> str:
        lines = [f"ShardPlan mesh={dict(self.mesh.shape)} dp={self.dp} "
                 f"tp={self.tp}"]
        for n, s in self.in_specs.items():
            lines.append(f"  in  {n}: {s}")
        for n, m in self.step_tp.items():
            lines.append(f"  tp  {n}: {m}-split")
        return "\n".join(lines)


def plan_backend(backend: str, mesh) -> str:
    """The dispatch backend a chain compiled against ``mesh`` plans with.

    GSPMD cannot partition a Mosaic kernel: compiling one into a program
    sharded over several TPU devices raises "Mosaic kernels cannot be
    automatically partitioned". So ``auto`` on a multi-device mesh plans
    XLA's own lowerings (``matmul:jnp``, ``conv:lax``, jnp segments), which
    GSPMD partitions. An explicit ``"pallas"`` is kept as asked."""
    if backend == "auto" and mesh is not None and mesh.size > 1:
        return "jnp"
    return backend


def _matmul_geometry(node: GConv, chain: Chain):
    """(match plan, G, M, N, K) of a grouped-matmul node, or None."""
    if node.kernel is None:
        return None
    classes = low.dim_classes(node)
    k_shape = tuple(chain.shape_of(node.kernel))
    mplan = low.match_grouped_matmul(node, classes, k_shape)
    if mplan is None:
        return None
    g_ix, m_ix, c_ix = mplan
    G = M = N = K = 1
    for i in g_ix:
        G *= node.dims[i].ng
    for i in m_ix:
        M *= node.dims[i].in_size
    for i in c_ix:
        N *= node.dims[i].nop
        K *= node.dims[i].nks
    return mplan, G, M, N, K


def derive_plan(chain: Chain, dispatch: Dict[str, str], mesh: Mesh) \
        -> ShardPlan:
    """Derive the chain's plan from its dispatch table and a mesh.

    ``dispatch`` is the compiled plan's node -> backend-tag table; only
    ``matmul:jnp`` nodes are candidates for the explicit tensor-parallel
    split. A multi-device plan holds no Pallas step under ``auto`` (see
    :func:`plan_backend`).
    """
    dp = policy.dp_axes(mesh)
    tp = "model" if "model" in mesh.axis_names else None
    tp_n = policy.axis_size(mesh, tp)

    in_specs = {n: policy.leading_batch_spec(mesh, i.shape, dp)
                for n, i in chain.inputs.items()}
    # params replicate: at chain scale the kernels are small relative to
    # activations, and the TP shard_map partitions its (G, K, N) form
    # in-program — pinning a host-side layout would only force reshards
    param_specs = {n: P() for n in chain.params}

    step_tp: Dict[str, str] = {}
    if tp is not None and tp_n > 1:
        for name, tag in dispatch.items():
            if tag != "matmul:jnp":
                continue
            node = chain.nodes[name]
            geo = _matmul_geometry(node, chain)
            if geo is None:
                continue
            _mplan, _G, _M, N, K = geo
            if N % tp_n == 0:
                step_tp[name] = COLUMN       # local matmul, no collective
            elif K % tp_n == 0:
                step_tp[name] = ROW          # explicit psum over tp
            # else: replicate — the divisibility fallback

    return ShardPlan(mesh=mesh, dp=dp, tp=tp, in_specs=in_specs,
                     param_specs=param_specs, step_tp=step_tp)


def wrap_steps(chain: Chain, steps, plan: ShardPlan):
    """Re-lower the plan's tensor-parallel matmul steps with their
    column/row split; every other step passes through untouched."""
    if not plan.step_tp:
        return list(steps)
    from .dispatch import Step, _gconv_step

    out = []
    dp_n = plan.dp_size
    for s in steps:
        mode = plan.step_tp.get(s.name)
        if mode is None:
            out.append(s)
            continue
        node = chain.nodes[s.name]
        geo = _matmul_geometry(node, chain)
        mplan, G, M, _N, _K = geo
        # the data axis rides along on G (batched/grouped kernels) or M
        # (plain batch rows) when it divides, so DP + TP compose without
        # gathers; otherwise the operands replicate over data for this
        # step (the with_sharding_constraint in _tp_matmul enforces it)
        dp_g = plan.dp if G % dp_n == 0 else None
        dp_m = plan.dp if dp_g is None and M % dp_n == 0 else None
        fn = low.lower_grouped_matmul(
            node, mplan, tp=(plan.mesh, plan.tp, mode, dp_g, dp_m))
        out.append(Step(s.name, f"{s.backend}+tp:{mode}",
                        _gconv_step(node, fn),
                        meta=dict(getattr(fn, "tp_meta", {}))))
    return out
