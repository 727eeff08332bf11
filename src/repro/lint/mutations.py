"""Seeded mutation corpus: deliberately-broken chains/plans/shard-plans
proving every lint rule actually fires.

Each mutant is (name, intended rule, base builder, mutate fn). The
corpus check is two-sided:

  * **no false negatives** — linting the mutated artifact must produce
    the intended rule;
  * **no false positives** — linting the clean base must NOT produce it.

``plan``/``shard`` mutants tamper the compiled artifacts (dispatch
table, step list, ShardPlan, step meta) the way a buggy
partition/dispatch/lowering change would — including a reconstruction
of the PR 5 missing-psum / unconstrained-replication bug, which the
runtime 8-fake-device sweep only caught after the fact.
"""
from __future__ import annotations

import copy
from typing import Callable, List, Tuple

from jax.sharding import PartitionSpec as P

from ..core import layers as L
from ..core.chain import Chain, Movement
from . import build_context, fake_mesh, lint_chain
from .registry import run_passes

MESH_SPEC = "4x2"


# ---------------------------------------------------------------------------
# clean bases
# ---------------------------------------------------------------------------
def base_small(name: str = "lint_small") -> Chain:
    """fc/relu/fc at C=64: small enough to stay under every Table-4
    global buffer (so chain.gb-capacity is clean on the base)."""
    c = Chain(name)
    x = c.add_input("x", (8, 64))
    h = L.fc(c, x, out_f=64, name="fc1")
    h = L.relu(c, h, name="act1")
    h = L.fc(c, h, out_f=64, name="fc2")
    c.mark_output(h)
    return c


def base_hot(name: str = "lint_hot") -> Chain:
    """fc/relu/fc at C=512: each matmul carries ~2M macs (>= HOT_MACS),
    so forcing one onto the oracle is plan.oracle-hot."""
    c = Chain(name)
    x = c.add_input("x", (8, 512))
    h = L.fc(c, x, out_f=512, name="fc1")
    h = L.relu(c, h, name="act1")
    h = L.fc(c, h, out_f=512, name="fc2")
    c.mark_output(h)
    return c


def base_tiny16(name: str = "lint_tiny16") -> Chain:
    """K=N=16 fc: far below mxu_min, auto dispatch keeps it on jnp."""
    c = Chain(name)
    x = c.add_input("x", (4, 16))
    h = L.fc(c, x, out_f=16, name="fc1")
    c.mark_output(h)
    return c


def base_col(name: str = "lint_col") -> Chain:
    """K=511 (odd), N=512: on a DxM=4x2 mesh the plan column-splits."""
    c = Chain(name)
    x = c.add_input("x", (8, 511))
    h = L.fc(c, x, out_f=512, name="fc1")
    c.mark_output(h)
    return c


def base_row(name: str = "lint_row") -> Chain:
    """K=512, N=511 (odd): N doesn't divide the model axis, K does —
    the plan row-splits with an explicit psum."""
    c = Chain(name)
    x = c.add_input("x", (8, 512))
    h = L.fc(c, x, out_f=511, name="fc1")
    c.mark_output(h)
    return c


def base_odd_batch(name: str = "lint_oddb") -> Chain:
    """Batch 6 on a data axis of 4: the leading-batch policy replicates
    (6 % 4 != 0); pinning it anyway is shard.input-spec-divisibility."""
    c = Chain(name)
    x = c.add_input("x", (6, 512))
    h = L.fc(c, x, out_f=512, name="fc1")
    c.mark_output(h)
    return c


# ---------------------------------------------------------------------------
# chain-layer mutants (mutate the Chain, lint via lint_chain)
# ---------------------------------------------------------------------------
def mut_dangling_output(c: Chain):
    c.outputs.append("ghost")


def mut_use_before_def(c: Chain):
    c.nodes = dict(reversed(list(c.nodes.items())))


def mut_shape_mismatch(c: Chain):
    info = c.params["fc2.w"]
    c.params["fc2.w"] = type(info)((1, info.shape[1] - 3), info.dtype)


def mut_dead_node(c: Chain):
    L.fc(c, "act1", out_f=8, name="fc_dead")   # never marked as output


def mut_unused_input(c: Chain):
    c.add_input("x_unused", (4, 4))


def mut_unused_param(c: Chain):
    c.add_param("w_unused", (4, 4))


def mut_noop_movement(c: Chain):
    out = c.outputs[-1]
    shape = c.shape_of(out)
    c.add(Movement("mv_id", input=out, perm=tuple(range(len(shape))),
                   out_shape=tuple(shape)))
    c.outputs = ["mv_id"]


def mut_quant_barrier(c: Chain):
    c.nodes["act1"].out_dtype = "float16"


def mut_gb_overflow(c: Chain):
    # an activation bigger than every Table-4 global buffer (words)
    x2 = c.add_input("x_big", (64, 65536))
    h = L.relu(c, x2, name="act_big")
    c.mark_output(h)


# ---------------------------------------------------------------------------
# plan-layer mutants (tamper the built LintContext's plan artifacts)
# ---------------------------------------------------------------------------
def mut_missing_dispatch(ctx):
    del ctx.plan.dispatch["fc2"]


def mut_oracle_hot(ctx):
    ctx.plan.dispatch["fc2"] = "oracle"
    for st in ctx.plan.steps:
        if st.name == "fc2":
            st.backend = "oracle"


def mut_pallas_mxu(ctx):
    ctx.plan.dispatch["fc1"] = "matmul:pallas"
    for st in ctx.plan.steps:
        if st.name == "fc1":
            st.backend = "matmul:pallas"


def mut_fusion_illegal(ctx):
    # claim a still-materialized reducing matmul as a fused member
    ctx.fusion.groups.setdefault("fc1", []).append("fc2")


def mut_step_disorder(ctx):
    ctx.plan.steps.reverse()


def mut_unknown_step(ctx):
    ctx.plan.steps[0].name = "ghost"


# ---------------------------------------------------------------------------
# shard-layer mutants (tamper ShardPlan / re-lowered step meta)
# ---------------------------------------------------------------------------
def mut_tp_indivisible(ctx):
    # flip the column split to row: K=511 does not divide model=2
    ctx.shard_plan.step_tp["fc1"] = "row"


def mut_missing_psum(ctx):
    # PR 5 reconstruction, part 1: lowering "forgets" the psum a
    # row-split's partial products need
    for st in ctx.sharded_steps:
        if st.meta:
            st.meta["psum"] = False


def mut_unconstrained(ctx):
    # PR 5 reconstruction, part 2: lowering skips the
    # with_sharding_constraint pinning operand replication under DP
    for st in ctx.sharded_steps:
        if st.meta:
            st.meta["constrained"] = False


def mut_bad_input_spec(ctx):
    # pin the (indivisible) leading batch dim anyway
    ctx.shard_plan.in_specs["x"] = P("data", None)


# (name, intended rule, base builder, mutate, layer)
MUTANTS: List[Tuple[str, str, Callable, Callable, str]] = [
    ("dangling_output", "chain.dangling-output", base_small,
     mut_dangling_output, "chain"),
    ("use_before_def", "chain.use-before-def", base_small,
     mut_use_before_def, "chain"),
    ("shape_mismatch", "chain.shape-mismatch", base_small,
     mut_shape_mismatch, "chain"),
    ("dead_node", "chain.dead-node", base_small, mut_dead_node, "chain"),
    ("unused_input", "chain.unused-input", base_small,
     mut_unused_input, "chain"),
    ("unused_param", "chain.unused-param", base_small,
     mut_unused_param, "chain"),
    ("noop_movement", "chain.noop-movement", base_small,
     mut_noop_movement, "chain"),
    ("quant_barrier", "chain.quant-fusion-barrier", base_small,
     mut_quant_barrier, "chain"),
    ("gb_overflow", "chain.gb-capacity", base_small,
     mut_gb_overflow, "chain"),
    ("missing_dispatch", "plan.missing-dispatch", base_hot,
     mut_missing_dispatch, "plan"),
    ("oracle_hot", "plan.oracle-hot", base_hot, mut_oracle_hot, "plan"),
    ("pallas_mxu", "plan.pallas-mxu-min", base_tiny16,
     mut_pallas_mxu, "plan"),
    ("fusion_illegal", "plan.fusion-illegal", base_hot,
     mut_fusion_illegal, "plan"),
    ("step_disorder", "plan.step-order", base_hot,
     mut_step_disorder, "plan"),
    ("unknown_step", "plan.unknown-step", base_hot,
     mut_unknown_step, "plan"),
    ("tp_indivisible", "shard.tp-divisibility", base_col,
     mut_tp_indivisible, "shard"),
    ("missing_psum", "shard.missing-psum", base_row,
     mut_missing_psum, "shard"),
    ("unconstrained_replication", "shard.unconstrained-replication",
     base_row, mut_unconstrained, "shard"),
    ("bad_input_spec", "shard.input-spec-divisibility", base_odd_batch,
     mut_bad_input_spec, "shard"),
]


def _lint_mutant(layer: str, base: Chain, mutate) :
    """Lint (clean_report, mutated_report) at the mutant's layer."""
    if layer == "chain":
        clean = lint_chain(base)
        broken = copy.deepcopy(base)
        mutate(broken)
        return clean, lint_chain(broken)
    mesh = fake_mesh(MESH_SPEC) if layer == "shard" else None
    ctx = build_context(base, mesh=mesh)
    clean = run_passes(ctx)
    ctx = build_context(base, mesh=mesh)
    mutate(ctx)
    return clean, run_passes(ctx)


def run_corpus() -> List[dict]:
    """Lint every mutant and its clean base; one row per mutant with the
    two-sided verdict."""
    rows = []
    for name, rule_id, builder, mutate, layer in MUTANTS:
        clean, broken = _lint_mutant(layer, builder(), mutate)
        caught = any(f.rule == rule_id for f in broken)
        clean_hit = any(f.rule == rule_id for f in clean)
        rows.append(dict(
            mutant=name, rule=rule_id, layer=layer, caught=caught,
            false_positive=clean_hit, clean_errors=len(clean.errors()),
            fired=sorted(broken.by_rule())))
    return rows


def corpus_ok(rows=None) -> bool:
    rows = run_corpus() if rows is None else rows
    return all(r["caught"] and not r["false_positive"]
               and r["clean_errors"] == 0 for r in rows)
