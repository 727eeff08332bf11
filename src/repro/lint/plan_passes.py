"""Plan passes: the compiled execution plan vs the fused chain.

These audit what ``exec.partition`` / ``exec.dispatch`` produced:
dispatch-table coverage, step-list consistency (order, names, backend
tags), §4.3 fusion-group legality (members must be reduce-free,
replication-free, dtype-neutral, non-output GCONVs that no longer
materialize), backend preconditions for the Pallas grouped matmul
(`kernels.common.pick_block` never-overshoot/divisibility contract and
the ``mxu_min`` eligibility gate), and the oracle-fallback detector —
a hot-path node silently landing on the O(macs) oracle interpreter is
an error, a cold tiny node is an informational note.
"""
from __future__ import annotations

from ..core.gconv import GConv
from ..exec.shardplan import _matmul_geometry
from ..kernels.common import block_contract_ok, pick_block
from ..kernels.gconv_matmul import (BLOCK_K, BLOCK_M, BLOCK_N,
                                    K_ALIGN, M_ALIGN, N_ALIGN)
from .registry import lint_pass, make_finding, rule

R_MISSING_DISPATCH = rule("plan.missing-dispatch", "plan", "error",
                          "a source node has no dispatch entry")
R_UNKNOWN_STEP = rule("plan.unknown-step", "plan", "error",
                      "a plan step names no fused-chain node (or a "
                      "dispatch entry names no source node)")
R_STEP_ORDER = rule("plan.step-order", "plan", "error",
                    "plan steps disagree with the fused chain's node "
                    "order / dispatch tags")
R_FUSION = rule("plan.fusion-illegal", "plan", "error",
                "a fusion-group member violates the §4.3 legality "
                "invariants")
R_ORACLE_HOT = rule("plan.oracle-hot", "plan", "error",
                    "a hot-path node dispatches to the O(macs) oracle "
                    "interpreter")
R_ORACLE_COLD = rule("plan.oracle-fallback", "plan", "info",
                     "a (cold) node dispatches to the oracle interpreter")
R_MXU = rule("plan.pallas-mxu-min", "plan", "error",
             "a Pallas matmul was auto-selected below the mxu_min "
             "K/N eligibility gate")
R_BLOCK = rule("plan.pallas-block-contract", "plan", "error",
               "a Pallas matmul's block sizes violate the pick_block "
               "contract (or the node has no grouped-matmul geometry)")
R_COMPILE = rule("plan.compile-failed", "plan", "error",
                 "the chain failed to compile and no chain-layer "
                 "finding explains why")


@lint_pass("plan")
def check_dispatch_cover(ctx):
    """Every source node has a dispatch entry; every entry is a node."""
    src = set(ctx.source.nodes)
    disp = ctx.plan.dispatch
    for n in sorted(src - set(disp)):
        yield make_finding(ctx, R_MISSING_DISPATCH, node=n,
                           message="no dispatch entry for this node")
    for n in sorted(set(disp) - src):
        yield make_finding(ctx, R_UNKNOWN_STEP, node=n,
                           message=f"dispatch entry {disp[n]!r} names no "
                                   f"source node")


@lint_pass("plan")
def check_step_consistency(ctx):
    """The emitted step list must be exactly the fused chain's nodes, in
    order, minus the fused-away (``fused:``-tagged) members — and each
    step's backend must match its dispatch tag."""
    fused = ctx.fused if ctx.fused is not None else ctx.source
    disp = ctx.plan.dispatch
    for st in ctx.plan.steps:
        if st.name not in fused.nodes:
            yield make_finding(ctx, R_UNKNOWN_STEP, node=st.name,
                               message=f"step {st.name!r} names no "
                                       f"fused-chain node")
        elif disp.get(st.name) != st.backend:
            yield make_finding(
                ctx, R_STEP_ORDER, node=st.name,
                message=f"step backend {st.backend!r} disagrees with "
                        f"dispatch tag {disp.get(st.name)!r}")
    want = [n for n in fused.nodes
            if not disp.get(n, "").startswith("fused:")]
    got = [st.name for st in ctx.plan.steps]
    if got != want:
        yield make_finding(
            ctx, R_STEP_ORDER, want=want, got=got,
            message=f"step order {got} != fused chain order {want}")


@lint_pass("plan")
def check_fusion_groups(ctx):
    """§4.3 legality: a fused member must be a reduce-free,
    replication-free, dtype-neutral, non-output GCONV of the source chain
    that no longer materializes in the fused chain."""
    if ctx.fusion is None or ctx.fused is None:
        return
    src, fused = ctx.source, ctx.fused
    for host, members in ctx.fusion.groups.items():
        if host not in fused.nodes:
            yield make_finding(ctx, R_FUSION, group=host,
                               message="group host is not a fused-chain "
                                       "node")
        for m in members:
            if m in fused.nodes:
                yield make_finding(
                    ctx, R_FUSION, node=m, group=host,
                    message="fused member still materializes in the "
                            "fused chain")
            node = src.nodes.get(m)
            if node is None:
                yield make_finding(ctx, R_FUSION, node=m, group=host,
                                   message="member is not a source node")
                continue
            if not isinstance(node, GConv):
                yield make_finding(ctx, R_FUSION, node=m, group=host,
                                   message="non-GCONV node in a fusion "
                                           "group")
                continue
            if node.reduce != "none":
                yield make_finding(
                    ctx, R_FUSION, node=m, group=host,
                    message=f"member reduces ({node.reduce}); only "
                            f"reduce-free GCONVs fuse")
            if node.out_dtype is not None:
                yield make_finding(
                    ctx, R_FUSION, node=m, group=host,
                    message="member is a quantization point (out_dtype "
                            "is semantic; fusion would drop the cast)")
            if any(d.nks > 1 or d.nop > 1 for d in node.dims):
                yield make_finding(
                    ctx, R_FUSION, node=m, group=host,
                    message="member replicates/contracts (nks/nop > 1)")
            if m in src.outputs:
                yield make_finding(ctx, R_FUSION, node=m, group=host,
                                   message="chain output fused away")


@lint_pass("plan")
def check_oracle_fallback(ctx):
    total = sum(n.macs for n in ctx.source.nodes.values()) or 1
    fused = ctx.fused if ctx.fused is not None else ctx.source
    for name, tag in ctx.plan.dispatch.items():
        if tag != "oracle":
            continue
        node = fused.nodes.get(name) or ctx.source.nodes.get(name)
        macs = node.macs if node is not None else 0
        share = macs / total
        hot = macs >= ctx.hot_macs and share >= ctx.hot_frac
        rid = R_ORACLE_HOT if hot else R_ORACLE_COLD
        yield make_finding(
            ctx, rid, node=name, macs=macs, share=round(share, 4),
            message=f"dispatches to the O(macs) oracle interpreter "
                    f"({macs} macs, {share:.1%} of the chain)")


@lint_pass("plan")
def check_pallas_preconditions(ctx):
    """Pallas grouped-matmul steps: the node must have grouped-matmul
    geometry, auto-selection must respect the ``mxu_min`` gate (K/N feed
    the MXU; M must fill at least one sublane tile — the heuristic in
    ``dispatch._prefer_pallas_matmul``), and the kernel's static default
    tile sizes must satisfy the ``pick_block`` contract for the node's
    (M, N, K)."""
    fused = ctx.fused if ctx.fused is not None else ctx.source
    for st in ctx.plan.steps:
        if st.backend != "matmul:pallas":
            continue
        node = fused.nodes.get(st.name)
        if not isinstance(node, GConv):
            continue                     # unknown-step already reported
        geo = _matmul_geometry(node, fused)
        if geo is None:
            yield make_finding(
                ctx, R_BLOCK, node=st.name,
                message="Pallas matmul step without grouped-matmul "
                        "geometry")
            continue
        _mplan, _G, M, N, K = geo
        if (ctx.backend == "auto"
                and (K < ctx.mxu_min or N < ctx.mxu_min or M < M_ALIGN)):
            yield make_finding(
                ctx, R_MXU, node=st.name, M=M, K=K, N=N,
                mxu_min=ctx.mxu_min,
                message=f"auto-dispatched to Pallas with M={M} K={K} "
                        f"N={N} below the mxu_min={ctx.mxu_min} / "
                        f"M_ALIGN={M_ALIGN} gate")
        for axis, n, target, align in (("M", M, BLOCK_M, M_ALIGN),
                                       ("N", N, BLOCK_N, N_ALIGN),
                                       ("K", K, BLOCK_K, K_ALIGN)):
            b = min(target, pick_block(n, target, align))
            if not block_contract_ok(n, b, align):
                yield make_finding(
                    ctx, R_BLOCK, node=st.name, axis=axis, n=n, block=b,
                    align=align,
                    message=f"block {b} for {axis}={n} violates the "
                            f"pick_block contract (align {align})")
