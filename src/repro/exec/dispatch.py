"""Backend dispatch + fused-segment detection for the compiled engine.

Per-node dispatch picks the cheapest sound lowering from
:mod:`repro.exec.lowering` using the dim-class vector and the kernel
tensor's (possibly broadcast) shape. On top of that, a peephole pass
recognizes multi-GCONV *segments* and lowers each to the hand-fused
implementation it denotes — proving the engine subsumes the paths that
used to be hand-wired into the LM models:

  * softmax   (max / sub-exp / sum / div, both the 4-node form and the
               §4.3-fused 3-node form)        -> ``jax.nn.softmax``
  * rmsnorm   (reduce-GCONV + broadcast-GCONV) -> ``models.common.norm``
               or the Pallas ``kernels.chain_norm``
  * attention (scores -> softmax -> values)    -> ``models.common.
               attention_naive`` or the Pallas ``kernels.flash_attention``

Interior segment nodes are never materialized; they appear in the dispatch
table as ``fused:<segment output>``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.chain import Chain, Concat, Movement
from ..core.gconv import GConv, Op
from ..kernels.common import use_interpret
from . import lowering as low


@dataclass
class Step:
    """One compiled execution step: produces env[name] from env."""

    name: str
    backend: str
    run: Callable                        # fn(env) -> array
    # static contract the lowering declares about `run` (e.g. the tensor-
    # parallel tp_mode/psum/constrained facts from lower_grouped_matmul);
    # audited by the repro.lint shard passes, never read at execution time
    meta: Dict[str, object] = field(default_factory=dict)


@dataclass
class Plan:
    steps: List[Step]
    dispatch: Dict[str, str]             # every original node -> backend tag
    signature: str = ""                  # stable program identity: chain
                                         # name + input shapes + per-step
                                         # backend decisions (the engine
                                         # appends mesh + tensor-parallel
                                         # splits for sharded programs).
                                         # Introspection/reporting only —
                                         # compile caches are per-engine,
                                         # so their keys need only
                                         # (keep_all, bucket)


# ---------------------------------------------------------------------------
# per-node dispatch
# ---------------------------------------------------------------------------
def _prefer_pallas_matmul(backend: str, mxu_min: int, plan, node) -> bool:
    """Static MXU-worthiness rule for the Pallas grouped matmul under
    ``backend="auto"`` on the chip. All three work axes must clear a
    threshold: K/N feed the MXU contraction, and M must at least
    fill one sublane tile — a tiny-M huge-K product (e.g. a (1, 4096) @
    (4096, 4096) head projection) is a matvec whose Pallas grid degenerates
    to one M-row of padded tiles, where ``jnp.matmul`` wins. The group
    axis never compensates for small M: G maps to the kernel grid, not the
    tile."""
    if backend == "pallas":
        return True
    if backend != "auto" or use_interpret():
        return False
    from ..kernels.gconv_matmul import M_ALIGN
    g_ix, m_ix, c_ix = plan
    M = int(np.prod([node.dims[i].in_size for i in m_ix])) if m_ix else 1
    K = int(np.prod([node.dims[i].nks for i in c_ix])) if c_ix else 1
    N = int(np.prod([node.dims[i].nop for i in c_ix])) if c_ix else 1
    return M >= M_ALIGN and K >= mxu_min and N >= mxu_min


def is_max_pool(node) -> bool:
    """A max over a sliding window (no kernel, ``reduce == "max"``, a
    window dim): a max pool."""
    return (isinstance(node, GConv) and node.main == "none"
            and node.reduce == "max" and low.WINDOW in low.dim_classes(node))


def dispatch_gconv(node: GConv, k_shape: Optional[Tuple[int, ...]],
                   backend: str = "auto", mxu_min: int = 128, *,
                   after_pool: bool = False) -> Tuple[str, Callable]:
    """Pick (backend_tag, fn(x, k, lookup)) for one GCONV node;
    ``after_pool``: a max pool produces its input (:func:`is_max_pool`),
    which a grouped conv needs to take ``gconv_spatial``."""
    classes = low.dim_classes(node)
    if all(c == low.BCAST for c in classes):
        return "elementwise", low.lower_elementwise(node)
    if low.GENERAL in classes:
        return "oracle", low.lower_oracle(node)
    if node.main == "none" and node.reduce in ("add", "max", "min"):
        if all(d.nop == 1 for d in node.dims):
            return "reduce", low.lower_reduce(node, classes)
        return "oracle", low.lower_oracle(node)
    if node.main == "mul" and node.reduce == "add":
        if low.WINDOW not in classes:
            plan = low.match_grouped_matmul(node, classes, k_shape)
            if plan is not None:
                if _prefer_pallas_matmul(backend, mxu_min, plan, node):
                    return ("matmul:pallas",
                            low.lower_grouped_matmul(node, plan, pallas=True))
                return "matmul:jnp", low.lower_grouped_matmul(node, plan)
        cplan = low.match_conv(node, classes, k_shape)
        if cplan is not None:
            pallas = backend == "pallas" or (backend == "auto"
                                             and not use_interpret())
            if pallas and low.is_depthwise(node, cplan):
                # the Pallas kernel on the VPU; ``:pallas`` alone names
                # the MXU kernels (matmul:pallas, conv:pallas)
                fn = low.lower_depthwise_pallas(node, cplan)
                if fn is not None:
                    return "dwconv:pallas-vpu", fn
            elif pallas:
                fn = low.lower_conv_pallas(node, cplan, after_pool=after_pool)
                if fn is not None:
                    return "conv:pallas", fn
            return "conv:lax", low.lower_conv(node, cplan)
        return "einsum", low.lower_einsum(node, classes)
    return "oracle", low.lower_oracle(node)


# ---------------------------------------------------------------------------
# segment detection
# ---------------------------------------------------------------------------
@dataclass
class Segment:
    kind: str
    out: str                             # the node whose value the segment produces
    members: Tuple[str, ...]             # interior nodes, never materialized
    run: Callable = None                 # fn(env) -> array


def _is_op(op: Op, name: str, operand: Optional[str] = None) -> bool:
    return (op.name == name and op.operand == operand)


def _single_axis_reduce(node: GConv, kind: str) -> Optional[int]:
    """Axis index when the node is a pure one-dim full reduction."""
    if not isinstance(node, GConv):
        return None
    if node.main != "none" or node.reduce != kind:
        return None
    classes = low.dim_classes(node)
    tap_ix = [i for i, d in enumerate(node.dims) if d.nks > 1]
    if len(tap_ix) != 1:
        return None
    i = tap_ix[0]
    if classes[i] != low.CONTRACT or node.dims[i].ng != 1:
        return None
    if node.dims[i].nop != 1:
        return None
    if any(c != low.BCAST for j, c in enumerate(classes) if j != i):
        return None
    return i


def _softmax_parts(chain: Chain, consumers, div_name: str):
    """Match the softmax segment ending at ``div_name``.

    Returns (x, axis, members) or None. Handles both the unfused 4-node
    form (max / sub-exp / sum / div) and the form §4.3 fusion produces
    (max / sum[pre=sub,exp] / div[pre=sub,exp])."""
    div = chain.nodes.get(div_name)
    if not isinstance(div, GConv) or div.main != "div":
        return None
    if div.reduce != "none" or div.post or div.kernel is None:
        return None
    s = chain.nodes.get(div.kernel)
    if not isinstance(s, GConv):
        return None

    def fused_pre(pre, m_name):
        return (len(pre) == 2 and _is_op(pre[0], "sub", m_name)
                and pre[0].const is None and _is_op(pre[1], "exp"))

    if not div.pre:                                      # unfused form
        e = chain.nodes.get(div.input)
        if (not isinstance(e, GConv) or e.main != "sub" or e.reduce != "none"
                or e.pre or len(e.post) != 1 or not _is_op(e.post[0], "exp")):
            return None
        m_name = e.kernel
        if s.input != e.name or s.pre or s.post:
            return None
        ax = _single_axis_reduce(s, "add")
        m = chain.nodes.get(m_name)
        if not isinstance(m, GConv) or m.input != e.input:
            return None
        if m.pre or m.post or _single_axis_reduce(m, "max") != ax:
            return None
        members = (m_name, e.name, s.name)
        x = e.input
        cons_ok = (sorted(consumers.get(e.name, [])) == sorted([s.name,
                                                                div_name])
                   and consumers.get(m_name, []) == [e.name]
                   and consumers.get(s.name, []) == [div_name])
    else:                                                # fused form
        if len(div.pre) != 2:
            return None
        m_name = div.pre[0].operand
        if m_name is None or not fused_pre(div.pre, m_name):
            return None
        if s.input != div.input or s.post or not fused_pre(s.pre, m_name):
            return None
        ax = _single_axis_reduce(s, "add")
        m = chain.nodes.get(m_name)
        if not isinstance(m, GConv) or m.input != div.input:
            return None
        if m.pre or m.post or _single_axis_reduce(m, "max") != ax:
            return None
        members = (m_name, s.name)
        x = div.input
        cons_ok = (sorted(consumers.get(m_name, []))
                   == sorted([s.name, div_name])
                   and consumers.get(s.name, []) == [div_name])
    if ax is None or not cons_ok:
        return None
    if any(n in chain.outputs for n in members):
        return None
    # interior nodes with an out_dtype quantize their intermediate in the
    # oracle; a segment computing end-to-end in f32 would diverge — refuse
    # and let per-node dispatch handle the mixed-precision chain
    if any(chain.nodes[n].out_dtype is not None for n in members):
        return None
    return x, ax, members


def match_softmax(chain: Chain, consumers, div_name: str) -> Optional[Segment]:
    parts = _softmax_parts(chain, consumers, div_name)
    if parts is None:
        return None
    x, ax, members = parts
    out_dtype = chain.nodes[div_name].out_dtype

    def run(env, _x=x, _ax=ax, _od=out_dtype):
        v = env[_x]
        y = jax.nn.softmax(v.astype(jnp.result_type(v.dtype, jnp.float32)),
                           axis=_ax)
        return y if _od is None else y.astype(_od)

    return Segment("segment:softmax", div_name, members, run)


def match_norm(chain: Chain, consumers, name: str,
               backend: str = "auto") -> Optional[Segment]:
    """rmsnorm pair: reduce-GCONV (square-mean-rsqrt) + broadcast-GCONV."""
    n2 = chain.nodes.get(name)
    if not isinstance(n2, GConv) or n2.main != "mul" or n2.reduce != "none":
        return None
    if n2.pre or len(n2.post) != 1 or n2.post[0].name != "mul":
        return None
    gamma = n2.post[0].operand
    if gamma is None or n2.kernel is None:
        return None
    ms = chain.nodes.get(n2.kernel)
    if not isinstance(ms, GConv) or ms.input != n2.input:
        return None
    if (len(ms.pre) != 1 or not _is_op(ms.pre[0], "square")
            or len(ms.post) != 2 or ms.post[0].name != "scale"
            or ms.post[1].name != "rsqrt_eps"):
        return None
    ax = _single_axis_reduce(ms, "add")
    if ax is None or ax != len(ms.dims) - 1:             # norm is over -1
        return None
    nks = ms.dims[ax].nks
    if not np.isclose(ms.post[0].const, 1.0 / nks):
        return None
    eps = ms.post[1].const if ms.post[1].const is not None else 1e-5
    if consumers.get(ms.name, []) != [name] or ms.name in chain.outputs:
        return None
    if ms.out_dtype is not None:         # oracle would quantize the stat
        return None
    if any(c != low.BCAST for c in low.dim_classes(n2)):
        return None
    try:
        gshape = chain.shape_of(gamma)
    except KeyError:
        return None
    # canonical (1, ..., C) gamma only: the chain_norm kernel reshapes it
    # to (C,); a further-broadcast gamma falls back to per-node dispatch
    C = ms.dims[ax].nks
    if gshape[-1] != C or any(s != 1 for s in gshape[:-1]):
        return None
    use_pallas = backend == "pallas" or (backend == "auto"
                                         and not use_interpret())
    x_name = n2.input

    out_dtype = n2.out_dtype

    def run(env, _x=x_name, _g=gamma, _eps=eps, _pallas=use_pallas,
            _od=out_dtype):
        x = env[_x]
        x = x.astype(jnp.result_type(x.dtype, jnp.float32))
        g = env[_g]
        if _pallas:
            from ..kernels.chain_norm import chain_norm
            y = chain_norm(x.reshape(-1, x.shape[-1]),
                           g.reshape(x.shape[-1]), eps=_eps, mode="rms")
            y = y.reshape(x.shape)
        else:
            from ..models import common
            y = common.norm(x, g, kind="rms", eps=_eps)
        return y if _od is None else y.astype(_od)

    tag = "segment:norm:" + ("pallas" if use_pallas else "jnp")
    return Segment(tag, name, (ms.name,), run)


def _canonical_attention(s: GConv, v: GConv, ks_shape, kv_shape):
    """(B, H..., Tq, Tk, D) scores/values pair in the layers.attention_*
    layout: returns (tk_axis, d_axis, scale) or None."""
    if len(s.dims) != len(v.dims):
        return None
    n = len(s.dims)
    if n < 3:
        return None
    tk, d = n - 2, n - 1
    ds, dv = s.dims, v.dims
    # scores: Tq=nop at n-3, Tk=nopc at n-2, D=nks at n-1, groups before
    tq = n - 3
    ok_s = (ds[tq].ng == 1 and ds[tq].nks == 1 and ds[tq].nopc == 1
            and ds[tk].nks == 1 and ds[tk].nop == 1 and ds[tk].ng == 1
            and ds[d].nopc == 1 and ds[d].nop == 1 and ds[d].ng == 1
            and all(low.classify_dim(ds[i]) == low.BCAST
                    and ds[i].nopc == 1 for i in range(tq)))
    ok_v = (dv[tq].ng >= 1 and dv[tq].nks == 1 and dv[tq].nop == 1
            and dv[tk].ng == 1 and dv[tk].nop == 1 and dv[tk].nopc == 1
            and dv[d].ng == 1 and dv[d].nks == 1 and dv[d].nopc == 1
            and all(low.classify_dim(dv[i]) == low.BCAST
                    and dv[i].nopc == 1 for i in range(tq)))
    if not (ok_s and ok_v):
        return None
    if ks_shape is None or kv_shape is None:
        return None
    # q broadcastless on groups/Tq/D, singleton on Tk; v singleton on Tq
    if ks_shape[tk] != 1 or kv_shape[tq] != 1:
        return None
    if not s.post:
        scale = 1.0
    elif len(s.post) == 1 and s.post[0].name == "scale":
        scale = float(s.post[0].const)
    else:
        return None
    return tk, d, scale


def match_attention(chain: Chain, consumers, v_name: str,
                    backend: str = "auto") -> Optional[Segment]:
    v = chain.nodes.get(v_name)
    if not isinstance(v, GConv) or v.main != "mul" or v.reduce != "add":
        return None
    if v.pre or v.post or v.kernel is None:
        return None
    probs_name = v.input
    parts = _softmax_parts(chain, consumers, probs_name)
    if parts is None or consumers.get(probs_name, []) != [v_name]:
        return None
    s_name, sm_ax, sm_members = parts
    if probs_name in chain.outputs:
        return None
    s = chain.nodes.get(s_name)
    if not isinstance(s, GConv) or s.main != "mul" or s.reduce != "add":
        return None
    if s.pre or s.kernel is None:
        return None
    if not set(consumers.get(s_name, [])) <= set(sm_members) | {probs_name}:
        return None
    if s_name in chain.outputs or any(m in chain.outputs for m in sm_members):
        return None
    # interior scores/probs with an out_dtype would be quantized by the
    # oracle; the fused segment computes in f32 — refuse (see _softmax_parts)
    if s.out_dtype is not None or chain.nodes[probs_name].out_dtype is not None:
        return None
    try:
        ks_shape = chain.shape_of(s.kernel)
        kv_shape = chain.shape_of(v.kernel)
    except KeyError:
        return None
    canon = _canonical_attention(s, v, ks_shape, kv_shape)
    if canon is None:
        return None
    tk, d_ax, scale = canon
    if sm_ax != tk:
        return None
    # values must contract the Tk axis and replicate over D
    if v.dims[tk].nks == 1 or v.dims[d_ax].nop == 1:
        return None
    use_pallas = backend == "pallas" or (backend == "auto"
                                         and not use_interpret())
    q_name, k_name, vv_name = s.kernel, s.input, v.kernel
    out_shape = v.out_shape
    n = len(s.dims)
    lead = tuple(s.dims[i].ng for i in range(n - 3))     # (B, H, ...) groups
    Tq, Tk, D = s.dims[n - 3].nop, s.dims[tk].nopc, s.dims[d_ax].nks
    out_dtype = v.out_dtype

    def run(env, _q=q_name, _k=k_name, _v=vv_name, _scale=scale,
            _pallas=use_pallas, _out=out_shape, _od=out_dtype):
        q, kk, vv = env[_q], env[_k], env[_v]
        ct = jnp.result_type(kk.dtype, jnp.float32)
        B = int(np.prod(lead)) if lead else 1
        q_ = jnp.broadcast_to(q.astype(ct), lead + (Tq, 1, D))
        q_ = q_.reshape(B, Tq, D)
        k_ = jnp.broadcast_to(kk.astype(ct), lead + (1, Tk, D))
        k_ = k_.reshape(B, Tk, D)
        v_ = jnp.broadcast_to(vv.astype(ct), lead + (1, Tk, D))
        v_ = v_.reshape(B, Tk, D)
        if _pallas:
            from ..kernels.flash_attention import flash_attention
            o = flash_attention(q_, k_, v_, causal=False, scale=_scale)
        else:
            from ..models import common
            o = common.attention_naive(
                q_[:, :, None], k_[:, :, None], v_[:, :, None],
                causal=False, scale=_scale)[:, :, 0]
        o = o.reshape(_out)
        return o if _od is None else o.astype(_od)

    tag = "segment:attention:" + ("pallas" if use_pallas else "jnp")
    members = (s_name,) + sm_members + (probs_name,)
    return Segment(tag, v_name, members, run)


# ---------------------------------------------------------------------------
# chain planning
# ---------------------------------------------------------------------------
def plan_chain(chain: Chain, *, backend: str = "auto", mxu_min: int = 128,
               segments: bool = True) -> Plan:
    consumers = chain.consumers()
    segs: Dict[str, Segment] = {}
    claimed: Dict[str, str] = {}         # interior node -> segment out
    if segments:
        # priority order matters: an attention segment's interior softmax
        # must not be claimed by the standalone softmax matcher first
        matchers = (
            lambda n: match_attention(chain, consumers, n, backend),
            lambda n: match_softmax(chain, consumers, n),
            lambda n: match_norm(chain, consumers, n, backend),
        )
        for matcher in matchers:
            for name in chain.nodes:
                if name in claimed or name in segs:
                    continue
                seg = matcher(name)
                if seg is None:
                    continue
                if any(m in claimed or m in segs for m in seg.members):
                    continue
                segs[seg.out] = seg
                for m in seg.members:
                    claimed[m] = seg.out

    steps: List[Step] = []
    dispatch: Dict[str, str] = {}
    for name, node in chain.nodes.items():
        if name in claimed:
            dispatch[name] = f"fused:{claimed[name]}"
            continue
        if name in segs:
            seg = segs[name]
            dispatch[name] = seg.kind
            steps.append(Step(name, seg.kind, seg.run))
            continue
        if isinstance(node, Concat):
            dispatch[name] = "concat"
            steps.append(Step(name, "concat", _concat_step(node)))
            continue
        if isinstance(node, Movement):
            dispatch[name] = "movement"
            steps.append(Step(name, "movement", _movement_step(node)))
            continue
        k_shape = (tuple(chain.shape_of(node.kernel))
                   if node.kernel is not None else None)
        tag, fn = dispatch_gconv(
            node, k_shape, backend, mxu_min,
            after_pool=is_max_pool(chain.nodes.get(node.input)))
        dispatch[name] = tag
        steps.append(Step(name, tag, _gconv_step(node, fn)))
    ins = ";".join(f"{n}:{'x'.join(map(str, i.shape))}:{i.dtype}"
                   for n, i in chain.inputs.items())
    prog = ";".join(f"{s.name}={s.backend}" for s in steps)
    return Plan(steps, dispatch, signature=f"{chain.name}|{ins}|{prog}")


def _gconv_step(node: GConv, fn: Callable) -> Callable:
    def run(env):
        x = env[node.input]
        k = env[node.kernel] if node.kernel is not None else None
        lookup = lambda op: env[op.operand]
        return fn(x, k, lookup)

    return run


def _concat_step(node: Concat) -> Callable:
    def run(env):
        return jnp.concatenate([env[r] for r in node.inputs], axis=node.axis)

    return run


def _movement_step(node: Movement) -> Callable:
    """Metadata-only reshape/transpose — the oracle's own Movement
    semantics (shared definition, gather stand-in included)."""
    from ..core.interpreter import apply_movement

    def run(env):
        return apply_movement(node, env[node.input])

    return run
