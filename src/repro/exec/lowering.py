"""Per-GCONV backend lowerings for the compiled chain engine.

Every GCONV dimension falls into one of four classes (derived from its four
loop parameters, paper §3.1):

  * ``bcast``    — no taps, no kernel replication, unit stride/pad: the
                   input axis maps to the output axis identically
                   (``Ng*Nopc`` elements pass through).
  * ``contract`` — ``Nopc == 1``, no padding: the ``Nks`` taps cover the
                   whole (per-group) axis; a pure reduction/contraction
                   with no window overlap (FC's C dim, softmax's axis,
                   batch-norm's batch axis).
  * ``window``   — true sliding windows (``Nopc > 1`` and ``Nks > 1``) with
                   stride/padding: conv/pool spatial dims, LRN's C dim. A
                   reduce folds its window dims in the tensor's own dim
                   order, in one ``lax.reduce_window`` or, for LRN's one
                   stride-1 dim, as shifted slices (``window_fold``); no
                   transpose or gather: on a TPU a gathered 3- or 5-tap
                   last axis fills few of a tile's 128 lanes.
  * ``general``  — anything else (strided decimation etc.): falls back to
                   the oracle interpreter semantics.

The class vector decides the backend (see ``dispatch``): elementwise jnp,
axis reductions, ``lax.conv_general_dilated`` / the Pallas spatial kernel,
grouped matmul (``jnp.matmul`` / the Pallas ``gconv_matmul``), a generic
windowed ``einsum``, or — for exotic operator combinations — the
:func:`repro.core.interpreter.eval_gconv` oracle itself. Each lowering is
allclose-equivalent to the oracle but never materializes the full
``(Ng, Nop, Nopc, Nks)`` expansion when the ``reduce`` operator folds it.

All lowerings share the signature ``fn(x, k, lookup) -> y`` where ``lookup``
resolves pre/post tensor operands from the execution environment, and
mirror the oracle's dtype discipline: compute in
``result_type(x.dtype, float32)``, cast to ``out_dtype`` at the end.

Batched-mode contract: the leading-batch execution path
(:class:`~repro.exec.engine.CompiledChain` with batch-extended inputs)
``jax.vmap``-wraps the whole step program, so every lowering here must be
(a) traceable with the chain's declared shapes only — all reshapes /
window index tables are built from the STATIC ``DimSpec`` geometry, never
from runtime values — and (b) row-independent: nothing may reduce or
gather across the (abstracted) batch axis. (a) is what lets one bucket
compile serve every batch size in the bucket; (b) is what makes zero-pad
rows inert, in the same way per-slot positions make pad-token decode
ticks inert in the serving programs (exec.serving).

Row-independence is ALSO the sharding invariant the mesh-aware mode
(``compile_chain(mesh=...)``, :mod:`repro.exec.shardplan`) relies on:
because no lowering communicates across the leading batch axis, sharding
that axis over the mesh's "data" bundle partitions the program into
independent per-device replicas — GSPMD inserts no batch-axis collectives,
so the sharded program computes bit-for-bit the same per-row arithmetic as
the single-device one. The only collective a chain program ever needs is
the explicit ``psum`` of a row-split tensor-parallel grouped matmul
(:func:`lower_grouped_matmul` with ``tp=...``), which changes reduction
order but stays within the engine's differential-test tolerance.
"""
from __future__ import annotations

import functools
import string
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import operators as ops
from ..core.gconv import DimSpec, GConv

BCAST, CONTRACT, WINDOW, GENERAL = "bcast", "contract", "window", "general"


def classify_dim(d: DimSpec) -> str:
    if (d.nks == 1 and d.nop == 1 and d.stride == 1
            and d.pad == 0 and d.padr == 0):
        return BCAST
    if d.nopc == 1 and d.pad == 0 and d.padr == 0:
        return CONTRACT
    if d.ng == 1 and d.nop == 1:
        return WINDOW
    return GENERAL


def dim_classes(node: GConv) -> Tuple[str, ...]:
    return tuple(classify_dim(d) for d in node.dims)


def _compute_dtype(x):
    return jnp.result_type(x.dtype, jnp.float32)


def _finish(node: GConv, y, lookup):
    y = ops.apply_unary_seq(node.post, y, lookup)
    if node.out_dtype is not None:
        y = y.astype(node.out_dtype)
    return y


def _window_gather(x, axis: int, d: DimSpec, pad_val: float):
    """(…, Nips, …) -> (…, Nopc, Nks) at the end; ``axis`` must have ng==1."""
    x = jnp.moveaxis(x, axis, -1)
    if d.padr < 0:                      # crop: trailing elements never read
        x = x[..., : d.nips + d.padr]
    if d.pad > 0 or d.padr > 0:
        pad = [(0, 0)] * (x.ndim - 1) + [(d.pad, max(d.padr, 0))]
        x = jnp.pad(x, pad, constant_values=pad_val)
    idx = (np.arange(d.nopc)[:, None] * d.stride + np.arange(d.nks)[None, :])
    return x[..., idx]                  # (…, Nopc, Nks)


# ---------------------------------------------------------------------------
# elementwise: all dims bcast (any reduce is a no-op over singleton taps)
# ---------------------------------------------------------------------------
def lower_elementwise(node: GConv) -> Callable:
    dims = node.dims

    def fn(x, k, lookup):
        x = x.astype(_compute_dtype(x))
        x = ops.apply_unary_seq(node.pre, x, lookup)
        if node.main != "none":
            xs, ks = [], []
            for d, ka in zip(dims, k.shape):
                xs += [d.ng, d.nopc]
                ks += [d.ng, 1] if ka != 1 else [1, 1]
            y = ops.apply_main(node.main, x.reshape(xs),
                               k.astype(x.dtype).reshape(ks))
        else:
            y = x
        return _finish(node, y.reshape(node.out_shape), lookup)

    return fn


# ---------------------------------------------------------------------------
# reductions: main == 'none', reduce folds contract/window taps
# ---------------------------------------------------------------------------
def _reducer(name: str):
    return {"add": jnp.sum, "max": jnp.max, "min": jnp.min}[name]


_WINDOW_REDUCER = {"add": jax.lax.add, "max": jax.lax.max,
                   "min": jax.lax.min}
_SLICE_MAX_TAPS = 8


def window_fold(node: GConv) -> Optional[str]:
    """How :func:`lower_reduce` folds the node's window dims: ``"slice"``
    for one window dim of stride 1 and at most ``_SLICE_MAX_TAPS`` taps
    (LRN's window over C), ``"reduce_window"`` for any other window
    geometry (pools), None without a window dim.

    Timed alone on a TPU v5e at GoogLeNet's b32 shapes, LRN's window folds
    about three times faster as shifted slices than as a
    ``lax.reduce_window``, and a 3x3 stride-2 max pool about eight times
    slower as strided slices. The tap bound keeps the unrolled fold
    small."""
    win = [d for d, c in zip(node.dims, dim_classes(node)) if c == WINDOW]
    if not win:
        return None
    if (len(win) == 1 and win[0].stride == 1
            and win[0].nks <= _SLICE_MAX_TAPS):
        return "slice"
    return "reduce_window"


def lower_reduce(node: GConv, classes: Sequence[str]) -> Callable:
    """Window dims fold as :func:`window_fold` says, over the tensor in its
    own dim order, then contract dims fold in one axis reduction.

    Padded taps read the reduce's identity, as the oracle pads; a negative
    ``padr`` first crops the trailing elements no window reads. One
    ``lax.reduce_window`` folds every window dim at once; the ``slice``
    fold pads its one window dim and combines the ``Nks`` shifted slices
    of it. No :func:`_window_gather` per dim: on a TPU that puts a 3- or
    5-tap last axis on the 128 lanes of a tile and copies the whole
    tensor on each side of it (the transposes)."""
    dims = node.dims
    red = _reducer(node.reduce)
    combine = _WINDOW_REDUCER[node.reduce]
    init = ops.pad_value(node.reduce)
    fold = window_fold(node)
    is_win = [c == WINDOW for c in classes]
    contract_ix = [i for i, c in enumerate(classes) if c == CONTRACT]
    window = tuple(d.nks if w else 1 for d, w in zip(dims, is_win))
    strides = tuple(d.stride if w else 1 for d, w in zip(dims, is_win))
    padding = tuple((d.pad, max(d.padr, 0)) if w else (0, 0)
                    for d, w in zip(dims, is_win))
    crop = tuple(d.in_size + min(d.padr, 0) if w else d.in_size
                 for d, w in zip(dims, is_win))

    def fold_slices(x):
        i = is_win.index(True)
        d = dims[i]
        x = jnp.pad(x, padding, constant_values=init)
        taps = [jax.lax.slice_in_dim(x, t, t + d.nopc, axis=i)
                for t in range(d.nks)]
        return functools.reduce(combine, taps)

    def fn(x, k, lookup):
        x = x.astype(_compute_dtype(x))
        x = ops.apply_unary_seq(node.pre, x, lookup)
        if fold is not None:
            if crop != x.shape:         # trailing elements never read
                x = jax.lax.slice(x, (0,) * x.ndim, crop)
            if fold == "slice":
                x = fold_slices(x)
            else:
                x = jax.lax.reduce_window(x, init, combine, window, strides,
                                          padding)
        if contract_ix:
            shape, axes = [], []
            for i, d in enumerate(dims):
                if i in contract_ix:
                    shape += [d.ng, d.nks]
                    axes.append(len(shape) - 1)
                else:
                    shape.append(x.shape[i])
            x = red(x.reshape(shape), axis=tuple(axes))
        return _finish(node, x.reshape(node.out_shape), lookup)

    return fn


# ---------------------------------------------------------------------------
# conv: main=mul/reduce=add with one grouped channel contraction + sliding
# spatial dims -> lax.conv_general_dilated (or the Pallas spatial kernel)
# ---------------------------------------------------------------------------
def match_conv(node: GConv, classes: Sequence[str],
               k_shape: Optional[Tuple[int, ...]]):
    """Return (channel_ix, window_ix, batch_ix) or None."""
    if node.main != "mul" or node.reduce != "add" or k_shape is None:
        return None
    channel = [i for i, c in enumerate(classes)
               if c == CONTRACT and k_shape[i] == node.dims[i].k_size]
    if not channel:
        # depthwise: icg == 1 makes the channel dim a pure-Ng (bcast) dim
        # with a full kernel axis — feature_group_count = Ng, I = 1
        channel = [i for i, (d, c) in enumerate(zip(node.dims, classes))
                   if c == BCAST and d.nopc == 1 and k_shape[i] == d.k_size
                   and k_shape[i] != 1]
    windows = [i for i, c in enumerate(classes)
               if c == WINDOW and k_shape[i] == node.dims[i].nks]
    batch = [i for i, c in enumerate(classes)
             if c == BCAST and k_shape[i] == 1]
    if len(channel) != 1 or not windows:
        return None
    if sorted(channel + windows + batch) != list(range(len(classes))):
        return None
    return channel[0], windows, batch


def is_depthwise(node: GConv, plan) -> bool:
    """A conv (``match_conv``'s plan) whose channel dim is ``Ng = C,
    Nks = 1, Nop = 1``: each channel its own window, nothing contracted
    over channels (match_conv's depthwise branch)."""
    d = node.dims[plan[0]]
    return d.nks == 1 and d.nop == 1


def lower_conv(node: GConv, plan) -> Callable:
    """``lax.conv_general_dilated`` at XLA's default precision, except a
    depthwise conv (:func:`is_depthwise`), which runs at
    ``Precision.HIGHEST``: float32 products and sums, the arithmetic of
    :func:`lower_depthwise_pallas`, so its plan means the same numbers on
    every backend."""
    ch, windows, batch = plan
    dims = node.dims
    dch = dims[ch]
    groups, ocg, icg = dch.ng, dch.nop, dch.nks
    precision = (jax.lax.Precision.HIGHEST if is_depthwise(node, plan)
                 else None)
    spatial = "".join("xyzuv"[i] for i in range(len(windows)))
    dn = ("NC" + spatial, "OI" + spatial, "NC" + spatial)
    strides = tuple(dims[i].stride for i in windows)

    def fn(x, k, lookup):
        ct = _compute_dtype(x)
        x = x.astype(ct)
        x = ops.apply_unary_seq(node.pre, x, lookup)
        # N = flattened batch axes; C = Ng*Nks of the channel dim
        perm = batch + [ch] + windows
        xb = jnp.transpose(x, perm)
        b_sizes = [dims[i].in_size for i in batch]
        nb = int(np.prod(b_sizes)) if b_sizes else 1
        xb = xb.reshape((nb, dch.in_size)
                        + tuple(dims[i].nips for i in windows))
        padding = []
        for i in windows:
            d = dims[i]
            if d.padr < 0:              # crop trailing elements never read
                ax = 2 + windows.index(i)
                xb = jax.lax.slice_in_dim(xb, 0, d.nips + d.padr, axis=ax)
            padding.append((d.pad, max(d.padr, 0)))
        kb = jnp.transpose(k.astype(ct), [ch] + windows + batch)
        kb = kb.reshape((groups * ocg, icg)
                        + tuple(dims[i].nks for i in windows))
        y = jax.lax.conv_general_dilated(
            xb, kb, strides, padding, dimension_numbers=dn,
            feature_group_count=groups, precision=precision)
        # (N, G*Nop, *Nopc) -> original dim order -> out_shape
        y = y.reshape(tuple(b_sizes) + (groups * ocg,)
                      + tuple(dims[i].nopc for i in windows))
        inv = np.argsort(perm)
        y = jnp.transpose(y, inv).reshape(node.out_shape)
        return _finish(node, y, lookup)

    return fn


def lower_conv_pallas(node: GConv, plan) -> Optional[Callable]:
    """NHWC Pallas spatial kernel for the plain 2-D case, at the kernel's
    own ``BLOCK_O``; None when the geometry doesn't fit, and the caller
    dispatches to :func:`lower_conv`.

    Eligibility, checked statically on every backend so the plan is the
    same in interpret mode and on the chip:

      * groups == 1, square stride and padding, symmetric padding (the
        kernel's signature);
      * ``kernels.gconv_spatial.mosaic_refusal`` is None. Each of its
        rules is a refusal seen compiling for a TPU v5e:

        - stride != 1 — full-width AlexNet conv1 (32x227x227x3, 11x11,
          stride 4): "'vector.extract_strided_slice' op expected strides
          to be confined to [1, 2)";
        - block_o < O and not a multiple of 128 — e.g. block_o=64 at
          AN conv3 (O=384): "The Pallas TPU lowering
          currently requires that the last two dimensions of your block
          shape are divisible by 8 and 128 respectively";
        - double-buffered blocks over ``VMEM_BLOCK_BUDGET`` — a
          (32,112,112,64) 3x3 conv, and (32,80,80,64) 3x3: "Ran out of
          memory in memory space vmem while allocating on stack". The
          input block is counted as the kernel holds it: one padded
          image whose W axis is widened by ``block_width(ow) - ow``
          zero columns, so that every tap reads a window whose width
          is a multiple of the 8-row sublane tile."""
    from ..kernels.gconv_spatial import mosaic_refusal

    ch, windows, batch = plan
    dims = node.dims
    dch = dims[ch]
    if len(windows) != 2 or dch.ng != 1:
        return None
    dh, dw = dims[windows[0]], dims[windows[1]]
    if (dh.stride, dh.pad) != (dw.stride, dw.pad):
        return None
    if dh.padr != dh.pad or dw.padr != dw.pad:
        return None
    if mosaic_refusal(dh.nips, dw.nips, dch.in_size, dh.nks, dw.nks, dch.nop,
                      stride=dh.stride, pad=dh.pad):
        return None

    from ..kernels.gconv_spatial import gconv_spatial

    def fn(x, k, lookup):
        ct = _compute_dtype(x)
        x = x.astype(ct)
        x = ops.apply_unary_seq(node.pre, x, lookup)
        perm = batch + [ch] + windows
        xb = jnp.transpose(x, perm)
        b_sizes = [dims[i].in_size for i in batch]
        nb = int(np.prod(b_sizes)) if b_sizes else 1
        xb = xb.reshape(nb, dch.in_size, dh.nips, dw.nips)
        xb = jnp.transpose(xb, (0, 2, 3, 1))                 # NHWC
        kb = jnp.transpose(k.astype(ct), [ch] + windows + batch)
        kb = kb.reshape(dch.nop, dch.nks, dh.nks, dw.nks)    # OIHW
        kb = jnp.transpose(kb, (2, 3, 1, 0))                 # HWIO
        y = gconv_spatial(xb, kb, stride=dh.stride, pad=dh.pad)
        y = jnp.transpose(y, (0, 3, 1, 2))
        y = y.reshape(tuple(b_sizes) + (dch.nop, dh.nopc, dw.nopc))
        y = jnp.transpose(y, np.argsort(perm)).reshape(node.out_shape)
        return _finish(node, y, lookup)

    return fn


def depthwise_refusal(node: GConv, plan) -> Optional[str]:
    """Why :func:`lower_depthwise_pallas` will not take this depthwise
    conv, or None when it does. Checked from the ``DimSpec``s alone, so the
    plan is the same in interpret mode and on the chip."""
    from ..kernels.gconv_depthwise import mosaic_refusal

    ch, windows, batch = plan
    dims = node.dims
    if not is_depthwise(node, plan):
        return "not depthwise"
    if len(windows) != 2:
        return f"{len(windows)} window dims, the kernel's plane has 2"
    dh, dw = dims[windows[0]], dims[windows[1]]
    if (dh.nks, dh.stride, dh.pad) != (dw.nks, dw.stride, dw.pad):
        return "window, stride or padding differ between H and W"
    for d in (dh, dw):
        # symmetric padding must give the chain's output size; a right
        # pad below the left one only drops taps no output reads
        if (d.nips + 2 * d.pad - d.nks) // d.stride + 1 != d.nopc \
                or d.padr > d.pad:
            return (f"padding ({d.pad}, {d.padr}) is not the kernel's "
                    f"symmetric {d.pad}")
    return mosaic_refusal(dh.nips, dw.nips, dims[ch].ng, dh.nks,
                          stride=dh.stride, pad=dh.pad)


def lower_depthwise_pallas(node: GConv, plan) -> Optional[Callable]:
    """Channels-last Pallas depthwise kernel (``kernels.gconv_depthwise``,
    float32 on the VPU); None where :func:`depthwise_refusal` names a
    reason, and the caller dispatches to :func:`lower_conv` at
    ``Precision.HIGHEST``."""
    if depthwise_refusal(node, plan) is not None:
        return None
    from ..kernels.gconv_depthwise import gconv_depthwise

    ch, windows, batch = plan
    dims = node.dims
    dch, dh, dw = dims[ch], dims[windows[0]], dims[windows[1]]
    perm = batch + [ch] + windows
    b_sizes = [dims[i].in_size for i in batch]
    nb = int(np.prod(b_sizes)) if b_sizes else 1

    def fn(x, k, lookup):
        ct = _compute_dtype(x)
        x = x.astype(ct)
        x = ops.apply_unary_seq(node.pre, x, lookup)
        xb = jnp.transpose(x, perm).reshape(nb, dch.ng, dh.nips, dw.nips)
        xb = jnp.transpose(xb, (0, 2, 3, 1))                 # NHWC
        kb = jnp.transpose(k.astype(ct), [ch] + windows + batch)
        kb = jnp.transpose(kb.reshape(dch.ng, dh.nks, dw.nks), (1, 2, 0))
        y = gconv_depthwise(xb, kb, stride=dh.stride, pad=dh.pad)
        y = jnp.transpose(y, (0, 3, 1, 2))
        y = y.reshape(tuple(b_sizes) + (dch.ng, dh.nopc, dw.nopc))
        y = jnp.transpose(y, np.argsort(perm)).reshape(node.out_shape)
        return _finish(node, y, lookup)

    return fn


# ---------------------------------------------------------------------------
# grouped matmul: main=mul/reduce=add, no window dims -> (G,M,K) @ (G,K,N)
# ---------------------------------------------------------------------------
def match_grouped_matmul(node: GConv, classes: Sequence[str],
                         k_shape: Optional[Tuple[int, ...]]):
    """Assign each dim a role in the grouped contraction, or None.

    roles: g_ix (batch groups, kernel varies per group), m_ix (x-only
    output axes), c_ix (contractions contributing N=Nop / K=Nks).
    """
    if node.main != "mul" or node.reduce != "add" or k_shape is None:
        return None
    g_ix, m_ix, c_ix = [], [], []
    for i, (d, c) in enumerate(zip(node.dims, classes)):
        ka = k_shape[i]
        if c == BCAST and ka == 1:
            m_ix.append(i)
        elif c == BCAST and ka == d.k_size and d.nopc == 1:
            g_ix.append(i)
        elif c == CONTRACT and d.ng == 1 and ka == d.k_size:
            c_ix.append(i)
        elif c == CONTRACT and d.ng == 1 and ka == 1 and d.nop == 1:
            c_ix.append(i)              # kernel constant across the taps
        else:
            return None
    return g_ix, m_ix, c_ix


def _fused_matmul_seq(seq, dims, g_ix, m_ix, c_ix, stage, lookup):
    """Translate a pre/post Op sequence into the Pallas ``gconv_matmul``
    ``prologue``/``epilogue`` form: ``(name, const, slot)`` triples plus
    operand arrays reshaped to ``(G|1, M|1, 1)`` / ``(G|1, 1, L|1)``
    (L = K for the prologue, N for the epilogue). Returns None when an
    operand's broadcast pattern doesn't fit those layouts — the caller
    then applies the sequence in jnp instead."""
    triples, arrays = [], []
    for op in seq:
        if op.operand is None:
            triples.append((op.name, op.const, None))
            continue
        arr = lookup(op)
        if arr.ndim != len(dims):
            return None
        at = jnp.transpose(arr, g_ix + m_ix + c_ix)
        ng = len(g_ix)
        nm = len(m_ix)
        g_sz = at.shape[:ng]
        m_sz = at.shape[ng:ng + nm]
        c_sz = at.shape[ng + nm:]
        g_full = tuple(dims[i].ng for i in g_ix)
        m_full = tuple(dims[i].in_size for i in m_ix)
        c_full = tuple((dims[i].nks if stage == "pro" else dims[i].nop)
                       for i in c_ix)

        def collapse(sz, full):
            if all(s == 1 for s in sz):
                return 1
            if tuple(sz) == tuple(full):
                return int(np.prod(full)) if full else 1
            return None                      # mixed broadcast: not fusable

        gp, mp, cp = (collapse(g_sz, g_full), collapse(m_sz, m_full),
                      collapse(c_sz, c_full))
        if gp is None or mp is None or cp is None:
            return None
        if mp != 1 and cp != 1:              # (G, M, L) has no kernel layout
            return None
        triples.append((op.name, op.const, len(arrays)))
        arrays.append(at.reshape(gp, mp, cp))
    return tuple(triples), tuple(arrays)


def _tp_matmul(xb, kb, tp):
    """Tensor-parallel ``(G,M,K) @ (G,K,N)`` under a ``shard_map``.

    column: kernel sharded on N (the Cout/channel GCONV axis) — each shard
            computes its own output columns, no collective; the result
            stays N-sharded for downstream GSPMD propagation.
    row:    both operands sharded on K — partial products need the one
            explicit collective in the engine, a psum over the model axis.

    The data-parallel axis rides along on G (grouped/batched kernels) or
    M (plain batch rows) when it divides — ``dp_g``/``dp_m`` come from the
    plan — so DP + TP compose without gathers. Operands are explicitly
    constrained to the in_specs first: shard_map TRUSTS (does not enforce)
    that an unmentioned mesh axis means "replicated along it", and under
    data parallelism the operands arrive data-sharded — skipping the
    constraint silently computes garbage (caught by the zoo differential
    sweep on a (4, 2) mesh).

    Divisibility of N/K over the model axis is guaranteed by the plan
    (repro.exec.shardplan); an axis that doesn't divide never reaches
    here.
    """
    from jax.sharding import NamedSharding as _NS, PartitionSpec as _P

    mesh, ax, mode, dp_g, dp_m = tp
    if mode == "column":
        x_spec = _P(dp_g, dp_m, None)
        k_spec = _P(dp_g, None, ax)
        out_spec = _P(dp_g, dp_m, ax)
        mm = jnp.matmul
    else:
        x_spec = _P(dp_g, dp_m, ax)
        k_spec = _P(dp_g, ax, None)
        out_spec = _P(dp_g, dp_m, None)

        def mm(xs, ks):
            return jax.lax.psum(jnp.matmul(xs, ks), ax)

    xb = jax.lax.with_sharding_constraint(xb, _NS(mesh, x_spec))
    kb = jax.lax.with_sharding_constraint(kb, _NS(mesh, k_spec))
    return jax.shard_map(mm, mesh=mesh, in_specs=(x_spec, k_spec),
                         out_specs=out_spec)(xb, kb)


def lower_grouped_matmul(node: GConv, plan, *, pallas: bool = False,
                         tp=None) -> Callable:
    g_ix, m_ix, c_ix = plan
    dims = node.dims
    G = int(np.prod([dims[i].ng for i in g_ix])) if g_ix else 1
    M = int(np.prod([dims[i].in_size for i in m_ix])) if m_ix else 1
    K = int(np.prod([dims[i].nks for i in c_ix])) if c_ix else 1
    N = int(np.prod([dims[i].nop for i in c_ix])) if c_ix else 1

    def fn(x, k, lookup):
        ct = _compute_dtype(x)
        x = x.astype(ct)
        # on the Pallas path, ride the fused pre/post sequences in-register
        # (the §4.3 result) when their operands fit the kernel layouts
        pro = epi = None
        if pallas:
            pro = _fused_matmul_seq(node.pre, dims, g_ix, m_ix, c_ix,
                                    "pro", lookup)
            epi = _fused_matmul_seq(node.post, dims, g_ix, m_ix, c_ix,
                                    "epi", lookup)
        if pro is None:
            x = ops.apply_unary_seq(node.pre, x, lookup)
        xb = jnp.transpose(x, g_ix + m_ix + c_ix).reshape(G, M, K)
        # kernel: per-dim axes (g | squeeze-1 | (nop, nks)) -> (G, K, N)
        kshape, full, g_pos, nop_pos, nks_pos = [], [], [], [], []
        for i in g_ix + m_ix + c_ix:
            d, ka = dims[i], k.shape[i]
            if i in g_ix:
                g_pos.append(len(kshape))
                kshape.append(ka)       # kernel always full on g dims
                full.append(ka)
            elif i in m_ix:
                kshape.append(1)
                full.append(1)
            else:
                nop_pos.append(len(kshape))
                kshape.append(d.nop if ka != 1 else 1)
                full.append(d.nop)
                nks_pos.append(len(kshape))
                kshape.append(d.nks if ka != 1 else 1)
                full.append(d.nks)
        kb = jnp.transpose(k.astype(ct), g_ix + m_ix + c_ix).reshape(kshape)
        kb = jnp.broadcast_to(kb, full)   # expand broadcast-1 nop/nks axes
        rest = [p for p in range(len(kshape))
                if p not in g_pos + nop_pos + nks_pos]
        kb = jnp.transpose(kb, g_pos + nop_pos + nks_pos + rest)
        kb = kb.reshape(G, N, K).swapaxes(1, 2)              # (G, K, N)
        if pallas:
            from ..kernels.gconv_matmul import gconv_matmul
            pro_seq, pro_ops = pro if pro is not None else ((), ())
            epi_seq, epi_ops = epi if epi is not None else ((), ())
            epi_seq = tuple((nm, c, None if s is None else s + len(pro_ops))
                            for nm, c, s in epi_seq)
            y = gconv_matmul(xb, kb, prologue=pro_seq, epilogue=epi_seq,
                             operands=pro_ops + epi_ops)
        elif tp is not None:
            y = _tp_matmul(xb, kb, tp)                       # (G, M, N)
        else:
            y = jnp.matmul(xb, kb)                           # (G, M, N)
        out_axes = ([dims[i].ng for i in g_ix]
                    + [dims[i].in_size for i in m_ix]
                    + [dims[i].nop for i in c_ix])
        y = y.reshape(out_axes)
        y = jnp.transpose(y, np.argsort(g_ix + m_ix + c_ix))
        y = y.reshape(node.out_shape)
        if epi is not None:                  # post already ran in-register
            if node.out_dtype is not None:
                y = y.astype(node.out_dtype)
            return y
        return _finish(node, y, lookup)

    if tp is not None:
        # declare the tensor-parallel contract of this lowering where the
        # static verifier can see it: the branch conditions mirror
        # _tp_matmul exactly (row splits psum partial products; both modes
        # pin operand replication with with_sharding_constraint). The
        # repro.lint shard passes audit this against the ShardPlan.
        _mesh, _ax, _mode, _dp_g, _dp_m = tp
        fn.tp_meta = {"tp_mode": _mode, "axis": _ax,
                      "psum": _mode == "row", "constrained": True,
                      "dp_g": _dp_g, "dp_m": _dp_m}
    return fn


# ---------------------------------------------------------------------------
# generic windowed einsum: main=mul/reduce=add over any bcast/contract/window
# mix (conv-like weight-gradient patterns, grouped attention exotica)
# ---------------------------------------------------------------------------
def lower_einsum(node: GConv, classes: Sequence[str]) -> Callable:
    dims = node.dims
    letters = iter(string.ascii_letters)
    # per dim: labels (g, opc/ks-free, ks) for x; (g, op, ks) for kernel
    lab = [(next(letters), next(letters), next(letters), next(letters))
           for _ in dims]               # (g, op, opc, ks)

    def fn(x, k, lookup):
        ct = _compute_dtype(x)
        x = x.astype(ct)
        x = ops.apply_unary_seq(node.pre, x, lookup)
        x_sub = []
        offset = 0
        for i, (d, c) in enumerate(zip(dims, classes)):
            g, o, cc, ks = lab[i]
            ax = i + offset
            if c == BCAST:
                x = jnp.reshape(x, x.shape[:ax] + (d.ng, d.nopc)
                                + x.shape[ax + 1:])
                x_sub += [g, cc]
                offset += 1
            elif c == CONTRACT:
                x = jnp.reshape(x, x.shape[:ax] + (d.ng, d.nks)
                                + x.shape[ax + 1:])
                x_sub += [g, ks]
                offset += 1
            else:                       # window (ng == 1)
                w = _window_gather(x, ax, d, 0.0)
                x = jnp.moveaxis(w, (-2, -1), (ax, ax + 1))
                x_sub += [cc, ks]
                offset += 1
        k_sub, kshape = [], []
        for i, (d, c) in enumerate(zip(dims, classes)):
            g, o, cc, ks = lab[i]
            ka = k.shape[i]
            if ka == 1:
                kshape += [1, 1, 1]
            else:
                kshape += [d.ng, d.nop, d.nks]
            k_sub += [g, o, ks]
        kb = k.astype(ct).reshape(kshape)
        # drop singleton axes from both operands (einsum labels must agree
        # on size; a broadcast-1 axis simply leaves the label out)
        x_sub2 = [s for s, n in zip(x_sub, x.shape) if n != 1]
        xv = x.reshape([n for n in x.shape if n != 1])
        k_sub2 = [s for s, n in zip(k_sub, kb.shape) if n != 1]
        kv = kb.reshape([n for n in kb.shape if n != 1])
        # output labels: (g, op, opc) per dim, sizes from the dims
        out_sub, out_sizes = [], []
        for i, d in enumerate(dims):
            g, o, cc, ks = lab[i]
            for s, n in ((g, d.ng), (o, d.nop), (cc, d.nopc)):
                out_sub.append(s)
                out_sizes.append(n)
        kept = set(x_sub2) | set(k_sub2)
        out_keep = [s for s, n in zip(out_sub, out_sizes)
                    if n != 1 and s in kept]
        eq = (f"{''.join(x_sub2)},{''.join(k_sub2)}->{''.join(out_keep)}")
        y = jnp.einsum(eq, xv, kv)
        # re-broadcast output axes whose size>1 label vanished (kernel
        # broadcast across Nop) and restore singleton axes
        full = []
        pos = 0
        for s, n in zip(out_sub, out_sizes):
            if n != 1 and s in kept:
                full.append(y.shape[pos])
                pos += 1
            else:
                full.append(1)
        y = y.reshape(full)
        y = jnp.broadcast_to(y, out_sizes)
        y = y.reshape(node.out_shape)
        return _finish(node, y, lookup)

    return fn


# ---------------------------------------------------------------------------
# oracle fallback
# ---------------------------------------------------------------------------
def lower_oracle(node: GConv) -> Callable:
    from ..core.interpreter import eval_gconv

    def fn(x, k, lookup):
        return eval_gconv(node, x, k, lookup)

    return fn
