"""Depthwise GCONV kernel — a ``Ng = C, Nks(C) = 1`` convolution on the VPU.

A depthwise conv gives every channel its own ``k x k`` window and no
channel contraction, so there is nothing for the MXU to do: each output is
``k * k`` products of one input channel with that channel's taps. The
kernel computes them in float32 on the VPU.

Stated arithmetic: operands float32 (the input and the taps are cast to
float32 before the kernel), every product ``x * w`` a float32 multiply,
and the ``k * k`` products of an output summed in float32, one add after
another in a fixed tap order. No operand is rounded to bfloat16, so the
result is that of ``lax.conv_general_dilated(..., feature_group_count=C,
precision=Precision.HIGHEST)`` up to the order of the float32 sum.

Two layouts, chosen by C, so that the lanes are full and XLA writes the
kernel's input without a relayout copy (both measured in MobileNet's
program on a TPU v5e, against a third that folded W and C onto the lanes):

- ``channels`` (C >= 128): ``(B, H, W, C)``, C on the 128 lanes, W on the
  sublanes, H leading. MobileNet's 7x7 to 56x56 planes with 128 to 1024
  channels fill their tiles, where W on the lanes would use 7 to 56 of
  128. Grid ``(B, C / cb)``; stride 2 is a phase split in the wrapper
  (``x[:, p::2, q::2]`` for the four phases ``(p, q)``), which XLA fuses
  into the op that writes the input.
- ``planes`` (C < 128): ``(B, H, C, W)``, W on the lanes, C on the
  sublanes, H leading: MobileNet's 112-wide planes with 32 or 64 channels,
  which would fill a quarter or half of the lanes channels-last. Grid
  ``(B,)``. Stride 2 takes every other row by splitting the leading axis
  in the kernel, and every column, at stride 1, the wrapper keeping every
  other one: a phase split of the lanes cost two relayout copies of the
  input.

Mosaic refuses strided vector slices, so the kernel only ever reads
unit-stride windows. The zero padding is the kernel's: each step copies
its input into a VMEM scratch whose border is zero, the data at a
tile-aligned offset, so no padded copy of the input is written to HBM.
Loops walk groups of output rows so that the running sum stays in a few
vector registers. :func:`mosaic_refusal` states which geometries the
kernel will not take; ``exec.lowering.lower_depthwise_pallas`` applies it
on every backend, so a CPU plan and a TPU plan agree, and sends refused
convs to ``lax.conv_general_dilated`` at ``Precision.HIGHEST``.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import cdiv, round_up, use_interpret

LANES = 128
# channels layout: scratch column at which each phase's data starts, one
# sublane tile, so the copy into the scratch is aligned and a left pad of
# up to 8 fits
OFF = 8
# double-buffered blocks plus the scratch, padded to the (8, 128) tile;
# MobileNet's largest step (dw1: a 112x112 plane of 64 channels) is
# 18.2 MiB, and a v5e core has 128 MiB
VMEM_BLOCK_BUDGET = 48 * 2**20
# the compiler's VMEM limit above the blocks, for the loops' values
VMEM_HEADROOM = 16 * 2**20
# a channel block smaller than C is kept under this, for pipelining
BLOCK_TARGET = 4 * 2**20
# vector registers of one row group's running sum
ACC_VREGS = 16


def _tile_bytes(*shape: int) -> int:
    """f32 bytes of a block padded to the (8, 128) sublane x lane tile."""
    return (4 * math.prod(shape[:-2]) * round_up(shape[-2], 8)
            * round_up(shape[-1], LANES))


def _row_group(n: int, per_row: int) -> int:
    """Rows per loop step: the largest divisor of ``n`` whose running sum,
    ``per_row`` vector registers a row, fits ``ACC_VREGS`` (at least one
    row)."""
    return max(r for r in range(1, n + 1)
               if n % r == 0 and (r == 1 or r * per_row <= ACC_VREGS))


# ---------------------------------------------------------------------------
# channels layout: (B, H, W, C), C >= 128
# ---------------------------------------------------------------------------
class Geometry(NamedTuple):
    oh: int          # output plane
    ow: int
    hs: int          # one phase of the (unpadded) input
    ws: int
    rows: int        # scratch rows and columns of one padded phase
    cols: int
    shift: tuple     # per padded phase p: (input phase, rows of pad above)


def geometry(h: int, w: int, k: int, stride: int, pad: int) -> Geometry:
    """Sizes of a ``k x k`` window at ``stride`` over an ``h x w`` plane
    padded by ``pad``. Padded row ``stride * i + p`` is input row
    ``stride * (i - d) + e`` of phase ``e = (p - pad) % stride``, with
    ``d = (e - p + pad) // stride`` rows of padding above it; columns
    alike."""
    s = stride
    oh = (h + 2 * pad - k) // s + 1
    ow = (w + 2 * pad - k) // s + 1
    hs, ws = cdiv(h, s), cdiv(w, s)
    reach = (k - 1) // s
    shift = tuple(((p - pad) % s, ((p - pad) % s - p + pad) // s)
                  for p in range(s))
    rows = max([oh + reach] + [d + hs for _e, d in shift])
    dmin = min(d for _e, d in shift)
    cols = round_up(OFF + max(ws, ow + reach - dmin), 8)
    return Geometry(oh, ow, hs, ws, rows, cols, shift)


def block_bytes(h: int, w: int, cb: int, k: int, *, stride: int,
                pad: int) -> int:
    """VMEM bytes of one step of the channels layout: the double-buffered
    input phases, taps and output plane, and the padded phases' scratch."""
    g = geometry(h, w, k, stride, pad)
    p = stride * stride
    return (2 * (_tile_bytes(p, g.hs, g.ws, cb) + _tile_bytes(k, k, cb)
                 + _tile_bytes(g.oh, g.ow, cb))
            + _tile_bytes(p, g.rows, g.cols, cb))


def channel_block(h: int, w: int, c: int, k: int, *, stride: int,
                  pad: int) -> int:
    """Channels per grid step: all of C where a step fits
    ``BLOCK_TARGET``, else the largest multiple of 128 dividing C that
    does, else 128 (or C, where C is not a multiple of 128)."""
    fits = lambda cb: block_bytes(h, w, cb, k, stride=stride,
                                  pad=pad) <= BLOCK_TARGET
    if c % LANES or fits(c):
        return c
    cands = [cb for cb in range(c - LANES, 0, -LANES) if c % cb == 0]
    return next((cb for cb in cands if fits(cb)), LANES)


def _channels_kernel(x_ref, w_ref, o_ref, xp_ref, *, k: int, stride: int,
                     g: Geometry, lanes: int):
    # x_ref (s*s, hs, ws, cb): the input's phases; w_ref (k, k, cb);
    # o_ref (oh, ow, cb); xp_ref (s*s, rows, cols, cb): padded phases,
    # data at column OFF
    s = stride
    reach = (k - 1) // s
    cb = o_ref.shape[-1]
    zero = lambda *shape: jnp.zeros(shape, jnp.float32)
    # the border: rows above and below the data, and the tile columns on
    # either side of it (before the data, which may share the right one)
    right = OFF + (g.ws // 8) * 8
    for p in range(s):
        for q in range(s):
            ph = p * s + q
            dp = g.shift[p][1]
            if dp:
                xp_ref[ph, :dp] = zero(dp, g.cols, cb)
            if g.rows > dp + g.hs:
                xp_ref[ph, dp + g.hs:] = zero(g.rows - dp - g.hs, g.cols, cb)
            xp_ref[ph, dp:dp + g.hs, :OFF] = zero(g.hs, OFF, cb)
            if g.cols > right:
                xp_ref[ph, dp:dp + g.hs, right:] = zero(
                    g.hs, g.cols - right, cb)
    crows = _row_group(g.hs, cdiv(g.ws, 8) * cdiv(cb, LANES))
    for p in range(s):
        for q in range(s):
            (e, dp), (f, _dq) = g.shift[p], g.shift[q]

            # input phase (e, f) is padded phase (p, q) less its pad; the
            # column shift is taken where the taps read
            def copy(i, carry, ph=p * s + q, src=e * s + f, dp=dp):
                r = pl.multiple_of(i * crows, crows)
                xp_ref[ph, pl.ds(r + dp, crows), pl.ds(OFF, g.ws)] = (
                    x_ref[src, pl.ds(r, crows)].astype(jnp.float32))
                return carry

            jax.lax.fori_loop(0, g.hs // crows, copy, 0)
    orows = _row_group(g.oh, cdiv(g.ow, 8) * cdiv(lanes, LANES))
    for lg in range(cb // lanes):
        ln = pl.ds(lg * lanes, lanes)
        taps = [[w_ref[a, b, ln].astype(jnp.float32).reshape(1, 1, lanes)
                 for b in range(k)] for a in range(k)]

        def body(i, carry, ln=ln, taps=taps):
            r0 = pl.multiple_of(i * orows, orows)
            acc = jnp.zeros((orows, g.ow, lanes), jnp.float32)
            for b in range(k):
                q, c0 = b % s, b // s
                col = OFF + c0 - g.shift[q][1]
                for p in range(min(s, k)):
                    # padded phase (p, q) from row r0: the taps (a, b)
                    # with a % s == p are row offsets of it
                    view = xp_ref[p * s + q, pl.ds(r0, orows + reach),
                                  pl.ds(col, g.ow), ln]
                    for a in range(p, k, s):
                        off = a // s
                        acc = acc + view[off:off + orows] * taps[a][b]
            o_ref[pl.ds(r0, orows), :, ln] = acc
            return carry

        jax.lax.fori_loop(0, g.oh // orows, body, 0)


def _phases(x, stride: int):
    """``(B, stride**2, ceil(H / stride), ceil(W / stride), C)``: phase
    ``p * stride + q`` is ``x[:, p::stride, q::stride]`` (zero-filled
    where H or W is not a multiple of the stride)."""
    B, H, W, C = x.shape
    s = stride
    if s == 1:
        return x[:, None]
    hs, ws = cdiv(H, s), cdiv(W, s)
    if (hs * s, ws * s) != (H, W):
        x = jnp.pad(x, ((0, 0), (0, hs * s - H), (0, ws * s - W), (0, 0)))
    x = jnp.transpose(x.reshape(B, hs, s, ws, s, C), (0, 2, 4, 1, 3, 5))
    return x.reshape(B, s * s, hs, ws, C)


def _channels_call(x, w, stride, pad, interpret):
    B, H, W, C = x.shape
    K = w.shape[0]
    g = geometry(H, W, K, stride, pad)
    cb = channel_block(H, W, C, K, stride=stride, pad=pad)
    lanes = LANES if cb % LANES == 0 else cb
    need = block_bytes(H, W, cb, K, stride=stride, pad=pad)
    P = stride * stride
    return pl.pallas_call(
        functools.partial(_channels_kernel, k=K, stride=stride, g=g,
                          lanes=lanes),
        grid=(B, C // cb),
        in_specs=[
            pl.BlockSpec((None, P, g.hs, g.ws, cb),
                         lambda b, c: (b, 0, 0, 0, c)),
            pl.BlockSpec((K, K, cb), lambda b, c: (0, 0, c)),
        ],
        out_specs=pl.BlockSpec((None, g.oh, g.ow, cb),
                               lambda b, c: (b, 0, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, g.oh, g.ow, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((P, g.rows, g.cols, cb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=need + VMEM_HEADROOM),
        interpret=interpret,
    )(_phases(x, stride), w)


# ---------------------------------------------------------------------------
# planes layout: (B, H, C, W), C < 128
# ---------------------------------------------------------------------------
class Planes(NamedTuple):
    oh: int          # output plane
    ow: int
    owf: int         # output columns at stride 1, of which every stride-th
                     # is kept
    hp: int          # scratch rows: the padded input and what the taps read
    lanes: int       # scratch lanes: the data at LANES, zeros either side


def planes(h: int, w: int, k: int, stride: int, pad: int) -> Planes:
    oh = (h + 2 * pad - k) // stride + 1
    ow = (w + 2 * pad - k) // stride + 1
    owf = w + 2 * pad - k + 1
    hp = max(h + 2 * pad, stride * oh + k - 1)
    lanes = round_up(LANES + max(w, owf + k - 1 - pad), LANES)
    return Planes(oh, ow, owf, hp, lanes)


def planes_bytes(h: int, w: int, c: int, k: int, *, stride: int,
                 pad: int) -> int:
    """VMEM bytes of one step of the planes layout: the double-buffered
    input plane, taps and output plane, and the padded plane's scratch."""
    g = planes(h, w, k, stride, pad)
    return (2 * (_tile_bytes(h, c, w) + _tile_bytes(k, k, c, g.owf)
                 + _tile_bytes(g.oh, c, g.ow))
            + _tile_bytes(g.hp, c, g.lanes))


def _planes_kernel(x_ref, w_ref, o_ref, xp_ref, *, k: int, stride: int,
                   pad: int, g: Planes, rows: int):
    # x_ref (h, c, w); w_ref (k, k, c, owf): the taps along the lanes;
    # o_ref (oh, c, ow); xp_ref (hp, c, lanes): the padded plane, data at
    # row pad and lane LANES
    s = stride
    h, c, w = x_ref.shape
    zero = lambda *shape: jnp.zeros(shape, jnp.float32)
    if pad:
        xp_ref[:pad] = zero(pad, c, g.lanes)
    if g.hp > pad + h:
        xp_ref[pad + h:] = zero(g.hp - pad - h, c, g.lanes)
    xp_ref[pad:pad + h, :, :LANES] = zero(h, c, LANES)

    def copy(i, carry):
        # the data's tile of lanes is zeroed first: its tail is the border
        xp_ref[pad + i, :, LANES:] = zero(c, g.lanes - LANES)
        xp_ref[pad + i, :, LANES:LANES + w] = x_ref[i].astype(jnp.float32)
        return carry

    jax.lax.fori_loop(0, h, copy, 0)
    if s > 1:
        # column s * j of the stride-1 row is output column j: a 0/1
        # matrix picks it on the MXU, exactly at float32 precision (a
        # phase split of the lanes cost XLA two relayout copies)
        iota = lambda d: jax.lax.broadcasted_iota(jnp.int32, (g.owf, g.ow), d)
        pick = (iota(0) == s * iota(1)).astype(jnp.float32)

    def body(i, carry):
        r0 = pl.multiple_of(i * rows, rows)
        acc = jnp.zeros((rows, c, g.owf), jnp.float32)
        for a in range(k):
            # padded rows s * (r0 + t) + a: contiguous rows, of which a
            # split of the leading axis keeps every s-th
            tap_rows = xp_ref[pl.ds(s * r0 + a, s * rows)]
            if s > 1:
                tap_rows = tap_rows.reshape(rows, s, c, g.lanes)[:, 0]
            for b in range(k):
                lo = LANES - pad + b
                acc = acc + tap_rows[:, :, lo:lo + g.owf] * w_ref[a, b]
        if s > 1:
            acc = jnp.dot(acc.reshape(rows * c, g.owf), pick,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
        o_ref[pl.ds(r0, rows)] = acc.reshape(rows, c, g.ow)
        return carry

    jax.lax.fori_loop(0, g.oh // rows, body, 0)


def _planes_call(x, w, stride, pad, interpret):
    B, H, W, C = x.shape
    K = w.shape[0]
    g = planes(H, W, K, stride, pad)
    rows = _row_group(g.oh, cdiv(C, 8) * cdiv(g.owf, LANES))
    need = planes_bytes(H, W, C, K, stride=stride, pad=pad)
    y = pl.pallas_call(
        functools.partial(_planes_kernel, k=K, stride=stride, pad=pad, g=g,
                          rows=rows),
        grid=(B,),
        in_specs=[pl.BlockSpec((None, H, C, W), lambda b: (b, 0, 0, 0)),
                  pl.BlockSpec((K, K, C, g.owf), lambda b: (0, 0, 0, 0))],
        out_specs=pl.BlockSpec((None, g.oh, C, g.ow),
                               lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, g.oh, C, g.ow), jnp.float32),
        scratch_shapes=[pltpu.VMEM((g.hp, C, g.lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=need + VMEM_HEADROOM),
        interpret=interpret,
    )(jnp.transpose(x, (0, 1, 3, 2)),
      jnp.broadcast_to(w[..., None], (K, K, C, g.owf)))
    return jnp.transpose(y, (0, 1, 3, 2))


# ---------------------------------------------------------------------------
# the rule and the entry point
# ---------------------------------------------------------------------------
def step_bytes(h: int, w: int, c: int, k: int, *, stride: int,
               pad: int) -> int:
    """VMEM bytes of one grid step in the layout C takes."""
    if c < LANES:
        return planes_bytes(h, w, c, k, stride=stride, pad=pad)
    cb = channel_block(h, w, c, k, stride=stride, pad=pad)
    return block_bytes(h, w, cb, k, stride=stride, pad=pad)


def mosaic_refusal(h: int, w: int, c: int, k: int, *, stride: int,
                   pad: int) -> Optional[str]:
    """Why the kernel will not take this geometry, or None when it does."""
    if stride not in (1, 2):
        return f"stride {stride}: the kernel covers strides 1 and 2"
    if not 0 <= pad <= min(k - 1, OFF):
        return f"pad {pad} outside [0, min(k - 1, {OFF})]"
    if h + 2 * pad < k or w + 2 * pad < k:
        return f"plane {h}x{w} padded by {pad} is smaller than the window"
    need = step_bytes(h, w, c, k, stride=stride, pad=pad)
    if need > VMEM_BLOCK_BUDGET:
        return (f"a step needs {need} B of VMEM > budget "
                f"{VMEM_BLOCK_BUDGET} B (one whole plane per step)")
    return None


def gconv_depthwise(x: jax.Array, w: jax.Array, *, stride: int = 1,
                    pad: int = 0,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Depthwise conv, channels last: x (B, H, W, C), w (K, K, C) ->
    (B, OH, OW, C) float32, each channel with its own ``K x K`` taps and
    symmetric zero padding ``pad``.

    ``interpret`` resolves outside the jit boundary so the
    ``REPRO_FORCE_INTERPRET`` override keys the jit cache."""
    if interpret is None:
        interpret = use_interpret()
    return _gconv_depthwise(x, w, stride=stride, pad=pad,
                            interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("stride", "pad", "interpret"))
def _gconv_depthwise(x, w, *, stride, pad, interpret):
    B, H, W, C = x.shape
    K, K2, C2 = w.shape
    assert K == K2 and C == C2, (x.shape, w.shape)
    why = mosaic_refusal(H, W, C, K, stride=stride, pad=pad)
    if why is not None:
        raise ValueError(f"gconv_depthwise refuses {x.shape}, k={K}: {why}")
    call = _planes_call if C < LANES else _channels_call
    return call(x.astype(jnp.float32), w.astype(jnp.float32), stride, pad,
                interpret)
