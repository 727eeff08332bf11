"""Spatial GCONV kernel — sliding-window convolution with VMEM overlap-reuse.

The paper's core efficiency argument against im2col (TIP) is that overlap
windows should be *reused*, not replicated. On TPU that means: land the input
tile in VMEM ONCE and let every (kh, kw) tap read shifted views of the same
resident block, feeding the MXU with (spatial-positions x C) @ (C x O)
contractions. HBM traffic is exactly the unique input footprint — the
Table-3 input-movement formula, not the im2col-replicated one.

Blocking: grid (B*G, O-tiles), G = 1 for an ungrouped conv. Each step
holds one padded input image (H+2p, W+2p+OWp-OW, C) and one kernel slice
(KH, KW, C, bo) in VMEM and produces the (OH, OW, bo) output block; in a
grouped conv C and the O-tiles are one group's. The static KH x KW
Python loop unrolls into MXU dots over the same VMEM block — this is the
Eyeriss overlap-reuse primitive (paper Fig. 8) re-derived for a
vector/matrix memory hierarchy.

Each tap reads its (OH, OWp, C) window straight from the input ref, where
OWp = :func:`block_width` (OW) is the output width rounded up to the
8-row sublane tile: the W offset is a sublane shift, the H offset a
leading-axis slice, and the reshape to (OH*OWp, C) for the dot only
merges whole tiles. At OW % 8 != 0 that reshape would otherwise relayout
every tap's window. The input's W axis takes OWp-OW extra zero columns on
the right, in the same ``jnp.pad`` as the conv padding; they feed only the
discarded outputs OW..OWp-1, and the store keeps the first OW columns, so
every kept output sums the same products in the same order.

A grouped conv (``groups=G``: each group's O/G outputs read only its
own C/G inputs) runs the same body, one grid row per image and group.
C/G need not fill the 128 lanes (AlexNet's 48 and 192), so no BlockSpec
could slice a group's channels out of (B, H, W, C): the wrapper splits
the input into (B*G, H, W, C/G), group-major, before the same
``jnp.pad``, each block
takes one group's whole channel axis, and the output is joined back from
(B*G, OH, OW, O/G) where it is sliced. The same ``pallas_call`` runs
under the jitted name ``_gconv_grouped``, so that a device trace tells
the grouped convs from the ungrouped ones (``_gconv_spatial``).

Nothing splits a feature map into halo tiles: the whole padded image is one
block, so Mosaic compiles the kernel only for stride 1 and for geometries
whose blocks fit :data:`VMEM_BLOCK_BUDGET`, the input block counted at its
widened width. :func:`mosaic_refusal` states that rule;
``exec.lowering.lower_conv_pallas`` applies it on every backend
(interpret mode included, so CPU and TPU plans agree) and sends refused
convs to ``lax.conv_general_dilated``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import cdiv, round_up, use_interpret

BLOCK_O = 128                   # default output-channel block
SUBLANE = 8                     # rows of an f32 (8, 128) tile
O_ALIGN = 128                   # a block narrower than O must fill lanes
# Double-buffered f32 input + weight + output blocks, each padded to the
# (8, 128) tile. Compiling for a TPU v5e, every zoo geometry at or under
# 11 MiB by this count compiled, and the smallest refusal
# ("Ran out of memory in memory space vmem") counted 11.4 MiB.
VMEM_BLOCK_BUDGET = 8 * 2**20


def vmem_block_bytes(hp: int, wp: int, c: int, kh: int, kw: int,
                     oh: int, ow: int, bo: int) -> int:
    """VMEM bytes of the kernel's double-buffered blocks: one padded image
    (hp, wp, c), one weight slice (kh, kw, c, bo), one output block
    (oh, ow, bo), all f32 and padded to the (8, 128) sublane x lane tile."""
    def tile(*shape):
        return (4 * math.prod(shape[:-2]) * round_up(shape[-2], SUBLANE)
                * round_up(shape[-1], 128))

    return 2 * (tile(hp, wp, c) + tile(kh, kw, c, bo) + tile(oh, ow, bo))


def block_width(ow: int) -> int:
    """Output columns one grid step computes for an output ``ow`` wide:
    ``ow`` rounded up to the sublane tile, so that each tap's window
    reshapes to the dot's rows without a relayout."""
    return round_up(ow, SUBLANE)


def out_block(o: int, groups: int, block_o: int = BLOCK_O) -> int:
    """Output channels of one grid step, for ``o`` outputs a group:
    ``block_o``, or all of ``o`` where it is smaller. A grouped conv whose
    ``o`` is no multiple of ``block_o`` takes its whole group in one block
    (AlexNet conv4: 192), not a zero-padded second one."""
    if groups > 1 and o % block_o:
        return o
    return min(block_o, o)


def mosaic_refusal(h: int, w: int, c: int, kh: int, kw: int, o: int, *,
                   stride: int, pad: int, block_o: int = BLOCK_O,
                   groups: int = 1) -> Optional[str]:
    """Why Mosaic refuses ``gconv_spatial`` at this geometry (``c`` and
    ``o`` over all ``groups``), or None when it compiles. Each rule is a
    refusal seen compiling for a TPU v5e; the block rules count one
    group's block."""
    if stride != 1:
        return (f"stride {stride}: strided window slices are refused "
                f"('vector.extract_strided_slice' strides must be 1)")
    c, o = c // groups, o // groups
    bo = out_block(o, groups, block_o)
    if bo != o and bo % O_ALIGN:
        return (f"block_o {bo} < O={o} is not a multiple of {O_ALIGN} "
                f"(block shape must be (8, 128)-divisible or whole)")
    hp, wp = h + 2 * pad, w + 2 * pad
    oh, ow = hp - kh + 1, wp - kw + 1
    need = vmem_block_bytes(hp, wp + block_width(ow) - ow, c, kh, kw,
                            oh, ow, bo)
    if need > VMEM_BLOCK_BUDGET:
        return (f"blocks need {need} B of VMEM > budget "
                f"{VMEM_BLOCK_BUDGET} B (whole padded image per block)")
    return None


def _kernel(x_ref, w_ref, o_ref, *, kh: int, kw: int, stride: int,
            oh: int, ow: int, owp: int):
    C = x_ref.shape[-1]
    acc = jnp.zeros((oh * owp, o_ref.shape[-1]), jnp.float32)
    for i in range(kh):                          # unrolled taps: overlap-reuse
        for j in range(kw):
            win = x_ref[0, pl.ds(i, (oh - 1) * stride + 1),
                        pl.ds(j, (owp - 1) * stride + 1), :]
            if stride != 1:
                win = win[::stride, ::stride]
            win = win.astype(jnp.float32)        # (oh, owp, C) shifted view
            wij = w_ref[i, j].astype(jnp.float32)     # (C, bo)
            acc += jax.lax.dot_general(
                win.reshape(oh * owp, C), wij,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    o_ref[0] = acc.reshape(oh, owp, -1)[:, :ow]


def gconv_spatial(x: jax.Array, w: jax.Array, *, groups: int = 1,
                  stride: int = 1, pad: int = 0, block_o: int = BLOCK_O,
                  interpret: Optional[bool] = None) -> jax.Array:
    """NHWC conv: x (B, H, W, C), w (KH, KW, C, O) -> (B, OH, OW, O) f32.

    At ``groups=G > 1``, w is (G, KH, KW, C/G, O/G), and the channels of x
    and of the output are group-major: group g reads x's channels
    g*C/G.. and writes outputs g*O/G.. (``feature_group_count=G``).

    ``interpret`` resolves outside the jit boundary so the
    ``REPRO_FORCE_INTERPRET`` override keys the jit cache."""
    if w.ndim != (4 if groups == 1 else 5) or (groups > 1
                                                and w.shape[0] != groups):
        raise ValueError(f"groups={groups} takes w as (KH, KW, C, O) at 1, "
                         f"else (G, KH, KW, C/G, O/G); got {w.shape}")
    if interpret is None:
        interpret = use_interpret()
    call = _gconv_spatial if groups == 1 else _gconv_grouped
    return call(x, w, stride=stride, pad=pad, block_o=block_o,
                interpret=bool(interpret))


def _call(x, w, *, stride, pad, block_o, interpret):
    """The kernel over grid (B*G, O-tiles): image b, group g is row
    b*G + g of a group-major (B*G, H, W, C/G) input. A 4-D w is one
    group, and its input is x as it is."""
    B, H, W, C = x.shape
    G = w.shape[0] if w.ndim == 5 else 1
    KH, KW, Cg, O = w.shape[-4:]
    assert C == G * Cg
    oh = (H + 2 * pad - KH) // stride + 1
    ow = (W + 2 * pad - KW) // stride + 1
    owp = block_width(ow)
    extra = (owp - ow) * stride          # zero columns for outputs ow..owp-1
    if G > 1:
        x = jnp.transpose(x.reshape(B, H, W, G, Cg), (0, 3, 1, 2, 4))
        x = x.reshape(B * G, H, W, Cg)
    if pad or extra:
        x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad + extra), (0, 0)))
    Hp, Wp = H + 2 * pad, W + 2 * pad + extra
    bo = out_block(O, G, block_o)
    Op = cdiv(O, bo) * bo
    w = w.reshape(G * KH, KW, Cg, O)     # group g: rows g*KH..
    if Op != O:          # boundary blocks must be well-defined: zero-pad O
        w = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, Op - O)))

    out = pl.pallas_call(
        functools.partial(_kernel, kh=KH, kw=KW, stride=stride, oh=oh, ow=ow,
                          owp=owp),
        grid=(B * G, Op // bo),
        in_specs=[
            pl.BlockSpec((1, Hp, Wp, Cg), lambda r, o: (r, 0, 0, 0)),
            pl.BlockSpec((KH, KW, Cg, bo),
                         lambda r, o: (r % G if G > 1 else 0, 0, 0, o)),
        ],
        out_specs=pl.BlockSpec((1, oh, ow, bo), lambda r, o: (r, 0, 0, o)),
        out_shape=jax.ShapeDtypeStruct((B * G, oh, ow, Op), jnp.float32),
        interpret=interpret,
    )(x, w)
    out = out[..., :O]
    if G > 1:
        out = jnp.transpose(out.reshape(B, G, oh, ow, O), (0, 2, 3, 1, 4))
    return out.reshape(B, oh, ow, G * O)


# two jitted names for one kernel: a device trace names a kernel's custom
# call after the jitted function that launches it
@functools.partial(
    jax.jit, static_argnames=("stride", "pad", "block_o", "interpret"))
def _gconv_spatial(x, w, *, stride, pad, block_o, interpret):
    return _call(x, w, stride=stride, pad=pad, block_o=block_o,
                 interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("stride", "pad", "block_o", "interpret"))
def _gconv_grouped(x, w, *, stride, pad, block_o, interpret):
    return _call(x, w, stride=stride, pad=pad, block_o=block_o,
                 interpret=interpret)
