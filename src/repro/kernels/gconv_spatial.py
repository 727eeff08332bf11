"""Spatial GCONV kernel — sliding-window convolution with VMEM overlap-reuse.

The paper's core efficiency argument against im2col (TIP) is that overlap
windows should be *reused*, not replicated. On TPU that means: land the input
tile in VMEM ONCE and let every (kh, kw) tap read shifted views of the same
resident block, feeding the MXU with (spatial-positions x C) @ (C x O)
contractions. HBM traffic is exactly the unique input footprint — the
Table-3 input-movement formula, not the im2col-replicated one.

Blocking: grid (B, O-tiles). Each step holds one padded input image
(H+2p, W+2p, C) and one kernel slice (KH, KW, C, bo) in VMEM and produces the
(OH, OW, bo) output block. The static KH x KW Python loop unrolls into
MXU dots over the same VMEM block — this is the Eyeriss overlap-reuse
primitive (paper Fig. 8) re-derived for a vector/matrix memory hierarchy.

Nothing splits a feature map into halo tiles: the whole padded image is one
block, so Mosaic compiles the kernel only for stride 1 and for geometries
whose blocks fit :data:`VMEM_BLOCK_BUDGET`. :func:`mosaic_refusal` states
that rule; ``exec.lowering.lower_conv_pallas`` applies it on every backend
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
        return (4 * math.prod(shape[:-2]) * round_up(shape[-2], 8)
                * round_up(shape[-1], 128))

    return 2 * (tile(hp, wp, c) + tile(kh, kw, c, bo) + tile(oh, ow, bo))


def mosaic_refusal(h: int, w: int, c: int, kh: int, kw: int, o: int, *,
                   stride: int, pad: int,
                   block_o: int = BLOCK_O) -> Optional[str]:
    """Why Mosaic refuses ``gconv_spatial`` at this geometry, or None when
    it compiles. Each rule is a refusal seen compiling for a TPU v5e."""
    if stride != 1:
        return (f"stride {stride}: strided window slices are refused "
                f"('vector.extract_strided_slice' strides must be 1)")
    bo = min(block_o, o)
    if bo != o and bo % O_ALIGN:
        return (f"block_o {bo} < O={o} is not a multiple of {O_ALIGN} "
                f"(block shape must be (8, 128)-divisible or whole)")
    hp, wp = h + 2 * pad, w + 2 * pad
    need = vmem_block_bytes(hp, wp, c, kh, kw, hp - kh + 1, wp - kw + 1, bo)
    if need > VMEM_BLOCK_BUDGET:
        return (f"blocks need {need} B of VMEM > budget "
                f"{VMEM_BLOCK_BUDGET} B (whole padded image per block)")
    return None


def _kernel(x_ref, w_ref, o_ref, *, kh: int, kw: int, stride: int,
            oh: int, ow: int):
    x = x_ref[0].astype(jnp.float32)            # (H+2p, W+2p, C)
    C = x.shape[-1]
    acc = jnp.zeros((oh * ow, o_ref.shape[-1]), jnp.float32)
    for i in range(kh):                          # unrolled taps: overlap-reuse
        for j in range(kw):
            win = jax.lax.slice(
                x, (i, j, 0),
                (i + (oh - 1) * stride + 1, j + (ow - 1) * stride + 1, C),
                (stride, stride, 1))             # (oh, ow, C) shifted view
            wij = w_ref[i, j].astype(jnp.float32)     # (C, bo)
            acc += jax.lax.dot_general(
                win.reshape(oh * ow, C), wij,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    o_ref[0] = acc.reshape(oh, ow, -1)


def gconv_spatial(x: jax.Array, w: jax.Array, *, stride: int = 1,
                  pad: int = 0, block_o: int = BLOCK_O,
                  interpret: Optional[bool] = None) -> jax.Array:
    """NHWC conv: x (B, H, W, C), w (KH, KW, C, O) -> (B, OH, OW, O) f32.

    ``interpret`` resolves outside the jit boundary so the
    ``REPRO_FORCE_INTERPRET`` override keys the jit cache."""
    if interpret is None:
        interpret = use_interpret()
    return _gconv_spatial(x, w, stride=stride, pad=pad, block_o=block_o,
                          interpret=bool(interpret))


@functools.partial(
    jax.jit, static_argnames=("stride", "pad", "block_o", "interpret"))
def _gconv_spatial(x, w, *, stride, pad, block_o, interpret):
    B, H, W, C = x.shape
    KH, KW, C2, O = w.shape
    assert C == C2
    oh = (H + 2 * pad - KH) // stride + 1
    ow = (W + 2 * pad - KW) // stride + 1
    if pad:
        x = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    Hp, Wp = H + 2 * pad, W + 2 * pad
    bo = min(block_o, O)
    Op = cdiv(O, bo) * bo
    if Op != O:          # boundary blocks must be well-defined: zero-pad O
        w = jnp.pad(w, ((0, 0), (0, 0), (0, 0), (0, Op - O)))
    grid = (B, Op // bo)

    out = pl.pallas_call(
        functools.partial(_kernel, kh=KH, kw=KW, stride=stride, oh=oh, ow=ow),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Hp, Wp, C), lambda b, o: (b, 0, 0, 0)),
            pl.BlockSpec((KH, KW, C, bo), lambda b, o: (0, 0, 0, o)),
        ],
        out_specs=pl.BlockSpec((1, oh, ow, bo), lambda b, o: (b, 0, 0, o)),
        out_shape=jax.ShapeDtypeStruct((B, oh, ow, Op), jnp.float32),
        interpret=interpret,
    )(x, w)
    return out[..., :O]
