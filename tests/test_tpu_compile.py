"""Mosaic compiles of the main path's kernels at real widths, for a TPU v5e
that is described, not attached, plus the CPU side of the conv dispatch
rule those compiles forced (``exec.lowering.lower_conv_pallas``).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and every
test worker imports this file. A host that cannot describe it skips the
compile tests from the fixture.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.exec import compile_chain
from repro.kernels.chain_norm import chain_norm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gconv_depthwise import gconv_depthwise
from repro.kernels.gconv_depthwise import mosaic_refusal as dw_refusal
from repro.kernels.gconv_matmul import gconv_matmul
from repro.kernels.gconv_spatial import (VMEM_BLOCK_BUDGET, gconv_spatial,
                                         mosaic_refusal, vmem_block_bytes)
from repro.models import cnn


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shardings, *shapes):
    if not isinstance(shardings, (list, tuple)):
        shardings = [shardings] * len(shapes)
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sh)
            for s, sh in zip(shapes, shardings)]
    return jax.jit(fn).lower(*args).compile()


def test_gconv_matmul_compiles_at_alexnet_fc6(one_chip):
    _compile(functools.partial(gconv_matmul, interpret=False), one_chip,
             (1, 32, 9216), (1, 9216, 4096))


def test_gconv_matmul_refused_in_a_sharded_program(topo):
    """GSPMD cannot partition a Mosaic kernel: why a multi-device mesh
    plans ``auto`` as XLA's lowerings (``exec.shardplan.plan_backend``)."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        _compile(functools.partial(gconv_matmul, interpret=False),
                 [NamedSharding(mesh, P(None, "data", None)),
                  NamedSharding(mesh, P())],
                 (1, 32, 9216), (1, 9216, 4096))


# (B, H, W, C, K, O, pad): the unpadded maps of GoogLeNet conv2 and a
# DenseNet-121 dense-block 3x3 conv, both 58x58 once padded, and three
# GoogLeNet convs whose output widths (28, 14, 7) the kernel pads to the
# sublane tile
SPATIAL = {"GLN.conv2": (32, 56, 56, 64, 3, 192, 1),
           "DN.b0.conv3x3": (32, 56, 56, 128, 3, 32, 1),
           "GLN.3b.3x3": (32, 28, 28, 128, 3, 192, 1),
           "GLN.4e.5x5": (32, 14, 14, 32, 5, 128, 2),
           "GLN.5b.3x3": (32, 7, 7, 192, 3, 384, 1)}


@pytest.mark.parametrize("geom", list(SPATIAL.values()), ids=list(SPATIAL))
def test_gconv_spatial_compiles(one_chip, geom):
    B, H, W, C, K, O, pad = geom
    assert mosaic_refusal(H, W, C, K, K, O, stride=1, pad=pad) is None
    _compile(functools.partial(gconv_spatial, pad=pad, interpret=False),
             one_chip, (B, H, W, C), (K, K, C, O))


# (B, H, W, C, K, O, pad) over both groups of AlexNet's two-group convs
# at b32: C/G = 48 and 192 fill no lane tile, conv4's O/G = 192 takes a
# whole-group output block
GROUPED = {"AN.conv2": (32, 27, 27, 96, 5, 256, 2),
           "AN.conv4": (32, 13, 13, 384, 3, 384, 1),
           "AN.conv5": (32, 13, 13, 384, 3, 256, 1)}


@pytest.mark.parametrize("geom", list(GROUPED.values()), ids=list(GROUPED))
def test_gconv_spatial_grouped_compiles(one_chip, geom):
    B, H, W, C, K, O, pad = geom
    assert mosaic_refusal(H, W, C, K, K, O, stride=1, pad=pad,
                          groups=2) is None
    _compile(functools.partial(gconv_spatial, groups=2, pad=pad,
                               interpret=False),
             one_chip, (B, H, W, C), (2, K, K, C // 2, O // 2))


def test_vmem_rule_counts_the_widened_input_block():
    """A 14x14 3x3 conv (pad 1) computes 16 output columns, so its input
    block is 18 wide, not 16. At C = 704 that widening alone takes the
    blocks over the budget."""
    need = vmem_block_bytes(16, 18, 704, 3, 3, 14, 14, 128)
    assert vmem_block_bytes(16, 16, 704, 3, 3, 14, 14, 128) \
        <= VMEM_BLOCK_BUDGET < need
    why = mosaic_refusal(14, 14, 704, 3, 3, 128, stride=1, pad=1)
    assert f"blocks need {need} B" in why


# (B, H, W, C, stride) of MobileNet's depthwise convs at b32: the largest
# plane (32 channels on 128 lanes), the stride-2 phase split at 112, and
# the 14x14 and 7x7 planes that most of them have
DEPTHWISE = {"MN.dw0": (32, 112, 112, 32, 1), "MN.dw1": (32, 112, 112, 64, 2),
             "MN.dw6": (32, 14, 14, 512, 1), "MN.dw11": (32, 14, 14, 512, 2),
             "MN.dw12": (32, 7, 7, 1024, 1)}


@pytest.mark.parametrize("geom", list(DEPTHWISE.values()), ids=list(DEPTHWISE))
def test_gconv_depthwise_compiles(one_chip, geom):
    B, H, W, C, stride = geom
    assert dw_refusal(H, W, C, 3, stride=stride, pad=1) is None
    _compile(functools.partial(gconv_depthwise, stride=stride, pad=1,
                               interpret=False),
             one_chip, (B, H, W, C), (3, 3, C))


def test_chain_norm_compiles(one_chip):
    _compile(functools.partial(chain_norm, interpret=False), one_chip,
             (2048, 2048), (2048,))


def test_flash_attention_compiles(one_chip):
    _compile(functools.partial(flash_attention, interpret=False), one_chip,
             (32, 2048, 64), (32, 2048, 64), (32, 2048, 64))


# geometries the v5e compiler refuses, each with the rule that catches it
REFUSED = {
    "AN.conv1-stride4": ((32, 227, 227, 3, 11, 96), 4, 0, 128, "stride"),
    "AN.conv3-block64": ((32, 13, 13, 256, 3, 384), 1, 1, 64, "block_o"),
    "112x112x64-vmem": ((32, 112, 112, 64, 3, 64), 1, 1, 128, "VMEM"),
}


@pytest.mark.parametrize("case", list(REFUSED.values()), ids=list(REFUSED))
def test_refused_geometry_fails_mosaic_and_the_rule(one_chip, case):
    (B, H, W, C, K, O), stride, pad, block_o, why = case
    assert why in mosaic_refusal(H, W, C, K, K, O, stride=stride, pad=pad,
                                 block_o=block_o)
    with pytest.raises(Exception):
        _compile(functools.partial(gconv_spatial, stride=stride, pad=pad,
                                   block_o=block_o, interpret=False),
                 one_chip, (B, H, W, C), (K, K, C, O))


def test_refused_grouped_geometry_fails_mosaic_and_the_rule(one_chip):
    """The VMEM rule counts one group's blocks: two groups of 64 channels
    on a 112x112 map hold the blocks of the refused ungrouped
    112x112x64 conv."""
    B, H, W, C, K, O = 32, 112, 112, 128, 3, 128
    assert "VMEM" in mosaic_refusal(H, W, C, K, K, O, stride=1, pad=1,
                                    groups=2)
    assert mosaic_refusal(H, W, C // 2, K, K, O // 2, stride=1, pad=1) \
        == mosaic_refusal(H, W, C, K, K, O, stride=1, pad=1, groups=2)
    with pytest.raises(Exception):
        _compile(functools.partial(gconv_spatial, groups=2, pad=1,
                                   interpret=False),
                 one_chip, (B, H, W, C), (2, K, K, C // 2, O // 2))


def test_alexnet_conv1_plans_lax_under_pallas():
    """Full-width AN under ``backend="pallas"``: conv1 (stride 4) takes
    ``conv:lax`` through the eligibility rule, and so do the two-group
    conv4 and conv5, which no max pool feeds, while ungrouped conv3 and
    two-group conv2, after a max pool, keep the Pallas kernel."""
    eng = compile_chain(cnn.build("AN"), backend="pallas")
    for name in ("conv1", "conv4", "conv5"):
        assert eng.dispatch[name] == "conv:lax"
    for name in ("conv2", "conv3"):
        assert eng.dispatch[name] == "conv:pallas"
    assert eng.dispatch["fc6"] == "matmul:pallas"
