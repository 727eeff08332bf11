"""Work counts of the benchmark (bench/work.py) against closed forms written
from the papers' layer tables, and the plan-derived shares read from them."""
from types import SimpleNamespace

import pytest

from bench import work
from bench.spec import metric_reader
from repro.exec import compile_chain
from repro.models import cnn

INCEPTION = {  # name: spatial size, (1x1, 3x3r, 3x3, 5x5r, 5x5, proj)
    "3a": (28, (64, 96, 128, 16, 32, 32)),
    "3b": (28, (128, 128, 192, 32, 96, 64)),
    "4a": (14, (192, 96, 208, 16, 48, 64)),
    "4b": (14, (160, 112, 224, 24, 64, 64)),
    "4c": (14, (128, 128, 256, 24, 64, 64)),
    "4d": (14, (112, 144, 288, 32, 64, 64)),
    "4e": (14, (256, 160, 320, 32, 128, 128)),
    "5a": (7, (256, 160, 320, 32, 128, 128)),
    "5b": (7, (384, 192, 384, 48, 128, 128)),
}
MOBILENET = ((64, 1), (128, 2), (128, 1), (256, 2), (256, 1), (512, 2),
             (512, 1), (512, 1), (512, 1), (512, 1), (512, 1), (1024, 2),
             (1024, 1))


def googlenet_macs_per_image() -> int:
    """Szegedy et al. 2014, Table 1: every conv and the classifier."""
    m = 112 * 112 * 64 * 3 * 49 + 56 * 56 * 64 * 64 + 56 * 56 * 192 * 64 * 9
    c = 192
    for s, (b1, b3r, b3, b5r, b5, pp) in INCEPTION.values():
        m += s * s * (c * (b1 + b3r + b5r + pp) + b3r * b3 * 9
                      + b5r * b5 * 25)
        c = b1 + b3 + b5 + pp
    return m + 1024 * 1000


def mobilenet_macs_per_image():
    """Howard et al. 2017, Table 1: (standard and pointwise convs plus the
    classifier, depthwise convs)."""
    std, dw = 112 * 112 * 32 * 27, 0
    s, c = 112, 32
    for out_c, stride in MOBILENET:
        s //= stride
        dw += s * s * c * 9
        std += s * s * out_c * c
        c = out_c
    return std + 1024 * 1000, dw


def _traditional_macs(chain) -> int:
    return sum(work.macs(node) for n, node in chain.nodes.items()
               if chain.meta.get(n, {}).get("traditional")
               and hasattr(node, "dims"))


@pytest.mark.parametrize("net,batch", [("MN", 32), ("GLN", 32), ("GLN", 1)])
def test_conv_fc_macs_match_closed_forms(net, batch):
    chain = cnn.build(net, batch=batch)
    if net == "GLN":
        assert work.model_macs(chain) == googlenet_macs_per_image() * batch
    else:
        std, dw = mobilenet_macs_per_image()
        assert work.model_macs(chain) == (std + dw) * batch


@pytest.mark.parametrize("net,batch,expect", [
    ("MN", 32, 17_804_833_792),
    ("GLN", 32, 51_157_096_960),
    ("GLN", 1, 1_598_659_280),
])
def test_traditional_layer_counts_match_the_chain_statistics(net, batch,
                                                              expect):
    """The main-op applications of the LeNet-era layers (conv, fc and the
    ReLU, max pool and softmax passes), as the chain's Table-1 statistics
    count them; they exceed the conv/fc MACs by the elementwise passes."""
    chain = cnn.build(net, batch=batch)
    assert _traditional_macs(chain) == expect
    assert work.model_macs(chain) < expect + (
        mobilenet_macs_per_image()[1] * batch if net == "MN" else 0)


def test_least_seconds_is_flops_over_the_peak():
    assert work.least_seconds([200, 300], {"flops_per_s": 100.0}) == 5.0


@pytest.mark.parametrize("net,expect_pct", [
    ("MN", 100 * 15_652_356_096 / (32 * sum(mobilenet_macs_per_image()))),
    ("GLN", 100 * (35_023_650_816 + 7_482_900_480)
     / (32 * googlenet_macs_per_image())),
])
def test_pallas_mac_share_of_the_chip_plan(monkeypatch, net, expect_pct):
    # plan as on the chip: Pallas kernels admitted, nothing executed
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")
    chain = cnn.build(net, batch=32)
    eng = compile_chain(chain, backend="auto", lint="off")
    ctx = SimpleNamespace(chain=chain, engine=eng)
    assert metric_reader("pallas_mac_pct")(ctx) == pytest.approx(expect_pct)
    flops = work.step_flops(eng.chain, eng.steps, "matmul:pallas")
    assert flops and all(f > 0 for f in flops)


def test_mfu_reader_counts_every_call_of_the_window():
    chain = cnn.build("GLN", batch=1)
    window = SimpleNamespace(calls=500, seconds=2.0)
    ctx = SimpleNamespace(chain=chain, window=window,
                          peaks={"flops_per_s": 197e12})
    want = 100 * 2 * googlenet_macs_per_image() * 250 / 197e12
    assert metric_reader("mfu_pct")(ctx) == pytest.approx(want)
