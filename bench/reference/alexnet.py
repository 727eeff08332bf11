"""Plain AlexNet forward pass, the benchmark's reference.

Written from Krizhevsky, Sutskever and Hinton, "ImageNet Classification
with Deep Convolutional Neural Networks" (NeurIPS 2012), section 3 and
Figure 2: NCHW float32 in ``jax.numpy``/``lax``, one function per layer,
no kernels, no fusion, no batching tricks. It imports nothing of the
program under test; its sizes (resolution, the five convs with their
groups, the fc widths, LRN, pools, dropout, classes) come from
``bench/configs/alexnet.json``. Parameters come in as a dict keyed by the
names below, in the shapes :func:`param_specs` gives; a conv weight is the
``(OC, IC / groups, k, k)`` tensor flattened to
``(1, OC * IC / groups, k, k)``, an fc weight the ``(out, in)`` matrix
flattened to ``(1, out * in)``.

A grouped conv is the paper's split over two GPUs: group ``g`` convolves
input channels ``g * IC/G ..`` with output filters ``g * OC/G ..`` alone.
It is written as that: ``G`` ordinary convs over channel slices, then a
concat, so it shares no code path with a grouped lowering
(``feature_group_count``).

Departures from the paper, matching the configuration as run: the input
is 227x227 (Caffe's ``bvlc_alexnet``; the paper says 224), LRN divides
alpha by n, as Caffe's LRN layer does, and dropout is the chain's explicit
mask input, which the benchmark fills with the keep probability so that
``x * mask / 0.5`` is the inference identity.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "configs", "alexnet.json")) as _f:
    CONFIG = json.load(_f)
CONVS = tuple(CONFIG["convs"])
FC = tuple(CONFIG["fc"])
CLASSES = CONFIG["classes"]
DROPOUT = CONFIG["dropout"]


def _sizes():
    """(in channels of each conv, flattened width after the last pool)."""
    side, c, ins = CONFIG["resolution"], CONFIG["in_channels"], []
    k, s = CONFIG["pool"]["size"], CONFIG["pool"]["stride"]
    for i, conv in enumerate(CONVS):
        ins.append(c)
        side = (side + 2 * conv["pad"] - conv["k"]) // conv["stride"] + 1
        if i in (0, 1, 4):                  # the three max pools
            side = (side - k) // s + 1
        c = conv["out"]
    return ins, c * side * side


def param_specs():
    """name -> (shape, role, fan_in); role is ``w``, ``fc_w`` or ``b``."""
    ins, flat = _sizes()
    specs = {}
    for conv, ci in zip(CONVS, ins):
        icg, k = ci // conv["groups"], conv["k"]
        specs[f"{conv['name']}.w"] = ((1, conv["out"] * icg, k, k), "w",
                                      icg * k * k)
        specs[f"{conv['name']}.b"] = ((1, conv["out"], 1, 1), "b",
                                      icg * k * k)
    widths = (flat,) + FC + (CLASSES,)
    for i, (fi, fo) in enumerate(zip(widths, widths[1:])):
        name = f"fc{6 + i}"
        role = "fc_w" if fo == CLASSES else "w"
        specs[f"{name}.w"] = ((1, fo * fi), role, fi)
        specs[f"{name}.b"] = ((1, fo), "b", fi)
    return specs


def input_specs(batch: int):
    """name -> shape of every chain input; the image comes first."""
    r = CONFIG["resolution"]
    return {"x": (batch, CONFIG["in_channels"], r, r),
            "dropout.mask": (batch, FC[0]), "dropout_1.mask": (batch, FC[1])}


def _conv(x, p, conv, dtype, precision):
    """``conv["groups"]`` convs over channel slices, concatenated."""
    g, oc, k = conv["groups"], conv["out"], conv["k"]
    icg, ocg = x.shape[1] // g, oc // g
    w = p[f"{conv['name']}.w"].reshape(oc, icg, k, k).astype(dtype)
    pad = ((conv["pad"], conv["pad"]),) * 2
    ys = [jax.lax.conv_general_dilated(
        x[:, i * icg:(i + 1) * icg], w[i * ocg:(i + 1) * ocg],
        (conv["stride"],) * 2, pad,
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=precision)
        for i in range(g)]
    y = ys[0] if g == 1 else jnp.concatenate(ys, axis=1)
    return y + p[f"{conv['name']}.b"].astype(dtype)


def _maxpool(x):
    k, s = CONFIG["pool"]["size"], CONFIG["pool"]["stride"]
    return jax.lax.reduce_window(
        x, jnp.array(-jnp.inf, x.dtype), jax.lax.max, (1, 1, k, k),
        (1, 1, s, s), "VALID")


def _lrn(x):
    n, alpha, beta, k = (CONFIG["lrn"][key]
                         for key in ("size", "alpha", "beta", "k"))
    sq = jnp.pad(x * x, ((0, 0), (n // 2, n // 2), (0, 0), (0, 0)))
    c = x.shape[1]
    s = sum(sq[:, i:i + c] for i in range(n))
    return x * (k + (alpha / n) * s) ** (-beta)


def _fc(x, p, name, dtype, precision):
    w = p[f"{name}.w"].reshape(-1, x.shape[1]).astype(dtype)
    return jnp.dot(x, w.T, precision=precision) + p[f"{name}.b"].astype(dtype)


def logits(params, inputs, *, dtype=jnp.float32,
           precision=jax.lax.Precision.HIGHEST):
    """Pre-softmax class scores ``(B, 1000)``, computed in ``dtype``."""
    relu = jax.nn.relu
    c1, c2, c3, c4, c5 = CONVS

    def conv(x, c):
        return relu(_conv(x, params, c, dtype, precision))

    def dropout(x, name):
        return x * inputs[name].astype(dtype) / jnp.asarray(1.0 - DROPOUT,
                                                            dtype)

    x = inputs["x"].astype(dtype)
    x = _maxpool(_lrn(conv(x, c1)))
    x = _maxpool(_lrn(conv(x, c2)))
    x = _maxpool(conv(conv(conv(x, c3), c4), c5))
    x = x.reshape(x.shape[0], -1)
    x = dropout(relu(_fc(x, params, "fc6", dtype, precision)), "dropout.mask")
    x = dropout(relu(_fc(x, params, "fc7", dtype, precision)),
                "dropout_1.mask")
    return _fc(x, params, "fc8", dtype, precision)
