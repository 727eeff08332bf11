"""Plain GoogLeNet (Inception v1) forward pass, the benchmark's reference.

Written from Szegedy et al. 2014 (arXiv:1409.4842), Table 1, without the
auxiliary heads: NCHW float32 in ``jax.numpy``/``lax``, one function per
layer, no kernels, no fusion, no batching tricks. It imports nothing of
the program under test; its sizes (resolution, channels, the inception
widths, LRN, dropout, classes) come from ``bench/configs/googlenet.json``. Parameters come in as a dict keyed by the names
below, in the shapes :func:`param_specs` gives; a conv weight is the
``(OC, IC, k, k)`` tensor flattened to ``(1, OC * IC, k, k)``, an fc
weight the ``(out, in)`` matrix flattened to ``(1, out * in)``.

Departures from the paper, matching the configuration as run: LRN uses
Caffe's form ``a / (2 + 1e-4 / 5 * sum_5 a^2) ** 0.75``, max pools round
their output size up (Caffe's ceil mode), and dropout is the chain's
explicit mask input, which the benchmark fills with the keep probability
so that ``x * mask / 0.6`` is the inference identity.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "configs", "googlenet.json")) as _f:
    CONFIG = json.load(_f)
# name, 1x1, 3x3 reduce, 3x3, 5x5 reduce, 5x5, pool proj
INCEPTION = tuple((name, *w) for name, w in CONFIG["inception"].items())
DROPOUT = CONFIG["dropout"]
CLASSES = CONFIG["classes"]


def _convs():
    """(name, in_c, out_c, k) of every conv layer, in order."""
    out = [("conv1", CONFIG["in_channels"], 64, 7), ("conv2r", 64, 64, 1),
           ("conv2", 64, 192, 3)]
    c = 192
    for name, b1, b3r, b3, b5r, b5, pp in INCEPTION:
        out += [(f"{name}.1x1", c, b1, 1), (f"{name}.3x3r", c, b3r, 1),
                (f"{name}.3x3", b3r, b3, 3), (f"{name}.5x5r", c, b5r, 1),
                (f"{name}.5x5", b5r, b5, 5), (f"{name}.proj", c, pp, 1)]
        c = b1 + b3 + b5 + pp
    return out, c


def param_specs():
    """name -> (shape, role, fan_in); role is ``w``, ``fc_w`` or ``b``."""
    convs, c = _convs()
    specs = {}
    for name, ci, co, k in convs:
        specs[f"{name}.w"] = ((1, co * ci, k, k), "w", ci * k * k)
        specs[f"{name}.b"] = ((1, co, 1, 1), "b", ci * k * k)
    specs["loss3.w"] = ((1, CLASSES * c), "fc_w", c)
    specs["loss3.b"] = ((1, CLASSES), "b", c)
    return specs


def input_specs(batch: int):
    """name -> shape of every chain input; the image comes first."""
    r = CONFIG["resolution"]
    return {"x": (batch, CONFIG["in_channels"], r, r),
            "dropout.mask": (batch, _convs()[1], 1, 1)}


def _conv(x, p, name, ci, co, k, stride, pad, dtype, precision):
    w = p[f"{name}.w"].reshape(co, ci, k, k).astype(dtype)
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=precision)
    return y + p[f"{name}.b"].astype(dtype)


def _maxpool(x, k, stride, pad, ceil):
    h = x.shape[2]
    span = h + 2 * pad - k
    n = (-(-span // stride) if ceil else span // stride) + 1
    right = (n - 1) * stride + k - h - pad
    return jax.lax.reduce_window(
        x, jnp.array(-jnp.inf, x.dtype), jax.lax.max, (1, 1, k, k),
        (1, 1, stride, stride), ((0, 0), (0, 0), (pad, right), (pad, right)))


def _lrn(x):
    n, alpha, beta, k = (CONFIG["lrn"][key]
                         for key in ("size", "alpha", "beta", "k"))
    sq = jnp.pad(x * x, ((0, 0), (n // 2, n // 2), (0, 0), (0, 0)))
    c = x.shape[1]
    s = sum(sq[:, i:i + c] for i in range(n))
    return x * (k + (alpha / n) * s) ** (-beta)


def logits(params, inputs, *, dtype=jnp.float32,
           precision=jax.lax.Precision.HIGHEST):
    """Pre-softmax class scores ``(B, 1000)``, computed in ``dtype``."""
    relu = jax.nn.relu
    convs = {name: (ci, co, k) for name, ci, co, k in _convs()[0]}

    def conv(x, name, stride=1, pad=None):
        ci, co, k = convs[name]
        return _conv(x, params, name, ci, co, k, stride,
                     k // 2 if pad is None else pad, dtype, precision)

    x = inputs["x"].astype(dtype)
    x = relu(conv(x, "conv1", stride=2, pad=3))
    x = _lrn(_maxpool(x, 3, 2, 0, True))
    x = relu(conv(x, "conv2r"))
    x = _lrn(relu(conv(x, "conv2")))
    x = _maxpool(x, 3, 2, 0, True)
    for name, *_ in INCEPTION:
        if name in ("4a", "5a"):
            x = _maxpool(x, 3, 2, 0, True)
        y1 = relu(conv(x, f"{name}.1x1"))
        y3 = relu(conv(relu(conv(x, f"{name}.3x3r")), f"{name}.3x3"))
        y5 = relu(conv(relu(conv(x, f"{name}.5x5r")), f"{name}.5x5"))
        yp = relu(conv(_maxpool(x, 3, 1, 1, False), f"{name}.proj"))
        x = jnp.concatenate([y1, y3, y5, yp], axis=1)
    x = jnp.mean(x, axis=(2, 3), keepdims=True)
    x = x * inputs["dropout.mask"].astype(dtype) / jnp.asarray(
        1.0 - DROPOUT, dtype)
    x = x.reshape(x.shape[0], -1)
    w = params["loss3.w"].reshape(CLASSES, -1).astype(dtype)
    return (jnp.dot(x, w.T, precision=precision)
            + params["loss3.b"].astype(dtype))
