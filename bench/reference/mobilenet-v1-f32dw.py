"""Plain MobileNet v1 forward pass, the benchmark's reference.

Written from Howard et al. 2017 (arXiv:1704.04861), Table 1, width
multiplier 1.0 at 224x224: NCHW float32 in ``jax.numpy``/``lax``, one
function per layer, no kernels and no fusion. It imports nothing of the
program under test. Parameters come in as a dict keyed by the names below,
in the shapes :func:`param_specs` gives; a conv weight is the
``(OC, IC / groups, k, k)`` tensor flattened to ``(1, OC * IC / groups,
k, k)``, the fc weight the ``(out, in)`` matrix flattened to
``(1, out * in)``. Its sizes (resolution, channels, the separable layers,
the norm's eps, classes) come from ``bench/configs/mobilenet-v1-f32dw.json``.

Departure from the paper, matching the configuration as run: batch
normalization takes its statistics from the batch itself, per activation
(mean and biased variance over the batch axis alone, eps 1e-5; the
paper's Table 2 FP1-FP4 of arXiv:2104.05541), followed by a learned
per-channel scale and shift. So every image's output depends on the
whole batch, and a batch of one is degenerate (every normalized value 0).

Departure from ``bench/reference/mobilenet-v1.py``, of which this is a
copy: the 13 depthwise convs always run at ``Precision.HIGHEST`` (float32
operands, products and sums), whatever ``precision`` the call asks of the
stem, the pointwise convs and the fc, as the configuration states. At
``"default"`` a TPU's depthwise conv has no stated arithmetic, so neither
would the comparison.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "configs", "mobilenet-v1-f32dw.json")) as _f:
    CONFIG = json.load(_f)
# (pointwise out channels, depthwise stride)
SEPARABLE = tuple(tuple(s) for s in CONFIG["separable"])
STEM = CONFIG["stem_channels"]
IN_C = CONFIG["in_channels"]
EPS = CONFIG["norm"]["eps"]
CLASSES = CONFIG["classes"]


def param_specs():
    """name -> (shape, role, fan_in); role is ``w``, ``fc_w``, ``b``,
    ``gamma`` or ``beta``."""
    specs = {"conv1.w": ((1, STEM * IN_C, 3, 3), "w", IN_C * 9)}

    def bn(name, c):
        specs[f"{name}.scale.gamma"] = ((1, c, 1, 1), "gamma", 1)
        specs[f"{name}.scale.beta"] = ((1, c, 1, 1), "beta", 1)

    bn("conv1", STEM)
    c = STEM
    for i, (out_c, _s) in enumerate(SEPARABLE):
        specs[f"dw{i}.w"] = ((1, c, 3, 3), "w", 9)
        bn(f"dw{i}", c)
        specs[f"pw{i}.w"] = ((1, out_c * c, 1, 1), "w", c)
        bn(f"pw{i}", out_c)
        c = out_c
    specs["fc.w"] = ((1, CLASSES * c), "fc_w", c)
    specs["fc.b"] = ((1, CLASSES), "b", c)
    return specs


def input_specs(batch: int):
    """name -> shape of every chain input; the image comes first."""
    r = CONFIG["resolution"]
    return {"x": (batch, IN_C, r, r)}


def _bn_scale_relu(x, params, name, dtype):
    mu = jnp.mean(x, axis=0, keepdims=True)
    t = x - mu
    y = t * jax.lax.rsqrt(jnp.mean(t * t, axis=0, keepdims=True)
                          + jnp.asarray(EPS, dtype))
    y = (y * params[f"{name}.scale.gamma"].astype(dtype)
         + params[f"{name}.scale.beta"].astype(dtype))
    return jax.nn.relu(y)


def _conv(x, w, stride, pad, groups, precision):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups, precision=precision)


def logits(params, inputs, *, dtype=jnp.float32,
           precision=jax.lax.Precision.HIGHEST):
    """Pre-softmax class scores ``(B, 1000)``, computed in ``dtype``;
    ``precision`` is that of every conv and matmul but the depthwise
    convs, which are at ``Precision.HIGHEST``."""
    def w(name, *shape):
        return params[f"{name}.w"].reshape(shape).astype(dtype)

    x = inputs["x"].astype(dtype)
    x = _conv(x, w("conv1", STEM, IN_C, 3, 3), 2, 1, 1, precision)
    x = _bn_scale_relu(x, params, "conv1", dtype)
    c = STEM
    for i, (out_c, s) in enumerate(SEPARABLE):
        x = _conv(x, w(f"dw{i}", c, 1, 3, 3), s, 1, c,
                  jax.lax.Precision.HIGHEST)
        x = _bn_scale_relu(x, params, f"dw{i}", dtype)
        x = _conv(x, w(f"pw{i}", out_c, c, 1, 1), 1, 0, 1, precision)
        x = _bn_scale_relu(x, params, f"pw{i}", dtype)
        c = out_c
    x = jnp.mean(x, axis=(2, 3))
    fc = params["fc.w"].reshape(CLASSES, c).astype(dtype)
    return jnp.dot(x, fc.T, precision=precision) + params["fc.b"].astype(dtype)
