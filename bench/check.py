"""The comparison that decides ``correct``.

The timed call returns class probabilities. Their logarithms are the
pre-softmax logits less one constant per image, so ``log(p)`` compared
with the reference's ``log_softmax(logits)`` compares the logits
themselves, with no saturation to hide an error. Two numbers are read; a
cell's file (``bench/cells/<cell>.json``) says which it limits:

- ``logit_rel_rms``: the root mean square of that difference over every
  class of every sampled image, each image's constant removed, relative to
  the root mean square spread of the reference's logits about their
  per-image mean. One wrong image among a few hundred moves it.
- ``logit_rel_gm``: each image's own relative error (the same ratio over
  that image alone, floored at ``FLOOR``), and their geometric mean over
  the sampled images: the typical image's error. A precision lowered
  everywhere raises every image's error; rounding cascades in the program
  raise only some.
"""
from __future__ import annotations

import numpy as np

# an image's relative error counts as at least this in ``logit_rel_gm``: the
# level at which two float32 computations of the same logits agree, so one
# bit-exact image cannot pull the geometric mean to 0
FLOOR = 1e-6


def log_softmax(z) -> np.ndarray:
    z = np.asarray(z, np.float64)
    z = z - z.max(axis=-1, keepdims=True)
    return z - np.log(np.exp(z).sum(axis=-1, keepdims=True))


def readings(probs, ref_logits) -> dict:
    """``logit_rel_rms``, ``logit_rel_gm`` and ``logprob_gap`` (the widest
    ``|log p - log_softmax(ref)|``, reported beside them) of a stack of
    program outputs ``(images, classes)`` against the reference's logits of
    the same images; ``inf`` where a probability is not finite and
    positive."""
    p = np.asarray(probs, np.float64).reshape(-1, np.shape(probs)[-1])
    if not np.all(np.isfinite(p)) or not np.all(p > 0):
        return {"logit_rel_rms": float("inf"), "logit_rel_gm": float("inf"),
                "logprob_gap": float("inf")}
    ref = log_softmax(np.reshape(ref_logits, p.shape))
    d = np.log(p) - ref
    gap = float(np.max(np.abs(d)))
    d = d - d.mean(axis=-1, keepdims=True)
    spread = ref - ref.mean(axis=-1, keepdims=True)
    err2, spread2 = np.sum(d * d, axis=-1), np.sum(spread * spread, axis=-1)
    rel = float(np.sqrt(err2.sum() / spread2.sum()))
    per_image = np.maximum(np.sqrt(err2 / spread2), FLOOR)
    gm = float(np.exp(np.mean(np.log(per_image))))
    return {"logit_rel_rms": rel, "logit_rel_gm": gm, "logprob_gap": gap}


def verdict(values: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` for every limited number."""
    return {name: {"value": values.get(name, float("nan")), "limit": limit}
            for name, limit in limits.items()}


def passed(checks: dict) -> bool:
    """Every number at most its limit (nan fails)."""
    return all(c["value"] <= c["limit"] for c in checks.values())
