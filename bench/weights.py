"""Weights and inputs from the seed, made on the device in one call each.

The recipe is the configuration's ``weights`` entry; the shapes and fan-ins
come from the configuration's reference (``param_specs``), so nothing here
reads the program. Weights are normal, scaled to fan-in so that no layer's
output vanishes or overflows and the softmax is not saturated:

- ``w``: std ``sqrt(w_gain / fan_in)`` (a conv or fc layer that a ReLU
  follows; gain 2 is He et al. 2015);
- ``fc_w``: std ``sqrt(fc_gain / fan_in)`` (the classifier);
- ``b``: std ``b_std``; ``gamma``: mean ``gamma_mean``, std ``gamma_std``;
  ``beta``: mean ``beta_mean``, std ``beta_std``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def key(seed: int):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed >> 32)


def _std_mean(role: str, fan_in: int, recipe) -> tuple:
    if role == "w":
        return math.sqrt(recipe["w_gain"] / fan_in), 0.0
    if role == "fc_w":
        return math.sqrt(recipe["fc_gain"] / fan_in), 0.0
    if role == "b":
        return recipe["b_std"], 0.0
    if role == "gamma":
        return recipe["gamma_std"], recipe["gamma_mean"]
    if role == "beta":
        return recipe["beta_std"], recipe["beta_mean"]
    raise ValueError(f"unknown parameter role {role!r}")


def make_params(specs, recipe, seed: int):
    """``{name: f32 array}`` for every ``(shape, role, fan_in)`` spec."""
    names = sorted(specs)
    plan = [(specs[n][0],) + _std_mean(specs[n][1], specs[n][2], recipe)
            for n in names]

    @jax.jit
    def make(k):
        keys = jax.random.split(k, len(plan))
        return [mean + std * jax.random.normal(ki, shape, jnp.float32)
                for ki, (shape, std, mean) in zip(keys, plan)]

    return dict(zip(names, make(jax.random.fold_in(key(seed), 1))))


def make_images(shape, pool: int, seed: int):
    """``pool`` distinct standard-normal image batches of ``shape``."""
    @jax.jit
    def make(k):
        return jax.random.normal(k, (pool,) + tuple(shape), jnp.float32)

    batches = make(jax.random.fold_in(key(seed), 2))
    return [batches[i] for i in range(pool)]
