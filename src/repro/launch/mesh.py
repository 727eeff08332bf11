"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is the cross-pod (DCN) data-parallel replica axis; "data" is
in-pod FSDP/data parallel; "model" is tensor/expert parallel on ICI.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax

from repro.shardpolicy import dp_axes  # noqa: F401  (re-export: the policy
# module owns the definition; launch code keeps importing it from here)
from repro.shardpolicy import parse_mesh_spec


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules here
    place arrays with ``with_sharding_constraint`` and let GSPMD propagate,
    which ``Explicit`` axes (the JAX default since 0.7) refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh over however many (virtual) devices a test asked for."""
    return make_mesh((n_data, n_model), ("data", "model"))


def mesh_from_spec(spec: str):
    """Parse a ``--mesh`` flag into a ("data", "model") mesh.

    ``"8"`` -> (8, 1) data-parallel; ``"4x2"`` -> (4, 2). The devices must
    already exist — on CPU hosts fake them BEFORE the first jax
    initialization with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (the recipe every ``--mesh``-taking CLI prints on failure).
    """
    d, m = parse_mesh_spec(spec)
    have = len(jax.devices())
    if d * m > have:
        raise RuntimeError(
            f"--mesh {spec} needs {d * m} devices but only {have} exist; "
            f"fake host devices with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={d * m} (must be set "
            f"before the first jax initialization)")
    return make_debug_mesh(d, m)
