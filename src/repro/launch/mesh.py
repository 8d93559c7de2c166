"""Production mesh construction.

Single pod: 16×16 (data, model) = 256 chips (TPU v5e pod slice).
Multi-pod:  2×16×16 (pod, data, model) = 512 chips; the "pod" axis carries
the cross-pod (DCN-class) collectives.

A FUNCTION, not a module constant — importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with auto axis types on every axis.

    ``jax.make_mesh`` defaults to explicit axes; every caller here relies on
    the auto (compiler-propagated) sharding behaviour.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=(1, 1), axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / examples)."""
    return make_mesh(shape, axes)
