"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
does not touch jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple):
    """`jax.make_mesh` with Auto axes: the partition rules annotate with
    `with_sharding_constraint`, which only accepts Auto mesh axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None, *, multi_pod: bool = False):
    """Small mesh over however many devices exist (tests)."""
    n = n_devices or len(jax.devices())
    if multi_pod and n % 2 == 0:
        model = 2 if n % 4 == 0 else 1
        return make_mesh((2, n // 2 // model, model),
                         ("pod", "data", "model"))
    model = 2 if n % 2 == 0 else 1
    return make_mesh((n // model, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
