"""Production mesh definitions (TPU v5e pods).

Functions, not module-level constants: importing this module never touches
jax device state. The dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import so these shapes are constructible on the CPU container.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto (compiler-chosen sharding
    inside jit, explicit specs at the boundaries)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU tests/examples (axes exist, size 1)."""
    return make_mesh((1, 1), ("data", "model"))


def make_data_mesh(n_shards: int):
    """1-D mesh over the ``data`` axis for the sharded data planes
    (DESIGN.md §15): the OTA fold's symbol axis and the retrieval
    arena's row axis both place over it. ``n_shards`` must not exceed
    the visible device count — on the CPU container that means setting
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* the
    first jax import (the multidevice test lane's subprocess helper,
    ``tests/_multidevice.py``, does exactly this)."""
    n = int(n_shards)
    if n < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    avail = len(jax.devices())
    if n > avail:
        raise ValueError(
            f"mesh of {n} data shards needs {n} devices but only {avail} "
            "visible; set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} before jax is imported (or lower the shard count)")
    return make_mesh((n,), ("data",))


# v5e hardware constants for the roofline model (per chip)
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link
