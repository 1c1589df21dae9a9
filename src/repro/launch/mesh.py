"""Production meshes (TPU v5e): single pod = (data=16, model=16) = 256
chips; multi-pod = (pod=2, data=16, model=16) = 512 chips.

make_production_mesh is a FUNCTION so importing this module never
touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
init; smoke tests see the single real CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(n_model=1, n_data=1):
    """Tiny mesh over however many (forced) host devices exist; used by
    sharding unit tests with --xla_force_host_platform_device_count=8."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


# TPU v5e hardware constants for the roofline model (per chip)
PEAK_FLOPS_BF16 = 197e12       # FLOP/s
HBM_BW = 819e9                 # B/s
ICI_BW = 50e9                  # B/s per link (assumed one active link/op)
HBM_PER_CHIP = 16 * 1024 ** 3  # bytes
