"""Production mesh construction (TPU v5e pods; host-device placeholders on CPU).

Importing this module never touches jax device state — meshes are built only
inside the factory functions.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """Public mesh factory: every axis Auto-sharded (GSPMD propagation)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# Production geometry — the single source the executor's mesh presets and
# make_production_mesh both read.  16x16 = 256 chips/pod; 2 pods multi-pod.
POD_SHAPE = ((16, 16), ("data", "model"))
MULTIPOD_SHAPE = ((2, 16, 16), ("pod", "data", "model"))


def make_production_mesh(*, multi_pod: bool = False):
    shape, axes = MULTIPOD_SHAPE if multi_pod else POD_SHAPE
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count)."""
    return make_mesh(shape, axes)


# TPU v5e hardware constants used by the roofline (per chip).
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link (~unidirectional per direction)
VMEM_BYTES = 128 * 2 ** 20
HBM_BYTES = 16 * 2 ** 30
