"""Production mesh: 16x16 = 256 chips/pod; 2 pods = 512 chips multi-pod.

Defined as a FUNCTION so importing this module never touches jax device
state (the dry-run sets --xla_force_host_platform_device_count=512 before
any jax import; tests/benches see the real 1-CPU device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    # explicit Auto axes keep GSPMD's behaviour
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for CI-scale sharding tests (8 host-platform devices)."""
    return _make_mesh((n_data, n_model), ("data", "model"))


# v5e hardware constants (roofline targets; see EXPERIMENTS.md §Roofline)
PEAK_BF16_FLOPS = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
