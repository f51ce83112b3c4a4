"""Production mesh construction (TPU v5e pods; 256 chips/pod).

Defined as functions (never module-level constants) so importing this
module never touches jax device state — required because the dry-run must
set XLA_FLAGS before first jax init while tests see the real 1-CPU world.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants (per chip) — the roofline denominators.
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis in Auto (GSPMD) sharding mode."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Data-parallel (data, model) mesh over every device present:
    ``data`` is the device count and ``model`` is 1."""
    return make_mesh((len(jax.devices()), 1), ("data", "model"))


def mesh_chips(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
