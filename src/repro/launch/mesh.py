"""Production mesh construction.

Kept as FUNCTIONS so importing this module never touches jax device state;
the dry-run sets XLA_FLAGS before any jax import to fake 512 host devices.
"""
from __future__ import annotations

import jax

# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1, data: int | None = None) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = jax.device_count()
    data = data or (n // model)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
