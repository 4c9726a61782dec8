"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
must set XLA_FLAGS before any jax initialization.
"""

from __future__ import annotations

import jax

# TPU v5e hardware constants used by the roofline analysis (launch/roofline.py)
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW_PER_LINK = 50e9  # bytes/s per link (~)


def _auto(axes):
    """``Auto`` axis types: ``jax.make_mesh`` defaults to ``Explicit``, which
    ``with_sharding_constraint`` (``distributed/sharding.py``) refuses."""
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_host_mesh(data: int = 2, model: int = 4):
    """Small mesh over host CPU devices for distributed tests."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(("data", "model")))
