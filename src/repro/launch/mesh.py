"""Production mesh construction (dry-run target: TPU v5e pods).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — device count is locked
on first jax init, and only the dry-run entrypoint forces 512 host devices.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Mesh whose axes are all ``Auto``: GSPMD propagates layouts from the
    planner's ``NamedSharding``s and the models' ``shard_hint``
    constraints.  jax's default ``Explicit`` axes put shardings in the
    array types and refuse such constraints."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips when ``multi_pod``.

    Axes: ("data", "model") single-pod, ("pod", "data", "model") multi-pod.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# TPU v5e hardware constants (per chip) used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # 197 TFLOP/s
HBM_BW = 819e9                    # 819 GB/s
ICI_BW_PER_LINK = 50e9            # ~50 GB/s/link
