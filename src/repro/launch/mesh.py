"""Mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; tests see 1 device).

Topology: TPU v5e, 256 chips/pod (16x16 ICI). Single-pod mesh (data=16,
model=16); multi-pod adds a leading pod axis over DCI: (pod=2, data=16,
model=16) = 512 chips. The batch shards over ("pod", "data"); tensor/expert
parallelism over "model".
"""

from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh) -> Tuple[str, ...]:
    """The batch-sharding axes of a production mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def make_host_mesh():
    """Data-parallel mesh over every device this process sees:
    (data=len(jax.devices()), model=1). One chip (or the single CPU device
    of the tests) gives (1, 1); a four-chip host gives (4, 1)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
