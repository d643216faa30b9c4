"""Where a device input's rows lie.

The resident tree builders (`models/gbdt.py`: `build_gbt`,
`build_gbt_bagged`) take rows that are already on the device and build
where those rows are, instead of assuming the process's default mesh: a
jax.Array carries its own sharding, and with it the mesh its rows are
divided over. This module reads that layout (`rows_mesh`), refuses one
the builders cannot take, and lays the per-row arrays that come beside
the rows (labels, weights) the same way (`rows_over`). Host inputs do
not come here: `mesh.shard_axis` pads and places them.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def rows_mesh(a: jax.Array, axis: int = 0) -> Optional[Mesh]:
    """The mesh a device array's rows are divided over, read from the
    array itself: None for an array on one device; for an array on
    several, the mesh of its `NamedSharding` when its `axis` (the rows)
    is split over that mesh's 'data' axis and nothing else is split.
    Any other layout over several devices raises, and the message names
    the layout the tree builders take."""
    if len(a.sharding.device_set) == 1:
        return None
    spec = [None] * a.ndim
    spec[axis] = "data"
    mesh = getattr(a.sharding, "mesh", None)
    n_data = mesh.shape.get("data", 1) if isinstance(mesh, Mesh) else 1
    if n_data > 1 and a.shape[axis] % n_data == 0 and \
            a.sharding.is_equivalent_to(NamedSharding(mesh, P(*spec)),
                                        a.ndim):
        return mesh
    raise ValueError(
        f"a device input on {len(a.sharding.device_set)} devices must "
        f"have its rows (axis {axis}, a multiple of the axis size) "
        f"divided over the 'data' axis of its mesh and nothing else "
        f"divided: NamedSharding(mesh, {P(*spec)}); got shape {a.shape} "
        f"with {a.sharding}")


def rows_over(mesh: Optional[Mesh], a, axis: int = 0) -> jax.Array:
    """A per-row array beside device inputs whose rows lie over `mesh`
    (`rows_mesh`; None: one device). A host array or one on a single
    device is placed by row; one already on several devices has to lie
    as the inputs do (`rows_mesh` raises otherwise) and stays there."""
    import jax.numpy as jnp
    if mesh is None:
        return jnp.asarray(a)
    placed = rows_mesh(a, axis) if isinstance(a, jax.Array) else None
    if placed is None:
        spec = [None] * np.ndim(a)
        spec[axis] = "data"
        return jax.device_put(a, NamedSharding(mesh, P(*spec)))
    if placed != mesh:
        raise ValueError("per-row device inputs lie over different "
                         f"meshes: {placed} and {mesh}")
    return a
