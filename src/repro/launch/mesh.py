"""The device mesh the sharded NMF engine runs on.

A function (not a module-level constant) so importing never touches jax
device state.
"""
from __future__ import annotations

import jax


def make_nmf_mesh(rows: int, cols: int) -> jax.sharding.Mesh:
    """The ("data", "model") grid the sharded NMF engine executes on —
    rows shard U / A's row blocks, cols shard V / A's column blocks.  This
    is the single construction point ``NMFConfig.mesh_shape`` lowers
    through (solvers, benchmarks, and tests all come here), so swapping in
    another topology is a one-line change."""
    import numpy as np

    devices = jax.devices()
    if len(devices) < rows * cols:
        raise ValueError(
            f"mesh_shape {(rows, cols)} needs {rows * cols} devices, "
            f"have {len(devices)}")
    return jax.sharding.Mesh(
        np.asarray(devices[: rows * cols]).reshape(rows, cols),
        ("data", "model"))
