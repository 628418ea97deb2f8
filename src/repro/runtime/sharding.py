"""The simulator's device mesh: one axis over the first ``n`` devices.

``core.simulator_jit`` splits independent simulation points over this
axis with ``shard_map``; the mesh carries no collectives.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def logical_device_mesh(n: int, axis_name: str = "dev") -> Mesh:
    """1-D mesh over the first ``n`` local devices.

    The sim dispatcher's shard axis (``core.simulator_jit``): simulation
    points are independent, so the mesh carries no collectives — it only
    names the axis ``shard_map`` splits the point dimension over.  The
    logical CPU devices themselves come from ``runtime.device_config``
    (``--xla_force_host_platform_device_count``).
    """
    devs = jax.devices()
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"logical_device_mesh: need 1 <= n <= {len(devs)} "
            f"available devices, got n={n} (configure the pool first — "
            "see repro.runtime.device_config)")
    return Mesh(np.asarray(devs[:n]), (axis_name,))
