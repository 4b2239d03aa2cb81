"""Mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — required for the tests to keep seeing one
device while the dry-run forces 512 host devices.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh_for(devices, *, multi_pod: bool = False):
    """Mesh over an explicit device list (elastic re-mesh path: after a
    failure the surviving device set is re-meshed and the program is
    re-lowered — see repro/dist/fault.py)."""
    import numpy as np
    from jax.sharding import Mesh

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    if len(devices) < n:
        raise ValueError(f"need {n} devices, got {len(devices)}")
    arr = np.asarray(devices[:n]).reshape(shape)
    return Mesh(arr, axes)


def device_mesh(devices=None):
    """("data", "model") mesh of shape (n, 1) over the devices present
    (``jax.devices()`` by default): one chip gives a 1x1 mesh.  Serving
    and training build their mesh here; the fixed 16x16 pod meshes
    above are only for the dry-run's forced host devices."""
    from jax.sharding import Mesh
    import numpy as np
    devices = list(jax.devices() if devices is None else devices)
    return Mesh(np.asarray(devices, dtype=object).reshape(len(devices), 1),
                ("data", "model"))
