"""Production mesh factory. A FUNCTION (not a module constant) so importing
this module never touches jax device state."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — the "
            "dry-run entrypoint must set XLA_FLAGS="
            "--xla_force_host_platform_device_count=512 before importing jax")
    import numpy as np
    return jax.sharding.Mesh(np.asarray(devices).reshape(shape), axes)


def make_mesh(shape, axes):
    """Arbitrary mesh over the first prod(shape) devices (tests, elastic)."""
    import numpy as np
    n = int(np.prod(shape))
    return jax.sharding.Mesh(np.asarray(jax.devices()[:n]).reshape(shape),
                             axes)
