"""Mesh construction (function, not module-level constant: importing this
module never touches jax device state)."""
from __future__ import annotations

import jax


def _mk(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """Single v5e pod: 16x16 (data, model).  Multi-pod: 2 pods x 16 x 16
    (pod, data, model); the ``pod`` axis is crossed by DCI, so only
    batch/gradient traffic is mapped onto it (dist/sharding.py)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = jax.device_count()
    assert data * model <= n, f"need {data * model} devices, have {n}"
    return _mk((data, model), ("data", "model"))
