"""Production meshes. Functions only — importing this module never touches jax
device state (required: smoke tests must keep seeing 1 CPU device)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_host_mesh(model: int = 1):
    """Whatever devices exist locally (tests/examples): (data, model) mesh."""
    n = len(jax.devices())
    assert n % model == 0
    return jax.make_mesh(
        (n // model, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
