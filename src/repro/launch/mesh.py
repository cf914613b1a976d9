"""Production mesh construction (deliverable e).

Kept as FUNCTIONS so importing this module never touches jax device state.
Single pod: 16×16 = 256 chips (data, model).  Multi-pod: 2 pods = 512 chips
(pod, data, model) — the "pod" axis carries data parallelism across the
inter-pod DCN/ICI boundary."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the model code pins activations with
    # with_sharding_constraint, which refuses make_mesh's default
    # Explicit axes.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Tiny mesh over whatever devices exist (tests / CPU engine)."""
    n = len(jax.devices())
    mp = min(model_parallel, n)
    return _auto_mesh((n // mp, mp), ("data", "model"))
