"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: 16x16 = 256 chips (data, model).  Multi-pod:
2x16x16 = 512 chips (pod, data, model) — the "pod" axis is the slow
inter-pod (DCN-ish) dimension; the sharding policy folds it into the
FSDP/DP axis set.
"""
from __future__ import annotations

import jax


def _auto(n: int) -> tuple:
    """Auto axis types: the sharding policy places arrays with
    ``with_sharding_constraint``, which only accepts Auto mesh axes."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, _auto(len(axes)))


def make_host_mesh(tp: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over the actually-available devices (tests / examples)."""
    n = len(jax.devices())
    dp = max(n // tp, 1)
    return jax.make_mesh((dp, tp), ("data", "model"), _auto(2))


def required_devices(multi_pod: bool) -> int:
    return 512 if multi_pod else 256
