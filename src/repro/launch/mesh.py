"""Mesh construction: production TPU v5e pods and serving host meshes.

Functions, not module-level constants — importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axis_names):
    """``jax.make_mesh`` with Auto axis types: the repo's sharding goes
    through ``with_sharding_constraint`` and AOT in/out shardings, which
    expect the partitioner to propagate layouts (``jax.make_mesh`` defaults
    to Explicit axes)."""
    auto = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(shape, axis_names, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Whatever this host actually has (CPU tests: 1 device)."""
    n = len(jax.devices())
    data = max(1, n // model_parallel)
    return make_mesh((data, model_parallel), ("data", "model"))


def make_serving_mesh(mesh: str = "", model_parallel: int = 0):
    """Resolve the serve CLI's mesh flags to a ("data", "model") host mesh.

    ``mesh``: explicit "DATA,MODEL" ways (e.g. "2,2").  ``model_parallel``:
    shortcut — KV heads sharded N ways, data ways = devices // N.  Both
    empty/zero -> None (single-device serving).  On CPU hosts pair with
    XLA_FLAGS=--xla_force_host_platform_device_count=K set before jax import.
    """
    if mesh:
        parts = [int(x) for x in mesh.split(",")]
        if len(parts) != 2 or any(p < 1 for p in parts):
            raise ValueError(f"--mesh expects 'data,model' ways, got {mesh!r}")
        return make_mesh(tuple(parts), ("data", "model"))
    if model_parallel:
        return make_host_mesh(model_parallel)
    return None
