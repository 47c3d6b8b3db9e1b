"""Production meshes for the serving/training fleet.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before jax
initializes its backend.

  single pod : (16, 16)    axes (data, model)   = 256 chips (v5e pod)
  multi-pod  : (2, 16, 16) axes (pod, data, model) = 512 chips
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    """jax.make_mesh with Auto axes: the sharding rules place
    with_sharding_constraint on these meshes, which Explicit axes (the
    make_mesh default of current jax) refuse."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devs = jax.devices()
    if len(devs) != need:
        # dry-run hosts expose 512 placeholder devices; the single-pod mesh
        # uses the first 256 of them.
        assert len(devs) >= need, (
            f"mesh {shape} needs {need} devices, found {len(devs)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512")
        devs = devs[:need]
    return _mesh(shape, axes, devs)


def make_test_mesh(model: int = 1, data: int = 1):
    """Tiny mesh over however many (CPU) devices exist — for tests."""
    n = len(jax.devices())
    assert model * data <= n, f"need {model * data} devices, have {n}"
    return _mesh((data, model), ("data", "model"))


def make_db_mesh(n_shards: int = 1):
    """1-D mesh over the router-DB capacity axis (sharding.DB_AXIS):
    RouterState panels partition their rows over these devices
    (DESIGN.md §12). Kept separate from the fleet's (data, model)
    serving meshes — the routing DB scales on its own axis.

    On CPU hosts run under XLA_FLAGS=--xla_force_host_platform_device_count=N
    (set BEFORE jax initializes) to expose multiple devices."""
    from repro.sharding import DB_AXIS
    devs = jax.devices()
    assert len(devs) >= n_shards, (
        f"DB mesh needs {n_shards} devices, found {len(devs)} — run under "
        f"XLA_FLAGS=--xla_force_host_platform_device_count={n_shards}")
    return _mesh((n_shards,), (DB_AXIS,), devs[:n_shards])
