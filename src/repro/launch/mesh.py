"""Production meshes. A FUNCTION, not a module-level constant — importing
this module never touches jax device state."""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, **kwargs):
    """``jax.make_mesh`` with Auto axis types: the executors partition with
    ``shard_map`` and ``NamedSharding``, not the explicit-sharding mode that
    ``jax.make_mesh`` defaults to."""
    kwargs.setdefault("axis_types",
                      (jax.sharding.AxisType.Auto,) * len(axis_names))
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kwargs)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (CPU smoke tests: 1 device)."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))


def make_fleet_mesh(n_devices: int | None = None):
    """1-D batch mesh over the local devices — the serving-fleet topology.

    The sharded executor splits the request batch over every mesh axis, so
    a flat ``("batch",)`` mesh is the natural spelling for data-parallel
    serving (one shard of every device batch per device). ``n_devices``
    caps the fleet to the first N local devices (``None`` = all of them) —
    a multi-model :class:`repro.api.Fleet` can carve disjoint sub-fleets
    this way.
    """
    devices = jax.devices()
    if n_devices is not None:
        if not 1 <= n_devices <= len(devices):
            raise ValueError(
                f"n_devices={n_devices} outside [1, {len(devices)}] local "
                f"devices")
        devices = devices[:n_devices]
    return make_mesh((len(devices),), ("batch",), devices=devices)
