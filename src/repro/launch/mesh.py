"""Production meshes, defined as functions so importing this module never
touches jax device state."""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 v5e pod (256 chips) or 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (tests / smoke runs)."""
    n = len(jax.devices())
    return _make_mesh((n, 1), ("data", "model"))


def make_data_mesh(n_shards: int):
    """1-D ``data`` mesh over the first ``n_shards`` local devices — the
    client-axis mesh the sharded federated path (ISSUE 4) runs on.

    Unlike ``jax.make_mesh`` this takes a device SUBSET, so a 2-shard mesh
    works on an 8-device host (simulated multi-device CI runs every shard
    count that divides the forced device count).  Raises with a pointer to
    ``force_host_devices`` when the host has too few devices.
    """
    import numpy as np

    devices = jax.devices()
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if n_shards > len(devices):
        raise ValueError(
            f"mesh_shards={n_shards} needs {n_shards} devices but only "
            f"{len(devices)} exist; on CPU simulate them with "
            f"repro.launch.hostdev.force_host_devices({n_shards}) before "
            f"jax initializes (CI sets REPRO_FORCE_HOST_DEVICES)")
    return jax.sharding.Mesh(np.asarray(devices[:n_shards]), ("data",))
