"""Mesh builders: the production pods (single-pod 16×16, multi-pod
2×16×16), the row-sharded serving mesh over the local chips, and a small
CPU debug mesh.

Functions, not module constants — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    # Auto axes: the models place activations with with_sharding_constraint
    # (distributed/sharding.shard_act), which Explicit axes refuse
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_data_mesh(n_devices: int):
    """1-D ``("data",)`` mesh over the first ``n_devices`` local devices —
    the row-sharded serving layout that ``ColumnStore(mesh=)`` and
    ``search.distributed`` agree on."""
    devs = jax.local_devices()
    if not 1 <= n_devices <= len(devs):
        raise ValueError(f"asked for {n_devices} devices, "
                         f"{len(devs)} are local")
    return jax.sharding.Mesh(devs[:n_devices], ("data",))


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CPU multi-device tests (host platform device count)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


def mesh_chip_count(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
