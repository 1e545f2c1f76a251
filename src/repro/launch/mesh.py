"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  The dry-run pins itself to 512 host platform devices
before it touches one; everything else sees the real device count.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before jax creates its backend (launch/dryrun.py does this)."
        )
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    """Small mesh for unit tests (requires forced host devices)."""
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


FAKE_MESH_FLAG = "--xla_force_host_platform_device_count=8"


def make_fake_mesh(shape=(4, 2), axes=("data", "model")) -> Mesh:
    """The spmd-tier mesh: 8 forced CPU host devices as (data=4, model=2).

    Callers must export ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    (:data:`FAKE_MESH_FLAG`) *before* the first jax import — this is what
    the CI ``spmd-tier`` job and ``tests/test_spmd.py`` do.
    """
    return make_test_mesh(shape, axes)


def mesh_context(mesh: Mesh | None):
    """``with mesh_context(m):`` — the mesh, or a no-op when None.  Step
    builders use this so tracing under a mesh activates the SPMD kernel
    routing even when the caller forgets the ``with mesh:`` block."""
    import contextlib

    return mesh if mesh is not None else contextlib.nullcontext()


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    """Axes that shard the batch (pod absorbs into data parallelism)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_axis(mesh: Mesh) -> str:
    return "model"
