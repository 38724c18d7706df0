"""Production mesh builders.

Functions, not module-level constants — importing this module never touches
jax device state.  The dry-run entrypoint (launch/dryrun.py) sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` BEFORE importing jax
so these meshes can be built on the CPU-only container.

Every builder validates the requested shape against the available device
count up front: jax's own failure mode is an opaque reshape error from deep
inside ``make_mesh`` ("cannot reshape array of size 1 into shape (16,16)"),
which names neither the mesh nor the fix.  The ``ValueError`` raised here
names both counts so a misconfigured launch (or a degraded host pool) is a
one-line diagnosis.
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence

import jax
from jax.sharding import AxisType


def _require(needed: int, available: int, what: str) -> None:
    """Fail fast with both counts named instead of jax's reshape error."""
    if available < needed:
        raise ValueError(
            f"{what} needs {needed} device(s) but only {available} "
            f"available; set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={needed} on CPU or shrink the requested topology")


def _mesh(shape, axes, devices=None):
    needed = math.prod(shape)
    available = len(devices) if devices is not None \
        else jax.local_device_count()
    _require(needed, available, f"mesh {dict(zip(axes, shape))}")
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (v5e); multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke tests / the real serving engine."""
    return _mesh((1, 1), ("data", "model"))


def make_serve_mesh(devices=None):
    """Data-parallel serving mesh over this host's local devices.

    One embedding tier fans its batches out over every device it was given
    (``('data', 'model')`` axes with the whole device count on ``data``), so
    the serve-mode sharding rules in ``repro.parallel.sharding`` apply
    unchanged: weights resident/replicated, batch sharded over ``data``.
    ``devices=None`` uses all local devices; a single device degrades to
    ``make_host_mesh()`` behaviour.
    """
    devices = list(jax.local_devices() if devices is None else devices)
    if not devices:
        raise ValueError("need at least one device for a serve mesh")
    return _mesh((len(devices), 1), ("data", "model"), devices=devices)


def make_replica_meshes(hosts: int = 1, replicas: int = 1,
                        devices: Optional[Sequence] = None) -> List:
    """Carve a device pool into ``hosts * replicas`` independent serve
    meshes — the hardware side of the multi-replica topology.

    The pool splits into equal contiguous groups, one serve mesh per
    replica, ordered host-major/replica-minor so index ``h * replicas + r``
    is replica ``(h, r)`` — the same order :func:`repro.core.routing.
    replicate` emits its ``TierSpec``s in, so ``zip(replicate(...),
    make_replica_meshes(...))`` pairs each replica tier with its mesh.
    Contiguity keeps a replica's devices on one host when the pool is laid
    out host-major (jax's ``local_devices`` order), which is what makes a
    per-replica breaker a *host* failure domain.

    Degrade rule (mirrors ``replicate`` / ``sharded_model``): ``1 x 1``
    returns ``[make_serve_mesh(devices)]`` — bitwise today's single-replica
    serve mesh.  A pool that does not split evenly raises a ``ValueError``
    naming required vs available counts (never jax's reshape error).
    """
    return [make_serve_mesh(g) for g in replica_groups(hosts, replicas,
                                                       devices)]


def replica_groups(hosts: int = 1, replicas: int = 1,
                   devices: Optional[Sequence] = None) -> List[List]:
    """The device groups :func:`make_replica_meshes` builds its meshes
    over: ``hosts * replicas`` equal contiguous slices of the pool."""
    if hosts < 1 or replicas < 1:
        raise ValueError(f"hosts and replicas must be >= 1, "
                         f"got {hosts}x{replicas}")
    devices = list(jax.local_devices() if devices is None else devices)
    groups = hosts * replicas
    if groups == 1:
        return [devices]
    _require(groups, len(devices),
             f"replica topology {hosts} host(s) x {replicas} replica(s)")
    if len(devices) % groups:
        raise ValueError(
            f"device pool of {len(devices)} does not split evenly over "
            f"{hosts} host(s) x {replicas} replica(s) = {groups} groups; "
            f"each replica needs an equal device group")
    per = len(devices) // groups
    return [devices[g * per:(g + 1) * per] for g in range(groups)]


def mesh_context(mesh):
    """Context manager enabling bare-PartitionSpec sharding constraints."""
    return jax.set_mesh(mesh)
