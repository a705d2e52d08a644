"""Mesh runtime — the TPU-native replacement for Harp's worker membership layer.

Reference parity: ``worker/Workers`` (worker/Workers.java:33) derived selfID / masterID
(= min ID) / maxID / nextID (ring neighbor) from a ``nodes`` file, and the YARN gang
allocator placed one JVM worker per node. Here a *worker* is a TPU device (or a
virtual CPU device in tests) on a ``jax.sharding.Mesh``; membership, ring order and
master selection fall out of the mesh axis order, and "gang scheduling" is inherent —
an SPMD program runs on all mesh devices or none.

The mesh may be multi-dimensional: the primary Harp-equivalent axis is ``workers``
(data/partition parallelism); algorithms that need a 2-D layout (model rotation grids,
tensor-parallel kernels) can ask for extra axes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical axis names.
WORKERS = "workers"  # Harp worker axis: partitions distribute over this.
MODEL = "model"      # optional second axis for model-parallel layouts.

# Link classes a mesh axis can be hinted with: ICI (on-pod interconnect,
# one monolithic ppermute per hop is right) vs DCN (cross-pod data-center
# network — slower, higher-latency; rotation hops chunk their payload so
# in-flight pieces pipeline, collectives.rotation.chunks_for_link).
LINK_CLASSES = ("ici", "dcn")
_AXIS_LINK_CLASS: dict = {}


def set_axis_link_class(axis_name: str, link_class: str) -> None:
    """Hint which physical link class a mesh axis crosses (default "ici").

    Gang launchers that place the ``workers`` axis across hosts/pods call
    ``set_axis_link_class(WORKERS, "dcn")`` once at bootstrap; the rotation
    pipeline and the collective benchmarks consult the hint for chunk
    sizing. Process-global (the mesh topology is, too)."""
    if link_class not in LINK_CLASSES:
        raise ValueError(
            f"link_class must be one of {LINK_CLASSES}, got {link_class!r}")
    _AXIS_LINK_CLASS[axis_name] = link_class


def axis_link_class(axis_name: str) -> str:
    """The hinted link class for a mesh axis ("ici" when never hinted)."""
    return _AXIS_LINK_CLASS.get(axis_name, "ici")


def force_host_devices(n: int) -> None:
    """Request ``n`` virtual CPU devices. Must run before JAX backends initialize.

    This replaces the reference's ssh-one-JVM-per-worker test harness
    (collective/Driver.java:93): deterministic multi-worker tests run in one process
    on a virtual device mesh.
    """
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def make_mesh(
    num_workers: int | None = None,
    *,
    model_axis: int = 1,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build the worker mesh.

    Args:
      num_workers: size of the ``workers`` axis; defaults to all devices / model_axis.
      model_axis: size of the optional ``model`` axis (1 = pure worker layout).
      devices: explicit device list (defaults to ``jax.devices()``).
    """
    devs = list(devices if devices is not None else jax.devices())
    if num_workers is None:
        num_workers = len(devs) // model_axis
    need = num_workers * model_axis
    if need > len(devs):
        raise ValueError(
            f"requested {num_workers}x{model_axis} mesh but only {len(devs)} devices"
        )
    grid = np.array(devs[:need]).reshape(num_workers, model_axis)
    return Mesh(grid, (WORKERS, MODEL))


@dataclasses.dataclass(frozen=True)
class WorkerGroup:
    """Static membership info derived from a mesh — Harp's ``Workers`` equivalent.

    Reference: worker/Workers.java:74-115 computed selfID, masterID, maxID, nextID.
    Under SPMD there is no host-side "self"; ``self_id`` exists only *inside* a
    shard_mapped program via ``jax.lax.axis_index``. The static facts live here.
    """

    mesh: Mesh

    @property
    def num_workers(self) -> int:
        return self.mesh.shape[WORKERS]

    @property
    def master_id(self) -> int:
        return 0  # Harp: min worker ID is master (Workers.java).

    @property
    def max_id(self) -> int:
        return self.num_workers - 1

    def next_id(self, worker: int) -> int:
        """Ring successor (Harp's nextID used by chain bcast / allgather / rotate)."""
        return (worker + 1) % self.num_workers

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    with mesh:
        yield mesh


def fetch(x) -> np.ndarray:
    """Fetch a device array to a full host ndarray on EVERY process.

    ``np.asarray`` on a jax.Array that spans non-addressable devices (a
    sharded output under a multi-process gang) raises — the role Harp's
    allgather-to-master played at job end (LDAMPCollectiveMapper's final
    table gathers) here needs an explicit cross-process gather. Single
    process (or replicated output): a plain, zero-collective ``np.asarray``.
    Multi-process with non-addressable shards: ``process_allgather`` —
    which is COLLECTIVE, so every process must reach this call (true for
    all fit paths: SPMD processes run the same program).
    """
    if isinstance(x, np.ndarray):
        return x
    if jax.process_count() == 1:
        # single process: everything is addressable — no sharding
        # property queries needed
        return np.asarray(x)
    if (isinstance(x, jax.Array) and not x.is_fully_addressable
            and not x.is_fully_replicated):
        # replicated outputs skip this: np.asarray reads the local replica
        # with zero collectives; only genuinely sharded spans pay the gather
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(x)
