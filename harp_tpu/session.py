"""HarpSession — the primary user entry point.

Reference parity: this replaces BOTH of Harp's entry layers —

* ``CollectiveMapper`` (core/harp-hadoop/.../mapred/CollectiveMapper.java:71): users
  subclassed it, wrote ``mapCollective()``, and called inherited collective methods;
  ``run():751`` bootstrapped the comm runtime from HDFS rendezvous files.
* the embryonic Python ``HarpSession`` (python/harp_session.py:6) that BASELINE.json
  designates as the primary TPU entry point.

TPU-native shape: there is no mapper subclass and no rendezvous-by-files. A session
owns a device mesh; the user writes a plain SPMD function that calls the collective
API, and ``session.spmd`` compiles it once over the mesh (shard_map + jit). Iterative
algorithms put their hot loop *inside* the compiled function with ``lax.scan`` /
``lax.fori_loop`` — one XLA program per training run, not one dispatch per collective
(which is where the TPU build beats the JVM+TCP reference).

Typical usage::

    sess = HarpSession(num_workers=8)

    def step(points, centroids):                 # SPMD: runs on every worker
        local = Table.local(partial_sums(points, centroids), num_workers=sess.num_workers)
        return table_ops.aggregate(local).trim()  # regroup+allgather, Harp-style

    new_cen = sess.spmd(step, in_specs=(sess.shard(), sess.replicate()),
                        out_specs=sess.replicate())(points, centroids)
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from harp_tpu import compat, telemetry
from harp_tpu.parallel import mesh as mesh_lib
from harp_tpu.parallel.mesh import WORKERS


class HarpSession:
    """Owns the worker mesh and compiles SPMD map-collective programs."""

    def __init__(
        self,
        num_workers: Optional[int] = None,
        *,
        mesh: Optional[Mesh] = None,
        devices: Optional[Sequence[jax.Device]] = None,
        name: str = "harp",
    ):
        self.name = name
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh(
            num_workers, devices=devices)
        self.workers = mesh_lib.WorkerGroup(self.mesh)

    # -- membership (Harp: CollectiveMapper.getSelfID/getNumWorkers/isMaster) ----
    @property
    def num_workers(self) -> int:
        return self.workers.num_workers

    @property
    def master_id(self) -> int:
        return self.workers.master_id

    # -- sharding specs ----------------------------------------------------------
    def shard(self, axis: int = 0) -> P:
        """Spec: sharded over workers along ``axis`` (a SHARDED table / input data)."""
        spec = [None] * (axis + 1)
        spec[axis] = WORKERS
        return P(*spec)

    def replicate(self) -> P:
        """Spec: replicated on every worker (a LOCAL/REPLICATED table)."""
        return P()

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    # -- data placement ----------------------------------------------------------
    def scatter(self, array, axis: int = 0) -> jax.Array:
        """Place a host array sharded over workers along ``axis``.

        The shape along ``axis`` must divide evenly; pad first if not (Table.local
        pads for you). This replaces Harp's whole-files-per-worker input split
        (MultiFileInputFormat) for in-memory data.
        """
        with telemetry.phase("session.place"):
            return jax.device_put(array, self.sharding(self.shard(axis)))

    def replicate_put(self, array) -> jax.Array:
        with telemetry.phase("session.place"):
            return jax.device_put(array, self.sharding(self.replicate()))

    # -- SPMD compilation --------------------------------------------------------
    def spmd(
        self,
        fn: Callable,
        *,
        in_specs: Any,
        out_specs: Any,
        static_argnums: Sequence[int] = (),
        donate_argnums: Sequence[int] = (),
    ) -> Callable:
        """Compile ``fn`` as an SPMD program over the worker mesh.

        ``fn`` sees per-worker local blocks for sharded inputs and may call any
        ``harp_tpu.collectives`` op. This is ``CollectiveMapper.mapCollective``
        turned inside-out: instead of a long-lived mapper process making one network
        call per collective, the whole iterative program is traced once and XLA
        schedules all collectives over ICI.
        """
        mapped = compat.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
        )
        return jax.jit(mapped, static_argnums=static_argnums,
                       donate_argnums=donate_argnums)

    def run(self, fn: Callable, *args, in_specs: Any, out_specs: Any, **kw):
        """One-shot: compile and invoke (for scripts; hot paths should keep the
        callable from :meth:`spmd`)."""
        with telemetry.phase("session.run"):
            return self.spmd(fn, in_specs=in_specs, out_specs=out_specs,
                             **kw)(*args)

    def barrier(self) -> None:
        """Host-level barrier across processes (multi-host); on a single host this
        is a device sync. Reference: Communication.barrier:61."""
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(f"{self.name}-barrier")
        else:
            (jax.device_put(np.zeros(()))).block_until_ready()

    # -- events (Harp: CollectiveMapper.getEvent:623/waitEvent:632/sendEvent:645)
    # Addressed by PROCESS rank (jax.process_index) — the host control plane —
    # not by device-level worker id.
    _event_gen = 0      # class-wide generation counter; SPMD processes run
    #                     identical code, so generations align across the gang

    def open_events(self):
        """Bring up the event plane (idempotent): the queue, client, and —
        multi-process — the P2P transport server. RECEIVERS that only poll
        with :meth:`get_event` must call this (or :meth:`wait_event`) so
        their server exists before a peer resolves it. Each open after a
        :meth:`close_events` is a new generation with a fresh KV-rendezvous
        namespace (coordinator KV keys are write-once)."""
        if not hasattr(self, "_events"):
            from harp_tpu.parallel import events as ev

            queue = ev.EventQueue()
            transport = None
            if jax.process_count() > 1:
                # true P2P between gang members (parallel/p2p.py; KV-store
                # rendezvous through the same coordinator the gang joined)
                from harp_tpu.parallel.p2p import P2PTransport

                gen = HarpSession._event_gen
                HarpSession._event_gen += 1
                transport = P2PTransport(
                    queue, rank=jax.process_index(),
                    kv_namespace=f"{self.name}-session-g{gen}")
            self._events = (queue, ev.EventClient(
                queue, worker_id=jax.process_index(), transport=transport),
                transport)
        return self._events

    def get_event(self):
        """Non-blocking event poll (CollectiveMapper.getEvent:623). Returns
        None when the plane has not been opened — a pure peek never spins
        up the transport server."""
        if not hasattr(self, "_events"):
            return None
        return self._events[0].get()

    def wait_event(self, timeout: Optional[float] = None):
        """Blocking event wait (CollectiveMapper.waitEvent:632); opens the
        event plane (receiving intent — the transport server must be up)."""
        return self.open_events()[0].wait(timeout)

    def send_event(self, payload, dest: Optional[int] = None,
                   source: Optional[int] = None) -> None:
        """CollectiveMapper.sendEvent:645: ``dest=None`` delivers to every
        process (COLLECTIVE — all processes must call, same ``source``);
        a concrete ``dest`` is a point-to-point MESSAGE to that PROCESS
        rank (sender-only call when the gang transport is up; see
        events.EventClient.send_message for the transportless fallback's
        call pattern).

        Ordering: all events share ONE queue and transport MESSAGEs are
        delivered asynchronously, so a peer's message may be dequeued
        before an event this process enqueued first — match on
        ``Event.type``/``source``, don't assume arrival order (the
        reference's EventQueue gave the same non-guarantee)."""
        if dest is not None and not (0 <= dest < jax.process_count()):
            raise ValueError(
                f"dest must be a process rank in [0, {jax.process_count()}) "
                f"— events are the host control plane, addressed per "
                f"PROCESS, not per device-level worker; got {dest}")
        client = self.open_events()[1]
        if dest is None:
            client.send_collective(payload, source=source)
        else:
            client.send_message(dest, payload, source=source)

    def close_events(self) -> None:
        """Tear down the event plane (CollectiveMapper teardown :783-788).
        A later open_events/send_event/wait_event starts a new generation."""
        if hasattr(self, "_events"):
            transport = self._events[2]
            if transport is not None:
                transport.close()
            del self._events
