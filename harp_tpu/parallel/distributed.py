"""Multi-host bootstrap — replaces Harp's YARN gang scheduling + HDFS rendezvous.

Reference parity: MapCollectiveContainerAllocator gang-allocated all workers at once
and MapCollectiveContainerLauncherImpl wrote ``<jobID>/{nodes,tasks,lock}`` rendezvous
files to HDFS that workers spun on (launcher/MapCollectiveContainerLauncherImpl.java:
294-331; CollectiveMapper.initCollCommComponents:253). TPU-native: the JAX
distributed coordinator service plays the AM role — every host calls
``jax.distributed.initialize`` with the coordinator address and blocks until the gang
is complete; device discovery over ICI/DCN replaces the nodes file.

Fail-stop semantics match the reference: a missing worker keeps initialization
blocked (Harp: spin on lock file), and a worker failure aborts the job (Harp: the
gang allocator never re-executes mappers; SURVEY §5 failure handling).
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax

log = logging.getLogger("harp_tpu.distributed")

_gang_watchdog = None


def _arm_watchdog() -> None:
    """Per-member heartbeat: device hang → process exit → launcher fail-stop
    (parallel.failure.start_gang_watchdog documents the chain)."""
    global _gang_watchdog
    from harp_tpu.parallel import failure

    if _gang_watchdog is None:
        _gang_watchdog = failure.start_gang_watchdog()


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    *,
    initialization_timeout_s: int = 1800,
) -> None:
    """Join the multi-host gang. No-op on single-process runs.

    The 1800 s default timeout mirrors Harp's DATA_MAX_WAIT_TIME
    (io/Constant.java:36). On Cloud TPU pods all three arguments are auto-detected
    from the environment; on CPU/GPU clusters pass them explicitly (they play the
    role of Harp's nodes/tasks files).
    """
    # the gang env written by parallel.launch (the depl/ nodes-file
    # launcher) plays the role of Harp's <jobID>/tasks file: each value is
    # adopted independently, only where the caller left the parameter None
    coordinator_address = coordinator_address or os.environ.get("HARP_COORDINATOR")
    if num_processes is None and "HARP_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["HARP_NUM_PROCESSES"])
    if process_id is None and "HARP_PROCESS_ID" in os.environ:
        process_id = int(os.environ["HARP_PROCESS_ID"])
    if coordinator_address is None and num_processes is None:
        # Single host or auto-detectable TPU pod environment.
        if os.environ.get("TPU_WORKER_HOSTNAMES") or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
            jax.distributed.initialize(initialization_timeout=initialization_timeout_s)
            log.info("joined TPU pod gang: process %d/%d",
                     jax.process_index(), jax.process_count())
            _arm_watchdog()
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        initialization_timeout=initialization_timeout_s,
    )
    log.info("joined gang at %s: process %d/%d", coordinator_address,
             jax.process_index(), jax.process_count())
    _arm_watchdog()


def shutdown() -> None:
    """Leave the gang (CollectiveMapper teardown :783-788 equivalent)."""
    global _gang_watchdog
    if _gang_watchdog is not None:
        _gang_watchdog.stop()
        _gang_watchdog = None
    if jax.process_count() > 1:
        jax.distributed.shutdown()
