"""Per-step structured telemetry — bounded ring buffer, JSONL flush.

Design contract (the tentpole's hard constraint): the models call
:func:`record_chunk` ONLY at host chunk boundaries where the per-step losses
are ALREADY host-synced (the ``fit_checkpointed`` chunk fetch, the final
``np.asarray`` of a scanned fit). No call here ever touches a device array,
so no new D2H sync can enter a jitted step program — the traced step programs
the collective-budget manifest pins (JL201/JL203) are bitwise identical with
telemetry on or off, and when telemetry is DISABLED (the default) the whole
layer is one module-level ``None`` check per boundary.

Events are one JSON object per training step::

    {"v": 1, "model": "kmeans", "rank": 0, "step": 17, "loss": 81.2,
     "step_s": 0.0031, "chunk_steps": 4, "chunk_wall_s": 0.0124,
     "phase": "fit", "ts": 1723456789.2, ...}

``step_s`` is the chunk wall amortized over the chunk's steps when the chunk
ran several iterations inside one compiled program (the honest per-step figure
available without syncing inside the scan); a one-step chunk's ``step_s`` is
a real per-step measurement: a count with a mean, not a span (the spans are
:mod:`~harp_tpu.telemetry.host_spans`' phases, whose ``kind: "phase"`` events
ride this stream). Events land in a bounded ring (oldest dropped
first, drops counted) and flush as JSONL to ``<dir>/rank<r>/steps.jsonl`` at
boundary cadence — never inside a step.
"""

from __future__ import annotations

import atexit
import collections
import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

EVENT_VERSION = 1
DEFAULT_CAPACITY = 4096        # ring slots (events), not bytes
DEFAULT_INTERVAL = 16          # chunk boundaries between flushes/hook runs

ENV_DIR = "HARP_TELEMETRY_DIR"
ENV_INTERVAL = "HARP_TELEMETRY_INTERVAL"


def _rank() -> int:
    return int(os.environ.get("HARP_PROCESS_ID", "0"))


class StepLog:
    """Bounded per-rank step-event buffer with JSONL persistence.

    ``interval`` is counted in chunk BOUNDARIES, not seconds: in a gang every
    rank runs the same SPMD host loop, so a count-based cadence keeps the
    boundary hooks (gang snapshot exchange, xprof windows — both collective
    host operations) aligned across ranks, where a wall-clock cadence would
    let rank A broadcast while rank B still thinks it has 100 ms to go.
    """

    def __init__(self, directory: str, *, capacity: int = DEFAULT_CAPACITY,
                 interval: int = DEFAULT_INTERVAL,
                 rank: Optional[int] = None, metrics=None):
        if metrics is None:
            from harp_tpu.utils.metrics import DEFAULT as metrics
        self.directory = directory
        self.rank = _rank() if rank is None else rank
        self.interval = max(1, int(interval))
        self.metrics = metrics
        self.capacity = capacity
        self.dropped = 0
        self.boundaries = 0
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        # flush drains the ring with a check-then-popleft loop and appends
        # to the JSONL file: single-writer in the training loops, but the
        # serving plane's span recorder (telemetry.spans.record_span) runs
        # on every RouterClient receive thread — the lock makes the drain
        # and the file append atomic (emit stays lock-free: deque.append
        # is atomic under the GIL)
        self._flush_lock = threading.Lock()
        self._hooks: List[Callable[[int, "StepLog"], None]] = []
        self._programs: set = set()     # step programs whose text is written
        self._rank_dir = os.path.join(directory, f"rank{self.rank}")
        os.makedirs(self._rank_dir, exist_ok=True)
        self.path = os.path.join(self._rank_dir, "steps.jsonl")

    # -- ring ---------------------------------------------------------------
    def emit(self, event: Dict) -> None:
        if len(self._ring) == self.capacity:
            # deque(maxlen) evicts silently; count the loss so a too-small
            # ring is visible in the metrics snapshot instead of silent
            self.dropped += 1
            self.metrics.count("telemetry.events_dropped")
        self._ring.append(event)

    def flush(self) -> int:
        """Drain the ring to the per-rank JSONL file; returns events
        written. Thread-safe (span recorders flush from client receive
        threads)."""
        with self._flush_lock:
            if not self._ring:
                return 0
            n = 0
            with open(self.path, "a") as f:
                while self._ring:
                    f.write(json.dumps(self._ring.popleft()) + "\n")
                    n += 1
        self.metrics.count("telemetry.events_flushed", n)
        return n

    # -- boundary hooks (gang aggregation, xprof windows) -------------------
    def add_boundary_hook(self, fn: Callable[[int, "StepLog"], None]) -> None:
        """Register ``fn(boundary_index, log)`` to run at EVERY chunk
        boundary (hooks gate themselves on cadence — the xprof window must
        tick per boundary while the gang gather runs every ``interval``)."""
        self._hooks.append(fn)

    def boundary(self) -> None:
        """One chunk boundary: run hooks, flush on the interval cadence."""
        self.boundaries += 1
        for fn in list(self._hooks):
            fn(self.boundaries, self)
        if self.boundaries % self.interval == 0 \
                or len(self._ring) >= self.capacity:
            self.flush()

    def close(self) -> None:
        """Flush and close boundary hooks that hold resources (an xprof
        window still open at the last boundary must stop its trace or the
        profile is never written — XprofController.close)."""
        for fn in self._hooks:
            closer = getattr(fn, "close", None)
            if closer is not None:
                closer()
        self.flush()


# -- module-level active log (the models' single None-check fast path) -------

_active: Optional[StepLog] = None
_env_checked = False
_atexit_installed = False


def _flush_at_exit() -> None:
    # the last chunk of a run usually lands below the flush cadence — a
    # process exiting must not lose the tail of its step log, and a
    # still-open xprof window must stop its trace (close() handles both)
    if _active is not None:
        _active.close()


def configure(directory: Optional[str] = None, *,
              interval: Optional[int] = None,
              capacity: int = DEFAULT_CAPACITY,
              rank: Optional[int] = None, metrics=None) -> Optional[StepLog]:
    """Install the process StepLog. ``directory=None`` reads
    ``HARP_TELEMETRY_DIR`` (still-unset means telemetry stays off). Returns
    the active log (or None). Reconfiguring replaces the log after flushing
    the old one."""
    global _active, _env_checked
    _env_checked = True
    if directory is None:
        directory = os.environ.get(ENV_DIR) or None
    if interval is None:
        interval = int(os.environ.get(ENV_INTERVAL, DEFAULT_INTERVAL))
    if _active is not None:
        _active.close()
        _active = None
    if directory:
        _active = StepLog(directory, capacity=capacity, interval=interval,
                          rank=rank, metrics=metrics)
        global _atexit_installed
        if not _atexit_installed:
            atexit.register(_flush_at_exit)
            _atexit_installed = True
    return _active


def disable() -> None:
    """Flush and turn telemetry off (tests; also ignores the env var until
    the next explicit :func:`configure`)."""
    global _active, _env_checked
    if _active is not None:
        _active.close()
    _active = None
    _env_checked = True


def active() -> Optional[StepLog]:
    """The process StepLog, auto-configured from the environment on first
    use (gang members inherit HARP_TELEMETRY_DIR from the launcher)."""
    global _env_checked
    if _active is None and not _env_checked:
        if os.environ.get(ENV_DIR):
            return configure()
        _env_checked = True
    return _active


# -- the one call the models make --------------------------------------------

def record_chunk(model: str, *, start: int,
                 losses: Optional[Sequence[float]] = None,
                 steps: Optional[int] = None,
                 wall_s: Optional[float] = None,
                 ledger=None, phase: str = "fit",
                 extra: Optional[Dict] = None) -> None:
    """Record one host chunk boundary: ``steps`` training steps beginning at
    0-based ``start``, with per-step ``losses`` that are ALREADY host-synced
    (never pass device arrays — convert at an existing D2H point or pass
    None), the chunk's measured ``wall_s``, and an optional
    :class:`~harp_tpu.telemetry.comm_ledger.CommLedger` to advance.

    No-op (one None check) when telemetry is off.
    """
    log = active()
    if log is None:
        return
    n = steps if steps is not None else (len(losses) if losses is not None
                                         else 1)
    if n <= 0:
        return
    step_s = (wall_s / n) if wall_s is not None else None
    if step_s is not None:
        # the straggler detector's signal: per-step wall into the bounded
        # timer reservoir (one sample per step so p50 weighs steps, not
        # chunks of different lengths)
        for _ in range(n):
            log.metrics.observe(f"telemetry.step.{model}", step_s)
    if ledger is not None:
        ledger.on_steps(n, wall_s=wall_s)
    ts = time.time()
    base = {"v": EVENT_VERSION, "model": model, "rank": log.rank,
            "phase": phase, "ts": round(ts, 3)}
    if extra:
        base.update(extra)
    if ledger is not None and ledger.bytes_per_step is not None:
        base["wire_bytes_per_step"] = ledger.bytes_per_step
        # "scaled": the model computed its payload ratio vs the traced shape
        # (exact); "traced_shape": fixed reference pricing, exact only at
        # tier-1 shapes (comm_ledger module docstring)
        base["wire_pricing"] = ("scaled" if getattr(ledger, "exact", False)
                                else "traced_shape")
    for i in range(n):
        ev = dict(base)
        ev["step"] = start + i
        ev["chunk_steps"] = n
        if wall_s is not None:
            ev["step_s"] = round(step_s, 9)
            ev["chunk_wall_s"] = round(wall_s, 6)
        if losses is not None and i < len(losses):
            ev["loss"] = float(losses[i])
        log.emit(ev)
    log.metrics.count(f"telemetry.steps.{model}", n)
    log.boundary()


def record_timing(name: str, *, timer: Optional[str] = None,
                  metrics=None, extra: Optional[Dict] = None) -> None:
    """Surface a bounded-timer percentile snapshot into ``steps.jsonl``.

    One event, ``kind: "timing"``, whose latency fields are EXACTLY
    ``utils.metrics.Metrics.timing()`` output (count/total_s/mean_s/last_s/
    p50_s/p90_s/p99_s) — the same schema the straggler report's per-rank
    rows carry, so serving-bench latency rows and straggler reports share
    one latency format instead of two drifting ones. ``timer`` names the
    reservoir to snapshot (default: ``name``); ``metrics`` overrides the
    registry (the serving load generator keeps per-mix registries so one
    mix's reservoir never dilutes the next). No-op when telemetry is off or
    the timer has no samples.
    """
    log = active()
    if log is None:
        return
    reg = metrics if metrics is not None else log.metrics
    t = reg.timing(timer or name)
    if not t:
        return
    ev = {"v": EVENT_VERSION, "kind": "timing", "name": name,
          "rank": log.rank, "ts": round(time.time(), 3)}
    ev.update(t)
    if extra:
        ev.update(extra)
    log.emit(ev)
    log.boundary()


def record_program(name: str, step, args: Sequence) -> None:
    """Write the compiled text of the step program ``name`` (``step`` is its
    jitted callable, ``args`` what it was just dispatched with) to
    ``<dir>/rank<r>/programs/<name>.hlo.txt``, once per log: the text whose
    ``op_name``s :func:`harp_tpu.telemetry.scopes.scope_map` reads, so that a
    profiler window opened on the running job can be read by kernel name.
    Costs one load from the compile cache. No-op (one None check) when
    telemetry is off."""
    log = active()
    if log is None or name in log._programs:
        return
    log._programs.add(name)
    directory = os.path.join(log._rank_dir, "programs")
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, name + ".hlo.txt"), "w") as f:
        f.write(step.lower(*args).compile().as_text())
