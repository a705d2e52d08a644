"""Host phases — the one host span of the training path.

``with telemetry.phase(name):`` times what the host does between two layer
boundaries (``prepare``, a placement, a one-shot program, a training call,
its dispatch, its fetch). Every phase

* is recorded as a :class:`PhaseRecord` on ``time.perf_counter()`` into a
  bounded in-memory ring (oldest dropped first, drops counted), with the
  phase that encloses it as ``parent`` and the ``call`` index its root took,
  so the spans of one training call share an identifier;
* enters a ``jax.profiler.TraceAnnotation(name)``, which does nothing unless a
  profiler session is open: whenever the benchmark or an xprof window traces,
  the phases lie in the trace's ``/host:CPU`` plane, on the device trace's
  clock, under their own names;
* with a :class:`~harp_tpu.telemetry.step_log.StepLog` configured, also feeds
  the ``telemetry.phase.<name>`` reservoir (read by ``/metrics``) and leaves a
  ``kind: "phase"`` event that ``steps.jsonl`` receives at the boundary cadence.

Off is the default: ring and annotation only, nothing written. A phase adds
no ``block_until_ready``: it times the host, and an asynchronous transfer's
tail shows in whatever waits for it next.

:func:`traced` is the counter beside the spans: one Python line inside a
traced function runs when jax traces it and never on a cached call.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import List, NamedTuple, Optional, Sequence

from jax.profiler import TraceAnnotation

from harp_tpu.telemetry import step_log
from harp_tpu.utils import metrics as metrics_lib

RING_CAPACITY = 4096
TRACE_MARK = "program.trace"


class PhaseRecord(NamedTuple):
    name: str
    start: float                 # time.perf_counter()
    end: float
    parent: Optional[int]        # id of the enclosing phase
    call: int                    # index the root phase of this call took
    id: int
    detail: Optional[str] = None   # a mark's subject (the program traced)


class _Ring:
    """The newest ``capacity`` records; what it lets go of is counted."""

    def __init__(self, capacity: int):
        self.records: collections.deque = collections.deque(maxlen=capacity)
        self.dropped = 0
        self._lock = threading.Lock()

    def append(self, record: PhaseRecord) -> None:
        with self._lock:
            if len(self.records) == self.records.maxlen:
                self.dropped += 1
            self.records.append(record)

    def snapshot(self) -> List[PhaseRecord]:
        with self._lock:
            return list(self.records)


_ring = _Ring(RING_CAPACITY)
_ids = itertools.count()         # next() is atomic under the GIL
_calls = itertools.count()
_here = threading.local()        # .phase: the innermost open phase


def _keep(record: PhaseRecord) -> None:
    _ring.append(record)
    log = step_log.active()
    if log is None:
        return
    if record.start != record.end:
        log.metrics.observe(f"telemetry.phase.{record.name}",
                            record.end - record.start)
    event = {"v": step_log.EVENT_VERSION, "kind": "phase", "rank": log.rank,
             **record._asdict()}
    if record.detail is None:
        del event["detail"]
    log.emit(event)


class phase:
    """Context manager: one host span (module docstring)."""

    __slots__ = ("name", "id", "call", "_parent", "_start", "_note")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "phase":
        parent = self._parent = getattr(_here, "phase", None)
        self.id = next(_ids)
        self.call = next(_calls) if parent is None else parent.call
        _here.phase = self
        self._note = TraceAnnotation(self.name)   # inert with no session
        self._note.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        self._note.__exit__(*exc)
        parent = _here.phase = self._parent
        _keep(PhaseRecord(self.name, self._start, end,
                          None if parent is None else parent.id,
                          self.call, self.id))


def traced(program: str) -> None:
    """Count one trace of ``program``: the ``program.traces.<program>``
    counter of ``utils.metrics.DEFAULT`` and a zero-length ``program.trace``
    mark in the ring. Call it from inside the traced function."""
    metrics_lib.DEFAULT.count(f"program.traces.{program}")
    parent = getattr(_here, "phase", None)
    now = time.perf_counter()
    _keep(PhaseRecord(
        TRACE_MARK, now, now, None if parent is None else parent.id,
        next(_calls) if parent is None else parent.call, next(_ids), program))


def phases(since: Optional[float] = None,
           until: Optional[float] = None) -> List[PhaseRecord]:
    """The ring's records that lie inside ``since..until`` on
    ``time.perf_counter()``, oldest first (either end may be left open)."""
    return [r for r in _ring.snapshot()
            if (since is None or r.start >= since)
            and (until is None or r.end <= until)]


def dropped() -> int:
    """How many records the ring has let go of since the process started."""
    return _ring.dropped


def self_seconds(records: Sequence[PhaseRecord], name: str) -> float:
    """Seconds the phases called ``name`` spent outside their children: each
    one's duration less what the records whose ``parent`` it is cover."""
    own = {r.id: r for r in records if r.name == name}
    total = sum(r.end - r.start for r in own.values())
    for r in records:
        span = own.get(r.parent)
        if span is not None:
            total -= max(0.0, min(r.end, span.end) - max(r.start, span.start))
    return total
