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

**The compile path** leaves its records where it runs. jax reports every
trace, lowering, backend compile and load from the persistent cache to
``jax.monitoring`` when it ends; this module listens (registered once, on
import) and keeps each as a record whose ``end`` is the moment of the report,
whose ``start`` lies the reported seconds before it, whose ``parent`` is the
phase open on that thread (a compile runs on the thread that dispatched, so
that is the ``step.dispatch`` or ``session.run`` that paid for it) and whose
``detail`` is the function's name as jax gives it:

* ``program.lower``: the trace to a jaxpr and the lowering to an MLIR module
  (a Pallas kernel's Mosaic lowering included);
* ``program.compile``: the backend's compile, or the cache's load in its
  place (jax times ``compile_or_get_cached`` whole);
* ``program.cache_load``: the retrieval from the persistent cache alone,
  inside the ``program.compile`` that asked for it (no ``detail``: jax gives
  that event no name).

jax reports a nested trace inside its caller's (every jitted ``jax.numpy``
function a traced body calls, hundreds a program): only the outermost of a
thread's open traces and lowerings leaves a record, so that a start-up does
not flood the ring, and a reader still adds the *union* of a name's intervals
(:func:`union_seconds`), never the durations: a lowering can trace. The
cache's hits and misses are counted in ``utils.metrics.DEFAULT`` as
``program.cache.hits`` / ``.misses``. Nothing of this fires on a cached call.

:data:`PHASES` lists every name the package emits, as
``telemetry.scopes.SCOPES`` does for the device.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import List, NamedTuple, Optional, Sequence

import jax.monitoring
from jax.profiler import TraceAnnotation

from harp_tpu.telemetry import step_log
from harp_tpu.utils import metrics as metrics_lib

RING_CAPACITY = 4096
TRACE_MARK = "program.trace"
LOWER = "program.lower"
COMPILE = "program.compile"
CACHE_LOAD = "program.cache_load"

PHASES = (
    "kmeans.prepare", "sgd_mf.prepare", "als.prepare", "ccd.prepare",
    "mds.prepare", "em.prepare", "lda.prepare",
    "session.place",    # HarpSession.scatter / replicate_put: the enqueue
    "session.run",      # a one-shot program: trace, compile or load, enqueue
    "session.fetch",    # a blocking fetch inside a prepare: the wait
    "kmeans.call", "sgd_mf.call", "als.call", "ccd.call", "mds.call", "em.call",
    "lda.call",
    "step.dispatch",    # the jitted call alone, which returns at the enqueue
    "step.fetch",       # the fetch of the call's quality: the wait for the run
    "kmeans.checkpoint", "sgd_mf.checkpoint", "lda.checkpoint",
    "gang.straggler_publish",
    TRACE_MARK, LOWER, COMPILE, CACHE_LOAD,
)


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
_here = threading.local()        # .phase: the innermost open phase;
#                                  .lowering: jax's open traces and lowerings


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
    """Context manager: one host span (module docstring). ``start`` is its
    beginning on ``time.perf_counter()`` and :meth:`elapsed` the seconds
    since, for whoever needs the call's wall time on the same clock
    (``record_chunk(wall_s=call.elapsed())``)."""

    __slots__ = ("name", "id", "call", "start", "_parent", "_note")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "phase":
        parent = self._parent = getattr(_here, "phase", None)
        self.id = next(_ids)
        self.call = next(_calls) if parent is None else parent.call
        _here.phase = self
        self._note = TraceAnnotation(self.name)   # inert with no session
        self._note.__enter__()
        self.start = time.perf_counter()
        return self

    def elapsed(self) -> float:
        return time.perf_counter() - self.start

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        self._note.__exit__(*exc)
        parent = _here.phase = self._parent
        _keep(PhaseRecord(self.name, self.start, end,
                          None if parent is None else parent.id,
                          self.call, self.id))


def _mark(name: str, start: float, end: float,
          detail: Optional[str]) -> None:
    """A record nobody entered: under the phase open on this thread."""
    parent = getattr(_here, "phase", None)
    _keep(PhaseRecord(
        name, start, end, None if parent is None else parent.id,
        next(_calls) if parent is None else parent.call, next(_ids), detail))


def traced(program: str) -> None:
    """Count one trace of ``program``: the ``program.traces.<program>``
    counter of ``utils.metrics.DEFAULT`` and a zero-length ``program.trace``
    mark in the ring. Call it from inside the traced function."""
    metrics_lib.DEFAULT.count(f"program.traces.{program}")
    now = time.perf_counter()
    _mark(TRACE_MARK, now, now, program)


# -- the compile path (module docstring) ------------------------------------- #

_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": LOWER,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
    "/jax/core/compile/backend_compile_duration": COMPILE,
    "/jax/compilation_cache/cache_retrieval_time_sec": CACHE_LOAD,
}
_COUNTS = {
    "/jax/compilation_cache/cache_hits": "program.cache.hits",
    "/jax/compilation_cache/cache_misses": "program.cache.misses",
}


def _on_start(event: str, _value: float, **_kw) -> None:
    # jax reports the start of what it will report the duration of
    if _DURATIONS.get(event) == LOWER:
        _here.lowering = getattr(_here, "lowering", 0) + 1


def _on_duration(event: str, seconds: float, **kw) -> None:
    name = _DURATIONS.get(event)
    if name is None:
        return
    if name == LOWER:
        _here.lowering = max(0, getattr(_here, "lowering", 1) - 1)
        if _here.lowering:
            return          # inside its caller's, which reports when it ends
    now = time.perf_counter()
    _mark(name, now - seconds, now, kw.get("fun_name"))


def _on_event(event: str, **_kw) -> None:
    counter = _COUNTS.get(event)
    if counter is not None:
        metrics_lib.DEFAULT.count(counter)


jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


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


def union_seconds(records: Sequence[PhaseRecord], name: str) -> float:
    """Seconds the records called ``name`` cover together: an interval that
    lies inside another of the name (a nested trace) is not counted twice."""
    total, reach = 0.0, float("-inf")
    for r in sorted((r for r in records if r.name == name),
                    key=lambda r: r.start):
        total += max(0.0, r.end - max(r.start, reach))
        reach = max(reach, r.end)
    return total


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
