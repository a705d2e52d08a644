"""Gang-wide telemetry — per-step structured events, comm-volume accounting,
straggler detection, and on-demand profiler windows.

The reference's only observability was log4j inline wall-clock per phase
(SURVEY §5: KMeansCollectiveMapper.java:190-195 per-iteration compute/merge/
aggregate ms). This package is that idiom grown into a subsystem, under one
hard constraint: **no telemetry operation and no host sync ever enters a
jitted step program**. Every hook lives at the host chunk boundaries where
the training loops ALREADY synchronize losses to the host (the
``fit_checkpointed`` chunk fetches, the final ``np.asarray`` of a scanned
fit) — jaxlint's JL104 host-sync check and the JL201/JL203 collective-budget
manifest are bitwise unchanged with telemetry on, and ``tools/ci_checks.sh``
gates exactly that. Names are not operations: the step programs carry
``jax.named_scope`` names on every kernel (metadata only).

Always on, writing nothing (one span layer, at the layer boundaries of the
training path):

* :mod:`~harp_tpu.telemetry.host_spans` — ``phase(name)``, the one host span:
  ``(name, start, end, parent, call)`` on ``time.perf_counter()`` in a bounded
  ring, and a ``TraceAnnotation`` in any open profiler session; ``traced``
  counts the traces of a program, and jax's own reports of every trace,
  lowering, compile and cache load become ``program.lower`` /
  ``program.compile`` / ``program.cache_load`` records under the phase that
  paid for them. ``PHASES`` lists the names. Readers: ``phases``,
  ``self_seconds``, ``union_seconds``.
* :mod:`~harp_tpu.telemetry.scopes` — the list of device scopes, the
  ``scoped`` decorator, the two readers that turn a compiled text and a
  profiler trace into device time per scope, and ``idle_by_phase``, which
  puts the device's idle time of a trace down to the host phases.

Layers that write, when enabled:

* :mod:`~harp_tpu.telemetry.step_log` — per-step structured events into a
  bounded ring buffer, flushed as JSONL per rank. ``record_chunk`` is the one
  call the models make; it is a single ``None``-check when telemetry is off.
* :mod:`~harp_tpu.telemetry.comm_ledger` — wire-volume accounting priced off
  the pinned collective-budget manifest (``tools/collective_budget.json``):
  bytes/step, cumulative GB, achieved busbw as gauges, with quantized paths
  priced at their quantized ``bytes_per_step`` rows. No hot-path
  instrumentation — EQuARX-style measured wire bytes for free.
* :mod:`~harp_tpu.telemetry.gang` — rank 0 collects per-rank
  ``Metrics.snapshot()`` over the authenticated events control plane and
  publishes a straggler report (suspect = sustained p50 step time > k× the
  gang median) consumable by ``parallel.supervisor``.
* :mod:`~harp_tpu.telemetry.xprof` — an ``events.send_collective`` payload
  makes every rank capture a ``jax.profiler`` trace for the next N chunk
  boundaries into a per-rank directory: profile a slow gang without
  restarting it.

The serving observability plane (PR 12) extends the same contracts to the
request path:

* :mod:`~harp_tpu.telemetry.spans` — end-to-end request tracing: sampled
  request frames carry per-stage host-boundary stamps through the serve
  router/batcher; completed spans land as ``kind: "span"`` events in the
  same JSONL stream. Zero-drift gated like the rest of the package.
* :mod:`~harp_tpu.telemetry.exporter` — a per-worker stdlib-HTTP pull
  exporter: ``/metrics`` (Prometheus text), ``/snapshot`` (JSON), and the
  gang-aggregated ``/gang`` view off the events-control-plane exchange.
* :mod:`~harp_tpu.telemetry.watchdog` — an SLO watchdog over the span /
  step stream (rolling p99 target + error budget) that, on sustained
  burn, auto-arms an xprof window, dumps the straggler-format snapshot,
  and journals the incident — the PR 7 machinery triggered by its own
  signal instead of an operator.

Enable with ``harp_tpu.run ... --telemetry-dir DIR [--telemetry-interval N]``
or programmatically via :func:`configure`; the ``HARP_TELEMETRY_DIR`` /
``HARP_TELEMETRY_INTERVAL`` environment variables do the same for embedded
callers (gang members inherit them from the launcher environment).
"""

from __future__ import annotations

from harp_tpu.telemetry import scopes, spans
from harp_tpu.telemetry.comm_ledger import (CommLedger, ledger_for,
                                            load_manifest, manifest_target)
from harp_tpu.telemetry.exporter import (MetricsExporter,
                                         aggregate_snapshots,
                                         prometheus_text)
from harp_tpu.telemetry.gang import (gather_snapshots, publish_straggler_report,
                                     straggler_report)
from harp_tpu.telemetry.host_spans import (PHASES, PhaseRecord, phase, phases,
                                           self_seconds, traced,
                                           union_seconds)
from harp_tpu.telemetry.spans import record_span
from harp_tpu.telemetry.step_log import (StepLog, active, configure, disable,
                                         record_chunk, record_program,
                                         record_timing)
from harp_tpu.telemetry.watchdog import SLOWatchdog
from harp_tpu.telemetry.xprof import XprofController, request_xprof

__all__ = [
    "CommLedger", "MetricsExporter", "PHASES", "PhaseRecord", "SLOWatchdog",
    "StepLog",
    "XprofController", "active", "aggregate_snapshots", "configure",
    "disable", "gather_snapshots", "ledger_for", "load_manifest",
    "manifest_target", "phase", "phases", "prometheus_text",
    "publish_straggler_report", "record_chunk", "record_program",
    "record_span", "record_timing", "request_xprof", "scopes",
    "self_seconds", "spans", "straggler_report", "traced", "union_seconds",
]
