"""Online serving — the traffic-bearing face of the trained models.

The reference stopped at batch fit/predict (every launcher ran one
map-collective job and exited); the ROADMAP north star says "heavy traffic
from millions of users". This package is that execution shape: a RESIDENT
online service instead of a batch job, built from the same primitives the
trainers use —

* :mod:`~harp_tpu.serve.router` — an async request router riding the
  existing authenticated p2p/events control plane
  (``parallel/p2p.py``, ``parallel/events.py``): clients submit point
  queries, the router fans each to the worker that owns the model, replies
  travel point-to-point back to the requesting client (no gang-wide call
  anywhere on the request path).
* :mod:`~harp_tpu.serve.batcher` — continuous micro-batching: in-flight
  requests coalesce (deadline- and size-bounded) into ONE resident jitted
  predict dispatch per (model, batch-bucket) — static bucket shapes, donated
  query buffers, zero per-request retrace. The jaxlint trace targets
  ``serve_classify_nn`` / ``serve_topk_mf`` pin the dispatch programs in
  ``tools/collective_budget.json`` (JL201/JL203), so a collective sneaking
  into the classify dispatch or a retrace-shaped cache regression fails CI
  exactly like a training-step drift.
* :mod:`~harp_tpu.serve.endpoints` — the resident model surfaces:
  classification endpoints for SVM / forest / NN ``predict`` (replicated
  parameters, sharded query batch, zero collectives), and recsys **top-k**
  served straight from the keyval push-pull machinery: SGD-MF/ALS user
  factors live in a mesh-sharded :class:`~harp_tpu.keyval.DistributedKV`
  (owner = ``id mod W``) and each dispatch routes the query ids to their
  owners and back through the same ``bucket_route``/``route_back``
  all_to_alls the parameter-server ops use.

Serving state follows the SNIPPETS.md flax-partitioner pattern: shapes are
resolved once, the sharding-annotated compiled fn stays resident, and every
subsequent request is a pure dispatch. The DrJAX framing (arXiv:2403.07128)
holds too: the serve step is a single traced program over the same mesh
primitives as the trainers — which is exactly what lets the jaxpr budget
engine police it.

Load generation lives in :mod:`harp_tpu.benchmark.serving_load`: p50/p99
latency + QPS at >=3 traffic mixes, published through
:mod:`harp_tpu.telemetry`.

The serving observability plane (r13) rides this package without touching
a traced program: sampled requests carry per-stage span stamps
(:mod:`harp_tpu.telemetry.spans`), every worker can serve a Prometheus
``/metrics`` + JSON ``/snapshot`` pull endpoint
(``ServeWorker(metrics_port=...)`` / ``local_gang(metrics_port=...)``),
the top-k endpoint histograms lookup skew per owning worker (the hot-key
signal), and an optional per-worker SLO watchdog
(``local_gang(slo_p99_s=...)``) turns sustained p99/error-budget burn
into an xprof window + straggler snapshot + journaled incident.

The FLEET layer (r15, ISSUE 14) makes the gang elastic and continuously
redeployed: :mod:`~harp_tpu.serve.fleet` runs workers as separate
processes (launched through the ``parallel/launch`` member-spawn path,
file rendezvous, authenticated p2p), supervises them (a dead worker is
classified crash/VANISH by exit code, its models re-routed by a versioned
placement push, its KV shard restored onto a spare through the on-device
reshard engine — ``TopKEndpoint.restore_shard``/``restore_full``), while
clients ride ``RouterClient.request_retry`` (bounded retries with jitter,
dead-rank fast-fail, placement re-sync). ``TopKEndpoint.push_epoch`` swaps
in new factor epochs under live traffic (versioned, snapshot-consistent —
every reply names the epoch that answered it), a shared
:class:`~harp_tpu.serve.cache.TopKReplyCache` absorbs Zipfian hot keys at
the router, and the whole recovery story is scripted through the serving
fault grammar (``HARP_FAULT=kill|vanish|slow@request=N``).

The AOT artifact layer (r16, ISSUE 15) makes cold starts loads instead of
compile events: :mod:`harp_tpu.aot` exports every (model, bucket) resident
dispatch once (``run.py aot warm``), and a worker constructed with
``ServeWorker(aot_store=)`` / ``local_gang(aot_dir=)`` /
``ProcessServeGang(aot_dir=)`` installs fresh store hits as its resident
dispatches and warms them BEFORE rendezvous — ``trace_counts`` stays 0 for
artifact-loaded buckets (asserted), so an elastic replacement never
recompiles under traffic. Stale artifacts (jax version, device kind,
world, layout, or model-hash mismatch) are rejected loudly and fall back
to compile; the compiled programs themselves are content-hash-pinned in
``tools/artifact_manifest.json`` (jaxlint ``--artifacts-only``).
Per-model coalescing deadlines (``max_wait_overrides``, with
:func:`~harp_tpu.serve.batcher.suggest_max_wait_s` deriving a value from
the span table's per-model coalesce stage) ride the same surfaces; jax's
persistent compilation cache is always on under them (``aot.cache``).
"""

from __future__ import annotations

from harp_tpu.serve.autoscaler import Autoscaler
from harp_tpu.serve.batcher import MicroBatcher, suggest_max_wait_s
from harp_tpu.serve.cache import TopKReplyCache
from harp_tpu.serve.endpoints import (ClassifyEndpoint, Endpoint,
                                      TopKEndpoint, classify_from_forest,
                                      classify_from_linear_svm,
                                      classify_from_multiclass_svm,
                                      classify_from_nn,
                                      rebalance_from_incidents,
                                      rebalance_from_report)
from harp_tpu.serve.protocol import (OP_CLASSIFY, OP_TOPK, ServeError,
                                     make_placement, make_placement_get,
                                     make_reply, make_request)
from harp_tpu.serve.router import RouterClient, ServeWorker, local_gang

__all__ = [
    "Autoscaler",
    "ClassifyEndpoint", "Endpoint", "MicroBatcher", "OP_CLASSIFY", "OP_TOPK",
    "RouterClient", "ServeError", "ServeWorker", "TopKEndpoint",
    "TopKReplyCache", "classify_from_forest", "classify_from_linear_svm",
    "classify_from_multiclass_svm", "classify_from_nn", "local_gang",
    "make_placement", "make_placement_get", "make_reply", "make_request",
    "rebalance_from_incidents", "rebalance_from_report",
    "suggest_max_wait_s",
]
