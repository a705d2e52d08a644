"""Serving load generator — p50/p99 latency + QPS at mixed traffic.

Drives a local serving gang (2 :class:`~harp_tpu.serve.router.ServeWorker`\\ s
on authenticated loopback p2p — worker 0 owns the classify endpoint, worker
1 the recsys top-k) with N closed-loop clients at >=3 traffic mixes, and
reports per-mix request latency percentiles and sustained QPS.

Protocol per mix:

* every client runs its share of requests back-to-back (closed loop:
  concurrency == number of clients — the batcher's coalescing window sees
  at most ``clients`` in-flight requests, so the measured occupancy is the
  honest low-traffic figure, not an open-loop flood);
* the op per request follows a per-client seeded RNG at the mix's top-k
  fraction, ids/feature vectors drawn from the served id/feature space;
* latency = submit -> reply, observed into a PER-THREAD bounded
  :class:`~harp_tpu.utils.metrics.TimerReservoir` (contention isolation:
  the hot loop never touches a shared registry lock) and
  merged serially after the join; the row's p50/p99 come from
  ``Metrics.timing()`` — the same percentile surface the straggler
  reports use (one latency format, ISSUE 10 satellite);
* a warmup pass first touches every (endpoint, bucket) the run can reach,
  so compile time never pollutes a latency sample (the endpoints hold ONE
  resident compiled dispatch per bucket — ``trace_counts`` rides in the
  row as proof no retrace happened mid-run).

When telemetry is active (``HARP_TELEMETRY_DIR`` / ``telemetry.configure``),
each mix row is also published into ``steps.jsonl`` via
:func:`harp_tpu.telemetry.record_timing` (``kind: "timing"`` events), and
the batcher's occupancy/batch-size gauges land in the shared metrics
registry.

Observability plane (r13): every Nth request is TRACED
(``trace_sample``, through :mod:`harp_tpu.telemetry.spans`) and the row
gains ``stage_breakdown`` (per-stage p50/p99/mean over the sampled spans
— the six stages partition each span's end-to-end latency) plus
``reconciliation`` (stage sums vs the measured end-to-end: the mean ratio
is ~1.0 by construction; the p50 ratio is reported, medians being not
additive across stages), ``lookup_skew`` (the TopK endpoint's per-owner
histogram), and a per-mix ``deadline_expired`` count (``deadline_s``
attaches deadlines to every request so expiry behavior is measurable).

Latency on a CPU-mesh session prices the ROUTER + BATCHER + dispatch stack
with CPU dispatch times; ``chip_smoke.py``'s serving leg drives the same
generator with real TPU dispatches (the row carries ``device`` so the two
never get confused).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

import numpy as np

# mix name -> fraction of requests that are top-k (the rest classify)
DEFAULT_MIXES: Dict[str, float] = {
    "topk_heavy": 0.8,
    "classify_heavy": 0.2,
    "mixed": 0.5,
}

CLASSIFY_MODEL = "classify"
TOPK_MODEL = "topk"


def build_gang(session, *, num_users: int = 512, num_items: int = 256,
               rank: int = 8, k: int = 10, classify_dim: int = 16,
               num_classes: int = 3, max_wait_s: float = 0.002,
               seed: int = 0, metrics=None, trace_sample: int = 0,
               slo_p99_s=None, slo_kw=None, quant=None, accept_enc=None):
    """A 2-worker serving gang over synthetic trained state.

    Returns ``(workers, make_client, meta)`` — ``meta`` carries the
    id/feature spaces the load threads draw from and the served state
    itself (``user_factors``/``item_factors``/``classify_params``, for a
    caller that checks replies against a reference). Factors are random
    (serving cost does not depend on their values); the tier-1 parity tests
    in tests/test_serve.py cover correctness against fitted models.

    ``quant="int8"`` builds BOTH endpoints with int8 resident state and
    ``accept_enc`` is forwarded to every client the returned factory makes
    (ISSUE 17) — the quantized-serving bench compares two gangs built from
    the same seed, one per mode.
    """
    from harp_tpu.models import nn
    from harp_tpu.serve import (TopKEndpoint, classify_from_nn, local_gang)

    rng = np.random.default_rng(seed)
    model = nn.MLPClassifier(session, nn.NNConfig(
        layers=(32,), num_classes=num_classes))
    model.params = nn.init_params((classify_dim, 32, num_classes), seed=seed)
    ep_classify = classify_from_nn(session, model, name=CLASSIFY_MODEL,
                                   quant=quant)
    user_factors = rng.normal(size=(num_users, rank)).astype(np.float32)
    item_factors = rng.normal(size=(num_items, rank)).astype(np.float32)
    ep_topk = TopKEndpoint(session, TOPK_MODEL, user_factors, item_factors,
                           k=k, metrics=metrics, quant=quant)
    workers, make_client = local_gang(
        session, [{CLASSIFY_MODEL: ep_classify}, {TOPK_MODEL: ep_topk}],
        max_wait_s=max_wait_s, metrics=metrics, trace_sample=trace_sample,
        slo_p99_s=slo_p99_s, slo_kw=slo_kw, accept_enc=accept_enc)
    meta = {"num_users": num_users, "num_items": num_items, "rank": rank,
            "k": k, "classify_dim": classify_dim,
            "user_factors": user_factors, "item_factors": item_factors,
            "classify_params": model.params,
            "endpoints": {CLASSIFY_MODEL: ep_classify, TOPK_MODEL: ep_topk}}
    return workers, make_client, meta


def _client_loop(client, n_requests: int, topk_fraction: float, meta: dict,
                 seed: int, metrics, timer_name: str, errors: list,
                 barrier: threading.Barrier, timeout: float,
                 deadline_s: Optional[float] = None) -> None:
    rng = np.random.default_rng(seed)
    from harp_tpu.serve import OP_CLASSIFY, OP_TOPK

    barrier.wait()
    for _ in range(n_requests):
        is_topk = rng.random() < topk_fraction
        if is_topk:
            data = int(rng.integers(0, meta["num_users"]))
            op, model = OP_TOPK, TOPK_MODEL
        else:
            data = rng.normal(size=(meta["classify_dim"],)).astype(
                np.float32)
            op, model = OP_CLASSIFY, CLASSIFY_MODEL
        t0 = time.perf_counter()
        try:
            deadline_ts = (time.time() + deadline_s
                           if deadline_s is not None else None)
            client.submit(op, model, data,
                          deadline_ts=deadline_ts).result(timeout)
        except Exception as e:
            # the load thread records ANY per-request failure (ServeError,
            # timeout, transport error) and keeps the mix running; failures
            # surface via the row's errors count, not by killing the
            # generator mid-measurement
            errors.append(f"{op}: {type(e).__name__}: {e}")
            continue
        metrics.observe(timer_name, time.perf_counter() - t0)


def measure(session=None, *, requests_per_mix: int = 900,
            num_clients: int = 3, mixes: Optional[Dict[str, float]] = None,
            max_wait_s: float = 0.002, request_timeout: float = 60.0,
            seed: int = 0, trace_sample: int = 4,
            deadline_s: Optional[float] = None) -> dict:
    """Run every mix; returns the bench row (see module docstring).

    ``trace_sample=N`` traces every Nth request through telemetry.spans
    (0 = off): the per-stage breakdown row and its end-to-end
    reconciliation come from those spans. ``deadline_s`` attaches a
    deadline to every request; expired ones are counted per mix
    (``deadline_expired``) so a client can see its deadline vs the
    coalescing window."""
    import jax

    from harp_tpu import telemetry
    from harp_tpu.serve import OP_CLASSIFY, OP_TOPK
    from harp_tpu.serve import protocol as serve_protocol
    from harp_tpu.telemetry import spans
    from harp_tpu.utils.metrics import Metrics

    if session is None:
        from harp_tpu.session import HarpSession

        session = HarpSession()
    mixes = dict(DEFAULT_MIXES if mixes is None else mixes)
    metrics = Metrics()          # fresh registry: reservoirs are per-run
    workers, make_client, meta = build_gang(
        session, max_wait_s=max_wait_s, metrics=metrics, seed=seed,
        trace_sample=trace_sample)
    # span timers are observed by each client's RECEIVE thread — one
    # registry per client so one client's spans never dilute another's,
    # merged serially after the mixes (reservoir adds are lock-guarded
    # since jaxlint v3, so this is isolation, not a race workaround)
    span_regs = [Metrics() for _ in range(num_clients)]
    clients = [make_client(span_metrics=span_regs[i])
               for i in range(num_clients)]
    rows: Dict[str, dict] = {}
    try:
        # warmup, two layers: (1) compile EVERY bucket a closed loop of
        # `num_clients` in-flight requests can reach — batches coalesce up
        # to num_clients, so on a narrow mesh (bucket_sizes start at W)
        # that can span several buckets, and a compile inside the measured
        # loop would pollute a latency sample; (2) one request per
        # (client, op) through the gang so the p2p connections and reply
        # paths are established too
        for name, ep in meta["endpoints"].items():
            top = ep.bucket_for(min(num_clients, ep.max_batch))
            for bucket in ep.bucket_sizes:
                if bucket > top:
                    break
                if name == TOPK_MODEL:
                    ep.dispatch(np.zeros(bucket, np.int64))
                else:
                    ep.dispatch(np.zeros(
                        (bucket, meta["classify_dim"]), np.float32))
        for c in clients:
            # warmup requests run UNTRACED: the first request per client
            # pays transport connect + add_peer, and that setup cost must
            # not land in the measured span percentiles
            sample = c.trace_sample
            c.trace_sample = 0
            try:
                c.request(OP_TOPK, TOPK_MODEL, 0, timeout=request_timeout)
                c.request(OP_CLASSIFY, CLASSIFY_MODEL,
                          np.zeros(meta["classify_dim"], np.float32),
                          timeout=request_timeout)
            finally:
                c.trace_sample = sample
        # warmup queried id 0 everywhere — it must not read as a hot key
        meta["endpoints"][TOPK_MODEL].reset_lookup_skew()
        for mix, frac in mixes.items():
            timer = f"serve.latency.{mix}"
            per_client = max(1, requests_per_mix // num_clients)
            errors: list = []
            barrier = threading.Barrier(num_clients + 1)
            # one registry PER CLIENT THREAD: recording privately keeps
            # the hot loop off the shared registry lock (zero contention
            # in the measured path) and the serial post-join merge exact
            thread_regs = [Metrics() for _ in clients]
            threads = [threading.Thread(
                target=_client_loop,
                args=(c, per_client, frac, meta, seed + 100 + i,
                      thread_regs[i], timer, errors, barrier,
                      request_timeout, deadline_s),
                name=f"harp-serve-load-{mix}-{i}", daemon=True)
                for i, c in enumerate(clients)]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            done = 0
            for reg in thread_regs:
                tr = reg.timers.get(timer)
                if tr is not None:
                    done += tr.count      # exact, even past the sample cap
                metrics.merge(reg)        # reservoir-merged, count exact
            timing = metrics.timing(timer)
            rows[mix] = {
                "topk_fraction": frac,
                "requests": done,
                "errors": len(errors),
                "error_sample": errors[:3],
                "deadline_expired": sum(
                    1 for e in errors
                    if serve_protocol.ERR_DEADLINE in e),
                "qps": round(done / wall, 1) if wall > 0 else None,
                "p50_ms": round(timing["p50_s"] * 1e3, 3) if timing else None,
                "p99_ms": round(timing["p99_s"] * 1e3, 3) if timing else None,
                "mean_ms": round(timing["mean_s"] * 1e3, 3) if timing
                else None,
            }
            # one latency format (ISSUE 10 satellite): the same timing()
            # dict the straggler report rows carry, into steps.jsonl
            telemetry.record_timing(timer, metrics=metrics,
                                    extra={"mix": mix,
                                           "qps": rows[mix]["qps"]})
            metrics.gauge(f"serve.qps.{mix}", rows[mix]["qps"] or 0.0)
        occupancy = {}
        for name in (CLASSIFY_MODEL, TOPK_MODEL):
            batch_t = metrics.timing(f"serve.batch.{name}")
            occupancy[name] = {
                "mean_batch": round(batch_t["mean_s"], 2) if batch_t
                else None,
                "dispatches": batch_t.get("count", 0) if batch_t else 0,
                "trace_counts": dict(
                    meta["endpoints"][name].trace_counts),
            }
        # per-stage breakdown from the sampled spans (whole run, all
        # mixes): the six stage durations PARTITION each span's end-to-end
        # latency exactly, so the stage MEAN sum reconciles with the span
        # mean to float noise; percentile sums are sub/super-additive
        # across differently-skewed stages, so the p50 ratio is reported,
        # not held to a band
        for reg in span_regs:
            metrics.merge(reg)
        stage_breakdown = {}
        for stage in ("total",) + spans.STAGES:
            t = metrics.timing(f"serve.span.{stage}")
            if t:
                stage_breakdown[stage] = {
                    "p50_ms": round(t["p50_s"] * 1e3, 3),
                    "p99_ms": round(t["p99_s"] * 1e3, 3),
                    "mean_ms": round(t["mean_s"] * 1e3, 3),
                    "count": t["count"]}
        reconciliation = None
        if "total" in stage_breakdown and all(
                s in stage_breakdown for s in spans.STAGES):
            stage_p50_sum = sum(stage_breakdown[s]["p50_ms"]
                                for s in spans.STAGES)
            stage_mean_sum = sum(stage_breakdown[s]["mean_ms"]
                                 for s in spans.STAGES)
            tot = stage_breakdown["total"]
            reconciliation = {
                "spans": tot["count"],
                "span_p50_ms": tot["p50_ms"],
                "stage_p50_sum_ms": round(stage_p50_sum, 3),
                "p50_ratio": round(stage_p50_sum / tot["p50_ms"], 4)
                if tot["p50_ms"] else None,
                "span_mean_ms": tot["mean_ms"],
                "stage_mean_sum_ms": round(stage_mean_sum, 3),
                "mean_ratio": round(stage_mean_sum / tot["mean_ms"], 4)
                if tot["mean_ms"] else None,
                "note": "stage durations partition each span exactly; "
                        "mean_ratio ~ 1.0 by construction, p50_ratio "
                        "reported only (percentiles are not "
                        "additive across stages)",
            }
            telemetry.record_timing("serve.span.total", metrics=metrics,
                                    extra={"stage_p50_sum_ms":
                                           round(stage_p50_sum, 3)})
        skew = meta["endpoints"][TOPK_MODEL].lookup_skew()
    finally:
        for c in clients:
            c.close()
        for w in workers:
            w.close()
    device = ("tpu" if any(d.platform == "tpu" for d in jax.devices())
              else jax.devices()[0].platform)
    row = {
        "gang": f"2 workers + {num_clients} closed-loop clients, "
                f"loopback authenticated p2p, max_wait_s={max_wait_s}, "
                f"trace_sample={trace_sample}",
        "device": device,
        "mixes": rows,
        "batching": occupancy,
        "stage_breakdown": stage_breakdown,
        "reconciliation": reconciliation,
        "lookup_skew": skew,
    }
    if device != "tpu":
        row["note"] = (
            f"{device}-mesh session: latency prices the router + "
            f"micro-batcher + {device} dispatch stack, not a TPU's "
            f"(same schema, device='tpu' there)")
    return row
