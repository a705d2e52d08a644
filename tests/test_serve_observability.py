"""Serving observability plane tests (ISSUE 12).

Covers the request-tracing tentpole (trace-id propagation and span
completeness across the forwarding hop on a 2-worker gang, the
partition-exact breakdown, the zero-drift budget gate with tracing ON),
the pull exporter (/metrics Prometheus schema, /snapshot JSON, /gang
aggregation, the per-worker wiring), the per-owner lookup-skew histogram
vs a known Zipfian id batch, the SLO watchdog (fires exactly once per
burn window; live integration under an injected slow@ fault with the
xprof trigger + snapshot chain), the batcher's pre-dispatch queue-depth
gauges, the deadline-exceeded reply detail, and the serving-load row's
observability keys.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from harp_tpu import telemetry
from harp_tpu.serve import (OP_CLASSIFY, OP_TOPK, MicroBatcher,
                            TopKEndpoint, classify_from_nn, local_gang,
                            protocol)
from harp_tpu.telemetry import spans
from harp_tpu.telemetry.exporter import (MetricsExporter,
                                         aggregate_snapshots,
                                         prometheus_text)
from harp_tpu.telemetry.watchdog import SLOWatchdog
from harp_tpu.utils.metrics import Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_off_after():
    yield
    telemetry.disable()


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f]


def _nn_model(session, dim=12, classes=3, seed=0):
    from harp_tpu.models import nn

    model = nn.MLPClassifier(session, nn.NNConfig(layers=(8,),
                                                  num_classes=classes))
    model.params = nn.init_params((dim, 8, classes), seed=seed)
    return model


def _two_worker_gang(session, rng, **gang_kw):
    ep_cls = classify_from_nn(session, _nn_model(session), name="classify")
    uf = rng.normal(size=(64, 8)).astype(np.float32)
    items = rng.normal(size=(32, 8)).astype(np.float32)
    ep_topk = TopKEndpoint(session, "topk", uf, items, k=4,
                           metrics=gang_kw.get("metrics"))
    return local_gang(session, [{"classify": ep_cls}, {"topk": ep_topk}],
                      **gang_kw), ep_topk


# --------------------------------------------------------------------------- #
# Tentpole: request tracing
# --------------------------------------------------------------------------- #

def test_trace_propagation_and_span_completeness_across_forward(
        session, rng, tmp_path):
    """A traced request forwarded worker 0 → worker 1 comes back with ONE
    trace id (the request id) and a complete stamp sequence; the direct
    leg completes too; both land as kind:"span" JSONL events."""
    m = Metrics()
    telemetry.configure(str(tmp_path), interval=1, metrics=m)
    (workers, make_client), _ep = _two_worker_gang(
        session, rng, metrics=m, trace_sample=1)
    client = make_client()
    try:
        # dest=0 but topk lives on worker 1: the forwarding leg
        row = client.request(OP_TOPK, "topk", 7, dest=0, timeout=30.0)
        assert row["found"]
        client.request(OP_CLASSIFY, "classify",
                       rng.normal(size=12).astype(np.float32), timeout=30.0)
    finally:
        client.close()
        for w in workers:
            w.close()
    log = telemetry.active()
    log.flush()
    events = [e for e in _read_jsonl(log.path) if e.get("kind") == "span"]
    assert len(events) == 2, events
    fwd = next(e for e in events if e["op"] == OP_TOPK)
    direct = next(e for e in events if e["op"] == OP_CLASSIFY)
    # trace id IS the request id: client rank, first two submits
    assert fwd["trace_id"] == f"{client.rank}-0"
    assert direct["trace_id"] == f"{client.rank}-1"
    assert fwd["forwarded"] and fwd["forward_hop_s"] >= 0.0
    assert not direct["forwarded"]
    for ev in events:
        stage_sum = sum(ev[f"{s}_s"] for s in spans.STAGES)
        assert ev["total_s"] == pytest.approx(stage_sum, abs=1e-6)
        assert ev["dispatch_s"] > 0.0 and ev["coalesce_s"] >= 0.0
    # the client-side per-stage timers observed both spans
    assert m.timing("serve.span.total")["count"] == 2
    assert m.counters["serve.spans"] == 2
    assert m.counters.get("serve.spans_forwarded", 0) == 1


def test_breakdown_partitions_total_and_rejects_incomplete():
    tr = {"id": "c-0", "op": "topk", "model": "m", "stamps": []}
    for stage, ts in ((spans.SUBMIT, 1.0), (spans.RECV, 1.010),
                      (spans.FORWARD, 1.011), (spans.RECV, 1.020),
                      (spans.ENQUEUE, 1.021), (spans.DISPATCH_START, 1.023),
                      (spans.DISPATCH_END, 1.027), (spans.REPLY_SEND, 1.028),
                      (spans.REPLY_RECV, 1.030)):
        tr["stamps"].append((stage, ts))
    bd = spans.breakdown(tr)
    assert bd["forwarded"] and bd["trace_id"] == "c-0"
    assert bd["total_s"] == pytest.approx(0.030)
    assert sum(bd[f"{s}_s"] for s in spans.STAGES) == pytest.approx(
        bd["total_s"])
    # route covers recv→enqueue INCLUDING the forward hop
    assert bd["route_s"] == pytest.approx(0.011)
    assert bd["forward_hop_s"] == pytest.approx(0.009)
    # a request rejected before the batcher has no dispatch stamps
    half = {"id": "c-1", "stamps": [(spans.SUBMIT, 1.0), (spans.RECV, 1.1),
                                    (spans.REPLY_SEND, 1.2),
                                    (spans.REPLY_RECV, 1.3)]}
    assert spans.breakdown(half) is None


def test_untraced_requests_carry_no_trace_key(session, rng):
    (workers, make_client), _ep = _two_worker_gang(session, rng,
                                                   trace_sample=0)
    client = make_client()
    try:
        assert client.trace_sample == 0
        pending = client.submit(OP_TOPK, "topk", 3)
        assert pending.result(30.0)["found"]
        assert spans.TRACE_KEY not in pending.reply
    finally:
        client.close()
        for w in workers:
            w.close()


def test_budget_manifest_zero_drift_with_request_tracing_on(
        tmp_path, monkeypatch):
    """The r13 CI gate, in-process: the serve dispatch programs traced
    with telemetry AND request tracing enabled must reproduce the pinned
    manifest exactly (stamps live in host router/batcher code — nothing
    enters the resident jitted dispatch). Full sweep in ci_checks.sh
    stage 2."""
    from tools.jaxlint import checkers_jaxpr

    monkeypatch.setenv(spans.ENV_SAMPLE, "1")
    telemetry.configure(str(tmp_path), interval=4)
    with open(os.path.join(REPO, "tools", "collective_budget.json")) as f:
        targets = json.load(f)["targets"]
    for name in ("serve_classify_nn", "serve_topk_mf"):
        counts, dtype_bad, nbytes = checkers_jaxpr.trace_target(name)
        assert counts == targets[name]["collectives"], name
        assert nbytes == targets[name]["bytes_by_kind"], name
        assert sum(nbytes.values()) == targets[name]["bytes_per_step"], name
        assert not dtype_bad


# --------------------------------------------------------------------------- #
# Exporter: /metrics, /snapshot, /gang
# --------------------------------------------------------------------------- #

def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.read().decode()


def test_exporter_metrics_snapshot_and_gang_schema():
    m = Metrics()
    m.count("serve.requests", 7)
    m.gauge("serve.queue_depth.topk", 3.0)
    m.count("telemetry.events_dropped", 2)
    for v in (0.001, 0.002, 0.004):
        m.observe("serve.span.total", v)
    other = Metrics()
    other.count("serve.requests", 5)
    other.observe("serve.span.total", 0.008)
    with MetricsExporter(m, rank=0,
                         gang=lambda: {0: m.snapshot(),
                                       1: other.snapshot()}) as ex:
        base = f"http://{ex.host}:{ex.port}"
        text = _get(base + "/metrics")
        lines = text.splitlines()
        assert "# TYPE harp_serve_requests counter" in lines
        assert "harp_serve_requests 7" in lines
        assert "# TYPE harp_serve_queue_depth_topk gauge" in lines
        assert "harp_telemetry_events_dropped 2" in lines
        assert "# TYPE harp_serve_span_total_seconds summary" in lines
        assert any(l.startswith(
            'harp_serve_span_total_seconds{quantile="0.99"}')
            for l in lines)
        assert "harp_serve_span_total_seconds_count 3" in lines
        snap = json.loads(_get(base + "/snapshot"))
        assert snap["rank"] == 0 and snap["counters"][
            "serve.requests"] == 7
        assert snap["timers"]["serve.span.total"]["count"] == 3
        gang = json.loads(_get(base + "/gang"))
        agg = gang["aggregated"]
        assert agg["num_ranks"] == 2
        assert agg["counters"]["serve.requests"] == 12
        t = agg["timers"]["serve.span.total"]
        assert t["count"] == 4 and t["worst_p99_s"] == pytest.approx(0.008)
        assert set(gang["ranks"]) == {"0", "1"}
        with pytest.raises(urllib.error.HTTPError):
            _get(base + "/nope")
    # closed: the socket is released
    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        _get(base + "/metrics")


def test_exporter_gang_view_absent_is_404():
    with MetricsExporter(Metrics(), rank=3) as ex:
        with pytest.raises(urllib.error.HTTPError):
            _get(f"http://{ex.host}:{ex.port}/gang")


def test_prometheus_text_is_pure_and_sanitizes():
    out = prometheus_text({"counters": {"a.b-c/d": 1.0}, "gauges": {},
                           "timers": {"t": {}}})
    assert "harp_a_b_c_d 1" in out          # empty timer rows are skipped
    assert "_seconds" not in out


def test_aggregate_snapshots_rolls_up_exact_sums():
    a = Metrics()
    a.count("x", 2)
    a.observe("t", 0.010)
    b = Metrics()
    b.count("x", 3)
    b.observe("t", 0.030)
    b.observe("t", 0.030)
    agg = aggregate_snapshots({0: a.snapshot(), 1: b.snapshot()})
    assert agg["counters"]["x"] == 5
    assert agg["timers"]["t"]["count"] == 3
    assert agg["timers"]["t"]["total_s"] == pytest.approx(0.070)
    assert agg["timers"]["t"]["worst_p99_s"] == pytest.approx(0.030)
    assert agg["timers"]["t"]["mean_s"] == pytest.approx(0.070 / 3)


def test_worker_exporter_serves_live_serving_counters(session, rng):
    m = Metrics()
    (workers, make_client), _ep = _two_worker_gang(
        session, rng, metrics=m, metrics_port=0)
    client = make_client()
    try:
        assert all(w.exporter is not None for w in workers)
        ports = {w.exporter.port for w in workers}
        assert len(ports) == 2                # one exporter per worker
        client.request(OP_TOPK, "topk", 3, timeout=30.0)
        text = _get(f"http://127.0.0.1:{workers[1].exporter.port}/metrics")
        assert "harp_serve_requests" in text
        assert "harp_serve_queue_depth_topk" in text
    finally:
        client.close()
        for w in workers:
            w.close()
    # the worker's close released the exporter socket too
    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        _get(f"http://127.0.0.1:{workers[0].exporter.port}/metrics")


# --------------------------------------------------------------------------- #
# Per-owner lookup-skew histogram (the hot-key measurement)
# --------------------------------------------------------------------------- #

def test_topk_lookup_skew_flags_zipfian_batch(session, rng):
    m = Metrics()
    uf = rng.normal(size=(64, 4)).astype(np.float32)
    items = rng.normal(size=(16, 4)).astype(np.float32)
    ep = TopKEndpoint(session, "mf", uf, items, k=3, metrics=m)
    w = session.num_workers
    # a Zipf-shaped batch: 7 of 8 ids hit owner 5 (id ≡ 5 mod 8), one id
    # lands elsewhere — the modulo placement's hot-key worst case
    hot = np.asarray([5, 13, 21, 29, 37, 45, 53, 2])
    ep.dispatch(hot)
    skew = ep.lookup_skew()
    assert skew["total"] == 8
    assert skew["hottest"] == 5
    assert skew["counts"][5] == 7 and sum(skew["counts"]) == 8
    assert skew["skew"] == pytest.approx(7 * w / 8)
    assert m.counters["serve.lookup_owner.mf.r5"] == 7
    assert m.gauges["serve.lookup_skew.mf"] == pytest.approx(7 * w / 8)
    # a uniform batch drags the cumulative skew back down
    ep.dispatch(np.arange(8))
    assert ep.lookup_skew()["skew"] == pytest.approx(8 * w / 16)
    ep.reset_lookup_skew()
    assert ep.lookup_skew()["total"] == 0 and ep.lookup_skew()["skew"] == 0.0


def test_lookup_skew_follows_rebalanced_owner_map(session, rng):
    uf = rng.normal(size=(64, 4)).astype(np.float32)
    items = rng.normal(size=(16, 4)).astype(np.float32)
    m = Metrics()
    ep = TopKEndpoint(session, "mf", uf, items, k=3, metrics=m)
    ep.rebalance(5)               # ids leave rank 5 for healthy workers
    ep.reset_lookup_skew()
    ep.dispatch(np.asarray([5, 13, 21, 29, 37, 45, 53, 61]))
    skew = ep.lookup_skew()
    # every one of those ids USED to live on rank 5; after the rebalance
    # the histogram must follow the moved shard map, not the modulo
    assert skew["counts"][5] == 0 and skew["total"] == 8


# --------------------------------------------------------------------------- #
# SLO watchdog
# --------------------------------------------------------------------------- #

def test_watchdog_fires_exactly_once_per_burn_window(tmp_path):
    m = Metrics()
    wd = SLOWatchdog(0.010, window_s=5.0, min_samples=5, sustain=2,
                     eval_interval_s=0.0, telemetry_dir=str(tmp_path),
                     metrics=m)
    t = 100.0
    for i in range(30):                       # sustained burn: 50ms >> 10ms
        wd.observe(0.050, now=t + i * 0.01)
    assert wd.incidents == 1 and wd.burning
    for i in range(30):                       # still the SAME burn window
        wd.observe(0.050, now=t + 1 + i * 0.01)
    assert wd.incidents == 1
    for i in range(150):                      # recovery: fast samples
        wd.observe(0.001, now=t + 10 + i * 0.05)
    assert not wd.burning and wd.incidents == 1
    for i in range(30):                       # a SECOND burn fires again
        wd.observe(0.050, now=t + 30 + i * 0.01)
    assert wd.incidents == 2
    incidents = _read_jsonl(tmp_path / "slo_incidents.jsonl")
    assert [r["incident"] for r in incidents] == [1, 2]
    assert incidents[0]["p99_s"] > incidents[0]["p99_target_s"]
    assert "xprof_request" in incidents[0]["triggered"]
    assert "metrics_snapshot" in incidents[0]["triggered"]
    # the xprof trigger file is the PR 7 operator-path format
    trig = json.loads((tmp_path / "xprof_request.json").read_text())
    assert trig["steps"] >= 1
    snap = json.loads((tmp_path / "slo_snapshot_rank0_1.json").read_text())
    assert "counters" in snap and "timers" in snap
    assert m.counters["slo.incidents"] == 2


def test_watchdog_error_budget_burns_without_latency(tmp_path):
    wd = SLOWatchdog(10.0, window_s=5.0, min_samples=5, sustain=1,
                     error_budget=0.2, eval_interval_s=0.0,
                     telemetry_dir=str(tmp_path), metrics=Metrics())
    t = 10.0
    for i in range(20):                       # fast but 50% errors
        wd.observe(0.001, ok=(i % 2 == 0), now=t + i * 0.01)
    assert wd.incidents == 1
    rec = _read_jsonl(tmp_path / "slo_incidents.jsonl")[0]
    assert rec["error_fraction"] > rec["error_budget"]


def test_watchdog_under_min_samples_never_fires():
    wd = SLOWatchdog(0.001, min_samples=50, sustain=1, eval_interval_s=0.0,
                     metrics=Metrics())
    for i in range(40):
        wd.observe(1.0, now=10.0 + i * 0.01)
    assert wd.incidents == 0 and not wd.burning


def test_watchdog_fires_under_slow_fault_and_triggers_pr7_chain(
        session, rng, tmp_path, monkeypatch):
    """The acceptance leg, live: a kmeans loop dragged by the slow@ fault
    grammar burns the chunk-boundary SLO; the watchdog journals ONE
    incident, arms the xprof trigger file, dumps the snapshot, attaches
    the published straggler report — and the XprofController boundary
    hook picks the trigger up and actually writes a profiler trace."""
    from harp_tpu.models import kmeans as km
    from harp_tpu.telemetry.gang import write_straggler_report
    from harp_tpu.telemetry.xprof import XprofController
    from harp_tpu.utils.checkpoint import Checkpointer

    tdir = str(tmp_path / "tele")
    m = Metrics()
    log = telemetry.configure(tdir, interval=1, metrics=m)
    # a previously-published straggler report (the GangCollector's cadence
    # output): the incident must attach it
    write_straggler_report(tdir, {"v": 1, "ts": time.time(),
                                  "suspects": [0], "bsp_suspects": []})
    ctl = XprofController(
        session, trigger_path=os.path.join(tdir, "xprof_request.json"),
        default_dir=os.path.join(tdir, "xprof"))
    log.add_boundary_hook(ctl)
    wd = SLOWatchdog(0.010, window_s=60.0, min_samples=3, sustain=2,
                     telemetry_dir=tdir, xprof_steps=2, metrics=m)
    log.add_boundary_hook(wd.boundary_hook())
    monkeypatch.setenv("HARP_FAULT", "slow@epoch=1:ms=40")
    monkeypatch.setenv("HARP_PROCESS_ID", "0")
    cfg = km.KMeansConfig(8, 16, iterations=10)
    pts = rng.normal(size=(64, 16)).astype(np.float32)
    model = km.KMeans(session, cfg)
    p, c = model.prepare(pts, pts[:8].copy())
    model.fit_checkpointed(p, c, Checkpointer(str(tmp_path / "ckpt")),
                           save_every=1)
    monkeypatch.delenv("HARP_FAULT")
    telemetry.disable()           # closes hooks (any open xprof window)
    assert wd.incidents == 1, (wd.incidents, wd.window_stats())
    rec = _read_jsonl(os.path.join(tdir, "slo_incidents.jsonl"))[0]
    assert rec["p99_s"] >= 0.040              # the fault's per-boundary drag
    assert rec["straggler_report"]["suspects"] == [0]
    assert set(rec["triggered"]) >= {"xprof_request", "metrics_snapshot",
                                     "straggler_report_attached"}
    # the controller consumed the trigger and wrote a per-rank trace dir
    trace_dir = os.path.join(tdir, "xprof", "rank0")
    assert os.path.isdir(trace_dir) and os.listdir(trace_dir)


def test_serving_worker_feeds_watchdog_and_burns_on_slow_dispatch(
        session, rng, tmp_path):
    """The serving leg: every reply feeds (request age, ok) into the
    worker's watchdog; a dispatch dragged past the p99 target burns it."""
    m = Metrics()
    (workers, make_client), ep = _two_worker_gang(
        session, rng, metrics=m,
        slo_p99_s=0.005,
        slo_kw={"window_s": 60.0, "min_samples": 3, "sustain": 1,
                "eval_interval_s": 0.0, "telemetry_dir": str(tmp_path)})
    # drag the topk dispatch past the target deterministically
    orig = ep.dispatch

    def slow_dispatch(batch):
        time.sleep(0.02)
        return orig(batch)

    ep.dispatch = slow_dispatch
    client = make_client()
    try:
        for i in range(6):
            client.request(OP_TOPK, "topk", int(i), timeout=30.0)
    finally:
        client.close()
        for w in workers:
            w.close()
    wd = workers[1].slo           # worker 1 owns topk
    assert wd is not None and wd.incidents == 1
    assert (tmp_path / "slo_incidents.jsonl").exists()


# --------------------------------------------------------------------------- #
# Batcher observability satellites
# --------------------------------------------------------------------------- #

class _BlockingEndpoint:
    name = "fake"
    op = "classify"
    bucket_sizes = (4,)
    max_batch = 4

    def __init__(self):
        self.release = threading.Event()
        self.entered = threading.Event()

    def bucket_for(self, n):
        return 4

    def validate_query(self, op, data):
        return None

    def dispatch(self, batch):
        self.entered.set()
        self.release.wait(10.0)
        return list(range(len(batch)))


def _msg(i, deadline_ts=None, ts=None):
    return {"kind": protocol.REQUEST, "id": f"t-{i}", "op": "classify",
            "model": "fake", "data": float(i),
            "reply_to": (9, "127.0.0.1", 1),
            "ts": time.time() if ts is None else ts,
            "deadline_ts": deadline_ts}


def test_batcher_pre_dispatch_queue_depth_and_high_watermark():
    ep = _BlockingEndpoint()
    m = Metrics()
    replies = []
    b = MicroBatcher(ep, lambda msg, ok, **kw: replies.append((msg, ok)),
                     metrics=m, max_wait_s=0.001)
    try:
        b.submit(_msg(0))
        assert ep.entered.wait(5.0)           # first dispatch is in flight
        for i in range(1, 7):                 # queue builds BEHIND it
            b.submit(_msg(i))
        assert m.gauges["serve.queue_depth.fake"] == 6.0
        assert m.gauges["serve.queue_high_watermark.fake"] == 6.0
        assert b.queue_high_watermark == 6
        # depth 5 and 6 exceeded max_batch=4: overload was visible twice
        assert m.counters["serve.queue_overfull.fake"] == 2
    finally:
        ep.release.set()
        b.drain_and_stop()
    # the watermark survives the drain (a past overload stays visible)
    assert m.gauges["serve.queue_high_watermark.fake"] == 6.0
    assert m.gauges["serve.queue_depth.fake"] <= 6.0


def test_deadline_exceeded_reply_carries_age_and_miss():
    class _Instant(_BlockingEndpoint):
        def __init__(self):
            super().__init__()
            self.release.set()

    ep = _Instant()
    m = Metrics()
    replies = []
    lock = threading.Lock()

    def reply(msg, ok, result=None, error=None, **kw):
        with lock:
            replies.append({"id": msg["id"], "ok": ok, "error": error})

    b = MicroBatcher(ep, reply, metrics=m, max_wait_s=0.001)
    try:
        now = time.time()
        b.submit(_msg(0, deadline_ts=now - 0.5, ts=now - 0.7))
        deadline = time.time() + 5.0
        while not replies and time.time() < deadline:
            time.sleep(0.005)
    finally:
        b.drain_and_stop()
    assert replies and not replies[0]["ok"]
    err = replies[0]["error"]
    assert err.startswith(protocol.ERR_DEADLINE)
    # the measured age and the miss margin ride the error, so a client can
    # tune its deadline vs the coalescing window from the reply alone
    assert "request age" in err and "missed deadline by" in err
    assert "max_wait_s" in err
    age = float(err.split("request age ")[1].split(" ms")[0])
    miss = float(err.split("missed deadline by ")[1].split(" ms")[0])
    assert age == pytest.approx(700, abs=250)
    assert miss == pytest.approx(500, abs=250)
    assert m.counters["serve.deadline_expired.fake"] == 1


# --------------------------------------------------------------------------- #
# Load-generator row: observability keys
# --------------------------------------------------------------------------- #

@pytest.mark.large
def test_serving_load_row_reconciles_spans_and_counts_expiry(session,
                                                             tmp_path):
    from harp_tpu.benchmark import serving_load

    telemetry.configure(str(tmp_path), interval=1)
    row = serving_load.measure(session, requests_per_mix=90, num_clients=3,
                               mixes={"mixed": 0.5}, trace_sample=2)
    telemetry.disable()
    assert row["mixes"]["mixed"]["errors"] == 0
    assert row["mixes"]["mixed"]["deadline_expired"] == 0
    sb = row["stage_breakdown"]
    assert set(sb) == {"total"} | set(spans.STAGES)
    rec = row["reconciliation"]
    assert rec["spans"] == sb["total"]["count"] > 0
    # stage durations partition each span, so the means reconcile tightly
    # whatever the host's load. Medians are not additive (under six xdist
    # workers the stage medians summed to 0.74 of the span's): the p50 row
    # is held to the stage table it was reduced from, not to a band
    assert rec["mean_ratio"] == pytest.approx(1.0, abs=0.02)
    assert rec["span_p50_ms"] == sb["total"]["p50_ms"] > 0
    assert rec["stage_p50_sum_ms"] == pytest.approx(
        sum(sb[s]["p50_ms"] for s in spans.STAGES), abs=1e-3)
    assert rec["p50_ratio"] == pytest.approx(
        rec["stage_p50_sum_ms"] / rec["span_p50_ms"], abs=1e-4)
    skew = row["lookup_skew"]
    assert skew["total"] > 0 and len(skew["counts"]) == 8
    # the spans flowed THROUGH telemetry: kind:"span" events in the JSONL
    events = _read_jsonl(tmp_path / "rank0" / "steps.jsonl")
    assert sum(e.get("kind") == "span" for e in events) == rec["spans"]


@pytest.mark.large
def test_serving_load_counts_deadline_expiry_per_mix(session, tmp_path):
    from harp_tpu.benchmark import serving_load

    row = serving_load.measure(session, requests_per_mix=24, num_clients=3,
                               mixes={"mixed": 0.5}, trace_sample=0,
                               deadline_s=-0.001)    # born expired
    mixed = row["mixes"]["mixed"]
    assert mixed["requests"] == 0                    # all expired
    assert mixed["deadline_expired"] == mixed["errors"] > 0
    # the expiry error carries the tuning detail (batcher satellite)
    assert any("missed deadline by" in e for e in mixed["error_sample"])
