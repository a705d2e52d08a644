"""Gang telemetry subsystem (ISSUE 7): step log, comm ledger, straggler
detection, xprof windows, metrics reservoir, and the no-drift guarantees.

The single-process legs of every gang path run here on the 8-worker virtual
mesh; the true multi-process exchange (snapshot gather over the control
plane, the events-triggered xprof window across ranks) runs in
``parallel.mp_smoke`` / tests/test_multiprocess.py."""

import json
import os
import sys
import time

import numpy as np
import pytest

from harp_tpu import telemetry
from harp_tpu.telemetry import comm_ledger, gang, step_log
from harp_tpu.utils.metrics import Metrics, TimerReservoir, log_device_mem_usage

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_off():
    """Every test starts and ends with telemetry disabled (module state)."""
    telemetry.disable()
    yield
    telemetry.disable()


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(line) for line in f.read().strip().splitlines()]


def _step_events(path):
    """The per-step events of a steps.jsonl (phases, spans and timings ride
    the same stream under a ``kind``)."""
    return [e for e in _read_jsonl(path) if "kind" not in e]


# --------------------------------------------------------------------------- #
# Metrics: bounded reservoir + percentiles (satellite: unbounded-growth fix)
# --------------------------------------------------------------------------- #

def test_timer_reservoir_is_bounded_with_exact_aggregates():
    r = TimerReservoir(cap=64)
    for i in range(10_000):
        r.add(float(i))
    assert len(r.samples) == 64            # bounded: RAM can't grow
    assert r.count == 10_000               # aggregates stay exact
    assert r.total == sum(range(10_000))
    assert r.last == 9999.0


def test_timer_percentiles_track_the_stream():
    m = Metrics()
    for i in range(1, 1001):
        m.observe("t", i / 1000.0)
    t = m.timing("t")
    assert set(t) == {"count", "total_s", "mean_s", "last_s",
                      "p50_s", "p90_s", "p99_s"}
    # uniform 1..1000 ms: reservoir percentiles land near the true ones
    assert abs(t["p50_s"] - 0.5) < 0.05
    assert abs(t["p90_s"] - 0.9) < 0.05
    assert t["p99_s"] <= 1.0 and t["p99_s"] > t["p50_s"]


def test_percentiles_single_sort_matches_percentile():
    r = TimerReservoir(cap=128)
    for i in range(100):
        r.add(float(i))
    assert r.percentiles([0.5, 0.9, 0.99]) == [r.percentile(0.5),
                                               r.percentile(0.9),
                                               r.percentile(0.99)]


def test_timer_context_still_works_and_snapshot_carries_percentiles():
    m = Metrics()
    with m.timer("phase"):
        pass
    snap = m.snapshot()
    assert snap["timers"]["phase"]["count"] == 1
    assert "p50_s" in snap["timers"]["phase"]


def test_log_device_mem_usage_cpu_is_quiet_and_narrow():
    # CPU devices return None from memory_stats (no broad except needed):
    # the result is empty, nothing raises
    assert log_device_mem_usage() == {}


def test_log_device_mem_usage_gauges_peak(monkeypatch):
    import jax

    class FakeDev:
        id = 0

        def memory_stats(self):
            return {"bytes_in_use": 100, "peak_bytes_in_use": 250}

        def __str__(self):
            return "FakeTPU:0"

    monkeypatch.setattr(jax, "devices", lambda: [FakeDev()])
    m = Metrics()
    out = log_device_mem_usage(m)
    assert out == {"FakeTPU:0": {"bytes_in_use": 100,
                                 "peak_bytes_in_use": 250}}
    assert m.gauges["device.0.peak_bytes_in_use"] == 250


# --------------------------------------------------------------------------- #
# Step log: bounded ring, JSONL schema, no-op fast path
# --------------------------------------------------------------------------- #

def test_record_chunk_is_noop_when_disabled(tmp_path):
    telemetry.record_chunk("kmeans", start=0, losses=[1.0], wall_s=0.1)
    assert telemetry.active() is None


def test_step_events_flush_as_jsonl_with_schema(tmp_path):
    m = Metrics()
    telemetry.configure(str(tmp_path), interval=100, metrics=m, rank=3)
    telemetry.record_chunk("kmeans", start=4, losses=[9.0, 8.0], wall_s=0.2,
                           extra={"comm": "allreduce"})
    telemetry.active().flush()
    events = _read_jsonl(tmp_path / "rank3" / "steps.jsonl")
    assert [e["step"] for e in events] == [4, 5]
    for e in events:
        assert e["v"] == step_log.EVENT_VERSION
        assert e["model"] == "kmeans" and e["rank"] == 3
        assert e["comm"] == "allreduce"
        assert e["chunk_steps"] == 2
        assert abs(e["step_s"] - 0.1) < 1e-9     # amortized chunk wall
    assert events[0]["loss"] == 9.0 and events[1]["loss"] == 8.0
    # per-step samples landed in the straggler timer
    assert m.timing("telemetry.step.kmeans")["count"] == 2


def test_record_timing_surfaces_timing_schema_in_steps_jsonl(tmp_path):
    """ISSUE 10 satellite: timing() percentile output rides steps.jsonl as
    `kind: "timing"` events — the serving bench's latency rows and the
    straggler report's per-rank rows share ONE latency format (the
    Metrics.timing() dict), instead of two drifting schemas."""
    m = Metrics()
    telemetry.configure(str(tmp_path), interval=100, rank=1)
    # no samples yet: record_timing is a no-op, never a malformed event
    telemetry.record_timing("serve.latency.mixed", metrics=m)
    for v in (0.001, 0.002, 0.003):
        m.observe("serve.latency.mixed", v)
    telemetry.record_timing("serve.latency.mixed", metrics=m,
                            extra={"mix": "mixed", "qps": 123.0})
    telemetry.active().flush()
    events = _read_jsonl(tmp_path / "rank1" / "steps.jsonl")
    assert len(events) == 1
    ev = events[0]
    assert ev["kind"] == "timing" and ev["rank"] == 1
    assert ev["name"] == "serve.latency.mixed"
    assert ev["mix"] == "mixed" and ev["qps"] == 123.0
    # the event's latency fields are EXACTLY the timing() dict — the same
    # keys gang.straggler_report reads from each rank's snapshot
    timing = m.timing("serve.latency.mixed")
    assert {k: ev[k] for k in timing} == timing
    assert set(timing) <= set(ev)


def test_record_timing_noop_when_disabled():
    m = Metrics()
    m.observe("serve.latency.mixed", 0.001)
    telemetry.record_timing("serve.latency.mixed", metrics=m)
    assert telemetry.active() is None


def test_ring_is_bounded_and_drops_are_counted(tmp_path):
    m = Metrics()
    log = step_log.StepLog(str(tmp_path), capacity=8, rank=0, metrics=m)
    for i in range(20):
        log.emit({"step": i})
    assert log.dropped == 12
    assert m.counters["telemetry.events_dropped"] == 12
    log.flush()
    events = _read_jsonl(log.path)
    assert [e["step"] for e in events] == list(range(12, 20))  # newest kept


def test_flush_cadence_follows_the_boundary_interval(tmp_path):
    m = Metrics()
    telemetry.configure(str(tmp_path), interval=3, metrics=m)
    for i in range(2):
        telemetry.record_chunk("m", start=i, losses=[0.0])
    assert not os.path.exists(telemetry.active().path)   # below cadence
    telemetry.record_chunk("m", start=2, losses=[0.0])   # 3rd boundary
    assert len(_read_jsonl(telemetry.active().path)) == 3


# --------------------------------------------------------------------------- #
# Host phases (ISSUE 25): one span, always recorded, written only when on
# --------------------------------------------------------------------------- #

def _since():
    return time.perf_counter()


def test_phase_feeds_the_reservoir_only_when_enabled(tmp_path):
    with telemetry.phase("x.checkpoint"):
        pass                               # off: ring only, no reservoir
    m = Metrics()
    telemetry.configure(str(tmp_path), metrics=m)
    with telemetry.phase("x.checkpoint"):
        pass
    assert m.timing("telemetry.phase.x.checkpoint")["count"] == 1


def test_phases_nest_and_name_their_parent():
    t0 = _since()
    with telemetry.phase("t.call") as root:
        with telemetry.phase("t.dispatch") as a:
            pass
        with telemetry.phase("t.fetch") as b:
            with telemetry.phase("t.inner"):
                pass
    recs = {r.name: r for r in telemetry.phases(t0)}
    assert set(recs) == {"t.call", "t.dispatch", "t.fetch", "t.inner"}
    assert recs["t.call"].parent is None
    assert recs["t.dispatch"].parent == recs["t.fetch"].parent == root.id
    assert recs["t.inner"].parent == b.id and a.id != b.id
    # a child lies inside its parent, on time.perf_counter()
    assert (t0 <= recs["t.call"].start <= recs["t.fetch"].start
            <= recs["t.inner"].start <= recs["t.inner"].end
            <= recs["t.fetch"].end <= recs["t.call"].end <= _since())
    # children are handed to the ring as they end, before their parent
    assert [r.name for r in telemetry.phases(t0)][-1] == "t.call"


def test_the_spans_of_one_call_share_its_index():
    t0 = _since()
    for _ in range(2):
        with telemetry.phase("t.call"):
            with telemetry.phase("t.dispatch"):
                pass
            with telemetry.phase("t.fetch"):
                pass
    recs = telemetry.phases(t0)
    calls = [r.call for r in recs if r.name == "t.call"]
    assert len(calls) == 2 and calls[1] == calls[0] + 1
    for call in calls:
        assert sorted(r.name for r in recs if r.call == call) == [
            "t.call", "t.dispatch", "t.fetch"]


def test_phase_survives_an_exception_and_restores_its_parent():
    t0 = _since()
    with telemetry.phase("t.outer") as outer:
        with pytest.raises(RuntimeError):
            with telemetry.phase("t.boom"):
                raise RuntimeError("boom")
        with telemetry.phase("t.after"):
            pass
    recs = {r.name: r for r in telemetry.phases(t0)}
    assert recs["t.boom"].parent == recs["t.after"].parent == outer.id


def test_self_seconds_is_the_span_less_its_children():
    R = telemetry.PhaseRecord
    records = [R("child", 1.0, 2.0, 7, 0, 8), R("child", 2.5, 3.0, 7, 0, 9),
               R("grandchild", 1.2, 1.4, 8, 0, 10),
               R("root", 0.0, 4.0, None, 0, 7),
               R("root", 10.0, 11.0, None, 1, 11)]
    assert telemetry.self_seconds(records, "root") == pytest.approx(3.5)
    assert telemetry.self_seconds(records, "child") == pytest.approx(1.3)
    assert telemetry.self_seconds(records, "absent") == 0.0
    t0 = _since()
    with telemetry.phase("t.root"):
        with telemetry.phase("t.child"):
            time.sleep(0.02)
    recs = telemetry.phases(t0)
    root = next(r for r in recs if r.name == "t.root")
    own = telemetry.self_seconds(recs, "t.root")
    assert 0.0 <= own < 0.01 < root.end - root.start


def test_phase_ring_is_bounded_and_counts_what_it_drops():
    from harp_tpu.telemetry import host_spans

    ring = host_spans._Ring(8)
    for i in range(20):
        ring.append(telemetry.PhaseRecord("x", 0.0, 1.0, None, i, i))
    assert [r.id for r in ring.snapshot()] == list(range(12, 20))
    assert ring.dropped == 12
    # the process's own ring: bounded at its capacity, drops counted
    before = host_spans.dropped()
    held = len(telemetry.phases())
    for _ in range(host_spans.RING_CAPACITY + 10):
        with telemetry.phase("t.fill"):
            pass
    assert len(telemetry.phases()) == host_spans.RING_CAPACITY
    assert host_spans.dropped() - before == held + 10


def test_phase_ring_loses_nothing_under_contention():
    import threading

    from harp_tpu.telemetry import host_spans

    ring = host_spans._Ring(64)
    threads = [threading.Thread(target=lambda: [ring.append(
        telemetry.PhaseRecord("x", 0.0, 1.0, None, 0, 0))
        for _ in range(2000)]) for _ in range(16)]
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(before)
    assert not any(t.is_alive() for t in threads)
    assert len(ring.snapshot()) + ring.dropped == 16 * 2000


def test_phases_are_per_thread():
    import threading

    t0 = _since()
    seen = {}

    def other():
        with telemetry.phase("t.thread") as p:
            seen["parent"] = p._parent

    with telemetry.phase("t.main"):
        th = threading.Thread(target=other)
        th.start()
        th.join(timeout=30)
    assert not th.is_alive() and seen["parent"] is None
    rec = next(r for r in telemetry.phases(t0) if r.name == "t.thread")
    assert rec.parent is None


def test_phase_shows_under_its_name_in_a_profiler_trace(tmp_path):
    """What test_tracing.py checked of ``annotate``: inside a profiler
    session a phase is a span of the trace's ``/host:CPU`` plane."""
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from harp_tpu.telemetry import xprof

    d = str(tmp_path / "trace")
    xprof.start_trace(d)
    try:
        with telemetry.phase("harp-test-phase"):
            jnp.sum(jnp.ones(16)).block_until_ready()
    finally:
        xprof.stop_trace()
    found = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs
             if f.endswith(".xplane.pb")]
    assert found, f"no trace under {d}"
    host = [p for p in ProfileData.from_file(found[0]).planes
            if p.name == "/host:CPU"]
    names = {e.name for p in host for line in p.lines for e in line.events}
    assert "harp-test-phase" in names
    # the window closes cleanly: a second capture opens
    xprof.start_trace(str(tmp_path / "again"))
    xprof.stop_trace()


def test_phases_off_write_no_file(session, rng, tmp_path, monkeypatch):
    from harp_tpu.models import kmeans as km

    monkeypatch.chdir(tmp_path)
    t0 = _since()
    model = km.KMeans(session, km.KMeansConfig(num_centroids=4, dim=8,
                                               iterations=2))
    pts = rng.normal(size=(64, 8)).astype(np.float32)
    p, c = model.prepare(pts, pts[:4])
    np.asarray(model.fit_prepared(p, c)[1])
    names = [r.name for r in telemetry.phases(t0)]
    assert names.count("kmeans.prepare") == 1
    assert names.count("session.place") == 2
    assert names.count("kmeans.call") == names.count("step.dispatch") == 1
    assert telemetry.active() is None
    assert os.listdir(tmp_path) == []


def test_phases_on_emit_phase_events_and_the_programs_text(
        session, rng, tmp_path):
    from harp_tpu.models import sgd_mf
    from harp_tpu.telemetry import scopes

    telemetry.configure(str(tmp_path), interval=1, metrics=Metrics())
    n = 600
    model = sgd_mf.SGDMF(session, sgd_mf.SGDMFConfig(rank=4, epochs=2))
    state = model.prepare(rng.integers(0, 64, n), rng.integers(0, 48, n),
                          rng.normal(size=n).astype(np.float32), 64, 48)
    model.train_prepared(state)
    model.train_prepared(state)
    telemetry.disable()
    events = [e for e in _read_jsonl(tmp_path / "rank0" / "steps.jsonl")
              if e.get("kind") == "phase"]
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
        assert {"v", "rank", "name", "start", "end", "parent", "call",
                "id"} <= set(e)
    assert len(by_name["sgd_mf.prepare"]) == 1
    assert len(by_name["session.run"]) == 1
    assert len(by_name["sgd_mf.call"]) == 2
    assert len(by_name["step.dispatch"]) == len(by_name["step.fetch"]) == 2
    call = by_name["sgd_mf.call"][0]
    assert {e["name"] for e in events if e["parent"] == call["id"]} >= {
        "step.dispatch", "step.fetch"}
    assert {e["detail"] for e in by_name["program.trace"]} == {
        "sgd_mf.densify", "sgd_mf.fit"}
    # the step's compiled text, once, readable by scope
    programs = os.listdir(tmp_path / "rank0" / "programs")
    assert programs == ["sgd_mf.fit.hlo.txt"]
    mapped = scopes.scope_map(
        (tmp_path / "rank0" / "programs" / programs[0]).read_text())
    assert "sgdmf.stripes" in set(mapped.values())


def test_program_traces_count_traces_not_calls(session, rng):
    from harp_tpu.models import kmeans as km
    from harp_tpu.utils.metrics import DEFAULT

    def traces():
        return DEFAULT.snapshot()["counters"].get(
            "program.traces.kmeans.fit", 0)

    model = km.KMeans(session, km.KMeansConfig(num_centroids=4, dim=8,
                                               iterations=2))
    pts = rng.normal(size=(128, 8)).astype(np.float32)
    before, t0 = traces(), _since()
    p, c = model.prepare(pts, pts[:4])
    model.fit_prepared(p, c)
    model.fit_prepared(p, c)
    assert traces() - before == 1          # two calls of one shape
    p2, c2 = model.prepare(pts[:64], pts[:4])
    np.asarray(model.fit_prepared(p2, c2)[1])
    assert traces() - before == 2          # a new shape traces again
    marks = [r for r in telemetry.phases(t0) if r.name == "program.trace"]
    assert [r.detail for r in marks] == ["kmeans.fit", "kmeans.fit"]
    assert all(r.start == r.end for r in marks)
    # the mark lies in the dispatch that traced
    dispatch = {r.id for r in telemetry.phases(t0)
                if r.name == "step.dispatch"}
    assert all(r.parent in dispatch for r in marks)


# --------------------------------------------------------------------------- #
# Comm ledger: manifest join, gauges, quant twins
# --------------------------------------------------------------------------- #

def _manifest():
    with open(os.path.join(REPO, "tools", "collective_budget.json")) as f:
        return json.load(f)


def test_manifest_target_resolution():
    assert comm_ledger.manifest_target("kmeans", comm="allreduce") == \
        "kmeans_allreduce"
    # quantized twin pinned in the manifest wins ...
    assert comm_ledger.manifest_target("kmeans", comm="allreduce",
                                       quant="int8") == "kmeans_allreduce_int8"
    # ... and falls back to the f32 row when no twin is pinned
    assert comm_ledger.manifest_target("kmeans", comm="rotation",
                                       quant="int8") == "kmeans_rotation"
    assert comm_ledger.manifest_target("lda", sub_block=True) == \
        "lda_cgs_subblock128"
    assert comm_ledger.manifest_target("sgd_mf", quant="int8") == \
        "sgd_mf_dense_int8"
    assert comm_ledger.manifest_target("nn") == "nn_mlp"
    assert comm_ledger.manifest_target("nonsuch") is None


def test_ledger_prices_steps_from_the_manifest():
    row = _manifest()["targets"]["kmeans_allreduce"]
    m = Metrics()
    led = comm_ledger.CommLedger("kmeans_allreduce", metrics=m)
    led.on_steps(10, wall_s=2.0)
    assert led.bytes_per_step == row["bytes_per_step"]
    assert led.cumulative_bytes == row["bytes_per_step"] * 10
    g = m.gauges
    assert g["comm.kmeans_allreduce.wire_bytes_per_step"] == \
        row["bytes_per_step"]
    assert g["comm.kmeans_allreduce.cumulative_gb"] == pytest.approx(
        row["bytes_per_step"] * 10 / 1e9)
    assert g["comm.kmeans_allreduce.busbw_gbps"] == pytest.approx(
        row["bytes_per_step"] * 10 / 2.0 / 1e9)


def test_ledger_quantized_row_prices_below_f32():
    t = _manifest()["targets"]
    led_q = comm_ledger.CommLedger("kmeans_allreduce_int8")
    led_f = comm_ledger.CommLedger("kmeans_allreduce")
    assert led_q.bytes_per_step < led_f.bytes_per_step / 2
    assert t["kmeans_allreduce_int8"]["bytes_per_step"] == led_q.bytes_per_step


def test_ledger_unknown_target_is_inert():
    m = Metrics()
    led = comm_ledger.CommLedger("no_such_row", metrics=m)
    led.on_steps(5, wall_s=1.0)
    assert led.bytes_per_step is None and m.gauges == {}


def test_ledger_scale_reprices_the_row():
    row = _manifest()["targets"]["kmeans_allreduce"]
    led = comm_ledger.CommLedger("kmeans_allreduce", scale=2.5)
    assert led.bytes_per_step == pytest.approx(row["bytes_per_step"] * 2.5)


def test_ledger_pricing_exactness_is_machine_readable(tmp_path):
    """A model that computed its payload scale (kmeans) gets exact pricing;
    one that didn't (lda/sgd_mf/als/nn) gets traced-shape reference pricing,
    flagged in the gauge and in every step event — a dashboard cannot
    mistake the reference counter for a measurement."""
    m = Metrics()
    telemetry.configure(str(tmp_path), interval=100, metrics=m)
    exact = comm_ledger.ledger_for("kmeans", comm="allreduce", scale=1.0)
    ref = comm_ledger.ledger_for("sgd_mf")
    assert exact.exact is True and ref.exact is False
    telemetry.record_chunk("kmeans", start=0, losses=[0.0], wall_s=0.01,
                           ledger=exact)
    telemetry.record_chunk("sgd_mf", start=0, losses=[0.0], wall_s=0.01,
                           ledger=ref)
    assert m.gauges["comm.kmeans_allreduce.pricing_exact"] == 1.0
    assert m.gauges["comm.sgd_mf_dense.pricing_exact"] == 0.0
    telemetry.active().flush()
    events = _read_jsonl(tmp_path / "rank0" / "steps.jsonl")
    pricing = {e["model"]: e["wire_pricing"] for e in events}
    assert pricing == {"kmeans": "scaled", "sgd_mf": "traced_shape"}


def test_ledger_for_is_none_when_telemetry_off():
    assert comm_ledger.ledger_for("kmeans", comm="allreduce") is None


# --------------------------------------------------------------------------- #
# Straggler detection (pure function) + slow fault grammar
# --------------------------------------------------------------------------- #

def _snap(p50, count=10):
    return {"timers": {"telemetry.step.kmeans":
                       {"count": count, "p50_s": p50, "p99_s": p50 * 1.2}}}


def test_straggler_report_flags_exactly_the_slow_rank():
    snaps = {r: _snap(0.010) for r in range(8)}
    snaps[5] = _snap(0.055)
    rep = gang.straggler_report(snaps, k=2.0)
    assert rep["suspects"] == [5]
    assert rep["gang_median_p50_s"] == pytest.approx(0.010)
    assert rep["num_ranks"] == 8


def test_straggler_bsp_signature_flags_the_rank_not_waiting():
    # BULK-SYNCHRONOUS loop: the victims' timers absorb the straggler's
    # delay (they wait in the chunk's first collective) and the straggler is
    # the one rank far BELOW the median — the signature the 3-member gang
    # drive measured (victims ~131 ms, scripted slow rank ~15 ms)
    snaps = {0: _snap(0.131), 1: _snap(0.015), 2: _snap(0.136)}
    rep = gang.straggler_report(snaps, k=2.0)
    assert rep["bsp_suspects"] == [1]
    assert rep["suspects"] == []


def test_straggler_report_spread_below_k_is_clean():
    snaps = {r: _snap(0.010 + 0.001 * r) for r in range(8)}
    assert gang.straggler_report(snaps, k=2.0)["suspects"] == []


def test_straggler_min_gap_ignores_microsecond_jitter():
    # 2x the median but only microseconds apart: drags nothing, not flagged
    snaps = {0: _snap(1e-6), 1: _snap(1e-6), 2: _snap(3e-6)}
    assert gang.straggler_report(snaps, k=2.0)["suspects"] == []


def test_straggler_cold_ranks_are_excluded_not_suspected():
    snaps = {r: _snap(0.010) for r in range(4)}
    snaps[2] = _snap(0.500, count=1)        # 1 sample < min_samples
    rep = gang.straggler_report(snaps, k=2.0, min_samples=3)
    assert rep["suspects"] == []
    assert rep["ranks"][2]["measurable"] is False


def test_straggler_single_measurable_rank_has_no_median():
    rep = gang.straggler_report({0: _snap(0.01)})
    assert rep["gang_median_p50_s"] is None and rep["suspects"] == []


def test_gather_snapshots_single_process_returns_local(session):
    m = Metrics()
    m.observe("telemetry.step.kmeans", 0.01)
    snaps = gang.gather_snapshots(session, metrics=m)
    assert list(snaps) == [0]
    assert snaps[0]["timers"]["telemetry.step.kmeans"]["count"] == 1


def test_slow_fault_grammar_and_sustained_fire(monkeypatch):
    from harp_tpu.parallel import faults

    specs = faults.parse_faults("slow@epoch=2:rank=1:ms=7")
    assert specs[0].kind == "slow" and specs[0].ms == 7
    with pytest.raises(ValueError):
        faults.parse_faults("crash@epoch=1:ms=7")   # ms is slow-only
    with pytest.raises(ValueError):
        faults.parse_faults("slow@epoch=1:ms=abc")
    monkeypatch.setenv("HARP_FAULT", "slow@epoch=2:ms=15")
    monkeypatch.setenv("HARP_PROCESS_ID", "0")
    t0 = time.perf_counter()
    faults.fire(1)
    before = time.perf_counter() - t0
    walls = []
    for epoch in (2, 3, 4):                 # SUSTAINED: every due boundary
        t0 = time.perf_counter()
        faults.fire(epoch)
        walls.append(time.perf_counter() - t0)
    assert before < 0.010
    assert all(w >= 0.014 for w in walls), walls


def test_supervisor_journal_attaches_straggler_report(tmp_path):
    from harp_tpu.parallel import supervisor

    tdir = tmp_path / "telemetry"
    tdir.mkdir()
    (tdir / gang.REPORT_NAME).write_text(json.dumps(
        {"v": 1, "ts": 1.0, "suspects": [3], "gang_median_p50_s": 0.1}))
    outcome = supervisor.supervise_local(
        [sys.executable, "-c", "import sys; sys.exit(1)"],
        policy=supervisor.RestartPolicy(max_restarts=1, backoff_base_s=0.0),
        telemetry_dir=str(tdir), sleep=lambda s: None)
    assert not outcome.ok
    events = {r["event"]: r for r in outcome.journal}
    assert events["restart"]["straggler"]["suspects"] == [3]
    assert events["give-up"]["straggler"]["suspects"] == [3]


# --------------------------------------------------------------------------- #
# No-drift guarantees: the pinned budget with telemetry ON
# --------------------------------------------------------------------------- #

def test_budget_manifest_zero_drift_with_telemetry_on(tmp_path):
    """The telemetry gate (satellite): tracing the instrumented models' step
    programs with telemetry ENABLED must reproduce the committed manifest
    exactly — counts, kinds, AND bytes (JL201/JL203 zero drift). The full
    14-target sweep runs in ci_checks.sh; three representative rows keep the
    gate in tier-1 (``wdamds``: phases, the ``traced`` mark and the counters
    of ISSUE 34 change no equation of the traced step)."""
    from tools.jaxlint import checkers_jaxpr

    telemetry.configure(str(tmp_path), interval=4)
    targets = _manifest()["targets"]
    for name in ("kmeans_regroupallgather", "sgd_mf_dense", "wdamds"):
        counts, dtype_bad, nbytes = checkers_jaxpr.trace_target(name)
        assert counts == targets[name]["collectives"], name
        assert nbytes == targets[name]["bytes_by_kind"], name
        assert sum(nbytes.values()) == targets[name]["bytes_per_step"], name
        assert not dtype_bad


def test_kmeans_fit_checkpointed_emits_telemetry_and_stays_bitwise(
        session, rng, tmp_path):
    """End-to-end: the kmeans loop with telemetry on (1) trains bitwise
    identically to telemetry off, (2) emits one event per iteration with the
    host-synced loss, (3) prices comm volume off the manifest row."""
    from harp_tpu.models import kmeans as km
    from harp_tpu.utils.checkpoint import Checkpointer

    cfg = km.KMeansConfig(8, 16, iterations=4)
    pts = rng.normal(size=(64, 16)).astype(np.float32)
    cen0 = pts[:8].copy()

    model = km.KMeans(session, cfg)
    p, c = model.prepare(pts, cen0)
    cen_off, costs_off, _ = model.fit_checkpointed(
        p, c, Checkpointer(str(tmp_path / "off")), save_every=2)

    m = Metrics()
    telemetry.configure(str(tmp_path / "tele"), interval=1, metrics=m)
    cen_on, costs_on, _ = model.fit_checkpointed(
        p, c, Checkpointer(str(tmp_path / "on")), save_every=2)
    telemetry.disable()

    np.testing.assert_array_equal(np.asarray(cen_off), np.asarray(cen_on))
    np.testing.assert_array_equal(costs_off, costs_on)

    events = _step_events(tmp_path / "tele" / "rank0" / "steps.jsonl")
    assert [e["step"] for e in events] == [0, 1, 2, 3]
    assert [e["loss"] for e in events] == pytest.approx(costs_on.tolist())
    assert all(e["model"] == "kmeans" and e["comm"] == cfg.comm
               for e in events)
    assert m.timing("telemetry.step.kmeans")["count"] == 4
    assert m.timing("telemetry.phase.kmeans.checkpoint")["count"] == 2
    # this config IS the manifest trace shape: scale 1.0, gauge == the row
    row = _manifest()["targets"]["kmeans_regroupallgather"]
    assert model.comm_scale() == pytest.approx(1.0)
    assert m.gauges["comm.kmeans_regroupallgather.wire_bytes_per_step"] == \
        pytest.approx(row["bytes_per_step"])
    assert m.gauges["comm.kmeans_regroupallgather.cumulative_gb"] == \
        pytest.approx(row["bytes_per_step"] * 4 / 1e9)


def test_lda_and_nn_fits_emit_per_epoch_events(session, rng, tmp_path):
    from harp_tpu.models import lda as plda
    from harp_tpu.models import nn as pnn

    m = Metrics()
    telemetry.configure(str(tmp_path), interval=1, metrics=m)
    docs = rng.integers(0, 48, size=(16, 8))
    model = plda.LDA(session, plda.LDAConfig(num_topics=4, vocab=48,
                                             epochs=3))
    _, _, ll = model.fit(docs, seed=0)
    x = rng.normal(size=(64, 10)).astype(np.float32)
    y = rng.integers(0, 3, size=64).astype(np.int32)
    clf = pnn.MLPClassifier(session, pnn.NNConfig(layers=(8,), num_classes=3,
                                                  epochs=2))
    losses = clf.fit(x, y, seed=0)
    telemetry.disable()
    events = _step_events(tmp_path / "rank0" / "steps.jsonl")
    by_model = {}
    for e in events:
        by_model.setdefault(e["model"], []).append(e)
    assert [e["loss"] for e in by_model["lda"]] == pytest.approx(
        np.asarray(ll).tolist())
    assert [e["loss"] for e in by_model["nn"]] == pytest.approx(
        losses.tolist())
    assert m.gauges["comm.lda_cgs.wire_bytes_per_step"] > 0
    assert m.gauges["comm.nn_mlp.wire_bytes_per_step"] > 0


def test_xprof_window_single_process(session, tmp_path):
    from harp_tpu.telemetry.xprof import XprofController, request_xprof

    ctrl = XprofController(session, rank=0)
    try:
        request_xprof(session, steps=2, directory=str(tmp_path / "xprof"))
        ctrl(1)
        assert ctrl.tracing
        import jax.numpy as jnp

        jnp.square(jnp.arange(64.0)).block_until_ready()  # something to trace
        ctrl(2)
        ctrl(3)
        assert not ctrl.tracing
        found = [os.path.join(r, f) for r, _, fs in os.walk(ctrl.trace_dir)
                 for f in fs]
        assert found, f"no trace artifacts under {ctrl.trace_dir}"
    finally:
        ctrl.close()
        session.close_events()


def test_xprof_file_trigger_operator_path(session, tmp_path):
    """The run.py CLI path: an operator drops DIR/xprof_request.json while
    the job runs; the controller opens a window at the next boundary. A file
    left over from a previous run must NOT arm at startup, and a malformed
    file must not kill training."""
    from harp_tpu.telemetry.xprof import XprofController

    trig = tmp_path / "xprof_request.json"
    trig.write_text(json.dumps({"steps": 1}))     # pre-existing: stale
    ctrl = XprofController(session, rank=0, trigger_path=str(trig),
                           default_dir=str(tmp_path / "xprof"))
    try:
        ctrl(1)
        assert not ctrl.tracing                    # stale file ignored
        trig.write_text("{not json")
        ctrl(2)
        assert not ctrl.tracing                    # malformed: noted, not fatal
        trig.write_text(json.dumps({"steps": 2}))  # rewritten: re-armed
        ctrl(3)
        assert ctrl.tracing
        ctrl(4)
        ctrl(5)
        assert not ctrl.tracing
        found = [os.path.join(r, f) for r, _, fs in os.walk(ctrl.trace_dir)
                 for f in fs]
        assert found
        ctrl(6)
        assert not ctrl.tracing                    # same content: consumed
    finally:
        ctrl.close()
        session.close_events()


def test_xprof_window_open_at_exit_is_closed_by_steplog(session, tmp_path):
    """A window still open when the run ends (request arrived with fewer
    boundaries left than requested) must stop its trace at StepLog.close()
    — the atexit path — or the profile is never written."""
    from harp_tpu.telemetry.xprof import XprofController, request_xprof

    log = telemetry.configure(str(tmp_path), interval=100, metrics=Metrics())
    ctrl = XprofController(session, rank=0)
    log.add_boundary_hook(ctrl)
    try:
        request_xprof(session, steps=50, directory=str(tmp_path / "xprof"))
        telemetry.record_chunk("m", start=0, losses=[0.0])   # boundary 1
        assert ctrl.tracing                                  # 49 left, run ends
        telemetry.disable()                                  # = atexit close
        assert not ctrl.tracing
        found = [os.path.join(r, f) for r, _, fs in os.walk(ctrl.trace_dir)
                 for f in fs]
        assert found, "open window lost its trace at exit"
    finally:
        ctrl.close()
        session.close_events()


def test_kmeans_pricing_inexact_off_the_traced_worker_count(rng, tmp_path):
    """comm_scale rescales table elements, but the sharded variants' traced
    operands also depend on the worker count — a mesh narrower than the
    manifest's w=8 must be flagged as reference pricing, not exact."""
    from harp_tpu.models import kmeans as km
    from harp_tpu.session import HarpSession
    from harp_tpu.utils.checkpoint import Checkpointer

    sess4 = HarpSession(num_workers=4)
    m = Metrics()
    telemetry.configure(str(tmp_path), interval=1, metrics=m)
    model = km.KMeans(sess4, km.KMeansConfig(8, 16, iterations=2))
    pts = rng.normal(size=(64, 16)).astype(np.float32)
    p, c = model.prepare(pts, pts[:8].copy())
    model.fit_checkpointed(p, c, Checkpointer(str(tmp_path / "ck")),
                           save_every=2)
    telemetry.disable()
    assert m.gauges["comm.kmeans_regroupallgather.pricing_exact"] == 0.0


def test_supervisor_command_flag_parse():
    from harp_tpu.parallel.supervisor import _command_flag

    cmd = ["python", "-m", "harp_tpu.run", "kmeans",
           "--telemetry-dir", "/a", "--telemetry-dir=/b"]
    assert _command_flag(cmd, "--telemetry-dir") == "/b"
    assert _command_flag(["python"], "--telemetry-dir") is None


def test_xprof_nonrequest_events_are_requeued(session):
    from harp_tpu.telemetry.xprof import XprofController

    try:
        session.send_event({"note": "operator-ping"})
        ctrl = XprofController(session, rank=0)
        ctrl(1)                       # no request: the ping must survive
        assert not ctrl.tracing
        ev = session.get_event()
        assert ev is not None and ev.payload == {"note": "operator-ping"}
    finally:
        session.close_events()


@pytest.mark.large
def test_telemetry_overhead_cpu_smoke(session, rng, tmp_path):
    """The <2% overhead contract, CPU flavor (the on-chip assert lives in the
    bench row): the telemetry layer's measured per-step cost must be < 2% of
    a real measured kmeans step on this mesh. The layer's cost is host-side
    and shape-independent, so this bounds the on-chip overhead too (on-chip
    steps at bench shapes are far longer than these)."""
    from harp_tpu.models import kmeans as km

    cfg = km.KMeansConfig(32, 64, iterations=6)
    pts = rng.normal(size=(16384, 64)).astype(np.float32)
    model = km.KMeans(session, cfg)
    p, c = model.prepare(pts, pts[:32].copy())
    model.fit_prepared(p, c)                      # compile + warm
    t0 = time.perf_counter()
    _, costs = model.fit_prepared(p, c)
    np.asarray(costs)
    step_s = (time.perf_counter() - t0) / cfg.iterations

    m = Metrics()
    telemetry.configure(str(tmp_path), interval=10**6, capacity=4096,
                        metrics=m)
    led = telemetry.ledger_for("kmeans", comm=cfg.comm,
                               scale=model.comm_scale())
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        telemetry.record_chunk("kmeans", start=i, losses=[0.0],
                               wall_s=step_s, ledger=led,
                               extra={"comm": cfg.comm})
    per_event = (time.perf_counter() - t0) / n
    telemetry.disable()
    overhead_pct = 100.0 * per_event / step_s
    assert overhead_pct < 2.0, (
        f"telemetry per-step cost {per_event * 1e6:.1f}us is "
        f"{overhead_pct:.2f}% of the {step_s * 1e3:.2f}ms kmeans step")


# --------------------------------------------------------------------------- #
# Metrics thread safety (ISSUE 13 satellite: one lock over the registry,
# reservoir adds lock-guarded — the load generator's per-thread-reservoir
# workaround is now isolation, not a correctness requirement)
# --------------------------------------------------------------------------- #

def test_metrics_registry_loses_no_updates_under_contention():
    import threading as th

    m = Metrics()
    n_threads, per = 8, 400
    barrier = th.Barrier(n_threads)

    def worker(i):
        barrier.wait()
        for j in range(per):
            m.count("requests")
            m.count("bytes", 3.0)
            m.observe("latency", 0.001)
            m.gauge(f"g{i}", float(j))

    threads = [th.Thread(target=worker, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # counters: every increment survives (the JL302 lost-update class)
    assert m.counters["requests"] == n_threads * per
    assert m.counters["bytes"] == 3.0 * n_threads * per
    # timers: exact count/total even though all threads shared ONE
    # reservoir (pre-v3 this undercounted, hence the per-thread pattern)
    assert m.timers["latency"].count == n_threads * per
    assert abs(m.timers["latency"].total - 0.001 * n_threads * per) < 1e-6
    snap = m.snapshot()
    assert snap["counters"]["requests"] == n_threads * per
    assert snap["timers"]["latency"]["count"] == n_threads * per


def test_timer_reservoir_concurrent_adds_stay_exact_and_bounded():
    import threading as th

    r = TimerReservoir(cap=64)
    n_threads, per = 8, 500
    barrier = th.Barrier(n_threads)

    def adder(i):
        barrier.wait()
        for j in range(per):
            r.add(float(i * per + j))

    threads = [th.Thread(target=adder, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert r.count == n_threads * per
    assert r.total == sum(range(n_threads * per))
    assert len(r.samples) == 64


def test_metrics_snapshot_is_consistent_while_writers_insert():
    # pre-v3 this raised "dictionary changed size during iteration" (the
    # exporter mid-scrape race); now a snapshot is lock-consistent
    import threading as th

    m = Metrics()
    stop = th.Event()

    def writer():
        i = 0
        while not stop.is_set():
            m.observe(f"timer.{i % 97}", 0.001)
            m.count(f"counter.{i % 89}")
            i += 1

    t = th.Thread(target=writer, daemon=True)
    t.start()
    try:
        for _ in range(60):
            snap = m.snapshot()           # must never raise
            assert isinstance(snap["timers"], dict)
    finally:
        stop.set()
        t.join(5.0)


def test_gang_collector_publish_is_scrape_consistent(session, tmp_path):
    # the PR 12 hand-review race, now fixed + linted (JL301): the
    # collector publishes (snapshots, report) atomically under its lock,
    # and the exporter's gang= source reads through the same lock
    from harp_tpu.telemetry.gang import GangCollector

    m = Metrics()
    for _ in range(4):
        m.observe("telemetry.step.fake", 0.01)
    log = step_log.StepLog(str(tmp_path), interval=1, rank=0, metrics=m)
    collector = GangCollector(session, str(tmp_path), every=1)
    assert collector.snapshots() is None and collector.last_report is None
    collector(1 * log.interval, log)      # one boundary publish
    # the pair-consistent accessor: (snapshots, report) from ONE publish
    snaps, report = collector.last_exchange()
    assert snaps is not None and 0 in snaps
    assert snaps[0]["timers"]["telemetry.step.fake"]["count"] == 4
    assert report is not None and report["num_ranks"] == 1
    # the property surface and the exporter source return the same object
    assert collector.snapshots() is snaps or collector.snapshots() == snaps
    assert collector.last_snapshots is snaps or \
        collector.last_snapshots == snaps
