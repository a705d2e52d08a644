"""The call ledger (ISSUE 36) from both ends, on the CPU and on made-up
records: the compile path's records where the compile happens
(``telemetry/host_spans.py``), the list of the names, the one clock a call,
and the six readers of ``benchmark/call_ledger.py`` on hand-made
``Context``s. What drives a cell stands in
``tests/benchmark/test_call_ledger_cells.py``; the trace recorded on the chip
is read in ``tests/test_scopes.py``."""

import os
import re
import time
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark import call_ledger
from harp_tpu import telemetry
from harp_tpu.telemetry import host_spans, step_log
from harp_tpu.telemetry.host_spans import PhaseRecord
from harp_tpu.utils.metrics import DEFAULT, Metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILE_READERS = ("program_lower_s", "program_cache_load_s")
CALL_READERS = ("call_overhead_ms", "call_roundtrip_ms", "call_host_ms")
STALL = "stall_host_ms"         # only where a call stalled
READERS = (*COMPILE_READERS, *CALL_READERS, STALL)


# --------------------------------------------------------------------------- #
# the compile path leaves its records where it runs
# --------------------------------------------------------------------------- #

def _named(records, name):
    return [r for r in records if r.name == name]


def test_a_compile_inside_a_phase_leaves_its_records_under_that_phase():
    @jax.jit
    def ledger_probe(x):
        return x * 3.0 + 1.0

    x = jnp.arange(8.0)                 # made outside: its own programs
    t0 = time.perf_counter()
    with telemetry.phase("t.dispatch") as paid:
        ledger_probe(x).block_until_ready()
    first = telemetry.phases(t0)
    lowers = _named(first, host_spans.LOWER)
    compiles = _named(first, host_spans.COMPILE)
    # the trace and the MLIR lowering, then the backend's compile
    assert [r.detail for r in lowers] == ["ledger_probe", "jit(ledger_probe)"]
    assert [r.detail for r in compiles] == ["jit(ledger_probe)"]
    for r in lowers + compiles:
        assert r.parent == paid.id and r.call == paid.call
        assert r.end - r.start > 0.0
        assert paid.start <= r.start <= r.end
    assert lowers[0].end <= lowers[1].start <= compiles[0].start
    # the cached call leaves nothing but the phase itself
    t1 = time.perf_counter()
    with telemetry.phase("t.dispatch"):
        ledger_probe(x).block_until_ready()
    assert [r.name for r in telemetry.phases(t1)] == ["t.dispatch"]


def test_nested_jits_leave_one_record_and_do_not_double_the_union():
    @jax.jit
    def ledger_inner(x):
        return jnp.where(x > 1.0, x, 0.0) * 2.0

    @jax.jit
    def ledger_outer(x):
        return ledger_inner(x) + ledger_inner(x + 1.0)

    x = jnp.arange(8.0)
    t0 = time.perf_counter()
    with telemetry.phase("t.dispatch") as paid:
        ledger_outer(x).block_until_ready()
    records = telemetry.phases(t0)
    lowers = _named(records, host_spans.LOWER)
    # jax reports ledger_inner's trace (and jnp.where's) inside ledger_outer's:
    # only the outermost is kept
    assert [r.detail for r in lowers] == ["ledger_outer", "jit(ledger_outer)"]
    union = telemetry.union_seconds(records, host_spans.LOWER)
    assert union == pytest.approx(sum(r.end - r.start for r in lowers))
    assert 0.0 < union <= time.perf_counter() - t0
    assert all(r.parent == paid.id for r in lowers)


def test_union_seconds_counts_a_nested_interval_once():
    def rec(name, start, end):
        return PhaseRecord(name, start, end, None, 0, 0)

    records = [rec("program.lower", 1.0, 5.0), rec("program.lower", 2.0, 3.0),
               rec("program.lower", 4.5, 6.0), rec("program.lower", 8.0, 9.0),
               rec("program.compile", 0.0, 100.0)]
    assert telemetry.union_seconds(records, "program.lower") == 6.0
    assert telemetry.union_seconds(records, "program.compile") == 100.0
    assert telemetry.union_seconds(records, "program.cache_load") == 0.0


def test_cache_hits_and_misses_are_counted_beside_the_traces():
    def count(name):
        return DEFAULT.snapshot()["counters"].get(name, 0)

    before = count("program.cache.hits"), count("program.cache.misses")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/tasks_using_cache")
    assert count("program.cache.hits") - before[0] == 1
    assert count("program.cache.misses") - before[1] == 2


def test_a_cache_load_lies_inside_the_compile_that_asked_for_it():
    """As jax reports them on a hit: the retrieval, then the whole
    ``compile_or_get_cached`` under the backend-compile event."""
    t0 = time.perf_counter()
    with telemetry.phase("t.dispatch") as paid:
        time.sleep(0.002)
        jax.monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.001)
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/backend_compile_duration", 0.0015,
            fun_name="jit(step)")
        jax.monitoring.record_event_duration_secs(
            "/jax/compilation_cache/compile_time_saved_sec", 3.0)
    records = telemetry.phases(t0)
    (load,) = _named(records, host_spans.CACHE_LOAD)
    (compiled,) = _named(records, host_spans.COMPILE)
    assert (load.detail, compiled.detail) == (None, "jit(step)")
    assert load.parent == compiled.parent == paid.id
    assert compiled.start <= load.start <= load.end <= compiled.end
    assert load.end - load.start == pytest.approx(0.001)
    assert [r.name for r in records] == [
        host_spans.CACHE_LOAD, host_spans.COMPILE, "t.dispatch"]


# --------------------------------------------------------------------------- #
# the list of the names, and the one clock a call
# --------------------------------------------------------------------------- #

def test_every_name_the_package_emits_is_listed():
    emitted = set()
    for folder, _, files in os.walk(os.path.join(REPO, "harp_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as fh:
                    emitted |= set(re.findall(
                        r'\bphase\(\s*"([^"]+)"\s*\)', fh.read()))
    assert {"kmeans.call", "session.fetch", "step.fetch"} <= emitted
    assert emitted <= set(telemetry.PHASES)
    assert {host_spans.TRACE_MARK, host_spans.LOWER, host_spans.COMPILE,
            host_spans.CACHE_LOAD} <= set(telemetry.PHASES)
    assert len(set(telemetry.PHASES)) == len(telemetry.PHASES)
    # five models, one anatomy
    for model in ("kmeans", "sgd_mf", "als", "ccd", "mds"):
        assert {f"{model}.prepare", f"{model}.call"} <= set(telemetry.PHASES)


def test_the_ring_holds_a_whole_run_of_the_shortest_calls():
    # sgdmf-k100.ml10m: 3 records a call, 1,215 calls in a 20 s window
    assert host_spans.RING_CAPACITY >= 3 * 1215 + 400


def test_record_chunk_is_handed_the_wall_time_on_the_phases_own_clock(
        tmp_path):
    log = telemetry.configure(str(tmp_path), interval=1, metrics=Metrics())
    try:
        with telemetry.phase("t.call") as call:
            time.sleep(0.01)
            telemetry.record_chunk("probe", start=0, losses=[1.0, 0.5],
                                   wall_s=call.elapsed())
            after = time.perf_counter() - call.start
        assert isinstance(log, step_log.StepLog)
    finally:
        telemetry.disable()
    import json
    with open(tmp_path / "rank0" / "steps.jsonl") as fh:
        events = [json.loads(line) for line in fh]
    steps = [e for e in events if e.get("model") == "probe"]
    assert len(steps) == 2
    assert 0.01 <= steps[0]["chunk_wall_s"] <= after
    assert steps[0]["step_s"] == pytest.approx(steps[0]["chunk_wall_s"] / 2,
                                               abs=1e-6)


def test_a_phases_elapsed_runs_from_its_start_to_now():
    with telemetry.phase("t.call") as call:
        first = call.elapsed()
        time.sleep(0.002)
        assert 0.0 <= first < call.elapsed() - 0.002 + 1e-4
    kept = telemetry.phases()[-1]
    assert kept.name == "t.call" and kept.start == call.start
    assert kept.end - kept.start >= 0.002
    assert call.elapsed() >= kept.end - kept.start     # it goes on counting


@pytest.mark.parametrize("module", ["kmeans", "sgd_mf", "als", "ccd", "mds"])
def test_no_call_path_keeps_a_second_clock(module):
    with open(os.path.join(REPO, "harp_tpu", "models", module + ".py")) as fh:
        text = fh.read()
    bodies = re.findall(r'phase\("\w+\.call"\) as call:\n(.*?)\n {0,4}def ',
                        text, flags=re.S)
    assert len(bodies) == 1
    assert "perf_counter" not in bodies[0] and "t0 =" not in bodies[0]
    assert "wall_s=call.elapsed()" in bodies[0]


# --------------------------------------------------------------------------- #
# the six readers on hand-made contexts
# --------------------------------------------------------------------------- #

class _Made:
    """A window of ``n`` calls on a made-up clock: the harness's spans, the
    program's records and the device's step times, as one run leaves them.
    A call is 10 ms on the device; the launch takes ``launch`` and the news
    of the end ``done``; the program's epilogue ``epilogue`` and the
    harness's loop ``loop``."""

    launch, done, epilogue, loop, step = 0.3e-3, 0.5e-3, 0.1e-3, 0.2e-3, 10e-3

    def __init__(self, n=6, model="sgd_mf", fetch=True, slow=None):
        self.records, self.spans, self.call_s, self.steps = [], [], [], []
        ids = iter(range(1000, 100000))
        t = 100.0
        self.spans.append(("data_gen", t, t + 1.0))
        # set-up: a prepare with a one-shot program that traced, lowered
        # (nested), loaded one executable from the cache and compiled another
        prep = next(ids)
        run = next(ids)
        for name, lo, hi, detail in (
                ("program.lower", t + 1.1, t + 1.5, "densify"),
                ("program.lower", t + 1.2, t + 1.3, "where"),
                ("program.lower", t + 1.5, t + 1.6, "jit(densify)"),
                ("program.cache_load", t + 1.62, t + 1.65, None),
                ("program.compile", t + 1.6, t + 1.7, "jit(densify)"),
                ("program.compile", t + 1.7, t + 2.2, "jit(fit)")):
            self.records.append(PhaseRecord(name, lo, hi, run, 0, next(ids),
                                            detail))
        self.records.append(PhaseRecord("session.run", t + 1.05, t + 2.3,
                                        prep, 0, run))
        self.records.append(PhaseRecord(f"{model}.prepare", t + 1.0, t + 2.4,
                                        None, 0, prep))
        t = self.start = 110.0
        for i in range(n):
            extra = slow[1] if slow and slow[0] == i else 0.0
            on_device = self.step + (slow[2] if slow and slow[0] == i else 0.0)
            c_lo = t
            root, call = next(ids), i + 1
            d_lo, d_hi = c_lo + 10e-6, c_lo + 10e-6 + self.launch
            wait_hi = d_hi + on_device + self.done + extra
            self.records.append(PhaseRecord("step.dispatch", d_lo, d_hi,
                                            root, call, next(ids)))
            if fetch:
                self.records.append(PhaseRecord("step.fetch", d_hi + 5e-6,
                                                wait_hi, root, call,
                                                next(ids)))
                r_hi = wait_hi + self.epilogue
                f_lo, f_hi = r_hi + 5e-6, r_hi + 15e-6
            else:                       # K-means: the harness's fetch waits
                r_hi = d_hi + self.epilogue
                f_lo, f_hi = r_hi + 5e-6, wait_hi + self.epilogue
            self.records.append(PhaseRecord(f"{model}.call", c_lo + 5e-6,
                                            r_hi, None, call, root))
            self.spans.append(("call", c_lo, r_hi + 2e-6))
            self.spans.append(("fetch_quality", f_lo, f_hi))
            self.call_s.append(f_hi + 1e-6 - c_lo)
            self.steps.append(on_device)
            t = f_hi + self.loop
        self.end = t

    def phases(self, since=None, until=None):
        return [r for r in self.records
                if (since is None or r.start >= since)
                and (until is None or r.end <= until)]

    def ctx(self, trace=True):
        return types.SimpleNamespace(
            window=types.SimpleNamespace(start=self.start, end=self.end,
                                         call_s=list(self.call_s)),
            spans=types.SimpleNamespace(records=list(self.spans)),
            trace=(types.SimpleNamespace(step_s=list(self.steps))
                   if trace else None))


@pytest.fixture()
def made(monkeypatch):
    def install(**kw):
        run = _Made(**kw)
        monkeypatch.setattr(telemetry, "phases", run.phases)
        monkeypatch.setattr(host_spans, "phases", run.phases)
        monkeypatch.setattr(host_spans, "dropped", lambda: 0)
        return run
    return install


def _read(ctx):
    return {name: getattr(call_ledger, name)(ctx) for name in READERS}


@pytest.mark.parametrize("fetch", [True, False])
def test_the_readers_partition_a_call(made, fetch):
    run = made(fetch=fetch, model="sgd_mf" if fetch else "kmeans")
    ctx = run.ctx()
    ledger = call_ledger.calls(ctx)
    assert len(ledger) == 6
    for c in ledger:
        # period = dispatch + wait + the program's epilogue + the harness
        assert c.overhead_s == pytest.approx(c.roundtrip_s + c.host_s,
                                             abs=1e-12)
        assert c.step_s == run.step and c.dispatch_s == pytest.approx(
            run.launch)
        assert c.roundtrip_s == pytest.approx(run.launch + run.done, abs=2e-5)
        assert c.host_s == pytest.approx(run.epilogue + run.loop, abs=5e-5)
    got = _read(ctx)
    assert got["call_overhead_ms"] == pytest.approx(
        got["call_roundtrip_ms"] + got["call_host_ms"], abs=1e-9)
    assert got["call_overhead_ms"] == pytest.approx(
        1e3 * (run.launch + run.done + run.epilogue + run.loop), abs=0.06)
    assert got["call_roundtrip_ms"] == pytest.approx(0.8, abs=0.02)
    assert got[STALL] is None                  # no call stalled: nothing
    # the overheads of all calls are the window less the device's time
    assert sum(c.overhead_s for c in ledger) == pytest.approx(
        run.end - run.start - 6 * run.step, abs=1e-9)


def test_the_compile_readers_add_the_union_of_the_set_up(made):
    got = _read(made().ctx())
    # 1.1-1.5 with a nested 1.2-1.3, then 1.5-1.6: the union, not the sum
    assert got["program_lower_s"] == pytest.approx(0.5)
    assert got["program_cache_load_s"] == pytest.approx(0.03)
    assert call_ledger.setup_union_s(made().ctx(), "program.compile") \
        == pytest.approx(0.6)


def test_a_cold_start_loads_nothing_and_says_nothing(made):
    run = made()
    run.records = [r for r in run.records if r.name != "program.cache_load"]
    got = _read(run.ctx())
    assert got["program_cache_load_s"] is None
    assert got["program_lower_s"] == pytest.approx(0.5)


def test_a_program_that_keeps_no_such_records_reads_as_nothing(
        made, monkeypatch):
    """The commit before: phases, but no list of them and no compile path."""
    run = made()
    run.records = [r for r in run.records
                   if not r.name.startswith("program.")]
    monkeypatch.delattr(host_spans, "PHASES")
    got = _read(run.ctx())
    assert [got[n] for n in COMPILE_READERS] == [None] * 2
    assert all(got[n] is not None for n in CALL_READERS)    # its calls pair


def _calls_read_nothing_and_the_set_up_still_reads(ctx):
    """The four readers of the window's calls say nothing; the set-up's two
    need no trace and no pairing, only the ring's records of the run."""
    got = _read(ctx)
    assert [got[n] for n in (*CALL_READERS, STALL)] == [None] * 4
    assert got["program_lower_s"] == pytest.approx(0.5)
    assert got["program_cache_load_s"] == pytest.approx(0.03)


def test_unequal_lists_read_as_nothing(made):
    run = made()
    ctx = run.ctx()
    ctx.trace.step_s.append(0.01)              # a program the calls did not run
    _calls_read_nothing_and_the_set_up_still_reads(ctx)
    ctx = run.ctx()
    ctx.window.call_s.pop()
    _calls_read_nothing_and_the_set_up_still_reads(ctx)
    ctx = run.ctx()
    ctx.spans.records.remove(next(s for s in ctx.spans.records
                                  if s[0] == "fetch_quality"))
    _calls_read_nothing_and_the_set_up_still_reads(ctx)
    run.records.remove(next(r for r in run.records
                            if r.name == "step.dispatch"))
    _calls_read_nothing_and_the_set_up_still_reads(run.ctx())


def test_a_dropped_record_of_this_run_reads_as_nothing(made, monkeypatch):
    run = made()
    monkeypatch.setattr(host_spans, "dropped", lambda: 3)
    # what the ring let go of was older than the run: nothing of it is lost
    older = [PhaseRecord("t.before", 1.0, 2.0, None, 0, 1)] + run.records
    monkeypatch.setattr(host_spans, "phases", lambda *a: list(older))
    got = _read(run.ctx())
    assert got.pop(STALL) is None and None not in got.values()
    # the oldest record kept is the run's own: some of the run may be gone
    monkeypatch.setattr(host_spans, "phases", lambda *a: list(run.records))
    assert set(_read(run.ctx()).values()) == {None}


def test_no_trace_reads_as_nothing(made):
    _calls_read_nothing_and_the_set_up_still_reads(made().ctx(trace=False))


def test_another_runs_trace_reads_as_nothing(made):
    """Equally long by chance, but a program cannot outlast the call that
    waited for it."""
    ctx = made().ctx()
    ctx.trace.step_s = [0.118] * 6
    _calls_read_nothing_and_the_set_up_still_reads(ctx)


def test_a_program_without_the_ring_reads_as_nothing(made, monkeypatch):
    ctx = made().ctx()
    monkeypatch.delattr(telemetry, "phases")
    assert set(_read(ctx).values()) == {None}


@pytest.mark.parametrize("extra, device, want", [
    (0.019, 0.0, None),             # under 20 ms: no stall, nothing
    (0.150, 0.0, 151.1),            # told late: near the call's excess
    (0.0, 0.150, 1.1),              # the device ran long: near the overhead
])
def test_stall_host_ms_says_where_the_longest_call_waited(made, extra, device,
                                                          want):
    run = made(n=9, slow=(4, extra, device))
    got = _read(run.ctx())
    assert got[STALL] == (want if want is None
                          else pytest.approx(want, abs=0.06))
    # one call of nine does not move a median
    assert got["call_overhead_ms"] == pytest.approx(1.1, abs=0.06)
    stalled = call_ledger.stall(call_ledger.calls(run.ctx()))
    assert (stalled is None) == (want is None)
    if stalled is not None:
        assert stalled.call_s == max(run.call_s)
