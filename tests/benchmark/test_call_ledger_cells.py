"""The call ledger (ISSUE 36) through the harness on the CPU, all six cells
at tiny shapes: what a cached call leaves in the program's ring, and the six
readers of ``benchmark/call_ledger.py`` on a trace that is and is not the
window's. No entry of ``BENCHMARK.json`` lists the readers yet
(``benchmark/call_ledger.py``'s docstring): they are read here directly."""

import dataclasses
import json
import os

import pytest

from benchmark import call_ledger, harness, program_spans, trace_reduce
from harp_tpu import telemetry
from tests.benchmark import test_als_cell as als_tests
from tests.benchmark import test_ccd_cell as ccd_tests
from tests.benchmark import test_wdamds_cell as wdamds_tests
from tests.benchmark import tiny
from tests.benchmark.test_program_spans import _Probe

COMPILE_READERS = ("program_lower_s", "program_cache_load_s")
CALL_READERS = ("call_overhead_ms", "call_roundtrip_ms", "call_host_ms")
READERS = (*COMPILE_READERS, *CALL_READERS, "stall_host_ms")
CELLS = (*tiny.CELLS, als_tests.CELL, ccd_tests.CELL, wdamds_tests.CELL)


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """The tiny tree with the three later cells cut as their own tests cut
    them."""
    root = tiny.build(str(tmp_path_factory.mktemp("bench")))
    for tests, config in ((als_tests, "als-k100"), (ccd_tests, "ccd-k100")):
        def cut(doc, tests=tests):
            doc["params"].update(tests._PARAMS)
            doc["target"]["at_most"] = tests._TARGET
        als_tests._rewrite(os.path.join(
            root, "benchmark", "workloads", tests.CELL + ".json"), cut)
        als_tests._rewrite(
            os.path.join(root, "benchmark", "configs", config + ".json"),
            lambda doc, tests=tests: doc.update(limits=tests._LIMITS))

    def cut_mds(doc):
        doc["params"]["points"] = wdamds_tests._POINTS
        doc["target"]["at_most"] = wdamds_tests._TARGET
        doc["max_epochs"] = 470

    als_tests._rewrite(os.path.join(
        root, "benchmark", "workloads", wdamds_tests.CELL + ".json"), cut_mds)
    als_tests._rewrite(
        os.path.join(root, "benchmark", "configs", "wdamds-d3.json"),
        lambda doc: doc.update(limits=wdamds_tests._LIMITS))
    return root


def test_the_manifest_is_the_parents():
    """This PR lists nothing: the accepted tests that hold where entries
    stand, and that every listed metric is in a CPU run's traced line, stay
    as they are (``PERF.md`` section 7, row 10 (h))."""
    manifest = harness.find_cell(tiny.KMEANS).manifest
    names = {m["name"] for m in manifest["per_layer"]}
    assert not names & set(READERS)
    assert [m["name"] for m in manifest["per_layer"]][-4:] == list(
        wdamds_tests.READERS)
    for name in READERS:
        assert callable(getattr(call_ledger, name))
        assert not os.path.exists(os.path.join(
            tiny.BENCH, "metrics", name + ".py"))


# --------------------------------------------------------------------------- #
# a cached call, and the readers on another run's trace
# --------------------------------------------------------------------------- #

def _traced(tree, cell, seed, monkeypatch, capture=None):
    tiny.as_v5e(monkeypatch, harness)
    if capture is None:
        tiny.recorded_trace(monkeypatch, harness)
    else:
        monkeypatch.setattr(harness, "capture_trace", capture)
    probe = _Probe(monkeypatch)
    result = harness.run_cell(cell, seed, 0.3, True,
                              require_accelerator=False, root=tree)
    return json.loads(json.dumps(result)), probe.ctx


def _readers(ctx):
    return {name: getattr(call_ledger, name)(ctx) for name in READERS}


@pytest.mark.parametrize("cell", CELLS)
def test_a_cached_call_leaves_three_records_and_k_means_two(
        tree, cell, monkeypatch):
    line, ctx = _traced(tree, cell, tiny.SEED + 21, monkeypatch)
    assert line["correct"] is True
    inside = program_spans.window_phases(ctx)
    calls = line["window"]["calls"]
    model = {tiny.KMEANS: "kmeans", als_tests.CELL: "als",
             ccd_tests.CELL: "ccd", wdamds_tests.CELL: "mds"}.get(cell,
                                                                  "sgd_mf")
    anatomy = [f"{model}.call", "step.dispatch"] + (
        [] if cell == tiny.KMEANS else ["step.fetch"])
    # nothing is added to a cached call: no mark, no compile-path record
    assert sorted(r.name for r in inside) == sorted(anatomy * calls)
    roots = {r.id for r in inside if r.parent is None}
    assert len(roots) == calls
    assert all(r.parent in roots for r in inside if r.parent is not None)
    # every name the run left, set-up included, is listed
    whole = program_spans.run_phases(ctx)
    assert {r.name for r in whole} <= set(telemetry.PHASES)
    names = {r.name for r in whole}
    assert {f"{model}.prepare", "session.place", "program.trace",
            "program.lower", "program.compile"} <= names
    assert ("session.fetch" in names) == (model == "mds")
    # the compile path's records stand under the phase that paid for them
    by_id = {r.id: r for r in whole}
    step = [r for r in whole if r.name == "program.compile"
            and by_id.get(r.parent) is not None
            and by_id[r.parent].name == "step.dispatch"]
    assert step and all(r.end - r.start > 0.0 for r in step)
    # the recorded trace is another run's: the readers of the window's
    # calls say nothing; the set-up's need no trace (the CPU keeps no cache)
    assert call_ledger.calls(ctx) is None
    got = _readers(ctx)
    assert [got[n] for n in (*CALL_READERS, "stall_host_ms")] == [None] * 4
    assert got["program_lower_s"] > 0.0
    assert got["program_cache_load_s"] is None


def _paired(scale: float):
    """A ``capture_trace`` that hands the readers the recorded reduction with
    the device's step times made up from the window's own calls."""
    def capture(run, span, name):
        win = run()
        summary = trace_reduce.reduce(tiny.TRACE, spans=harness.SPANS)
        return win, dataclasses.replace(
            summary, step_s=[scale * c for c in win.call_s])
    return capture


@pytest.mark.parametrize("cell", CELLS)
def test_the_readers_report_from_a_trace_that_pairs_with_the_window(
        tree, cell, monkeypatch):
    line, ctx = _traced(tree, cell, tiny.SEED + 22, monkeypatch,
                        capture=_paired(0.5))
    got = _readers(ctx)
    ledger = call_ledger.calls(ctx)
    assert len(ledger) == line["window"]["calls"]
    for c in ledger:
        assert c.overhead_s == pytest.approx(c.roundtrip_s + c.host_s,
                                             abs=1e-12)
        assert c.step_s == 0.5 * c.call_s
        assert c.dispatch_s > 0.0 and c.wait_s > 0.0
        # (the harness reads its clock for call_s just before the span)
        assert min(c.period_s, c.call_s) > c.dispatch_s + c.wait_s
    # all the calls' overheads are the window, from its first call on, less
    # the device's time
    first = min(lo for name, lo, _ in ctx.spans.records
                if name == "call" and lo >= ctx.window.start)
    assert sum(c.overhead_s for c in ledger) == pytest.approx(
        ctx.window.end - first - sum(c.step_s for c in ledger), abs=1e-9)
    assert 0.0 <= first - ctx.window.start < 0.01
    assert got["call_overhead_ms"] > got["call_host_ms"] > 0.0
    assert got["call_overhead_ms"] > got["call_roundtrip_ms"] > 0.0
    # the program's compile records are the inside twin of the harness's own
    # listener (``backend_compile_s``): no reader of their own until that one
    # is retired (PERF.md section 7, row 10 (d))
    outside = line["metrics"]["backend_compile_s"]["value"]
    assert call_ledger.setup_union_s(ctx, "program.compile") == pytest.approx(
        outside, rel=0.02, abs=0.02)
    assert got["program_lower_s"] > 0.0
    assert got["program_cache_load_s"] is None   # the CPU keeps no cache
    assert got["stall_host_ms"] is None or got["stall_host_ms"] > 0.0


def test_a_parent_without_the_compile_records_leaves_two_out(
        tree, monkeypatch):
    """The commit before has the phases of a call and no list of names: the
    readers of the calls read it, those of the set-up report nothing."""
    from harp_tpu.telemetry import host_spans

    monkeypatch.delattr(host_spans, "PHASES")
    _, ctx = _traced(tree, tiny.ML10M, tiny.SEED + 23, monkeypatch,
                     capture=_paired(0.5))
    got = _readers(ctx)
    assert [got[n] for n in COMPILE_READERS] == [None] * 2
    assert all(got[n] is not None for n in CALL_READERS)
