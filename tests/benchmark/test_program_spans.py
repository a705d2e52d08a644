"""The readers of the program's own phases (ISSUE 25) on the tiny CPU cells:
the three ``prepare_*`` metrics partition the program's ``*.prepare`` phase
and stay inside the harness's ``prepare`` span, every call of the window has
one ``step.dispatch``, and the count of traces does not depend on the seed.
A program without the phase ring (the commit before) reads as nothing."""

import os

import pytest

from benchmark import harness, program_spans
from harp_tpu import telemetry
from tests.benchmark import tiny

NEW = ("prepare_host_s", "prepare_place_s", "prepare_program_s",
       "dispatch_ms_p95", "program_traces")


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.build(str(tmp_path_factory.mktemp("bench")))


class _Probe:
    """Keeps the ``Context`` the readers were handed."""

    def __init__(self, monkeypatch):
        self.ctx = None
        real = harness.Context

        def keep(**kw):
            self.ctx = real(**kw)
            return self.ctx

        monkeypatch.setattr(harness, "Context", keep)


def _traced(tree, cell, seed, monkeypatch):
    tiny.as_v5e(monkeypatch, harness)
    tiny.recorded_trace(monkeypatch, harness)
    probe = _Probe(monkeypatch)
    result = harness.run_cell(cell, seed, 0.5, True,
                              require_accelerator=False, root=tree)
    return result, probe.ctx


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_prepare_metrics_partition_the_programs_prepare(tree, cell,
                                                        monkeypatch):
    result, ctx = _traced(tree, cell, tiny.SEED + 2, monkeypatch)
    got = {k: v["value"] for k, v in result["metrics"].items() if k in NEW}
    listed = {m["name"] for m in ctx.cell.metrics("per_layer")} & set(NEW)
    assert set(got) == listed
    assert ("prepare_program_s" in got) == (cell != tiny.KMEANS)
    records = program_spans.setup_phases(ctx)
    (root,) = program_spans.prepare_roots(records)
    parts = (got["prepare_host_s"] + got["prepare_place_s"]
             + got.get("prepare_program_s", 0.0))
    assert parts == pytest.approx(root.end - root.start, abs=1e-3)
    assert parts <= result["window"]["prepare_s"]
    assert all(got[k] >= 0.0 for k in got)
    assert got["prepare_place_s"] > 0.0
    # the program's phase lies inside the harness's span around it
    (span,) = [r for r in ctx.spans.records if r[0] == "prepare"]
    assert span[1] <= root.start <= root.end <= span[2]


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_every_call_of_the_window_has_one_dispatch(tree, cell, monkeypatch):
    result, ctx = _traced(tree, cell, tiny.SEED + 3, monkeypatch)
    inside = program_spans.window_phases(ctx)
    dispatches = [r for r in inside if r.name == program_spans.DISPATCH]
    assert len(dispatches) == len(ctx.window.call_s) \
        == result["window"]["calls"]
    roots = {r.id: r for r in inside if r.name.endswith(".call")}
    assert len(roots) == len(dispatches)
    assert all(r.parent in roots for r in dispatches)
    # each lies inside the harness's own span around the call
    calls = sorted(r for r in ctx.spans.records if r[0] == "call"
                   and r[1] >= ctx.window.start)
    for (_, lo, hi), d in zip(calls, sorted(dispatches,
                                            key=lambda r: r.start)):
        assert lo <= d.start <= d.end <= hi
    p95 = result["metrics"]["dispatch_ms_p95"]["value"]
    assert 0.0 < p95 <= 1e3 * max(ctx.window.call_s)
    # nothing traces inside the window
    assert not [r for r in inside if r.name == program_spans.TRACE_MARK]


@pytest.mark.parametrize("cell", (tiny.KMEANS, tiny.ML10M))
def test_program_traces_is_the_same_on_two_seeds(tree, cell, monkeypatch):
    counts = []
    for seed in (tiny.SEED + 4, tiny.SEED + 5):
        result, _ = _traced(tree, cell, seed, monkeypatch)
        counts.append(result["metrics"]["program_traces"]["value"])
    assert counts[0] == counts[1] == (1 if cell == tiny.KMEANS else 2)


def test_a_program_without_the_ring_reads_as_nothing(tree, monkeypatch):
    """The parent commit has no ``telemetry.phases``: every new reader gives
    None and raises nothing, and the line leaves the metric out."""
    monkeypatch.delattr(telemetry, "phases")
    result, ctx = _traced(tree, tiny.ML10M, tiny.SEED + 6, monkeypatch)
    assert not set(result["metrics"]) & set(NEW)
    assert "data_prep_s" in result["metrics"]
    for name in NEW:
        reader = harness.load_module(os.path.join(
            ctx.cell.bench_dir, "metrics", name + ".py"))
        assert reader.read(ctx) is None


def test_an_earlier_cell_of_the_process_is_not_counted(tree, monkeypatch):
    _traced(tree, tiny.ML10M, tiny.SEED + 7, monkeypatch)
    result, ctx = _traced(tree, tiny.KMEANS, tiny.SEED + 7, monkeypatch)
    assert result["metrics"]["program_traces"]["value"] == 1
    names = {r.name for r in program_spans.run_phases(ctx)}
    assert not {n for n in names if n.startswith("sgd_mf")}
