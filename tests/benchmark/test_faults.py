"""``correct`` comes out false when the timed path is broken underneath:
the harness's look for a chip is skipped, the rest of a run is driven."""

import numpy as np
import pytest

from benchmark import harness, readings, traffic
from tests.benchmark import tiny


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.build(str(tmp_path_factory.mktemp("bench")))


def _run(tree, cell):
    return harness.run_cell(cell, tiny.SEED + 2, 0.2, False,
                            require_accelerator=False, root=tree)


def _patch_driver(monkeypatch, tree, cell_name, **methods):
    """Every driver the harness builds for ``cell_name`` gets ``methods``."""
    real_part = harness.Cell.part

    def part(self, kind):
        module = real_part(self, kind)
        if kind == "driver":
            for name, make in methods.items():
                setattr(module.Driver, name, make(getattr(module.Driver, name)))
        return module

    monkeypatch.setattr(harness.Cell, "part", part)


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_a_step_that_returns_its_state_unchanged(tree, cell, monkeypatch):
    def unchanged(call):
        def broken(self, state):
            _, quality = call(self, state)
            return state, quality
        return broken

    _patch_driver(monkeypatch, tree, cell, call=unchanged)
    result = _run(tree, cell)
    assert result["correct"] is False
    assert result["compared"]["step1_diff"]["value"] == pytest.approx(1.0)
    assert result["compared"]["step1_norm_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_half_of_the_batch_left_out(tree, cell, monkeypatch):
    def on_half(init):
        def broken(self, config, cell_traffic, data, chips, overrides=None):
            init(self, config, cell_traffic, traffic.halved(data), chips,
                 overrides)
        return broken

    _patch_driver(monkeypatch, tree, cell, __init__=on_half)
    result = _run(tree, cell)
    assert result["correct"] is False
    assert result["compared"]["quality_gap"]["value"] > 0.3


def test_the_exchange_between_chips_left_out(tree):
    with readings.rotation_held_still():
        result = _run(tree, tiny.ML20M_X4)
    assert result["correct"] is False
    assert result["compared"]["step1_diff"]["value"] > 0.5


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_an_answer_altered_where_it_is_produced(tree, cell, monkeypatch):
    """One row of the model moved by a tenth of its length on its way
    out of the program."""
    def altered(finalize):
        def broken(self, state):
            leaves = {k: np.array(v) for k, v in finalize(self, state).items()}
            for leaf in leaves.values():
                leaf[3] = leaf[3] * 1.1 + 0.1
            return leaves
        return broken

    _patch_driver(monkeypatch, tree, cell, finalize=altered)
    result = _run(tree, cell)
    assert result["correct"] is False


@pytest.mark.parametrize("cell", (tiny.KMEANS, tiny.ML10M))
def test_rows_handed_back_in_the_wrong_order(tree, cell, monkeypatch):
    """The way out (un-padding, de-permutation) is compared too: two rows
    swapped fail the difference of the models, whatever the quality says."""
    def swapped(finalize):
        def broken(self, state):
            leaves = {k: np.array(v) for k, v in finalize(self, state).items()}
            for leaf in leaves.values():
                leaf[[0, 1]] = leaf[[1, 0]]
            return leaves
        return broken

    _patch_driver(monkeypatch, tree, cell, finalize=swapped)
    result = _run(tree, cell)
    assert result["correct"] is False
    assert result["compared"]["quality_gap"]["value"] < 1e-3
    assert result["compared"]["step1_diff"]["value"] > 0.1
