"""The comparison's arithmetic."""

import numpy as np
import pytest

from benchmark import compare


def _record(leaves1, leaves3, quality=(3.0, 2.0, 1.0)):
    return {"quality": list(quality), "after_1": leaves1, "after_3": leaves3}


FIRST = {"W": np.zeros((4, 3)), "H": np.zeros((2, 3))}
REF = _record({"W": np.ones((4, 3)), "H": np.ones((2, 3))},
              {"W": 2 * np.ones((4, 3)), "H": 2 * np.ones((2, 3))})


def test_equal_records_read_zero():
    read = compare.numbers(FIRST, REF, REF)
    assert read == dict.fromkeys(compare.NUMBERS, 0.0)


def test_gaps_are_measured_against_the_references_change():
    prog = _record({"W": 1.1 * np.ones((4, 3)), "H": np.ones((2, 3))},
                   REF["after_3"], quality=(3.0, 2.2, 1.0))
    read = compare.numbers(FIRST, prog, REF)
    assert read["quality_gap"] == pytest.approx(0.1)
    assert read["step1_norm_gap"] == pytest.approx(0.1)
    assert read["step1_diff"] == pytest.approx(0.1)
    assert read["step3_diff"] == 0.0


def test_a_leaf_that_barely_moves_is_measured_against_the_median_leaf():
    ref = _record({"a": np.full(4, 1e-9), "b": np.ones(4), "c": np.ones(4)},
                  {"a": np.full(4, 1e-9), "b": np.ones(4), "c": np.ones(4)})
    first = {k: np.zeros(4) for k in "abc"}
    prog = _record({"a": np.full(4, 3e-9), "b": np.ones(4), "c": np.ones(4)},
                   ref["after_3"])
    assert compare.numbers(first, prog, ref)["step1_diff"] < 1e-8


def test_swapped_rows_keep_the_norm_and_not_the_difference():
    w = np.arange(12.0).reshape(4, 3)
    ref = _record({"W": w}, {"W": w})
    prog = _record({"W": w[[1, 0, 2, 3]]}, {"W": w})
    read = compare.numbers({"W": np.zeros((4, 3))}, prog, ref)
    assert read["step1_norm_gap"] == pytest.approx(0.0)
    assert read["step1_diff"] > 0.1


@pytest.mark.parametrize("bad", (np.nan, np.inf))
def test_what_is_not_finite_meets_no_limit(bad):
    prog = _record({"W": np.full((4, 3), bad), "H": np.ones((2, 3))},
                   REF["after_3"], quality=(3.0, bad, 1.0))
    read = compare.numbers(FIRST, prog, REF)
    assert read["quality_gap"] == float("inf")
    assert read["step1_diff"] == float("inf")
    ok, _ = compare.verdict(read, {"step1_diff": 1e9})
    assert not ok


def test_a_leaf_of_another_shape_or_a_shorter_curve_meets_no_limit():
    prog = _record({"W": np.ones((5, 3)), "H": np.ones((2, 3))},
                   REF["after_3"], quality=(3.0, 2.0))
    read = compare.numbers(FIRST, prog, REF)
    assert read["step1_diff"] == float("inf")
    assert read["quality_gap"] == float("inf")


def test_verdict_holds_only_the_numbers_with_a_limit():
    read = dict.fromkeys(compare.NUMBERS, 0.5)
    ok, compared = compare.verdict(read, {"step1_diff": 0.6})
    assert ok and compared["step1_diff"] == {"value": 0.5, "limit": 0.6}
    assert compared["quality_gap"]["limit"] is None
    assert not compare.verdict(read, {"step1_diff": 0.4})[0]
    with pytest.raises(ValueError):
        compare.verdict(read, {})
    with pytest.raises(KeyError):
        compare.verdict(read, {"nonsense": 1.0})
