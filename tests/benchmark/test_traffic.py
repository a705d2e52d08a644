"""The generator: one seed, one data set; every seed the same sizes."""

import numpy as np
import pytest

from benchmark import harness, traffic
from tests.benchmark import tiny

MIX = {"points": 6400, "components": 100, "center_scale": 0.2,
       "noise_scale": 1.0, "init_offset": 0.5, "structure_seed": 7}
RATINGS = {"rows": 704, "cols": 300, "ratings": 20000, "planted_rank": 100,
           "row_offset": 30, "row_exponent": 1.0, "col_offset": 10,
           "col_exponent": 1.0, "structure_seed": 7, "mean": 3.5,
           "signal_scale": 1.0, "noise_scale": 0.5}
KCFG = {"num_centroids": 100, "dim": 100}


@pytest.mark.parametrize("seed", (0, 7, 2 ** 31 + 5, 2 ** 32 + 1))
def test_same_seed_same_points(seed):
    a = traffic.gaussian_mixture(MIX, KCFG, seed)
    b = traffic.gaussian_mixture(MIX, KCFG, seed)
    assert np.array_equal(a["points"], b["points"])
    assert a["planted_cost"] == b["planted_cost"]
    assert a["points"].shape == (6400, 100)
    assert a["points"].dtype == np.float32


def test_seeds_change_the_sample_and_not_the_structure():
    a = traffic.gaussian_mixture(MIX, KCFG, 1)
    b = traffic.gaussian_mixture(MIX, KCFG, 2)
    assert not np.array_equal(a["points"], b["points"])
    assert np.array_equal(a["centroids0"], b["centroids0"])
    # the planted cost is the noise's sum of squares: n d sigma^2 to 3 %
    assert a["planted_cost"] == pytest.approx(6400 * 100, rel=0.03)
    assert len(np.unique(a["points"], axis=0)) == 6400   # rows all differ


@pytest.mark.parametrize("seed", (3, 2 ** 31 + 5))
def test_ratings_are_exactly_the_published_count_of_distinct_pairs(seed):
    d = traffic.planted_ratings(RATINGS, {}, seed)
    keys = d["rows"].astype(np.int64) * 300 + d["cols"]
    assert len(keys) == 20000 == len(np.unique(keys))
    assert d["rows"].min() >= 0 and d["rows"].max() < 704
    assert d["cols"].min() >= 0 and d["cols"].max() < 300
    # the half-star scale, every value exact in bfloat16
    assert set(np.unique(d["vals"] * 2)) <= set(range(1, 11))
    again = traffic.planted_ratings(RATINGS, {}, seed)
    assert all(np.array_equal(d[k], again[k]) for k in ("rows", "cols", "vals"))


def test_popularity_is_skewed_and_differs_by_seed_only_in_the_sample():
    a = traffic.planted_ratings(RATINGS, {}, 1)
    b = traffic.planted_ratings(RATINGS, {}, 2)
    ca, cb = np.bincount(a["cols"], minlength=300), np.bincount(b["cols"], minlength=300)
    assert ca.max() > 5 * np.median(ca)
    assert np.corrcoef(ca, cb)[0, 1] > 0.9      # the same popular items
    assert not np.array_equal(a["rows"], b["rows"])


def test_halved_keeps_the_first_half_of_the_samples():
    d = traffic.planted_ratings(RATINGS, {}, 1)
    h = traffic.halved(d)
    assert h["samples_per_epoch"] == 10000 and len(h["vals"]) == 10000
    assert h["num_rows"] == 704
    k = traffic.halved(traffic.gaussian_mixture(MIX, KCFG, 1))
    assert k["points"].shape == (3200, 100)
    assert k["centroids0"].shape == (100, 100)


def test_an_unknown_generator_is_an_error():
    with pytest.raises(ValueError, match="not one of"):
        traffic.generate({"generator": "nope", "params": {}}, {}, 1)


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_every_committed_cell_names_a_generator_that_exists(cell):
    committed = harness.find_cell(cell)
    assert committed.traffic["generator"] in traffic.GENERATORS
    assert committed.traffic["epochs_per_call"] >= 1
    assert committed.traffic["max_epochs"] % committed.traffic["epochs_per_call"] == 0
