"""The generator: one seed, one data set; every seed the same sizes; a
generator is a built-in or a file of its own."""

import hashlib
import json
import os
import re

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


def test_an_unknown_generator_is_an_error(tmp_path):
    with pytest.raises(ValueError, match="not one of"):
        traffic.generate({"generator": "nope", "params": {}}, {}, 1)
    # the error names both places a generator is looked for
    with pytest.raises(ValueError) as err:
        traffic.generate({"generator": "corpus", "params": {}}, {}, 1,
                         str(tmp_path))
    assert "GENERATORS" in str(err.value) and str(tmp_path) in str(err.value)
    assert "corpus.py" in str(err.value)
    with pytest.raises(ValueError, match=re.escape(traffic.GENERATOR_DIR)):
        traffic.resolve("corpus")


@pytest.mark.parametrize("cell", tiny.CELLS)
def test_every_committed_cell_names_a_generator_that_exists(cell):
    committed = harness.find_cell(cell)
    assert callable(traffic.resolve(committed.traffic["generator"],
                                    _generators_of(committed)))
    assert committed.traffic["epochs_per_call"] >= 1
    assert committed.traffic["max_epochs"] % committed.traffic["epochs_per_call"] == 0


with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as _fh:
    _WORKLOADS = [w["name"] for w in json.load(_fh)["workloads"]]


def _generators_of(cell):
    """The ``generators/`` of the tree the cell was found in."""
    return os.path.join(cell.bench_dir, "generators")


@pytest.mark.parametrize("cell", _WORKLOADS)
def test_every_cell_of_the_manifest_names_a_generator_that_resolves(cell):
    found = harness.find_cell(cell)
    name, directory = found.traffic["generator"], _generators_of(found)
    assert callable(traffic.resolve(name, directory))
    assert name in traffic.GENERATORS or os.path.isfile(
        os.path.join(directory, name + ".py"))


_FILE_GENERATOR = """
import numpy as np


def generate(params, config, seed):
    rng = np.random.default_rng([int(seed), 9])
    rows = rng.standard_normal((int(params["rows"]), int(config["dim"])))
    return {"rows": rows.astype(np.float32),
            "samples_per_epoch": int(params["rows"])}
"""


def _generator_file(directory, name, text=_FILE_GENERATOR):
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, name + ".py"), "w") as fh:
        fh.write(text)
    return str(directory)


@pytest.mark.parametrize("seed", (5, 2 ** 32 + 1))
def test_a_generator_file_is_found_and_one_seed_gives_one_data_set(
        tmp_path, seed):
    directory = _generator_file(tmp_path / "generators", "labelled_rows")
    cell = {"generator": "labelled_rows", "params": {"rows": 64}}
    a = traffic.generate(cell, {"dim": 100}, seed, directory)
    b = traffic.generate(cell, {"dim": 100}, seed, directory)
    assert a["rows"].shape == (64, 100) and a["samples_per_epoch"] == 64
    assert np.array_equal(a["rows"], b["rows"])
    c = traffic.generate(cell, {"dim": 100}, seed + 1, directory)
    assert not np.array_equal(a["rows"], c["rows"])
    # loaded once per file
    assert traffic.resolve("labelled_rows", directory) is traffic.resolve(
        "labelled_rows", directory)
    # the committed tree has no such file: the name is unknown there
    with pytest.raises(ValueError, match="not one of"):
        traffic.generate(cell, {"dim": 100}, seed)


def test_the_default_directory_is_read_at_the_call(tmp_path, monkeypatch):
    # what lets a test on a copied tree hand the harness its own generators
    directory = _generator_file(tmp_path / "generators", "labelled_rows")
    monkeypatch.setattr(traffic, "GENERATOR_DIR", directory)
    data = traffic.generate({"generator": "labelled_rows",
                             "params": {"rows": 8}}, {"dim": 3}, 1)
    assert data["rows"].shape == (8, 3)


def test_a_name_both_built_in_and_a_file_is_an_error(tmp_path):
    directory = _generator_file(tmp_path, "planted_ratings")
    with pytest.raises(ValueError, match="both in GENERATORS"):
        traffic.generate({"generator": "planted_ratings", "params": RATINGS},
                         {}, 1, directory)


def test_a_generator_without_samples_per_epoch_is_an_error(tmp_path):
    directory = _generator_file(
        tmp_path, "countless", "def generate(params, config, seed):\n"
                               "    return {'rows': []}\n")
    with pytest.raises(ValueError, match="samples_per_epoch"):
        traffic.generate({"generator": "countless", "params": {}}, {}, 1,
                         directory)


def _digest(data):
    h = hashlib.sha256()
    for key in sorted(data):
        v = np.asarray(data[key])
        h.update(key.encode() + str(v.dtype).encode() + str(v.shape).encode())
        h.update(np.ascontiguousarray(v).tobytes())
    return h.hexdigest()


# both built-ins' outputs at the sizes above on tiny.SEED, pinned from the
# commit before generators could be files (6455394): that edit moved no byte
_PINNED = {
    "gaussian_mixture": (
        MIX, KCFG,
        "9474a6297eb55b434b27cd1034309aedc5f8d339c241cea9e0b5771b934d5dd1"),
    "planted_ratings": (
        RATINGS, {},
        "4644062918b65ea4a45e94a485c26845cec4d8d3c5596a9db2b40abb1cb5c6a9"),
}


@pytest.mark.parametrize("name", sorted(_PINNED))
def test_the_built_ins_give_the_bytes_they_gave_before(name):
    params, config, pinned = _PINNED[name]
    data = traffic.generate({"generator": name, "params": params}, config,
                            tiny.SEED)
    assert _digest(data) == pinned
