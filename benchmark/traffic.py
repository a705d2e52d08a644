"""The one general generator: a cell's traffic file -> the job's inputs.

A traffic file (``benchmark/workloads/<cell>.json``) is data: the name of a
generator and its parameters. A later PR adds a cell by adding such a file,
and a new *kind* of data is a new file under ``generators/``:
``benchmark/generators/<name>.py`` exposes ``generate(params, config, seed)``
and returns a dict with ``samples_per_epoch``, as the two built-ins in
``GENERATORS`` below do. A name is looked up in ``GENERATORS`` first, then
as such a file; a name found in both places is an error. Everything random
is drawn from ``--seed`` with numpy's ``SeedSequence``, so one seed gives one
data set on any machine.

What differs between seeds is the sample (points, noise, which ratings
exist), never the sizes or the structure: the mixture's centres, the initial
model's offset and the popularity laws come from ``structure_seed`` in the
traffic file. Every seed therefore gives the same job in another sample, and
``epochs_to_target`` is a property of the cell, not of the seed.
"""

from __future__ import annotations

import concurrent.futures
import importlib.util
import os
from typing import Callable, Dict, Optional

import numpy as np

# fixed partition of the rows into blocks, each with its own child seed: the
# result does not depend on how many threads fill them
_BLOCKS = 32
_THREADS = 8


def _seed_seq(seed: int, stream: int) -> np.random.SeedSequence:
    # --seed may be larger than 2**31; SeedSequence takes any whole number
    return np.random.SeedSequence([int(seed), stream])


def gaussian_mixture(params: dict, config: dict, seed: int) -> dict:
    """``points`` rows in ``dim`` columns from ``components`` Gaussians.

    Centres are ``center_scale * N(0, I)`` and the first model is the centres
    plus ``init_offset * N(0, I)``, both from ``structure_seed``. Labels are
    uniform and every point is its centre plus ``noise_scale * N(0, I)``,
    from ``seed``. ``planted_cost`` is the cost of the generating model on
    this sample (the noise's sum of squares): K-means targets are given as a
    ratio to it, which cancels the sample's own chi-square wobble.
    """
    n, d = int(params["points"]), int(config["dim"])
    k = int(params["components"])
    srng = np.random.default_rng(_seed_seq(params["structure_seed"], 0))
    centres = (params["center_scale"]
               * srng.standard_normal((k, d))).astype(np.float32)
    init = centres[: config["num_centroids"]] + (
        params["init_offset"] * srng.standard_normal(
            (config["num_centroids"], d))).astype(np.float32)
    sigma = np.float32(params["noise_scale"])
    points = np.empty((n, d), np.float32)
    bounds = np.linspace(0, n, _BLOCKS + 1).astype(np.int64)
    children = _seed_seq(seed, 1).spawn(_BLOCKS)

    def fill(b: int) -> float:
        lo, hi = int(bounds[b]), int(bounds[b + 1])
        rng = np.random.default_rng(children[b])
        lab = rng.integers(0, k, hi - lo)
        out = points[lo:hi]
        rng.standard_normal(out=out, dtype=np.float32)
        out *= sigma
        sq = float(np.einsum("ij,ij->", out, out, dtype=np.float64))
        out += centres[lab]
        return sq

    with concurrent.futures.ThreadPoolExecutor(_THREADS) as pool:
        planted = sum(pool.map(fill, range(_BLOCKS)))
    return {"points": points, "centroids0": init, "planted_cost": planted,
            "samples_per_epoch": n}


_TABLE = 1 << 22      # resolution of the inverse-CDF lookup tables


def _popularity(n: int, offset: float, exponent: float,
                rng: np.random.Generator) -> np.ndarray:
    """Inverse-CDF table of the law p(rank) ~ (rank + offset)^-exponent over
    ids in a seeded random order (ids of a public data set are not sorted by
    count): ``table[uniform integer below _TABLE]`` is a draw from it."""
    p = (np.arange(n, dtype=np.float64) + offset) ** -exponent
    p = p[rng.permutation(n)]
    cdf = np.cumsum(p / p.sum())
    at = (np.arange(_TABLE, dtype=np.float64) + 0.5) / _TABLE
    return np.minimum(np.searchsorted(cdf, at), n - 1).astype(np.int32)


def planted_ratings(params: dict, config: dict, seed: int) -> dict:
    """Exactly ``ratings`` distinct (row, col) pairs of a ``rows`` x ``cols``
    matrix, users and items drawn by power-law popularity, valued by a
    planted rank-``planted_rank`` model plus noise and rounded to the
    half-star scale 0.5 .. 5.0 (every value is exact in bfloat16)."""
    m, n, nnz = int(params["rows"]), int(params["cols"]), int(params["ratings"])
    srng = np.random.default_rng(_seed_seq(params["structure_seed"], 0))
    row_of = _popularity(m, params["row_offset"], params["row_exponent"], srng)
    col_of = _popularity(n, params["col_offset"], params["col_exponent"], srng)
    rng = np.random.default_rng(_seed_seq(seed, 2))
    keys = np.empty(0, np.int64)
    while len(keys) < nnz:
        draw = int((nnz - len(keys)) * 1.25) + 1024
        r = row_of[rng.integers(0, _TABLE, draw)]
        c = col_of[rng.integers(0, _TABLE, draw)]
        keys = np.unique(np.concatenate([keys, r.astype(np.int64) * n + c]))
    rng.shuffle(keys)            # a random subset of exactly nnz, shuffled
    keys = keys[:nnz]
    rows = (keys // n).astype(np.int32)
    cols = (keys % n).astype(np.int32)

    kp = int(params["planted_rank"])
    u = rng.standard_normal((m, kp), dtype=np.float32)
    v = rng.standard_normal((n, kp), dtype=np.float32)
    signal = np.empty(nnz, np.float32)
    bounds = np.linspace(0, nnz, 4 * _BLOCKS + 1).astype(np.int64)

    def dot(b: int) -> None:
        sl = slice(int(bounds[b]), int(bounds[b + 1]))
        signal[sl] = np.einsum("ij,ij->i", u[rows[sl]], v[cols[sl]])

    with concurrent.futures.ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(dot, range(4 * _BLOCKS)))
    signal *= np.float32(params["signal_scale"] / np.sqrt(kp))
    raw = (np.float32(params["mean"]) + signal
           + np.float32(params["noise_scale"])
           * rng.standard_normal(nnz, dtype=np.float32))
    vals = np.clip(np.round(raw * 2.0) / 2.0, 0.5, 5.0).astype(np.float32)
    return {"rows": rows, "cols": cols, "vals": vals, "num_rows": m,
            "num_cols": n, "samples_per_epoch": nnz}


GENERATORS: Dict[str, Callable[[dict, dict, int], dict]] = {
    "gaussian_mixture": gaussian_mixture,
    "planted_ratings": planted_ratings,
}


GENERATOR_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "generators")
_LOADED: Dict[str, Callable[[dict, dict, int], dict]] = {}


def resolve(name: str, directory: Optional[str] = None
            ) -> Callable[[dict, dict, int], dict]:
    """The generator called ``name``: a built-in of ``GENERATORS``, else the
    ``generate`` of ``<directory>/<name>.py``, each file loaded once.

    ``directory`` defaults to ``GENERATOR_DIR``, read at the call: the
    harness passes none, so a run takes the ``generators/`` beside this
    module, which is its checkout's. A test on a copied tree passes the
    tree's own (or sets ``GENERATOR_DIR``)."""
    directory = GENERATOR_DIR if directory is None else directory
    path = os.path.join(directory, f"{name}.py")
    in_file = os.path.basename(str(name)) == name and os.path.isfile(path)
    if name in GENERATORS:
        if in_file:
            raise ValueError(f"traffic generator {name!r} is both in "
                             f"GENERATORS and the file {path}")
        return GENERATORS[name]
    if not in_file:
        raise ValueError(
            f"traffic generator {name!r} is not one of GENERATORS "
            f"{sorted(GENERATORS)} nor a file {name}.py in {directory}")
    if path not in _LOADED:
        spec = importlib.util.spec_from_file_location(
            "bench_generator_" + "".join(c if c.isalnum() else "_"
                                         for c in path), path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _LOADED[path] = module.generate
    return _LOADED[path]


def generate(traffic: dict, config: dict, seed: int,
             directory: Optional[str] = None) -> dict:
    """The inputs of one job of this cell, from ``seed``."""
    name = traffic.get("generator")
    data = resolve(name, directory)(traffic["params"], config, seed)
    if "samples_per_epoch" not in data:
        raise ValueError(f"traffic generator {name!r} returned no "
                         "samples_per_epoch")
    return data


def halved(data: dict) -> dict:
    """The same inputs with the second half of the samples left out (a
    planted fault for the readings and the tests, never a cell's traffic)."""
    n = int(data["samples_per_epoch"])
    out = {k: (v[: n // 2] if isinstance(v, np.ndarray) and v.ndim
               and len(v) == n else v) for k, v in data.items()}
    out["samples_per_epoch"] = n // 2
    return out
