"""CCD++ over ALS's dense planes (ISSUE 31): the rank-one sweep kernel
against its ``jax.numpy`` twin, the program against the configuration's plain
reference, one worker against four, and the span layer of ``CCD``, at sizes a
CPU test can hold."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, harness, traffic
from harp_tpu import telemetry
from harp_tpu.models import ccd
from harp_tpu.ops import ccd_sweep
from harp_tpu.session import HarpSession
from harp_tpu.utils import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the cell's generator at a small shape: sizes only, every law stays
PARAMS = {"rows": 704, "cols": 300, "ratings": 20000, "planted_rank": 100,
          "row_offset": 30, "row_exponent": 1.0, "col_offset": 10,
          "col_exponent": 1.0, "structure_seed": 7, "mean": 3.5,
          "signal_scale": 1.0, "noise_scale": 0.5}
SEED = 2 ** 31 + 31


def _reference_module():
    return harness.load_module(os.path.join(
        REPO, "benchmark", "configs", "ccd-k100.reference.py"))


@pytest.fixture(scope="module")
def ratings():
    data = traffic.planted_ratings(PARAMS, {}, SEED)
    data["init_seed"] = SEED
    return data


def _plane(rng, rows, cols, density=0.1):
    plane = np.full((rows, cols), np.nan, np.float32)
    seen = rng.random((rows, cols)) < density
    plane[seen] = rng.integers(1, 11, int(seen.sum())) / 2.0   # half-stars
    return plane, seen


def _operands(rng, rows, cols, k, store):
    mine = (0.3 * rng.standard_normal((k, rows))).astype(np.float32)
    other = np.zeros((k, store), np.float32)
    other[:, :cols] = 0.3 * rng.standard_normal((k, cols))
    return (jnp.asarray(mine, jnp.bfloat16), jnp.asarray(other, jnp.bfloat16),
            jnp.asarray(other[3]))


# --------------------------------------------------------------------------- #
# the pass: kernel and twin
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("rows, cols, tiles", [
    (704, 1100, (512, 1536)),       # two row blocks, the second overhanging
    (300, 1100, (256, 512)),        # three column tiles, rows and columns
    (640, 512, (128, 512)),         # nothing overhangs
])
def test_the_kernel_in_interpret_mode_is_the_jax_numpy_pass(rows, cols, tiles):
    """Both against numpy in float64 on the same bfloat16 operands; a row
    and a column with no rating among them; the overhang of the last row
    block and column tile (unspecified cells) adds nothing."""
    rng = np.random.default_rng(rows + cols)
    k = 16
    row_tile, col_tile = tiles
    store = ccd_sweep.sweep_store(cols, col_tile)
    plane, seen = _plane(rng, rows, cols)
    plane[5], seen[5] = np.nan, False
    plane[:, 7], seen[:, 7] = np.nan, False
    mine, other, col = _operands(rng, rows, cols, k, store)
    args = (jnp.asarray(plane, jnp.bfloat16), mine, other, col)
    s_k, d_k = ccd_sweep.sweep_pallas(*args, row_tile, col_tile,
                                      interpret=True)
    s_x, d_x = ccd_sweep.sweep_xla(*args, (rows, 1))
    s_b, d_b = ccd_sweep.sweep_xla(*args, (128, -(-rows // 128)))
    pred = (np.asarray(mine, np.float64).T
            @ np.asarray(other, np.float64)[:, :cols])
    v = np.asarray(col, np.float64)[None, :cols]
    s = np.where(seen, (np.nan_to_num(plane) - pred) * v, 0.0).sum(1)
    d = np.where(seen, v * v, 0.0).sum(1)
    for got_s, got_d in ((s_k, d_k), (s_x, d_x), (s_b, d_b)):
        np.testing.assert_allclose(got_s, s, rtol=0, atol=2e-5 * np.abs(s).max())
        np.testing.assert_allclose(got_d, d, rtol=1e-5)
    assert float(d_k[5]) == 0.0 and float(s_k[5]) == 0.0


def test_tiles_come_from_the_shape_and_vmem():
    kp = 112
    for rows, cols in ((71_567, 10_681), (10_681, 71_567)):
        row_tile, col_tile = ccd_sweep.sweep_tiles(rows, cols, kp)
        store = ccd_sweep.sweep_store(cols, col_tile)
        assert row_tile == 512 and col_tile % ccd_sweep.CHUNK == 0
        assert cols <= store < cols + col_tile and store % col_tile == 0
        assert col_tile <= 16384
        assert ccd_sweep.sweep_vmem_bytes(kp, store, row_tile,
                                          col_tile) <= ccd_sweep.VMEM_LIMIT
    assert ccd_sweep.sweep_tiles(71_567, 10_681, 100) == (0, 0)   # rank % 16
    assert ccd_sweep.sweep_tiles(100, 10_681, kp) == (0, 0)       # < a tile
    assert ccd_sweep.sweep_tiles(71_567, 300, kp) == (0, 0)
    # a side too wide for VMEM at any row tile: the resident factors alone
    assert ccd_sweep.sweep_tiles(4096, 400_000, kp) == (0, 0)
    assert ccd_sweep.sweep_store(300, 0) == 300


def test_the_dispatch_decides_from_backend_and_shape(monkeypatch):
    assert not ccd_sweep.use_ccd_sweep_pallas(71_567, 10_681, 112)   # CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ccd_sweep.use_ccd_sweep_pallas(71_567, 10_681, 112)
    assert ccd_sweep.use_ccd_sweep_pallas(10_681, 71_567, 112)
    assert not ccd_sweep.use_ccd_sweep_pallas(71_567, 300, 112)


# --------------------------------------------------------------------------- #
# the program against the plain reference
# --------------------------------------------------------------------------- #

def _program(data, k, calls, workers=1, inner=2):
    """The model after each of ``calls`` one-iteration calls, each starting
    from the factors the call before returned."""
    model = ccd.CCD(HarpSession(num_workers=workers), ccd.CCDConfig(
        rank=k, lam=0.05, outer_iterations=1, inner_iterations=inner))
    state = model.prepare(data["rows"], data["cols"], data["vals"],
                          data["num_rows"], data["num_cols"],
                          seed=data["init_seed"])
    key, placed, m, n = state
    first = {"U": np.asarray(placed[-2])[:m], "V": np.asarray(placed[-1])[:n]}
    quality, after = [], {}
    factors = placed[-2:]
    for call in range(1, calls + 1):
        u, v, rmse = model.train_prepared(
            (key, (*placed[:-2], *factors), m, n))
        factors = (u, v)
        quality += list(rmse)
        after[call] = {"U": np.asarray(u)[:m], "V": np.asarray(v)[:n]}
    return model, first, quality, after


@pytest.mark.parametrize("k, step_diff, quality_gap",
                         [(8, 0.03, 0.002), (100, 0.1, 0.1)])
def test_ccd_against_the_configurations_reference(ratings, k, step_diff,
                                                  quality_gap):
    """From the seed's factors, after 1 and after 3 outer iterations: the
    gaps ``benchmark/compare.py`` reads, at the scale the bfloat16 operands
    of the prediction leave on the CPU (at rank 100 these 20,000 ratings are
    fitted to an RMSE of 0.008, and the curve's relative gap shows it); the
    float8 control of the same reference reads 3x the program or more."""
    config = {"rank": k, "lam": 0.05, "inner_iterations": 2}
    ref = _reference_module().Reference(config, ratings, 1)
    first = ref.initial()
    _, mine, quality, after = _program(ratings, k, 3)
    np.testing.assert_array_equal(mine["U"], first["U"])
    np.testing.assert_array_equal(mine["V"], first["V"])

    def follow(products=None):
        state, record = first, {"quality": []}
        for call in (1, 2, 3):
            state, q = ref.advance(state, 1, products=products)
            record["quality"] += list(q)
            record[f"after_{call}"] = state
        return record

    reference = follow()
    read = compare.numbers(first, {"quality": quality, "after_1": after[1],
                                   "after_3": after[3]}, reference)
    assert read["step1_diff"] < step_diff and read["step3_diff"] < step_diff
    assert read["quality_gap"] < quality_gap, read
    assert read["step1_norm_gap"] < 1e-3 and read["step3_norm_gap"] < 1e-3
    worse = compare.numbers(first, follow(jnp.float8_e4m3fn), reference)
    for number in ("step1_diff", "step3_diff", "quality_gap"):
        assert worse[number] >= 3 * read[number], (number, worse, read)


@pytest.mark.parametrize("k", [8, 100])
def test_four_workers_give_the_one_worker_factors(ratings, k):
    """704 and 300 divide by four: the first factors agree, and each worker
    sweeps its own rows of either plane."""
    _, first1, q1, after1 = _program(ratings, k, 3, workers=1)
    _, first4, q4, after4 = _program(ratings, k, 3, workers=4)
    np.testing.assert_array_equal(first4["U"], first1["U"])
    np.testing.assert_allclose(q4, q1, rtol=1e-4)
    for call in (1, 3):
        for leaf in ("U", "V"):
            a, b = after1[call][leaf], after4[call][leaf]
            np.testing.assert_allclose(b, a, rtol=0,
                                       atol=2e-4 * np.abs(a).max())


def test_the_program_through_the_kernel_is_the_program_through_xla(
        monkeypatch):
    """The predicate turned on off the TPU: the same sweeps through the
    kernel in interpret mode, at the tiles the shapes give (two column
    tiles a plane, the second row block and column tile overhanging)."""
    k = 8
    data = traffic.planted_ratings(
        {**PARAMS, "cols": 640, "ratings": 30000}, {}, SEED + 1)
    data["init_seed"] = SEED + 1
    plain, _, q_x, after_x = _program(data, k, 1)
    assert plain.last_layout_stats["sweep"] == "xla"
    monkeypatch.setattr(ccd_sweep, "use_ccd_sweep_pallas",
                        lambda rows, cols, kp: True)
    monkeypatch.setattr(ccd_sweep, "_MAX_COL_TILE", 512)
    fused, _, q_k, after_k = _program(data, k, 1)
    stats = fused.last_layout_stats
    assert stats["sweep"] == "pallas"
    assert stats["row_tile"] == [512, 512] and stats["col_tile"] == [512, 512]
    np.testing.assert_allclose(q_k, q_x, rtol=1e-5)
    # the order of a float32 row sum differs; where it turns a bfloat16
    # rounding of a new column (a part in 256), the sweeps behind it carry
    # that on: V reads 1.6e-3 of its norm away, U 1.6e-4
    for leaf in ("U", "V"):
        a, b = after_x[1][leaf], after_k[1][leaf]
        np.testing.assert_allclose(b, a, rtol=0, atol=8e-3 * np.abs(a).max())
        assert np.linalg.norm(b - a) < 4e-3 * np.linalg.norm(a)


def test_a_row_and_a_column_with_no_rating_keep_their_values():
    rng = np.random.default_rng(3)
    m, n, k = 96, 64, 8
    plane, seen = _plane(rng, m, n, density=0.3)
    seen[11], seen[:, 5] = False, False
    rows, cols = np.nonzero(seen)
    vals = plane[rows, cols]
    model = ccd.CCD(HarpSession(num_workers=1), ccd.CCDConfig(
        rank=k, outer_iterations=2))
    state = model.prepare(rows, cols, vals, m, n, seed=4)
    u0, v0 = (np.asarray(x) for x in state[1][-2:])
    u, v, rmse = model.fit_prepared(state)
    np.testing.assert_array_equal(u[11], u0[11])
    np.testing.assert_array_equal(v[5], v0[5])
    assert np.all(u[10] != u0[10]) and np.all(v[4] != v0[4])
    assert np.all(np.isfinite(rmse)) and rmse[1] < rmse[0]
    # the reference keeps them too
    ref = _reference_module().Reference(
        {"rank": k, "lam": 0.05, "inner_iterations": 2},
        {"rows": rows, "cols": cols, "vals": vals, "num_rows": m,
         "num_cols": n, "init_seed": 4}, 1)
    first = ref.initial()
    after, _ = ref.advance(first, 2)
    np.testing.assert_array_equal(after["U"][11], first["U"][11])
    np.testing.assert_array_equal(after["V"][5], first["V"][5])
    np.testing.assert_allclose(u, after["U"], rtol=0,
                               atol=0.05 * np.abs(after["U"]).max())


def test_prepare_raises_past_the_plane_budget(monkeypatch):
    model = ccd.CCD(HarpSession(num_workers=1), ccd.CCDConfig(rank=8))
    rows = np.array([0, 1], np.int32)
    with pytest.raises(ValueError, match=r"138493 x 26744.*14815427168 bytes"):
        model.prepare(rows, rows, np.ones(2, np.float32), 138_493, 26_744)
    # the budget is a worker's: four workers hold a quarter each
    monkeypatch.setattr(ccd, "DENSE_PLANE_BYTES", 4 * 64 * 48)
    ccd.CCD(HarpSession(num_workers=4), ccd.CCDConfig(rank=8)).prepare(
        rows, rows, np.ones(2, np.float32), 128, 96)
    with pytest.raises(ValueError, match="DENSE_PLANE_BYTES"):
        model.prepare(rows, rows, np.ones(2, np.float32), 128, 96)


def test_duplicates_are_dropped_keep_first_and_counted():
    rows = np.array([0, 1, 0, 2], np.int32)
    cols = np.array([1, 2, 1, 0], np.int32)
    vals = np.array([4.0, 3.0, 1.0, 5.0], np.float32)
    model = ccd.CCD(HarpSession(num_workers=1), ccd.CCDConfig(rank=8))
    state = model.prepare(rows, cols, vals, 8, 8)
    assert model.last_layout_stats["duplicates_dropped"] == 1
    assert model.last_layout_stats["plane_bytes"] == 2 * 8 * 8 * 2
    assert float(state[1][0][0, 1]) == 4.0


# --------------------------------------------------------------------------- #
# the span layer
# --------------------------------------------------------------------------- #

def test_ccd_leaves_its_phases_marks_and_counters(ratings):
    before = dict(metrics.DEFAULT.counters)
    t0 = telemetry.phases()[-1].end if telemetry.phases() else 0.0
    # two calls, the second from the factors of the first: no retrace
    model, _, _, _ = _program(ratings, 8, 2, inner=3)
    records = telemetry.phases(since=t0)
    names = [r.name for r in records]
    assert names.count("ccd.prepare") == 1 and names.count("ccd.call") == 2
    assert names.count("step.dispatch") == names.count("step.fetch") == 2
    prepare = next(r for r in records if r.name == "ccd.prepare")
    under = [r.name for r in records if r.parent == prepare.id]
    # the user plane and both factor tables are placed; the item plane is
    # the user plane transposed on the device
    assert under.count("session.place") == 3
    assert under.count("session.run") == 1
    marks = [r for r in records if r.name == "program.trace"]
    assert [r.detail for r in marks] == ["ccd"]

    def grew(name):
        return metrics.DEFAULT.counters[name] - before.get(name, 0)

    assert grew("program.traces.ccd") == 1
    assert grew("ccd.passes") == 2 * 8 * 3       # sides x rank x rounds
    assert grew("ccd.sweeps.xla") == 2 and grew("ccd.sweeps.pallas") == 0
    assert model.last_layout_stats == {
        "layout": "dense", "plane_bytes": 2 * 704 * 300 * 2,
        "duplicates_dropped": 0, "row_tile": [0, 0], "col_tile": [0, 0],
        "sweep": "xla"}


def test_every_ccd_scope_is_listed():
    from harp_tpu.telemetry import scopes

    for name in ("ccd.sweep", "ccd.column", "ccd.monitor"):
        assert name in scopes.SCOPES
        assert scopes.scope_of(f"jit(f)/while/body/{name}/dot_general") == name
