"""WDA-SMACOF on the normal path (ISSUE 34): the annealed program against the
benchmark's plain reference and the module's numpy oracle, the two kernels
in interpret mode against their ``jax.numpy`` twins, the stored type of the
weights, coincident points, the 8-worker mesh, and what the span layer
records. CPU, small N, target dimension 3."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from harp_tpu import telemetry
from harp_tpu.models import mds
from harp_tpu.ops import mds_kernels as mk
from harp_tpu.session import HarpSession
from harp_tpu.utils import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = {"target_dim": 3, "cg_iters": 10, "alpha": 0.95,
          "level_iterations": 4, "t_floor": 0.02, "distance_cut": 15.2}


def _points(n: int, seed: int = 3) -> np.ndarray:
    """Clusters as the cell's: 30 centres N(0, I) in 100 dimensions, noise
    0.3, so that the cut falls inside the between-cluster distances."""
    rng = np.random.default_rng(seed)
    centres = rng.standard_normal((30, 100)).astype(np.float32)
    return centres[rng.integers(0, 30, n)] + np.float32(0.3) * \
        rng.standard_normal((n, 100)).astype(np.float32)


def _config(iterations: int, **kw) -> mds.MDSConfig:
    return mds.MDSConfig(dim=3, iterations=iterations, cg_iters=10, **kw)


def _matrices(n: int):
    dist = mds.distance_matrix(_points(n))
    return dist, (dist <= np.float32(CONFIG["distance_cut"])).astype(np.float32)


def _calls(model, state, calls: int):
    curve = []
    for _ in range(calls):
        carry, sigma = model.train_prepared(state)
        state = (state[0], (*state[1][:-2], *carry))
        curve.append(sigma)
    return model.embedding(carry), np.concatenate(curve), state


def _reference(n: int, seed: int, calls: int, per_call: int, products=None):
    ref = harness.load_module(os.path.join(
        REPO, "benchmark", "configs", "wdamds-d3.reference.py")).Reference(
            CONFIG, {"points": _points(n), "init_seed": seed})
    first = state = ref.initial()
    curve = []
    for _ in range(calls):
        state, sigma = ref.advance(state, per_call, products=products)
        curve.append(sigma)
    return first["X"], state["X"], np.concatenate(curve)


def _use_kernels(monkeypatch):
    monkeypatch.setattr(mk, "use_mds_pallas", lambda *a: True)


# --------------------------------------------------------------------------- #
# the schedule
# --------------------------------------------------------------------------- #

def test_the_schedule_is_fixed_by_the_configuration():
    cfg = mds.MDSConfig(dim=3)
    shares = mds.schedule(cfg)
    # alpha^(k + 1) while at least t_floor, then 0: 76 levels, T = 0 from
    # iteration 304, a job of 308
    assert len(shares) == 77 and shares[-1] == 0.0
    assert shares[0] == np.float32(0.95) and shares[75] >= 0.02 > 0.95 ** 77
    assert np.all(np.diff(shares) < 0)
    assert mds.schedule_iterations(cfg) == 308
    assert mds.schedule_iterations(mds.MDSConfig(level_iterations=1)) == 77
    for bad in ({"alpha": 1.0}, {"t_floor": 0.0}, {"iterations": 0},
                {"level_iterations": 0}, {"cg_iters": -1}):
        with pytest.raises(ValueError):
            mds.MDSConfig(**bad)


def test_program_and_reference_read_the_same_distance_file():
    """Bit for bit, past one block of rows: the weights are a cut of these
    numbers, and a pair one bit off the cut flips between the two sides."""
    ref = harness.load_module(os.path.join(
        REPO, "benchmark", "configs", "wdamds-d3.reference.py"))
    pts = np.random.default_rng(3).standard_normal((2304, 100))
    np.testing.assert_array_equal(mds.distance_matrix(pts),
                                  ref._distances(pts))


def test_the_host_passes_make_no_wide_temporaries():
    pts = _points(300)
    dist = mds.distance_matrix(pts)
    want = np.sqrt(((pts[:, None].astype(np.float64) - pts[None]) ** 2
                    ).sum(-1))
    assert dist.dtype == np.float32 and np.all(np.diag(dist) == 0)
    np.testing.assert_allclose(dist, want, rtol=2e-5, atol=2e-4)
    # 0/1 masks and small dyadic confidences are exact in bfloat16 ...
    for w in (np.float32(dist < 14), np.float32(dist < 14) * 0.375 + 2.0):
        stored = mds._stored_weights(w)
        assert stored.dtype == jnp.bfloat16
        np.testing.assert_array_equal(stored.astype(np.float32), w)
    # ... a weight that is not is kept as handed over, and so is a matrix
    # handed over in bfloat16 (the benchmark's driver makes its mask so)
    w = np.float32(dist < 14) * np.float32(0.3)
    assert mds._stored_weights(w) is w
    assert mds._stored_weights(stored) is stored


# --------------------------------------------------------------------------- #
# the kernels against their twins
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("w_dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("n", [1024, 1280])
def test_the_kernels_in_interpret_mode_are_the_jax_numpy_passes(n, w_dtype):
    """Aligned (1024) and ragged (1280: a column tile of 2048 with an
    overhang, three row tiles of 512 with one) against the twins and a
    float64 count; one pair of points coincides."""
    rng = np.random.default_rng(n)
    dist, w = _matrices(n)
    if w_dtype == "float32":
        w = w * rng.uniform(0.2, 3.0, w.shape).astype(np.float32)
    np.fill_diagonal(w, 0)
    x = rng.standard_normal((n, 3)).astype(np.float32)
    x[5] = x[7]
    row_tile, bc_ct, mv_ct = mk.tiles(n, n, jnp.dtype(w_dtype).itemsize)
    assert row_tile == 512 and bc_ct % mk.CHUNK == 0
    assert (n % mk.CHUNK != 0) == (n == 1280)

    def padded(a, store):
        out = np.zeros((mk.DIM_PAD, store), np.float32)
        out[:3, :n] = a.T
        return jnp.asarray(out)

    xi = jnp.asarray(np.pad(x, ((0, 0), (0, mk.DIM_PAD - 3))))
    delta, wd = jnp.asarray(dist), jnp.asarray(w, w_dtype)
    shift = jnp.float32(1.3)
    t_k, s_k = jax.jit(lambda *a: mk.bc_pallas(
        *a, 3, row_tile, bc_ct, interpret=True))(
            delta, wd, xi, padded(x, mk.store(n, bc_ct)), shift)
    t_x, s_x = mk.bc_xla(delta, wd, xi, padded(x, n), shift, 3,
                         mk.row_blocks(n, n))
    x64 = x.astype(np.float64)
    d = np.sqrt(((x64[:, None] - x64[None]) ** 2).sum(-1))
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = np.where(d > 0, w * np.maximum(dist - 1.3, 0) / d, 0.0)
    t_o = (ratio.sum(1)[:, None] * x64 - ratio @ x64).T
    s_o = (w * (dist - d) ** 2).sum(1)
    for t, s in ((t_k, s_k), (t_x, s_x)):
        assert t.shape == (mk.DIM_PAD, n) and not np.asarray(t)[3:].any()
        np.testing.assert_allclose(np.asarray(t)[:3], t_o,
                                   atol=2e-6 * np.abs(t_o).max())
        np.testing.assert_allclose(np.asarray(s), s_o, rtol=2e-6)

    p = rng.standard_normal((n, 3)).astype(np.float32)
    q_k = jax.jit(lambda a, b: mk.matvec_pallas(
        a, mk.matvec_operand(b, a.dtype), row_tile, mv_ct, interpret=True))(
            wd, padded(p, mk.store(n, mv_ct)))
    q_x = mk.matvec_xla(wd, mk.matvec_operand(padded(p, n), wd.dtype),
                        mk.row_blocks(n, n))
    q_o = (w.astype(np.float64) @ p).T
    # three exact bfloat16 terms are a float32 product; float32 operands
    # run HIGHEST (on the CPU one plain float32 pass)
    tol = 3e-7 if w_dtype == "bfloat16" else 3e-6
    for q in (q_k, q_x):
        assert q.shape == (mk.DIM_PAD, n) and not np.asarray(q)[3:].any()
        np.testing.assert_allclose(np.asarray(q)[:3], q_o,
                                   atol=tol * np.abs(q_o).max())
    np.testing.assert_allclose(np.asarray(q_k), np.asarray(q_x),
                               atol=1e-6 * np.abs(q_o).max())


def test_the_three_terms_of_the_matvecs_operand_add_up_exactly():
    rng = np.random.default_rng(0)
    pt = np.zeros((mk.DIM_PAD, 256), np.float32)
    pt[:3] = rng.standard_normal((3, 256)) * 10.0 ** rng.integers(-6, 6, 256)
    op = np.asarray(mk.matvec_operand(jnp.asarray(pt), jnp.bfloat16)
                    ).astype(np.float64)
    assert op.shape == (256, 128) and not op[:, 24:].any()
    total = op[:, 0:8] + op[:, 8:16] + op[:, 16:24]
    np.testing.assert_array_equal(total.T, pt.astype(np.float64))
    one = np.asarray(mk.matvec_operand(jnp.asarray(pt), jnp.float32))
    np.testing.assert_array_equal(one[:, :8].T, pt)
    assert not one[:, 8:].any()


def test_tiles_and_dispatch_come_from_backend_and_shape(monkeypatch):
    # the cell's shape: 512 rows of both matrices a grid step
    assert mk.tiles(32768, 32768, 2) == (512, 8192, 16384)
    assert mk.tiles(32768, 32768, 4) == (512, 8192, 8192)
    assert mk.tiles(8192, 32768, 2) == (512, 8192, 16384)   # a worker of four
    assert mk.tiles(64, 32768, 2) == (0, 0, 0)              # under one tile
    assert mk.tiles(512, 512, 2) == (0, 0, 0)               # under one chunk
    assert mk.store(1280, 2048) == 2048 and mk.store(1280, 0) == 1280
    assert not mk.use_mds_pallas(32768, 32768, 3, 2)        # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert mk.use_mds_pallas(32768, 32768, 3, 2)
    assert not mk.use_mds_pallas(32768, 32768, 8, 2)        # no spare sublane
    assert not mk.use_mds_pallas(64, 64, 3, 2)
    rb, blocks = mk.row_blocks(32768, 32768)
    assert rb * blocks >= 32768 and 10 * 4 * rb * 32768 <= mk.SCRATCH_BYTES


# --------------------------------------------------------------------------- #
# the program against the plain reference and the oracle
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("form, workers, n", [
    ("xla", 1, 256), ("xla", 8, 256), ("pallas", 1, 1024), ("pallas", 2, 1024)])
def test_the_program_follows_the_reference_across_temperature_changes(
        monkeypatch, form, workers, n):
    """Three calls of 4 iterations: the temperature changes after the first
    and after the second. Program (twins, kernels in interpret mode, one
    worker and a mesh) against the benchmark's reference, embedding centred
    on both sides; the reference with bfloat16 products, the control, lies
    20 times further off."""
    if form == "pallas":
        _use_kernels(monkeypatch)
    dist, w = _matrices(n)
    model = mds.WDAMDS(HarpSession(num_workers=workers), _config(4))
    state = model.prepare(dist, w, seed=7)
    assert model.last_layout_stats["kernel"] == form
    assert model.last_layout_stats["weights_dtype"] == "bfloat16"
    x, sigma, _ = _calls(model, state, 3)
    x0, x_ref, s_ref = _reference(n, 7, 3, 4)
    assert len(sigma) == 12 and np.all(np.isfinite(sigma))
    np.testing.assert_allclose(sigma, s_ref, rtol=2e-5)
    change = np.linalg.norm(x_ref - x0)
    assert np.linalg.norm(x - x_ref) < 2e-5 * change
    assert abs(x.mean(axis=0)).max() < 1e-6
    if form == "xla" and workers == 1:
        _, x_ctl, _ = _reference(n, 7, 3, 4, products=jnp.bfloat16)
        assert np.linalg.norm(x_ctl - x_ref) > 20 * np.linalg.norm(x - x_ref)


@pytest.mark.parametrize("weights, stored", [
    ("uniform", "float32"), ("dyadic", "bfloat16"), (None, "bfloat16")])
def test_the_weights_stored_type_follows_the_data_and_agrees(weights, stored):
    """Weights that are not all exact in bfloat16 take the float32 store;
    either store follows the module's float64 oracle over a whole job's
    first temperatures, on the 8-worker mesh and on one worker."""
    rng = np.random.default_rng(11)
    n = 64
    dist = mds.distance_matrix(rng.standard_normal((n, 3)))
    if weights == "uniform":
        w = rng.uniform(0.2, 3.0, (n, n)).astype(np.float32)
    elif weights == "dyadic":
        w = rng.choice(np.float32([0.0, 0.5, 1.0, 2.0]), (n, n))
    else:
        w = None
    if w is not None:
        w = (w + w.T) / 2
    cfg = _config(12)
    curves = []
    for workers in (8, 1):
        model = mds.WDAMDS(HarpSession(num_workers=workers), cfg)
        state = model.prepare(dist, w, seed=2)
        assert model.last_layout_stats["weights_dtype"] == stored
        assert model.last_layout_stats["resident_bytes"] == n * n * (
            4 + (2 if stored == "bfloat16" else 4))
        assert state[1][1].dtype == stored
        assert not np.diag(np.asarray(state[1][1].astype(jnp.float32))).any()
        x, sigma, _ = _calls(model, state, 2)
        curves.append((x, sigma))
    x0 = np.random.default_rng(2).standard_normal((n, 3)).astype(np.float32)
    x_o, s_o = mds.numpy_wda_smacof(
        dist, np.ones((n, n)) if w is None else w, x0 - x0.mean(axis=0),
        cfg, 24)
    for x, sigma in curves:
        np.testing.assert_allclose(sigma, s_o, rtol=1e-4)
        np.testing.assert_allclose(x, x_o - x_o.mean(axis=0), atol=2e-4)


def test_coincident_points_give_finite_output_and_part(monkeypatch):
    """B_ij = 0 where d_ij = 0: a start with every point on one spot stays
    there (stress 1, finite), one with pairs of points on one spot is
    finite and parts them once their targets differ."""
    n = 256
    dist, w = _matrices(n)
    model = mds.WDAMDS(HarpSession(num_workers=1), _config(8))
    key, placed = model.prepare(dist, w, seed=1)
    spot = jnp.zeros_like(placed[-2]).at[:3].set(1.5)
    carry, sigma = model.train_prepared((key, (*placed[:-2], spot,
                                               placed[-1])))
    assert np.all(np.isfinite(sigma)) and sigma[0] == pytest.approx(1.0)
    assert np.all(np.isfinite(np.asarray(carry[0])))
    pairs = np.array(placed[-2])
    pairs[:, 1::2] = pairs[:, 0::2]
    carry, sigma = model.train_prepared((key, (*placed[:-2],
                                               jnp.asarray(pairs), placed[-1])))
    x = np.asarray(carry[0])
    assert np.all(np.isfinite(sigma)) and np.all(np.isfinite(x))
    assert np.abs(x[:3, 1::2] - x[:3, 0::2]).max() > 0


def test_a_job_runs_the_schedule_to_its_end_and_resumes_from_a_carry():
    n = 64
    dist = mds.distance_matrix(np.random.default_rng(5).standard_normal((n, 3)))
    model = mds.WDAMDS(HarpSession(num_workers=1), _config(50))
    state = model.prepare(dist, seed=3)
    lines = []
    x, sigma = model.fit_prepared(
        state, on_call=lambda done, call: lines.append((done, call[-1])))
    # 308 iterations of schedule in calls of 50: seven calls
    assert len(sigma) == 350 and sigma[1] > sigma[0] > sigma[-1]
    assert lines == [(50 * c, sigma[50 * c - 1]) for c in range(1, 8)]
    assert sigma[-1] < 1e-6                  # exact distances in 3-D
    assert model.temperature(0) > model.temperature(303) > 0.0
    assert model.temperature(304) == 0.0
    d_emb = np.sqrt(((x[:, None] - x[None]) ** 2).sum(-1))
    np.testing.assert_allclose(d_emb, dist, atol=1e-3)
    # two calls by hand, then the job from their carry: the same curve
    _, head, resumed = _calls(model, state, 2)
    x2, tail = model.fit_prepared(resumed)
    np.testing.assert_array_equal(np.concatenate([head, tail]), sigma)
    np.testing.assert_array_equal(x2, x)


# --------------------------------------------------------------------------- #
# the span layer
# --------------------------------------------------------------------------- #

def test_mds_leaves_its_phases_marks_and_counters():
    before = dict(metrics.DEFAULT.counters)
    t0 = telemetry.phases()[-1].end if telemetry.phases() else 0.0
    dist, w = _matrices(128)
    model = mds.WDAMDS(HarpSession(num_workers=1), _config(6))
    state = model.prepare(dist, w, seed=1)
    _calls(model, state, 2)             # the second from the first's carry
    records = telemetry.phases(since=t0)
    names = [r.name for r in records]
    assert names.count("mds.prepare") == 1 and names.count("mds.call") == 2
    assert names.count("step.dispatch") == names.count("step.fetch") == 2
    prepare = next(r for r in records if r.name == "mds.prepare")
    under = [r.name for r in records if r.parent == prepare.id]
    # both matrices, the first embedding and the count are placed; the
    # diagonal, V's diagonal and the two scales are one program
    assert under.count("session.place") == 4
    assert under.count("session.run") == 1
    marks = [r for r in records if r.name == "program.trace"]
    assert [r.detail for r in marks] == ["mds"]      # no retrace

    def grew(name):
        return metrics.DEFAULT.counters[name] - before.get(name, 0)

    assert grew("program.traces.mds") == 1
    assert grew("mds.bc.xla") == 1 and grew("mds.bc.pallas") == 0
    # the warm start's residual and the CG step's: two traced matvecs
    assert grew("mds.matvec.xla") == 2 and grew("mds.matvec.pallas") == 0
    assert grew("mds.cg.steps") == 10
    # 12 iterations at 4 a temperature: levels 0 -> 1 (call 1), 1 -> 3
    assert grew("mds.anneal.levels") == 3
    assert model.last_layout_stats == {
        "row_tile": 0, "weights_dtype": "bfloat16",
        "resident_bytes": 128 * 128 * 6, "kernel": "xla"}


def test_every_mds_scope_is_listed():
    from harp_tpu.telemetry import scopes

    for name in ("mds.anneal", "mds.bc", "mds.cg"):
        assert name in scopes.SCOPES
        assert scopes.scope_of(f"jit(f)/while/body/{name}/mul") == name
