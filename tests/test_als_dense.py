"""The dense ALS half-step in row blocks, the batched SPD solve past k = 64
and the span layer of ``ALS`` (ISSUE 27), and the packed block-triangle
operand both stand on (ISSUE 30), at sizes a CPU test can hold."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harp_tpu import telemetry
from harp_tpu.models import als
from harp_tpu.ops import pallas_kernels
from harp_tpu.session import HarpSession
from harp_tpu.utils import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _plane(rng, rows, others, density=0.1):
    plane = np.full((rows, others), np.nan, np.float32)
    seen = rng.random((rows, others)) < density
    plane[seen] = rng.integers(1, 11, int(seen.sum())) / 2.0   # half-stars
    return jnp.asarray(plane, jnp.bfloat16), seen


def _factors(rng, n, k):
    return jnp.asarray((rng.random((n, k)) / np.sqrt(k)).astype(np.float32))


def _coo(rng, rows, cols, nnz):
    keys = rng.choice(rows * cols, nnz, replace=False)
    vals = rng.integers(1, 11, nnz) / 2.0
    return ((keys // cols).astype(np.int32), (keys % cols).astype(np.int32),
            vals.astype(np.float32))


# --------------------------------------------------------------------------- #
# the blocks
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("implicit", (True, False))
def test_row_blocks_give_the_unblocked_half_step_bitwise(implicit):
    rng = np.random.default_rng(0)
    rows, others, k = 300, 333, 12
    plane, _ = _plane(rng, rows, others)
    f = _factors(rng, others, k)
    cfg = als.ALSConfig(rank=k, lam=0.05, alpha=40.0, implicit=implicit)
    whole = np.asarray(als._half_step_dense(f, plane, rows, cfg))
    assert als._dense_blocks(rows, others, k) == (rows, 1, others, 1)
    # three blocks of 128 rows: the last is taken flush with the end and
    # recomputes 84 rows of the second, to the same bits
    blocked = np.asarray(als._half_step_dense(
        f, plane, rows, cfg, blocks=(128, 3, others, 1)))
    assert np.array_equal(blocked, whole)


@pytest.mark.parametrize("implicit", (True, False))
def test_contraction_chunks_agree_to_float32_rounding(implicit):
    """Chunks change the order of the float32 sum over the other side; the
    columns the last chunk shares with the one before count once."""
    rng = np.random.default_rng(1)
    rows, others, k = 200, 333, 12
    plane, _ = _plane(rng, rows, others)
    f = _factors(rng, others, k)
    cfg = als.ALSConfig(rank=k, lam=0.05, alpha=40.0, implicit=implicit)
    whole = np.asarray(als._half_step_dense(f, plane, rows, cfg))
    chunked = np.asarray(als._half_step_dense(
        f, plane, rows, cfg, blocks=(128, 2, 128, 3)))
    np.testing.assert_allclose(chunked, whole, rtol=0,
                               atol=2e-5 * np.abs(whole).max())


def test_blocks_of_the_cells_shape_fit_the_scratch_budget():
    k, packed = 100, 5824
    for rows, others in ((71_567, 10_681), (10_681, 71_567)):
        rb, n_rb, ce, n_ce = als._dense_blocks(rows, others, k)
        assert rb * n_rb >= rows and ce * n_ce >= others
        assert n_rb == 1 or (rb % 512 == 0 and rb <= rows)
        assert n_ce == 1 or (ce % 128 == 0 and ce <= min(others, 16384))
        scratch = rb * (8 * packed + 4 * ce) + 2 * packed * ce
        assert scratch <= als.DENSE_SCRATCH_BYTES
    # the users' side is what has to be blocked: 1.7 GB of packed systems at
    # once; in four blocks where the full matrices took five
    assert als._dense_blocks(71_567, 10_681, k) == (17_920, 4, 10_681, 1)
    assert als._dense_blocks(10_681, 71_567, k) == (10_681, 1, 14_336, 5)
    assert als._row_block(300, 8 * 200) == (300, 1)


def _full_half_step(f, plane, cfg, blocks):
    """The half-step over the FULL normal equations, all Kp² outer products
    against the weights and ``(Kp, Kp, rb)`` into the exact solver: what the
    packed operand replaced, on the same blocks in the same order."""
    k, (rows, e) = cfg.rank, plane.shape
    kp = als.round_up(k, 8)
    rb, n_rb, ce, n_ce = blocks
    f32, bf16 = jnp.float32, jnp.bfloat16
    f_t = jnp.pad(f.T, ((0, kp - k), (0, 0))).astype(bf16)
    shift = jnp.diag(jnp.where(jnp.arange(kp) < k, cfg.lam, 1.0).astype(f32))
    if cfg.implicit:
        gram = jax.lax.dot_general(f, f, (((0,), (0,)), ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=f32)
        shift = shift + jnp.pad(gram, ((0, kp - k), (0, kp - k)))
    out = np.zeros((rows, k), np.float32)
    for i in range(n_rb):
        r0 = min(i * rb, rows - rb)
        a, b = jnp.zeros((kp * kp, rb), f32), jnp.zeros((kp, rb), f32)
        for c in range(n_ce):
            c0 = min(c * ce, e - ce)
            blk, f_c = plane[r0:r0 + rb, c0:c0 + ce], f_t[:, c0:c0 + ce]
            vv = (f_c[:, None, :] * f_c[None, :, :]).reshape(kp * kp, ce)
            obs = jnp.isfinite(blk) & (c0 + jnp.arange(ce) >= c * ce)[None, :]
            vz = jnp.where(obs, blk, 0).astype(bf16)
            if cfg.implicit:
                w_a = (cfg.alpha * vz).astype(bf16)
                w_b = jnp.where(obs, 1.0 + cfg.alpha * vz.astype(f32),
                                0.0).astype(bf16)
            else:
                w_a, w_b = obs.astype(bf16), vz
            da = jax.lax.dot_general(vv, w_a, (((1,), (1,)), ((), ())),
                                     preferred_element_type=f32)
            db = jax.lax.dot_general(f_c, w_b, (((1,), (1,)), ((), ())),
                                     preferred_element_type=f32)
            a, b = (da, db) if n_ce == 1 else (a + da, b + db)
        full = a.reshape(kp, kp, rb) + shift[:, :, None]
        out[r0:r0 + rb] = als._spd_solve(
            jnp.transpose(full, (2, 0, 1))[:, :k, :k], b[:k].T, cfg)
    return out


@pytest.mark.parametrize("blocks", [(300, 1, 333, 1), (128, 3, 333, 1),
                                    (128, 3, 128, 3)],
                         ids=["whole", "blocked", "chunked"])
@pytest.mark.parametrize("implicit", (True, False))
def test_the_packed_half_step_is_the_full_one_bitwise(implicit, blocks):
    """The kept entries are the same bf16 products summed in the same
    order, and the exact solver is handed them back in both triangles."""
    rng = np.random.default_rng(9)
    rows, others, k = 300, 333, 12
    plane, _ = _plane(rng, rows, others)
    f = _factors(rng, others, k)
    cfg = als.ALSConfig(rank=k, lam=0.05, alpha=40.0, implicit=implicit)
    packed = np.asarray(als._half_step_dense(f, plane, rows, cfg,
                                             blocks=blocks))
    assert np.array_equal(packed, _full_half_step(f, plane, cfg, blocks))


@pytest.mark.parametrize("rows, others", [(71_567, 10_681), (10_681, 71_567)])
def test_no_product_of_the_cells_half_steps_has_k_squared_rows(rows, others):
    """Lowered from shapes alone at the cell's two sides: the plane GEMM's M
    is the 5,824 packed rows, and no product is 10,816 long (off the TPU the
    exact solver's operand is, unpacked behind the GEMM)."""
    cfg = als.ALSConfig(rank=100, lam=0.05, alpha=40.0, layout="dense")
    text = jax.jit(lambda f, plane: als._half_step_dense(
        f, plane, rows, cfg)).lower(
        jax.ShapeDtypeStruct((others, 100), jnp.float32),
        jax.ShapeDtypeStruct((rows, others), jnp.bfloat16)).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    rb, _, ce, _ = als._dense_blocks(rows, others, 100)
    assert any(f"tensor<5824x{ce}xbf16>" in line
               and f"-> tensor<5824x{rb}xf32>" in line for line in dots), dots
    assert not [line for line in dots if "10816" in line]


def test_the_monitor_in_blocks_counts_every_observed_cell_once(monkeypatch):
    rng = np.random.default_rng(2)
    rows, others, k = 300, 200, 8
    plane, seen = _plane(rng, rows, others)
    u, v = _factors(rng, rows, k), _factors(rng, others, k)
    cfg = als.ALSConfig(rank=k)
    whole = als._monitor_dense(u, v, plane, cfg)
    monkeypatch.setattr(als, "_row_block", lambda rows, per_row: (128, 3))
    blocked = als._monitor_dense(u, v, plane, cfg)
    assert float(blocked[1]) == float(whole[1]) == seen.sum()
    assert float(blocked[0]) == pytest.approx(float(whole[0]), rel=1e-5)


# --------------------------------------------------------------------------- #
# the solve
# --------------------------------------------------------------------------- #

def _spd_batch(rng, n, k):
    g = rng.standard_normal((n, k, 2 * k)).astype(np.float32)
    a = g @ np.transpose(g, (0, 2, 1)) / (2 * k) + 0.05 * np.eye(
        k, dtype=np.float32)
    return a, rng.standard_normal((n, k)).astype(np.float32)


@pytest.mark.parametrize("kp, packed", [(8, 64), (16, 192), (104, 5824)])
def test_the_packing_is_the_block_upper_triangle_row_by_row(kp, packed):
    rows_j, rows_c = pallas_kernels.spd_pack_rows(kp)
    want = [(j, c) for j in range(kp) for c in range(8 * (j // 8), kp)]
    assert list(zip(rows_j.tolist(), rows_c.tolist())) == want
    assert len(want) == packed == pallas_kernels.spd_pack_size(kp)
    pairs = set(want)
    assert len(pairs) == packed                       # each once
    assert all((j, c) in pairs for j in range(kp) for c in range(j, kp))
    for j in range(kp):                     # a row's segment, 8-aligned
        (at,) = np.nonzero(rows_j == j)
        assert at[0] % 8 == 0 and len(at) == kp - 8 * (j // 8)
        assert np.array_equal(at, np.arange(at[0], at[0] + len(at)))
        assert np.array_equal(rows_c[at], np.arange(8 * (j // 8), kp))
        if j % 8 == 0:
            assert at[0] == pallas_kernels._spd_group_offset(kp, j // 8)
    with pytest.raises(ValueError):
        pallas_kernels.spd_pack_rows(12)


@pytest.mark.parametrize("kp", [8, 16, 104])
def test_pack_reads_the_listed_entries_and_unpack_gives_them_back(kp):
    a, _ = _spd_batch(np.random.default_rng(kp), 5, kp)
    at = jnp.transpose(jnp.asarray(a), (1, 2, 0))
    rows_j, rows_c = pallas_kernels.spd_pack_rows(kp)
    assert np.array_equal(a, np.transpose(a, (0, 2, 1)))
    packed = pallas_kernels.spd_pack(at)
    assert np.array_equal(packed, at[rows_j, rows_c])
    back = pallas_kernels.spd_unpack(packed, kp)
    assert back.shape == (kp, kp, 5) and np.array_equal(back, at)
    # the outer products' rows, formed slab by slab, are the same entries
    f = jnp.asarray(a[0], jnp.bfloat16)
    assert np.array_equal(pallas_kernels.spd_pack_outer(f),
                          f[rows_j] * f[rows_c])


@pytest.mark.parametrize("k, n", [(8, 70), (12, 150), (32, 200), (64, 128),
                                  (100, 130)])
def test_spd_solve_pallas_interpret_matches_linalg_solve(k, n):
    """Batches that are and are not a multiple of the 128 lanes; k = 100 is
    padded to 104 by an identity block that leaves the solution alone, and
    k = 8 packs to its whole matrix."""
    a, b = _spd_batch(np.random.default_rng(k), n, k)
    want = jnp.linalg.solve(jnp.asarray(a), jnp.asarray(b)[..., None])[..., 0]
    got = pallas_kernels.spd_solve_pallas(jnp.asarray(a), jnp.asarray(b),
                                          tile_b=128, interpret=True)
    assert got.shape == (n, k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-5 * np.abs(want).max())


def test_spd_solve_lanes_reads_the_systems_packed_and_batch_last():
    k, n = 16, 70
    a, b = _spd_batch(np.random.default_rng(3), n, k)
    want = np.linalg.solve(a.astype(np.float64), b.astype(np.float64)[..., None])
    rows_j, rows_c = pallas_kernels.spd_pack_rows(k)
    got = pallas_kernels.spd_solve_lanes(
        jnp.asarray(a[:, rows_j, rows_c].T), jnp.asarray(b).T,
        tile_b=128, interpret=True)
    assert got.shape == (k, n)
    np.testing.assert_allclose(np.asarray(got).T, want[..., 0], atol=1e-4)
    for at, bt in [(jnp.zeros((16, 16, 8)), jnp.zeros((16, 8))),  # unpacked
                   (jnp.zeros((100, 8)), jnp.zeros((10, 8)))]:
        with pytest.raises(ValueError):
            pallas_kernels.spd_solve_lanes(at, bt, interpret=True)


@pytest.mark.parametrize("k", [16, 104])
def test_the_kernel_never_needed_the_entries_the_packing_drops(k):
    """NaN in every entry below its row's sublane group, before packing:
    the same bits as from the symmetric matrices."""
    n = 40
    a, b = _spd_batch(np.random.default_rng(k), n, k)
    rows_j, rows_c = pallas_kernels.spd_pack_rows(k)
    j, c = np.divmod(np.arange(k * k), k)
    poisoned = a.copy().reshape(n, k * k)
    poisoned[:, c < 8 * (j // 8)] = np.nan
    poisoned = poisoned.reshape(n, k, k)
    assert np.isnan(poisoned).sum() == n * (k * k - len(rows_j))

    def solve(m):
        return np.asarray(pallas_kernels.spd_solve_lanes(
            jnp.asarray(m[:, rows_j, rows_c].T), jnp.asarray(b).T,
            tile_b=128, interpret=True))

    got = solve(poisoned)
    assert np.isfinite(got).all() and np.array_equal(got, solve(a))


def test_the_dispatch_decides_from_backend_rank_and_vmem(monkeypatch):
    assert not pallas_kernels.use_spd_solve_pallas(100)      # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_kernels.use_spd_solve_pallas(32)
    assert pallas_kernels.use_spd_solve_pallas(100)
    assert pallas_kernels.use_spd_solve_pallas(128)
    assert not pallas_kernels.use_spd_solve_pallas(512)      # 400 MB a tile
    assert pallas_kernels.spd_solve_tile(32) == 512
    assert pallas_kernels.spd_solve_tile(100) == 512         # 51 MB
    assert pallas_kernels.spd_solve_tile(128) == 512         # 75 MB
    assert pallas_kernels.spd_solve_tile(200) == 256
    assert (pallas_kernels.spd_solve_vmem_bytes(
        100, pallas_kernels.spd_solve_tile(100))
        <= pallas_kernels.SPD_SOLVE_VMEM_LIMIT)


def test_dense_als_with_the_kernel_matches_the_exact_solver():
    """``solver="pallas"`` off the TPU runs the kernel in interpret mode
    through the blocked dense half-step, batch-last as on the chip."""
    rng = np.random.default_rng(4)
    rows, cols, vals = _coo(rng, 96, 80, 1500)
    sess = HarpSession(num_workers=1)
    out = {}
    for solver in ("cholesky", "pallas"):
        cfg = als.ALSConfig(rank=12, lam=0.05, alpha=40.0, iterations=1,
                            layout="dense", solver=solver)
        out[solver] = als.ALS(sess, cfg).fit(rows, cols, vals, 96, 80, seed=5)
    # two float32 factorisations of systems whose condition number is ~1e4
    for exact, fast in zip(out["cholesky"], out["pallas"]):
        np.testing.assert_allclose(fast, exact, rtol=0,
                                   atol=2e-3 * np.abs(exact).max())


# --------------------------------------------------------------------------- #
# against the configuration's own reference
# --------------------------------------------------------------------------- #

def _reference_module():
    path = os.path.join(REPO, "benchmark", "configs", "als-k100.reference.py")
    spec = importlib.util.spec_from_file_location("als_k100_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("k, step1_diff, quality_gap",
                         [(12, 0.05, 0.01), (100, 0.3, 0.05)])
def test_dense_als_against_the_configurations_reference(k, step1_diff,
                                                        quality_gap):
    """Implicit, dense, row-blocked, one worker, from the seed's factors:
    the gaps ``benchmark/compare.py`` reads, at the scale the bfloat16
    operands of the plane products leave on the CPU."""
    from benchmark import compare

    rng = np.random.default_rng(6)
    m, n, nnz = 384, 256, 12000
    rows, cols, vals = _coo(rng, m, n, nnz)
    config = {"rank": k, "lam": 0.05, "alpha": 40.0, "implicit": True}
    data = {"rows": rows, "cols": cols, "vals": vals, "num_rows": m,
            "num_cols": n, "init_seed": 2 ** 31 + 9}
    ref = _reference_module().Reference(config, data, 1)
    first = ref.initial()
    after, q_ref = ref.advance(first, 1)

    model = als.ALS(HarpSession(num_workers=1), als.ALSConfig(
        iterations=1, layout="dense", **config))
    state = model.prepare(rows, cols, vals, m, n, seed=data["init_seed"])
    np.testing.assert_array_equal(np.asarray(state[1][-2])[:m], first["U"])
    u, v, q = model.fit_prepared(state)
    read = compare.numbers(
        first, {"quality": q, "after_1": {"U": u, "V": v},
                "after_3": {"U": u, "V": v}},
        {"quality": q_ref, "after_1": after, "after_3": after})
    assert read["step1_diff"] < step1_diff, read
    assert read["quality_gap"] < quality_gap, read
    # the float8 control of the same reference reads 3x the program or more
    control, q_c = ref.advance(first, 1, products=jnp.float8_e4m3fn)
    worse = compare.numbers(
        first, {"quality": q_c, "after_1": control, "after_3": control},
        {"quality": q_ref, "after_1": after, "after_3": after})
    assert worse["step1_diff"] >= 3 * read["step1_diff"], (worse, read)


def test_eight_workers_give_the_one_worker_factors(session):
    rng = np.random.default_rng(7)
    m, n = 160, 96
    rows, cols, vals = _coo(rng, m, n, 3000)
    cfg = als.ALSConfig(rank=8, lam=0.05, alpha=40.0, iterations=2,
                        layout="dense")
    one = als.ALS(HarpSession(num_workers=1), cfg).fit(rows, cols, vals, m, n)
    eight = als.ALS(session, cfg).fit(rows, cols, vals, m, n)
    # the first factors are drawn at the padded sizes, which agree here
    for a, b in zip(one, eight):
        np.testing.assert_allclose(b, a, rtol=0, atol=1e-4 * np.abs(a).max())


# --------------------------------------------------------------------------- #
# the span layer
# --------------------------------------------------------------------------- #

def test_als_leaves_its_phases_marks_and_counters():
    rng = np.random.default_rng(8)
    rows, cols, vals = _coo(rng, 64, 48, 600)
    model = als.ALS(HarpSession(num_workers=1), als.ALSConfig(
        rank=8, iterations=1, layout="dense"))
    before = dict(metrics.DEFAULT.counters)
    t0 = telemetry.phases()[-1].end if telemetry.phases() else 0.0
    state = model.prepare(rows, cols, vals, 64, 48, seed=1)
    u, v, _ = model.train_prepared(state)
    # a second call starts from the factors the first returned: no retrace
    key, placed, us, vs = state
    model.train_prepared((key, (*placed[:-2], u, v), us, vs))
    records = telemetry.phases(since=t0)
    names = [r.name for r in records]
    assert names.count("als.prepare") == 1 and names.count("als.call") == 2
    assert names.count("step.dispatch") == names.count("step.fetch") == 2
    prepare = next(r for r in records if r.name == "als.prepare")
    under = [r.name for r in records if r.parent == prepare.id]
    # the user plane and both factor tables are placed; the item plane is
    # the user plane transposed on the device
    assert under.count("session.place") == 3
    assert under.count("session.run") == 1
    marks = [r for r in records if r.name == "program.trace"]
    assert [r.detail for r in marks] == ["als.fit"]

    def grew(name):
        return metrics.DEFAULT.counters[name] - before.get(name, 0)

    assert grew("program.traces.als.fit") == 1
    assert grew("als.row_blocks") == 2           # one block a half-step
    assert grew("als.gram.rows") == 2 * 64       # rank 8 packs to the whole
    assert grew("als.solve.xla") == 2 and grew("als.solve.pallas") == 0


def test_every_als_scope_is_listed():
    from harp_tpu.telemetry import scopes

    for name in ("als.outer", "als.gram", "als.rhs", "als.solve",
                 "als.monitor"):
        assert name in scopes.SCOPES
        assert scopes.scope_of(f"jit(f)/while/body/{name}/dot_general") == name
