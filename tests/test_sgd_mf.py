"""SGD-MF convergence tests (reference: sgd/SGDCollectiveMapper + BASELINE SGD-MF).

Statistical-parity strategy per SURVEY §7: the reference's async Hogwild updates are
only statistically specified, so we assert monotone-ish RMSE descent and recovery of
a low-rank signal, not a bitwise trajectory.
"""

import dataclasses

import pytest

import numpy as np

from harp_tpu.io import datagen
from harp_tpu.models import sgd_mf
from harp_tpu.session import HarpSession


def test_sgd_mf_converges(session):
    rows, cols, vals = datagen.sparse_ratings(
        num_users=96, num_items=80, rank=4, density=0.25, seed=3, noise=0.01)
    cfg = sgd_mf.SGDMFConfig(rank=8, lam=0.01, lr=0.08, epochs=20,
                             minibatches_per_hop=4)
    model = sgd_mf.SGDMF(session, cfg)
    w_f, h_f, rmse = model.fit(rows, cols, vals, 96, 80)

    assert rmse.shape == (cfg.epochs,)
    # pre-update streaming RMSE of the first epoch reflects the random init
    assert rmse[0] > 0.2
    # strong descent over training
    assert rmse[-1] < 0.25 * rmse[0]
    # final factors actually reconstruct the ratings
    final = sgd_mf.numpy_rmse(w_f, h_f, rows, cols, vals)
    assert final < 0.12


def test_sgd_mf_rmse_monitor_matches_factors(session):
    rows, cols, vals = datagen.sparse_ratings(
        num_users=64, num_items=64, rank=3, density=0.3, seed=11, noise=0.0)
    cfg = sgd_mf.SGDMFConfig(rank=6, lam=0.0, lr=0.05, epochs=12,
                             minibatches_per_hop=2)
    w_f, h_f, rmse = sgd_mf.SGDMF(session, cfg).fit(rows, cols, vals, 64, 64)
    # reported streaming RMSE (pre-update) should upper-bound the post-training
    # reconstruction error of the same epoch's end state
    final = sgd_mf.numpy_rmse(w_f, h_f, rows, cols, vals)
    assert final <= rmse[-1] * 1.5 + 1e-3
    assert np.all(np.isfinite(rmse))


def test_bucketize_covers_all_entries():
    rng = np.random.default_rng(0)
    nnz = 500
    rows = rng.integers(0, 40, nnz).astype(np.int32)
    cols = rng.integers(0, 30, nnz).astype(np.int32)
    vals = rng.standard_normal(nnz).astype(np.float32)
    r, c, v, m, rpw, cpb = sgd_mf.bucketize(rows, cols, vals, 8, 40, 30, 4)
    assert int(m.sum()) == nnz
    np.testing.assert_allclose(v[m > 0].sum(), vals.sum(), rtol=1e-4)
    # localized indices stay inside their blocks
    assert r.max() < rpw and c.max() < cpb
    # bucket length divisible by minibatch count
    assert r.shape[2] % 4 == 0


def test_serpentine_assign_balances_and_fits_capacity():
    rng = np.random.default_rng(7)
    counts = (rng.zipf(1.4, size=1000) * 3).astype(np.int64)
    bins, slots = sgd_mf.serpentine_assign(counts, 8)
    cap = -(-1000 // 8)
    assert slots.max() < cap
    # every bin holds ceil/floor ids
    sizes = np.bincount(bins, minlength=8)
    assert sizes.max() - sizes.min() <= 1
    # loads near-balanced (LPT-style bound: one heaviest id + an average share)
    loads = np.bincount(bins, weights=counts, minlength=8)
    assert loads.max() <= counts.max() + 2.0 * counts.sum() / 8
    # (bin, slot) is injective
    assert len(np.unique(bins.astype(np.int64) * cap + slots)) == 1000


def test_sparse_layout_bounds_padding_on_zipf_data(session):
    """VERDICT #4: power-law data must not blow up bucket padding."""
    rows, cols, vals = datagen.zipf_ratings(
        num_users=512, num_items=512, rank=4, alpha=1.2, density=0.05, seed=2)
    cfg = sgd_mf.SGDMFConfig(rank=8, lam=0.01, lr=0.05, epochs=8,
                             minibatches_per_hop=2, layout="sparse")
    model = sgd_mf.SGDMF(session, cfg)
    state = model.prepare(rows, cols, vals, 512, 512)
    assert model.last_layout_stats["overhead"] <= 4.0
    # and convergence is unchanged by the balanced remap
    w_f, h_f, rmse = model.fit_prepared(state)
    assert rmse[-1] < 0.6 * rmse[0]
    assert np.isfinite(sgd_mf.numpy_rmse(w_f, h_f, rows, cols, vals))

    # the round-1 contiguous layout on the same data, for contrast
    plain = sgd_mf.SGDMF(session, dataclasses.replace(cfg, balance=False))
    plain.prepare(rows, cols, vals, 512, 512)
    assert (model.last_layout_stats["overhead"]
            <= plain.last_layout_stats["overhead"] + 1e-9)


def test_dense_and_sparse_layouts_agree(session):
    """The masked dense-stripe path is the same SGD math as the sparse
    bucket path — both must recover the low-rank signal on identical data."""
    rows, cols, vals = datagen.sparse_ratings(
        num_users=96, num_items=80, rank=4, density=0.25, seed=3, noise=0.01)
    # dedupe so both layouts see the exact same entry set
    keys = rows.astype(np.int64) * 80 + cols
    _, first = np.unique(keys, return_index=True)
    rows, cols, vals = rows[first], cols[first], vals[first]
    finals = {}
    for layout in ("sparse", "dense"):
        cfg = sgd_mf.SGDMFConfig(rank=8, lam=0.01, lr=0.08, epochs=20,
                                 minibatches_per_hop=4, layout=layout)
        w_f, h_f, rmse = sgd_mf.SGDMF(session, cfg).fit(
            rows, cols, vals, 96, 80)
        finals[layout] = sgd_mf.numpy_rmse(w_f, h_f, rows, cols, vals)
        assert rmse[-1] < 0.3 * rmse[0], layout
    assert abs(finals["dense"] - finals["sparse"]) < 0.06


def test_hop_budget_tuner_policy():
    """adjustMiniBatch analog: sweeps once, then settles on the largest budget
    within slack of the fastest; EWMA tracks drift."""
    t = sgd_mf.HopBudgetTuner([1, 2, 4, 8], slack=0.2)
    # sweep order is ascending candidates
    sweep = [t.next_budget() for _ in range(4)]
    for nmb, sec in zip([1, 2, 4, 8], [1.0, 1.0, 1.1, 2.0]):
        assert t.next_budget() == nmb
        t.record(nmb, sec)
    assert sweep[0] == 1
    # 4 is within 20% of the best (1.0) -> pick the LARGEST qualifying budget
    assert t.chosen == 4
    assert t.next_budget() == 4
    # drift: budget 4 becomes slow; EWMA pushes choice down
    for _ in range(12):
        t.record(4, 3.0)
    assert t.chosen == 2


@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_fit_adaptive_converges_and_tunes(session, layout):
    rows, cols, vals = datagen.sparse_ratings(
        num_users=96, num_items=80, rank=4, density=0.25, seed=3, noise=0.01)
    cfg = sgd_mf.SGDMFConfig(rank=8, lam=0.01, lr=0.08, epochs=16,
                             minibatches_per_hop=4, layout=layout)
    model = sgd_mf.SGDMF(session, cfg)
    state = model.prepare(rows, cols, vals, 96, 80)
    w_f, h_f, rmse, tuner = model.fit_adaptive(state)
    assert rmse.shape == (16,)
    # every candidate was measured during the sweep, then a choice stuck
    assert set(tuner.times) == {1, 2, 4}
    assert tuner.chosen in (1, 2, 4)
    # convergence unhurt by the tuning epochs
    assert rmse[-1] < 0.3 * rmse[0]
    assert sgd_mf.numpy_rmse(w_f, h_f, rows, cols, vals) < 0.15


@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_fit_checkpointed_resume_matches_uninterrupted(session, tmp_path,
                                                       layout):
    """VERDICT #10: interrupt + resume mid-training reproduces the
    uninterrupted run exactly (training is deterministic given data+factors
    at the per-epoch program granularity)."""
    from harp_tpu.utils.checkpoint import Checkpointer

    rows, cols, vals = datagen.sparse_ratings(
        num_users=96, num_items=80, rank=4, density=0.25, seed=3, noise=0.01)
    cfg = sgd_mf.SGDMFConfig(rank=8, lam=0.01, lr=0.08, epochs=6,
                             minibatches_per_hop=4, layout=layout)
    model = sgd_mf.SGDMF(session, cfg)
    state = model.prepare(rows, cols, vals, 96, 80)

    # uninterrupted
    w_a, h_a, rmse_a, start_a = model.fit_checkpointed(
        state, Checkpointer(str(tmp_path / "a")), save_every=2)
    assert start_a == 0 and rmse_a.shape == (6,)

    # interrupted after 3 epochs, then resumed to completion
    ckpt_b = Checkpointer(str(tmp_path / "b"))
    model.fit_checkpointed(state, ckpt_b, epochs=3, save_every=1)
    w_b, h_b, rmse_b, start_b = model.fit_checkpointed(state, ckpt_b,
                                                       save_every=1)
    assert start_b == 3 and rmse_b.shape == (3,)
    np.testing.assert_array_equal(w_a, w_b)
    np.testing.assert_array_equal(h_a, h_b)
    np.testing.assert_array_equal(rmse_a[3:], rmse_b)

    # a fully-resumed call (nothing left to do) returns the final state
    w_c, h_c, rmse_c, start_c = model.fit_checkpointed(state, ckpt_b,
                                                       save_every=1)
    assert start_c == 6 and rmse_c.shape == (0,)
    np.testing.assert_array_equal(w_c, w_a)


@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_sgd_mf_two_slice_pipeline_converges(session, layout):
    """numModelSlices=2 parity: double-buffered rotation (dymoro pipeline)
    converges like the single-slice schedule — on BOTH data layouts."""
    rows, cols, vals = datagen.sparse_ratings(
        num_users=96, num_items=80, rank=4, density=0.25, seed=3, noise=0.01)
    cfg = sgd_mf.SGDMFConfig(rank=8, lam=0.01, lr=0.08, epochs=20,
                             minibatches_per_hop=4, num_slices=2,
                             layout=layout)
    w_f, h_f, rmse = sgd_mf.SGDMF(session, cfg).fit(rows, cols, vals, 96, 80)
    assert rmse[-1] < 0.25 * rmse[0]
    assert sgd_mf.numpy_rmse(w_f, h_f, rows, cols, vals) < 0.12


def test_sgd_mf_two_slice_covers_every_rating(session):
    """Every rating is visited exactly once per epoch (streaming count)."""
    rows, cols, vals = datagen.sparse_ratings(64, 64, 3, 0.3, seed=1)
    cfg = sgd_mf.SGDMFConfig(rank=4, epochs=1, minibatches_per_hop=2,
                             num_slices=2)
    model = sgd_mf.SGDMF(session, cfg)
    state = model.prepare(rows, cols, vals, 64, 64)
    # cnt accumulated in the epoch equals nnz -> rmse is finite and well-formed
    _, _, rmse = model.fit_prepared(state)
    assert np.all(np.isfinite(rmse))
    # direct check: bucket masks cover all ratings exactly once
    _, _, _, mask, _, _ = sgd_mf.bucketize(rows, cols, vals, 8, 64, 64, 2,
                                           num_col_blocks=16)
    assert int(mask.sum()) == len(vals)


def test_nan_ratings_rejected_and_auto_dense_respects_int32_guard(session):
    """NaN is the dense missing-entry sentinel: NaN input values raise; and
    auto layout never picks a dense slab the int32 scatter could not index."""
    rows = np.array([0, 1], np.int32)
    cols = np.array([0, 1], np.int32)
    vals = np.array([1.0, np.nan], np.float32)
    m = sgd_mf.SGDMF(session, sgd_mf.SGDMFConfig(rank=4, epochs=1))
    with pytest.raises(ValueError, match="NaN"):
        m.prepare(rows, cols, vals, 8, 8)

    # a geometry whose slab would exceed 2^31 elements must auto-pick sparse
    # even under an unlimited byte budget
    big = sgd_mf.SGDMF(session, sgd_mf.SGDMFConfig(
        rank=4, epochs=1, dense_max_bytes=1 << 62))
    assert big._choose_layout(200_000, 200_000) == "sparse"
    assert big._choose_layout(512, 512) == "dense"


@pytest.mark.parametrize("nmb", [2, 3])
@pytest.mark.parametrize("col_tile", [128, 256, 512])
def test_dense_mf_hop_pallas_matches_xla_stripes(col_tile, nmb):
    """The fused pallas hop (interpret mode on CPU) is bit-comparable to the
    XLA stripe loop in models/sgd_mf._build_dense at the tiles the stored
    layout reaches: rank 104 with four zero rank columns, pad rows at every
    stripe's end and pad columns at the block's (NaN cells, zero counts,
    zero factors), which a hop must leave bitwise as they were. Two stripes
    and three, over eight, four and two column tiles: every stripe's W block
    is another draw, so a stripe that ran on the bf16 operands an earlier
    stripe's first tile built would miss the stripe scan by the factors'
    own size."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.ops import pallas_kernels as pk

    rng = np.random.default_rng(0)
    NMB, S, CPB, K = nmb, 128, 1024, 104
    S_LIVE, CPB_LIVE, K_LIVE = 121, 1001, 100
    RPW = NMB * S
    LR, LAM = 0.05, 0.01
    live_row = np.tile(np.arange(S) < S_LIVE, NMB)
    live_col = np.arange(CPB) < CPB_LIVE
    v = rng.random((RPW, CPB)).astype(np.float32)
    v[rng.random((RPW, CPB)) < 0.9] = np.nan
    v[~live_row] = np.nan
    v[:, ~live_col] = np.nan
    vb = jnp.asarray(v, jnp.bfloat16)
    w_np = (0.1 * rng.standard_normal((RPW, K))).astype(np.float32)
    h_np = (0.1 * rng.standard_normal((CPB, K))).astype(np.float32)
    w_np[~live_row], h_np[~live_col] = 0.0, 0.0
    w_np[:, K_LIVE:], h_np[:, K_LIVE:] = 0.0, 0.0
    w0, h0 = jnp.asarray(w_np), jnp.asarray(h_np)
    rc = jnp.asarray(rng.integers(1, 5, RPW) * live_row, jnp.float32)
    cc = jnp.asarray(rng.integers(1, 5, (NMB, CPB)) * live_col, jnp.float32)
    bf = jnp.bfloat16

    def stripe(state, xs):
        hb, sse = state
        w_s, v_s, rc_s, cc_s = xs
        hb_b = hb.astype(bf)
        pred = jax.lax.dot_general(w_s.astype(bf), hb_b,
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        g = jnp.where(jnp.isnan(v_s), jnp.asarray(0.0),
                      v_s.astype(jnp.float32) - pred).astype(bf)
        dw = jax.lax.dot_general(g, hb_b, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dh = jax.lax.dot_general(g, w_s.astype(bf), (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        w_s = w_s + LR * (dw - LAM * rc_s[:, None] * w_s)
        hb = hb + LR * (dh - LAM * cc_s[:, None] * hb)
        sse = sse + jnp.sum(g.astype(jnp.float32) ** 2)
        return (hb, sse), w_s

    (h_ref, sse_ref), w_ref = jax.lax.scan(
        stripe, (h0, jnp.zeros(())),
        (w0.reshape(NMB, S, K), vb.reshape(NMB, S, CPB),
         rc.reshape(NMB, S), cc))
    # a ring of one: its (1, rpw, cpb) slab and block 0
    w_t, h_t, sse_pl = pk.dense_mf_hop_pallas(
        vb[None], 0, w0.T, h0.T, rc.reshape(NMB, S), cc, LR, LAM,
        col_tile=col_tile, interpret=True)
    w_new, h_new = np.asarray(w_t.T), np.asarray(h_t.T)
    np.testing.assert_allclose(np.asarray(w_ref.reshape(RPW, K)), w_new,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_ref), h_new,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(sse_ref), float(sse_pl), rtol=1e-4)
    # the live part moved, the pads did not (both paths)
    assert np.abs(w_new[live_row, :K_LIVE] - w_np[live_row, :K_LIVE]).max() > 0
    for got in (w_new, np.asarray(w_ref.reshape(RPW, K))):
        assert not got[~live_row].any() and not got[:, K_LIVE:].any()
    for got in (h_new, np.asarray(h_ref)):
        assert not got[~live_col].any() and not got[:, K_LIVE:].any()


@pytest.mark.parametrize("workers, epochs", [(1, 2), (2, 1), (4, 1)])
def test_the_fused_program_carries_w_in_the_kernels_form(monkeypatch, workers,
                                                         epochs):
    """The dense fused program carries W as ``(K, rpw)`` across its hops and
    epochs and transposes it at the call's two edges only. Two hops of it
    (two epochs on a ring of one, one epoch on a ring of two) and four (a
    ring of four) leave, bit for bit, the stored tables the same hops leave
    through the kernel's ``(rows, K)`` interface of PR 36: ``W.T`` handed in
    and ``.T`` taken back at every hop, the ring walked by hand (the kernel
    in interpret mode on the CPU mesh)."""
    import functools

    import jax

    from harp_tpu.ops import pallas_kernels as pk

    rows, cols, vals = datagen.sparse_ratings(
        num_users=96, num_items=80, rank=4, density=0.25, seed=5)
    cfg = sgd_mf.SGDMFConfig(rank=6, lam=0.01, lr=0.05, epochs=epochs,
                             layout="dense", minibatches_per_hop=2)
    hop = functools.partial(pk.dense_mf_hop_pallas, interpret=True)
    monkeypatch.setattr(pk, "use_dense_mf_pallas", lambda *shape: True)
    monkeypatch.setattr(pk, "dense_mf_hop_pallas", hop)
    model = sgd_mf.SGDMF(HarpSession(num_workers=workers), cfg)
    state = model.prepare(rows, cols, vals, 96, 80)
    assert model.last_layout_stats["fused_hop"] is True
    _, (v_slab, row_cnt, col_cnt), w0, h0, meta = state
    g = meta[6]
    w_dev, h_dev, _ = model.train_prepared(state)
    assert w_dev.shape == (workers * g.rpw_store, g.rank_store)

    v_slab, row_cnt, col_cnt = (np.asarray(a) for a in
                                (v_slab, row_cnt, col_cnt))
    w = np.asarray(w0).reshape(workers, g.rpw_store, g.rank_store).copy()
    h = np.asarray(h0).reshape(workers, g.cpb_store, g.rank_store).copy()
    one_hop = jax.jit(lambda slab, b, wt, ht, rc, cc: hop(
        slab, b, wt, ht, rc, cc, cfg.lr, cfg.lam,
        col_tile=model.last_layout_stats["col_tile"])[:2])
    for t in range(epochs * workers):
        for wid in range(workers):
            b = (wid - t) % workers            # the block resident at hop t
            w_t, h_t = one_hop(
                v_slab[wid], np.int32(b), w[wid].T, h[b].T,
                row_cnt[wid, b].reshape(g.nmb, g.s_store), col_cnt[wid, b])
            w[wid], h[b] = np.asarray(w_t).T, np.asarray(h_t).T
    np.testing.assert_array_equal(
        np.asarray(w_dev), w.reshape(-1, g.rank_store))
    # block b ends its last trip round the ring where it started
    np.testing.assert_array_equal(
        np.asarray(h_dev), h.reshape(-1, g.rank_store))
    assert np.abs(w - np.asarray(w0).reshape(w.shape)).max() > 0


@pytest.mark.parametrize("block", [0, 1, 2])
def test_dense_mf_hop_pallas_reads_the_block_its_index_names(block):
    """The kernel picks the resident block in its own index map (interpret
    mode on the CPU, a traced index): on ``(slab, b)`` it gives bitwise the
    factors and the SSE of the kernel on ``(slab[b:b+1], 0)``, three blocks
    with different NaN patterns giving three different results, and a block
    the index does not name leaves no trace: rewritten, it changes no bit."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.ops import pallas_kernels as pk

    rng = np.random.default_rng(28)
    NB, NMB, S, CPB, K = 3, 2, 128, 512, 16
    RPW = NMB * S
    v = rng.random((NB, RPW, CPB)).astype(np.float32)
    for b in range(NB):
        v[b][rng.random((RPW, CPB)) < 0.5 + 0.2 * b] = np.nan
    slab = jnp.asarray(v, jnp.bfloat16)
    w_t = jnp.asarray(0.1 * rng.standard_normal((K, RPW)), jnp.float32)
    h_t = jnp.asarray(0.1 * rng.standard_normal((K, CPB)), jnp.float32)
    rc = jnp.asarray(rng.integers(1, 5, (NMB, S)), jnp.float32)
    cc = jnp.asarray(rng.integers(1, 5, (NMB, CPB)), jnp.float32)

    hop = jax.jit(lambda slab, b: pk.dense_mf_hop_pallas(
        slab, b, w_t, h_t, rc, cc, 0.05, 0.01, col_tile=256, interpret=True))

    def same(got, want):
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    picked = hop(slab, jnp.int32(block))
    same(picked, hop(slab[block:block + 1], jnp.int32(0)))
    # the other blocks' ratings are not this hop's
    for other in range(NB):
        if other != block:
            assert float(hop(slab, jnp.int32(other))[2]) != float(picked[2])
    # ... and nothing of them reaches it: one all missing, one all rated
    others = [b for b in range(NB) if b != block]
    rewritten = slab.at[others[0]].set(jnp.nan).at[others[1]].set(7.0)
    same(hop(rewritten, jnp.int32(block)), picked)
    assert np.isfinite(float(picked[2])) and float(picked[2]) > 0


# --------------------------------------------------------------------------- #
# the dense layout's two geometries (DenseGeometry): the job is logical, the
# device arrays are stored at the fused hop's tiles
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("workers, num_rows, num_cols", [
    (1, 71_567, 10_681),        # MovieLens-10M on one chip
    (4, 138_493, 26_744),       # MovieLens-20M on four
])
def test_storage_geometry_of_the_public_shapes_reaches_the_fused_hop(
        workers, num_rows, num_cols):
    """Pure shapes: at rank 100 the stored geometry of both MovieLens cells
    tiles the kernel and fits its VMEM estimate, while the logical geometry
    (the mini-batches of the benchmark's reference) stays as it was and
    satisfies none of the three."""
    from harp_tpu.ops import pallas_kernels as pk

    model = sgd_mf.SGDMF(HarpSession(num_workers=workers), sgd_mf.SGDMFConfig(
        rank=100, minibatches_per_hop=8))
    g, n_blocks = model._dense_geometry(num_rows, num_cols)
    rpw = -(-(-(-num_rows // workers)) // 8) * 8
    assert (g.rpw, g.cpb, g.rank) == (rpw, -(-num_cols // workers), 100)
    assert g.cpb % 128 and g.rank % 8 and g.s_rows % 128
    assert pk.dense_mf_col_tile(g.cpb, g.s_rows, g.rank) == 0
    tile = pk.dense_mf_col_tile(g.cpb_store, g.s_store, g.rank_store)
    assert tile == (512 if workers == 1 else 256) and g.cpb_store % tile == 0
    assert pk.dense_mf_hop_vmem_bytes(
        g.rank_store, g.cpb_store, g.s_store, tile) <= pk.DENSE_MF_VMEM_LIMIT
    cells = n_blocks * g.rpw_store * g.cpb_store
    assert cells < 2 ** 31 and cells / (n_blocks * g.rpw * g.cpb) < 1.04
    assert model._choose_layout(num_rows, num_cols) == "dense"
    # a coarser budget of fit_adaptive merges whole stored stripes
    for nmb in (4, 2, 1):
        assert (g.rpw_store // nmb) % 128 == 0


@pytest.fixture(scope="module")
def unaligned_bench(tmp_path_factory):
    """The benchmark's tiny tree with both SGD-MF cells at a shape that is a
    small analogue of ML-10M's: no stripe, block or rank on a tile."""
    import os

    from tests.benchmark import tiny

    root = tiny.build(str(tmp_path_factory.mktemp("bench")))
    for cell in (tiny.ML10M, tiny.ML20M_X4):
        tiny._rewrite(
            os.path.join(root, "benchmark", "workloads", cell + ".json"),
            lambda doc: doc["params"].update(rows=710, cols=301))
    return root


@pytest.mark.parametrize("chips", [1, 4])
def test_unaligned_shape_trains_the_reference_job(unaligned_bench, chips):
    """``s_rows % 8``, ``cpb % 128`` and ``rank % 8`` all non-zero, on one
    worker and on four: the first model is ``default_rng(seed)``'s draw at
    the LOGICAL sizes, the way out returns the logical tables, and 15
    epochs follow the benchmark's plain reference within the configuration's
    limits (as the tiny tree holds them on the CPU)."""
    import os

    import jax

    from benchmark import compare, harness
    from tests.benchmark import tiny

    precision = jax.config.jax_default_matmul_precision
    try:
        cell, _, _ = harness.open_cell(
            tiny.ML10M if chips == 1 else tiny.ML20M_X4, unaligned_bench,
            require_accelerator=False)
    finally:
        jax.config.update("jax_default_matmul_precision", precision)
    assert cell.chips == chips
    data = harness.make_data(cell, tiny.SEED + 26)
    driver = cell.part("driver").Driver(cell.config, cell.traffic, data, chips)
    driver.prepare()
    g = driver._state[4][6]
    rank = cell.config["rank"]
    assert (g.nmb * g.s_rows * chips >= 710 and g.cpb * chips >= 301
            and g.rank == rank == 100)
    assert g.s_rows % 8 and g.cpb % 128 and g.rank % 8
    assert (g.s_store % 128, g.cpb_store % 256, g.rank_store % 8) == (0, 0, 0)

    rng = np.random.default_rng(data["init_seed"])
    scale = 1.0 / np.sqrt(rank)
    w_draw = (scale * rng.standard_normal(
        (chips * g.rpw, rank))).astype(np.float32)
    h_draw = (scale * rng.standard_normal(
        (chips * g.cpb, rank))).astype(np.float32)
    first = driver.finalize(driver.initial())
    assert first["W"].shape == (710, rank) and first["H"].shape == (301, rank)
    np.testing.assert_array_equal(first["W"], w_draw[:710])
    np.testing.assert_array_equal(first["H"], h_draw[:301])
    # the stored arrays: the draw embedded, every pad 0
    w_dev, h_dev = (np.asarray(a) for a in driver.initial())
    assert w_dev.shape == (chips * g.rpw_store, g.rank_store)
    assert h_dev.shape == (chips * g.cpb_store, g.rank_store)
    meta = driver._state[4]
    w_log, h_log = sgd_mf.SGDMF._to_logical(w_dev, h_dev, meta)
    np.testing.assert_array_equal(w_log, w_draw)
    np.testing.assert_array_equal(h_log, h_draw)
    assert np.count_nonzero(w_dev) == np.count_nonzero(w_draw)
    assert np.count_nonzero(h_dev) == np.count_nonzero(h_draw)

    record = harness.first_calls(driver, harness.Spans())
    driver.free()
    first_ref, reference = harness.follow_reference(cell, data)
    np.testing.assert_array_equal(first_ref["W"], first["W"])
    np.testing.assert_array_equal(first_ref["H"], first["H"])
    read = compare.numbers(first_ref, record, reference)
    ok, compared = compare.verdict(read, cell.limits)
    assert ok, compared
    assert len(record["quality"]) == 15
    # ... and within the five limits the committed configuration holds on
    # the chip, which the tiny tree loosens to two
    committed = harness.load_json(os.path.join(
        tiny.BENCH, "configs", "sgdmf-k100.json"))["limits"]
    assert len(committed) == 5
    ok, compared = compare.verdict(read, committed)
    assert ok, compared


def test_layout_stats_and_hop_counter_say_which_update_runs(session):
    """``last_layout_stats`` says what engaged and what it cost; the
    ``sgd_mf.hops.*`` counter moves when jax traces a hop body and never on
    a cached call. Off the TPU the stripe scan runs on the stored arrays."""
    from harp_tpu.utils import metrics

    rows, cols, vals = datagen.sparse_ratings(
        num_users=96, num_items=80, rank=4, density=0.25, seed=3)
    model = sgd_mf.SGDMF(session, sgd_mf.SGDMFConfig(
        rank=6, epochs=2, layout="dense", minibatches_per_hop=2))
    state = model.prepare(rows, cols, vals, 96, 80)
    g = state[4][6]
    stats = model.last_layout_stats
    assert (stats["fused_hop"], stats["col_tile"]) == (False, 0)
    assert stats["pad_overhead"] == pytest.approx(
        g.rpw_store * g.cpb_store / (g.rpw * g.cpb))
    assert stats["padded"] == (session.num_workers ** 2
                               * g.rpw_store * g.cpb_store)

    def hops(which="xla"):
        return dict(metrics.DEFAULT.counters).get("sgd_mf.hops." + which, 0)

    before, fused_before = hops(), hops("fused")
    model.fit_prepared(state)
    traced = hops() - before
    assert traced >= 1
    model.fit_prepared(state)
    assert hops() - before == traced
    assert model.last_layout_stats["fused_hop"] is False
    assert hops("fused") == fused_before


def test_slab_pick_says_where_the_resident_block_is_picked(monkeypatch):
    """On a mesh of four the resident block changes every hop. The XLA
    stripe scan copies it (``slab_pick == "copy"``, ``sgd_mf.picks.copied``
    per traced hop body); with the fused hop switched on (the kernel in
    interpret mode, at a small shape the stored layout tiles) the kernel
    reads it out of the whole slab (``"in_kernel"``,
    ``sgd_mf.picks.in_kernel``) and the four-worker factors are the XLA
    path's, to the tolerance the kernel is held to against the stripe scan
    above. A slab of one block is picked by a static index, and counts
    neither."""
    import functools

    from harp_tpu.ops import pallas_kernels as pk
    from harp_tpu.utils import metrics

    rows, cols, vals = datagen.sparse_ratings(
        num_users=96, num_items=80, rank=4, density=0.25, seed=3)
    cfg = sgd_mf.SGDMFConfig(rank=6, lam=0.01, lr=0.05, epochs=2,
                             layout="dense", minibatches_per_hop=2)

    def counters():
        return dict(metrics.DEFAULT.counters)

    def picks(before):
        return {k: v - before.get(k, 0) for k, v in counters().items()
                if k.startswith("sgd_mf.picks.") and v != before.get(k, 0)}

    before = counters()
    xla = sgd_mf.SGDMF(HarpSession(num_workers=4), cfg)
    state = xla.prepare(rows, cols, vals, 96, 80)
    assert xla.last_layout_stats["slab_pick"] == "copy"
    w_xla, h_xla, rmse_xla = xla.fit_prepared(state)
    traced = picks(before)
    assert set(traced) == {"sgd_mf.picks.copied"}
    assert traced["sgd_mf.picks.copied"] >= 1
    xla.fit_prepared(state)                  # a cached call traces nothing
    assert picks(before) == traced

    before = counters()
    one = sgd_mf.SGDMF(HarpSession(num_workers=1), cfg)
    one.fit_prepared(one.prepare(rows, cols, vals, 96, 80))
    assert one.last_layout_stats["slab_pick"] == "static"
    assert picks(before) == {}

    monkeypatch.setattr(pk, "use_dense_mf_pallas", lambda *shape: True)
    monkeypatch.setattr(pk, "dense_mf_hop_pallas", functools.partial(
        pk.dense_mf_hop_pallas, interpret=True))
    before = counters()
    fused = sgd_mf.SGDMF(HarpSession(num_workers=4), cfg)
    state = fused.prepare(rows, cols, vals, 96, 80)
    stats = fused.last_layout_stats
    assert (stats["slab_pick"], stats["fused_hop"]) == ("in_kernel", True)
    assert stats["col_tile"] == 256
    w_f, h_f, rmse_f = fused.fit_prepared(state)
    traced = picks(before)
    assert set(traced) == {"sgd_mf.picks.in_kernel"}
    assert counters()["sgd_mf.hops.fused"] >= 1
    np.testing.assert_allclose(w_f, w_xla, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h_f, h_xla, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(rmse_f, rmse_xla, rtol=1e-4)
    assert np.abs(w_f).max() > 0 and rmse_f[-1] < rmse_f[0]
