"""Lane-packing + one-hot-GEMM scatter engine (ops/lane_pack) parity tests.

Shape coverage mirrors the spd_solve pattern (aligned / needs-padding /
prime): the engine must be exact at lane-aligned shapes, shapes whose token
count needs chunk padding, and prime widths that defeat every divisor
heuristic. The gemm_scatter 'exact_pm1' policy is BITWISE-checked against
``segment_sum`` — 0/1 one-hots and ±1/0 deltas are bf16-representable and
integer sums are exact in the f32 accumulator regardless of reduction order,
which is the whole exactness argument the LDA count write rests on.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harp_tpu.io import datagen
from harp_tpu.models import kmeans as km
from harp_tpu.models import lda, sparse
from harp_tpu.ops import distance, lane_pack, pallas_kernels, ring_dma


# --------------------------------------------------------------------------- #
# gemm_scatter
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("t,width,k,chunk", [
    (256, 128, 32, 64),     # lane-aligned, chunk divides
    (300, 96, 10, 77),      # needs chunk padding (the spd K=10/N=300 shape)
    (997, 13, 7, None),     # prime token count AND prime width
])
def test_gemm_scatter_bitwise_matches_segment_sum(rng, t, width, k, chunk):
    ids = jnp.asarray(rng.integers(0, width, t), jnp.int32)
    delta = jnp.asarray(rng.integers(-1, 2, (t, k)), jnp.float32)  # ±1/0
    got = lane_pack.gemm_scatter(ids, delta, width, chunk=chunk)
    want = jax.ops.segment_sum(delta, ids, num_segments=width)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,t,width", [(4, 256, 128), (3, 301, 128),
                                       (5, 97, 11)])
def test_gemm_scatter_batched_matches_per_slice(rng, b, t, width):
    """The batched form (one batched GEMM per chunk — the vocab-sub-block
    LDA scatter) is bitwise the per-slice unbatched scatter."""
    ids = jnp.asarray(rng.integers(0, width, (b, t)), jnp.int32)
    delta = jnp.asarray(rng.integers(-1, 2, (b, t, 6)), jnp.float32)
    got = lane_pack.gemm_scatter(ids, delta, width, chunk=64)
    assert got.shape == (b, width, 6)
    for i in range(b):
        want = lane_pack.gemm_scatter(ids[i], delta[i], width, chunk=64)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))


def test_gemm_scatter_f32_policy_for_real_valued_deltas(rng):
    """policy='f32' (the densify/CVB0 route): arbitrary real deltas, f32
    one-hot GEMM — per-cell sums agree with segment_sum to float tolerance
    (the two reduce in different orders)."""
    ids = jnp.asarray(rng.integers(0, 40, 500), jnp.int32)
    delta = jnp.asarray(rng.standard_normal((500, 5)), jnp.float32)
    got = lane_pack.gemm_scatter(ids, delta, 40, policy="f32")
    want = jax.ops.segment_sum(delta, ids, num_segments=40)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_gemm_scatter_policy_checks(rng):
    ids = jnp.asarray(rng.integers(0, 8, 16), jnp.int32)
    ok = jnp.ones((16, 2), jnp.float32)
    with pytest.raises(TypeError, match="exact_pm1"):
        # an int delta cannot have been produced under the ±1/0 f32/bf16
        # contract (f64 would be the other offender, but x64-off silently
        # downcasts it before the check can see it)
        lane_pack.gemm_scatter(ids, ok.astype(jnp.int32), 8)
    with pytest.raises(ValueError, match="policy"):
        lane_pack.gemm_scatter(ids, ok, 8, policy="fast_and_wrong")
    with pytest.raises(ValueError, match="trailing K"):
        lane_pack.gemm_scatter(ids, jnp.ones((16,), jnp.float32), 8)
    with pytest.raises(ValueError, match="token axes"):
        lane_pack.gemm_scatter(ids, jnp.ones((15, 2), jnp.float32), 8)


# --------------------------------------------------------------------------- #
# densify_rows
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("b,m,width", [(64, 8, 128), (300, 10, 96),
                                       (31, 7, 13)])
def test_densify_rows_matches_numpy(rng, b, m, width):
    idx = rng.integers(0, width, (b, m))
    vals = rng.standard_normal((b, m)).astype(np.float32)
    want = np.zeros((b, width), np.float32)
    np.add.at(want, (np.arange(b)[:, None], idx), vals)
    got = lane_pack.densify_rows(jnp.asarray(idx), jnp.asarray(vals), width)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------- #
# padding helpers
# --------------------------------------------------------------------------- #

def test_round_up_and_lane_target():
    assert lane_pack.round_up(100, 128) == 128
    assert lane_pack.round_up(128, 128) == 128
    assert lane_pack.round_up(129, 128) == 256
    assert lane_pack.round_up(0, 8) == 8          # never zero-sized
    # lane multiple that still splits over W workers
    assert lane_pack.lane_target(100, divisor=8) == 128
    assert lane_pack.lane_target(100, divisor=3) == 384   # lcm(128, 3)
    assert lane_pack.lane_target(129, divisor=8) == 256
    with pytest.raises(ValueError):
        lane_pack.round_up(4, 0)
    with pytest.raises(ValueError):
        lane_pack.lane_target(4, divisor=-1)


def test_pad_rows_cols_and_mask(rng):
    a = jnp.asarray(rng.standard_normal((10, 100)), jnp.float32)
    p = lane_pack.pad_rows(a, 16)
    assert p.shape == (16, 100) and np.all(np.asarray(p[10:]) == 0)
    assert lane_pack.pad_rows(a, 10) is a          # no-op, no copy
    q = lane_pack.pad_cols(a, 128)
    assert q.shape == (10, 128) and np.all(np.asarray(q[:, 100:]) == 0)
    assert lane_pack.pad_cols(a, 100) is a
    with pytest.raises(ValueError):
        lane_pack.pad_rows(a, 9)
    s = lane_pack.mask_phantom_cols(a, 60)
    assert np.all(np.isinf(np.asarray(s)[:, 60:]))
    np.testing.assert_array_equal(np.asarray(s)[:, :60], np.asarray(a)[:, :60])
    assert lane_pack.mask_phantom_cols(a, 100) is a


def test_scatter_chunk_budget_and_divisors():
    # divisor near the budget is preferred (no per-call pad concat)
    assert 1000 % lane_pack.scatter_chunk(1000, 64) == 0
    # large prime token count: falls back to the budget size
    c = lane_pack.scatter_chunk(1000003, 8192)
    assert c == (64 * 1024 * 1024) // (2 * 8192)
    # batch multiplies the transient: chunk shrinks accordingly (prime
    # token count so the divisor preference cannot kick in)
    assert (lane_pack.scatter_chunk(1000003, 128, batch=64)
            == (64 * 1024 * 1024) // (2 * 128 * 64))
    # ... and with a composite count, a nearby divisor wins instead
    assert 10**9 % lane_pack.scatter_chunk(10**9, 128, batch=64) == 0
    assert lane_pack.scatter_chunk(0, 128) == 1


def test_sub_block_split():
    slots = jnp.asarray([0, 127, 128, 300], jnp.int32)
    sub, within = lane_pack.sub_block_split(slots)
    np.testing.assert_array_equal(np.asarray(sub), [0, 0, 1, 2])
    np.testing.assert_array_equal(np.asarray(within), [0, 127, 0, 44])


# --------------------------------------------------------------------------- #
# call-site parity: the engine IS the implementation behind all three users
# --------------------------------------------------------------------------- #

def test_lda_subblock_ns1_is_bitwise_the_flat_layout(session):
    """vocab_sub_block == vpb (NS=1): identical token layout and chunk, so
    the batched engine path must reproduce the flat gemm_scatter trajectory
    BITWISE — the engine-vs-inline equivalence proof at the model level."""
    docs = datagen.lda_corpus(num_docs=64, vocab=96, num_topics=4,
                              doc_len=24, seed=6)
    cfg = lda.LDAConfig(num_topics=4, vocab=96, epochs=6,
                        wt_access="gemm_scatter")
    base = lda.LDA(session, cfg).fit(docs, seed=3)
    sub = lda.LDA(session, dataclasses.replace(
        cfg, vocab_sub_block=12)).fit(docs, seed=3)   # vpb = 96/8 = 12
    for a, b in zip(base, sub):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lda_subblock_multi_sub_converges_and_conserves_counts(session):
    """NS > 1 re-orders tokens (different draws, statistically equivalent
    chain): counts stay exactly conserved and the likelihood improves."""
    docs = datagen.lda_corpus(num_docs=64, vocab=96, num_topics=4,
                              doc_len=24, seed=6)
    model = lda.LDA(session, lda.LDAConfig(
        num_topics=4, vocab=96, epochs=15, wt_access="gemm_scatter",
        vocab_sub_block=4))                           # vpb=12 -> NS=3
    dt, wt, ll = model.fit(docs, seed=3)
    assert model.last_layout_stats["sub_blocks_per_block"] == 3
    assert np.isclose(dt.sum(), docs.size, atol=1e-2)
    assert np.isclose(wt.sum(), docs.size, atol=1e-2)
    assert np.all(np.isfinite(ll)) and ll[-1] > ll[0]


def test_lda_subblock_config_validation(session):
    with pytest.raises(ValueError, match="vocab_sub_block"):
        lda.LDA(session, lda.LDAConfig(method="cvb0", vocab_sub_block=128))
    with pytest.raises(ValueError, match="vocab_sub_block"):
        lda.LDA(session, lda.LDAConfig(wt_access="gather",
                                       vocab_sub_block=128))


def test_kmeans_lane_pad_matches_unpadded_trajectory(session):
    """128-lane padding (phantom centroids masked, zero feature columns) is
    a layout change, not a math change: same trajectory as lane_pad=False
    and as the numpy reference."""
    pts = datagen.dense_points(1000, 100, seed=7, num_clusters=10)
    cen0 = datagen.initial_centroids(pts, 10, seed=3)
    outs = {}
    for lp in (True, False):
        cfg = km.KMeansConfig(10, 100, 8, "regroupallgather", lane_pad=lp)
        cen, costs = km.KMeans(session, cfg).fit(pts, cen0)
        assert cen.shape == (10, 100)
        outs[lp] = (np.asarray(cen), np.asarray(costs))
    np.testing.assert_allclose(outs[True][0], outs[False][0],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs[True][1], outs[False][1], rtol=1e-5)
    ref = km.numpy_reference(pts.astype(np.float64),
                             cen0.astype(np.float64), 8)
    np.testing.assert_allclose(outs[True][0], ref, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("comm", km.COMM_VARIANTS)
def test_kmeans_lane_pad_all_variants_agree(session, comm):
    """Cross-variant bit-identity survives lane padding (every variant pads
    the same way, phantoms average to zero everywhere)."""
    pts = datagen.dense_points(400, 17, seed=11, num_clusters=5)
    cen0 = datagen.initial_centroids(pts, 5, seed=5)
    cfg = km.KMeansConfig(5, 17, 5, comm, lane_pad=True)
    cen, _ = km.KMeans(session, cfg).fit(pts, cen0)
    base_cfg = km.KMeansConfig(5, 17, 5, "regroupallgather", lane_pad=True)
    base, _ = km.KMeans(session, base_cfg).fit(pts, cen0)
    np.testing.assert_allclose(np.asarray(cen), np.asarray(base),
                               rtol=1e-5, atol=1e-6, err_msg=comm)


def test_partial_sums_counts_valid_k_masks_phantoms(rng):
    """The E-step with a lane-padded centroid table (+ valid_k) returns the
    unpadded stats exactly, phantom rows all-zero."""
    x = jnp.asarray(rng.standard_normal((256, 100)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((10, 100)), jnp.float32)
    s_ref, n_ref, cost_ref = distance.partial_sums_counts(x, c)
    # phantom rows are ZERO — without masking they'd WIN points (score 0
    # beats positive scores), which is exactly what valid_k prevents
    c_pad = lane_pack.pad_rows(c, 128)
    s, n, cost = distance.partial_sums_counts(x, c_pad, valid_k=10)
    # counts are exact integers; sums agree to float tolerance (the wider
    # output lets XLA re-tile the N-reduction — ulp-level differences)
    np.testing.assert_allclose(np.asarray(s[:10]), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(n[:10]), np.asarray(n_ref))
    assert np.all(np.asarray(s[10:]) == 0) and np.all(np.asarray(n[10:]) == 0)
    np.testing.assert_allclose(float(cost), float(cost_ref), rtol=1e-6)
    # feature padding is an exact no-op
    x_pad = lane_pack.pad_cols(x, 128)
    c_pad2 = lane_pack.pad_cols(c_pad, 128)
    s2, n2, cost2 = distance.partial_sums_counts(x_pad, c_pad2, valid_k=10)
    np.testing.assert_allclose(np.asarray(s2[:10, :100]), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(n2[:10]), np.asarray(n_ref))
    assert np.all(np.asarray(s2[:, 100:]) == 0)


def test_sparse_kmeans_densify_rides_engine(session, rng):
    """CSR K-means 'densify' (now on lane_pack.densify_rows) still matches
    the dense trajectory on the equivalent matrix."""
    n, d, kk = 96, 24, 4
    dense = (rng.random((n, d)) * (rng.random((n, d)) < 0.3)).astype(
        np.float32)
    rows, cols = np.nonzero(dense)
    vals = dense[rows, cols]
    cen0 = dense[:kk].copy()
    model = sparse.SparseKMeans(session, sparse.SparseKMeansConfig(
        kk, d, 5, strategy="densify"))
    cen_sp, _ = model.fit(rows, cols, vals, n, cen0)
    ref = km.numpy_reference(dense.astype(np.float64),
                             cen0.astype(np.float64), 5)
    np.testing.assert_allclose(cen_sp, ref, rtol=1e-3, atol=1e-4)


# --------------------------------------------------------------------------- #
# kernel dispatch: the backend and the shape, nothing from outside the program
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("predicate,shape,old_variable", [
    (pallas_kernels.use_flash_pallas, (8192,), "HARP_FLASH_PALLAS"),
    (pallas_kernels.use_flash_head_pack, (8, 64, 64), "HARP_FLASH_HEADPACK"),
    (pallas_kernels.use_spd_solve_pallas, (100,), "HARP_ALS_PALLAS"),
    (pallas_kernels.use_dense_mf_pallas, (10752, 8960, 104),
     "HARP_DENSE_PALLAS"),
    (ring_dma.use_ring_dma, (), "HARP_RING_DMA"),
], ids=["use_flash_pallas", "use_flash_head_pack", "use_spd_solve_pallas",
        "use_dense_mf_pallas", "use_ring_dma"])
def test_kernel_dispatch_ignores_the_environment(monkeypatch, predicate,
                                                 shape, old_variable):
    """Each predicate picks its kernel on the TPU at a shape the kernel
    fits, whatever the process environment says; the four that test the
    backend pick XLA's path off the TPU."""
    monkeypatch.setenv(old_variable, "0")
    if predicate is not pallas_kernels.use_flash_head_pack:
        assert not predicate(*shape)                          # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert predicate(*shape)
