"""The fused K-means E-step (ops/kmeans_kernels ``kmeans_estep``, ISSUE 35),
in interpret mode against its XLA twin ``distance.partial_sums_counts``: the
same sums, counts and cost in the precision the ambient setting states, the
tie rule of ``jnp.argmin``, phantoms and a last tile's overhang that add
nothing, the predicate's shapes, the counters that say which E-step a traced
body runs, and ``KMeans`` with the predicate patched on. On the CPU, so
nothing here is a time.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harp_tpu.io import datagen
from harp_tpu.models import kmeans as km
from harp_tpu.ops import distance, kmeans_kernels as kk, lane_pack
from harp_tpu.utils import metrics

PALLAS = "kmeans.estep.pallas"
XLA = "kmeans.estep.xla"


@pytest.fixture()
def counted():
    """``counted()``: (pallas, xla) traced E-step bodies since the test began."""
    before = {name: metrics.DEFAULT.counters[name] for name in (PALLAS, XLA)}
    return lambda: tuple(int(metrics.DEFAULT.counters[name] - before[name])
                         for name in (PALLAS, XLA))


def _block(rng, n=2048, k=10, d=100, stored_d=128, k_pad=128):
    """Points stored lane-padded, centroids padded to whole lane tiles; ~200
    points a centroid."""
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)
    return (lane_pack.pad_cols(x, stored_d),
            lane_pack.pad_rows(lane_pack.pad_cols(c, stored_d), k_pad))


def _both(x, c, compute_dtype, valid_k, valid_d, tiles, ambient="highest"):
    """``(twin's, kernel's)`` (sums, counts, cost) under one ambient setting.
    Jitted: the CPU's eager dot refuses bfloat16 x bfloat16 -> float32."""
    with jax.default_matmul_precision(ambient):
        twin = jax.jit(partial(
            distance.partial_sums_counts, compute_dtype=compute_dtype,
            valid_k=valid_k, valid_d=valid_d))(x, c)
        fused = jax.jit(partial(
            kk.estep_pallas, compute_dtype=compute_dtype, valid_k=valid_k,
            valid_d=valid_d, tiles=tiles, interpret=True))(x, c)
    return twin, fused


@pytest.mark.parametrize("k, valid_k", [(10, 10), (100, 100), (128, 128),
                                        (128, None)])
@pytest.mark.parametrize("valid_d", [100, None], ids=["spare", "nospare"])
@pytest.mark.parametrize("compute_dtype", [None, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_kernel_returns_the_twins_stats(rng, compute_dtype, valid_d, k,
                                            valid_k):
    """Phantom rows take no part: the kernel works on the live rows' whole
    sublane tiles (16 of 128 at 10 centroids, 112 at 100, all at 128)."""
    x, c = _block(rng, k=k)
    if compute_dtype is not None:
        x = x.astype(compute_dtype)         # as KMeans.prepare stores them
    twin, fused = _both(x, c, compute_dtype, valid_k, valid_d, (1024, 256))
    np.testing.assert_array_equal(np.asarray(fused[1]), np.asarray(twin[1]))
    np.testing.assert_allclose(np.asarray(fused[0]), np.asarray(twin[0]),
                               rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(float(fused[2]), float(twin[2]), rtol=2e-6)
    assert fused[0].dtype == fused[1].dtype == fused[2].dtype == jnp.float32
    assert fused[0].shape == (128, 128) and fused[1].shape == (128,)


def test_three_terms_are_the_float32_product_and_one_term_is_not(rng):
    """At ``highest`` the kernel's scores separate centroids a bfloat16
    product cannot: every point sits 1e-3 nearer its own of two close
    centroids. At the ambient default (one term) some flip."""
    base = rng.standard_normal(100).astype(np.float32)
    c = np.stack([base, base + 1e-3 * rng.standard_normal(100)])
    labels = rng.integers(0, 2, 2048)
    x = (c[labels] + 1e-5 * rng.standard_normal((2048, 100))).astype(
        np.float32)
    x = lane_pack.pad_cols(jnp.asarray(x), 128)
    c = lane_pack.pad_rows(lane_pack.pad_cols(jnp.asarray(c, jnp.float32),
                                              128), 128)
    want = np.bincount(labels, minlength=2)
    run = jax.jit(partial(kk.estep_pallas, valid_k=2, valid_d=100,
                          tiles=(1024, 512), interpret=True))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_array_equal(np.asarray(run(x, c)[1])[:2], want)
    with jax.default_matmul_precision("default"):
        one_term = jax.jit(partial(kk.estep_pallas, valid_k=2, valid_d=100,
                                   tiles=(1024, 512), interpret=True))(x, c)
    assert np.abs(np.asarray(one_term[1])[:2] - want).sum() > 0


@pytest.mark.parametrize("ambient, terms, pairs", [
    ("highest", 3, 6), ("float32", 3, 6), ("high", 2, 3), ("default", 1, 1),
    (None, 1, 1), ("BF16_BF16_F32_X3", None, 0)])
def test_the_terms_follow_the_ambient_precision(ambient, terms, pairs):
    with jax.default_matmul_precision(ambient):
        assert kk.ambient_terms() == terms
    if terms:
        assert len(kk._pairs(terms)) == pairs
        assert kk._pairs(terms)[-1] == (0, 0)       # the largest last


def test_the_split_is_exact_at_three_terms(rng):
    x = jnp.asarray(rng.standard_normal((64, 128)) * 1e3, jnp.float32)
    terms = kk.split_terms(x, 3)
    assert all(t.dtype == jnp.bfloat16 for t in terms)
    back = sum(np.asarray(t, np.float64) for t in terms)
    np.testing.assert_array_equal(back, np.asarray(x, np.float64))
    assert kk.split_terms(x.astype(jnp.bfloat16), 3)[0].dtype == jnp.bfloat16
    assert len(kk.split_terms(x.astype(jnp.bfloat16), 3)) == 1


@pytest.mark.parametrize("compute_dtype", [None, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_no_point_goes_to_a_phantom(rng, compute_dtype):
    """Phantom rows hold zeros, nearer the origin than any centroid: without
    the mask points near the origin would go to them."""
    x, c = _block(rng, k=10)
    x = x * 0.01
    (_, twin_counts, _), (sums, counts, _) = _both(
        x, c, compute_dtype, 10, 100, (1024, 256))
    assert float(jnp.sum(counts[:10])) == x.shape[0]
    assert np.all(np.asarray(counts[10:]) == 0)
    assert np.all(np.asarray(sums[10:]) == 0)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(twin_counts))


def test_an_exact_tie_goes_to_the_lower_index(rng):
    x, c = _block(rng, k=10)
    c = c.at[7].set(c[3])                   # centroid 7 IS centroid 3
    c = c.at[5].set(c[1])
    (_, twin_counts, _), (_, counts, _) = _both(
        x, c, None, 10, 100, (1024, 256))
    assert float(counts[7]) == 0 and float(counts[5]) == 0
    assert float(counts[3]) > 0 and float(counts[1]) > 0
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(twin_counts))


@pytest.mark.parametrize("rows, tiles", [
    (1000, (512, 256)), (777, (512, 128)), (300, (1024, 1024)),
    (1025, (1024, 512))])
@pytest.mark.parametrize("valid_d", [100, None], ids=["spare", "nospare"])
def test_a_last_tiles_overhang_adds_nothing(rng, rows, tiles, valid_d):
    x, c = _block(rng, n=rows)
    twin, fused = _both(x, c, None, 10, valid_d, tiles)
    assert float(jnp.sum(fused[1])) == rows
    np.testing.assert_array_equal(np.asarray(fused[1]), np.asarray(twin[1]))
    np.testing.assert_allclose(np.asarray(fused[0]), np.asarray(twin[0]),
                               rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(float(fused[2]), float(twin[2]), rtol=2e-6)


def test_the_counts_are_exact_through_the_spare_lane(rng):
    """~400 points a centroid, past what a bfloat16 sum of ones could hold;
    the column that carried the 1 leaves as the padding it is."""
    x, c = _block(rng, n=4096)
    x = x.astype(jnp.bfloat16)
    twin, (sums, counts, _) = _both(x, c, jnp.bfloat16, 10, 100, (2048, 512))
    counts = np.asarray(counts)
    assert counts.sum() == 4096 and counts.max() > 256
    assert np.all(counts == np.round(counts))
    np.testing.assert_array_equal(counts, np.asarray(twin[1]))
    assert np.all(np.asarray(sums[:, 100:]) == 0)


@pytest.mark.parametrize("rows, shape, tile, chunk, ragged", [
    (8_000_000, (128, 128, 4), 12_800, 6_400, False),   # the cell: no mask
    (8_000_000, (128, 128, 2), 32_000, 6_400, False),   # its control
    (2_000_000, (128, 128, 4), 16_000, 3_200, False),
    (65_536, (128, 128, 4), 16_384, 4_096, False),
    (24_576, (128, 128, 4), 12_288, 6_144, False),
    (1_000_003, (128, 128, 4), 16_384, 4_096, True),    # nothing divides
    (1_500, (128, 128, 4), 1_536, 1_536, True),         # one tile
    (4_000_000, (256, 128, 4), 6_400, 3_200, False),    # wider points
    (1_000_000, (128, 1024, 4), 16_384, 1_024, True),   # more centroids
    (200_000, (1024, 1024, 4), 2_048, 512, True),
    (100_000, (32_768, 128, 4), 0, 0, False)])          # no tile fits
def test_the_tiles_divide_the_block_where_they_can(rows, shape, tile, chunk,
                                                   ragged):
    assert kk.estep_tiles(rows, *shape) == (tile, chunk)
    if tile:
        assert tile % chunk == 0 and chunk % 128 == 0
        assert chunk <= kk.MAX_CHUNK
        assert tile * shape[0] * shape[2] <= kk.TILE_BYTES
        assert kk.estep_vmem_bytes(tile, chunk, *shape) <= kk.VMEM_LIMIT
        assert (rows % tile != 0) == ragged


@pytest.mark.parametrize("rows, stored_d, k_pad, dtype, ambient, on", [
    (8_000_000, 128, 128, jnp.float32, "highest", True),    # the cell
    (8_000_000, 128, 128, jnp.bfloat16, "highest", True),   # its control
    (1_000_000, 256, 384, jnp.float32, None, True),
    (8_000_000, 100, 128, jnp.float32, "highest", False),   # lane_pad=False
    (8_000_000, 128, 104, jnp.float32, "highest", False),   # lane_pad=False
    (8_000_000, 128, 128, jnp.float16, "highest", False),
    (512, 128, 128, jnp.float32, "highest", False),         # nothing to win
    ((1 << 24) + 8, 128, 128, jnp.float32, "highest", False),
    (1_000_000, 32_768, 128, jnp.float32, "highest", False),  # no tile fits
    (8_000_000, 128, 128, jnp.float32, "BF16_BF16_F32_X3", False)])
def test_the_predicate_reads_backend_and_shape(monkeypatch, rows, stored_d,
                                               k_pad, dtype, ambient, on):
    with jax.default_matmul_precision(ambient):
        assert not kk.use_kmeans_estep_pallas(rows, stored_d, k_pad, dtype)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert kk.use_kmeans_estep_pallas(rows, stored_d, k_pad, dtype) == on


def test_the_kernel_refuses_shapes_it_cannot_tile(rng):
    x, c = _block(rng, n=256)
    with pytest.raises(ValueError, match="inconsistent shapes"):
        kk.estep_pallas(x[:, :100], c[:, :100], interpret=True)
    with pytest.raises(ValueError, match="inconsistent shapes"):
        kk.estep_pallas(x, c[:100], interpret=True)
    with pytest.raises(ValueError, match="tiling constraints"):
        kk.estep_pallas(x, c, tiles=(256, 100), interpret=True)


# -- the model ---------------------------------------------------------------- #

K, D, N, ITERS = 10, 100, 1024, 6
ESTEP_VARIANTS = ("regroupallgather", "allreduce", "pushpull", "bcastreduce")


@pytest.fixture(scope="module")
def data():
    pts = datagen.dense_points(N, D, seed=7, num_clusters=K)
    return pts, datagen.initial_centroids(pts, K, seed=3)


def _fit(session, data, comm, compute_dtype="float32", lane_pad=True):
    model = km.KMeans(session, km.KMeansConfig(
        K, D, ITERS, comm, compute_dtype=compute_dtype, lane_pad=lane_pad))
    # float32 products on both sides: the CPU's default IS float32, the
    # kernel issues the terms the ambient setting states
    with jax.default_matmul_precision("highest"):
        cen, costs = model.fit_prepared(*model.prepare(*data))
    return np.asarray(cen), np.asarray(costs)


@pytest.mark.parametrize("comm", ESTEP_VARIANTS)
def test_fit_prepared_with_the_kernel_equals_the_twin(
        session, data, monkeypatch, counted, comm):
    twin_cen, twin_costs = _fit(session, data, comm)
    assert counted() == (0, 1)
    monkeypatch.setattr(kk, "use_kmeans_estep_pallas", lambda *a: True)
    cen, costs = _fit(session, data, comm)
    assert counted() == (1, 1)
    np.testing.assert_allclose(cen, twin_cen, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(costs, twin_costs, rtol=1e-5)


def test_the_bfloat16_path_takes_the_kernel_too(session, data, monkeypatch,
                                                counted):
    twin_cen, twin_costs = _fit(session, data, "allreduce", "bfloat16")
    monkeypatch.setattr(kk, "use_kmeans_estep_pallas", lambda *a: True)
    cen, costs = _fit(session, data, "allreduce", "bfloat16")
    assert counted() == (1, 1)
    np.testing.assert_allclose(cen, twin_cen, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(costs, twin_costs, rtol=1e-4)


def test_rotation_keeps_the_xla_products(session, data, monkeypatch, counted):
    monkeypatch.setattr(kk, "use_kmeans_estep_pallas", lambda *a: True)
    cen, _ = _fit(session, data, "rotation")
    assert counted() == (0, 0)              # it never goes through estep
    np.testing.assert_allclose(cen, _fit(session, data, "allreduce")[0],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("lane_pad, compute_dtype", [
    (True, "float32"), (False, "float32"), (True, "float16")])
def test_on_the_cpu_and_at_odd_shapes_the_twin_runs(session, data, counted,
                                                    monkeypatch, lane_pad,
                                                    compute_dtype):
    if not lane_pad or compute_dtype == "float16":
        # even on a TPU: stored widths 100 x 16, or operands the kernel has
        # no terms for
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _fit(session, data, "regroupallgather", compute_dtype, lane_pad)
    assert counted() == (0, 1)


def test_the_kernel_path_hoists_no_norms(session, data, monkeypatch):
    """With the kernel the step holds no ``kmeans.norms`` reduction: the
    tile is in VMEM anyway."""
    model = km.KMeans(session, km.KMeansConfig(K, D, ITERS))
    args = model.prepare(*data)
    assert "kmeans.norms" in model._fit.lower(*args).as_text(
        debug_info=True)
    monkeypatch.setattr(kk, "use_kmeans_estep_pallas", lambda *a: True)
    fused = km.KMeans(session, km.KMeansConfig(K, D, ITERS))
    text = fused._fit.lower(*args).as_text(debug_info=True)
    assert "kmeans.norms" not in text and "kmeans.estep" in text
