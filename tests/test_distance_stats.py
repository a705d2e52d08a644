"""The K-means stats product (ops/distance ``onehot_stats``, ISSUE 32): where
the points are stored with a spare lane the product counts its own rows;
elsewhere the one-hot is reduced a second time. The choice is by shape, and the counters say which way a traced body
went. On the CPU, so nothing here is a time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harp_tpu.io import datagen
from harp_tpu.models import kmeans as km
from harp_tpu.ops import distance, lane_pack
from harp_tpu.utils import metrics

FOLDED = "kmeans.stats.counts_folded"
REDUCED = "kmeans.stats.counts_reduced"


@pytest.fixture()
def counted():
    """``counted()``: (folded, reduced) traced bodies since the test began."""
    before = {name: metrics.DEFAULT.counters[name]
              for name in (FOLDED, REDUCED)}
    return lambda: tuple(int(metrics.DEFAULT.counters[name] - before[name])
                         for name in (FOLDED, REDUCED))


def _block(rng, n=4096, k=10, d=100):
    """Points stored lane-padded to 128 and centroids in their own width;
    ~400 points a centroid, past what a bf16 sum of ones could hold."""
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)
    return lane_pack.pad_cols(x, 128), lane_pack.pad_cols(c, 128)


@pytest.mark.parametrize("valid_k", [None, 10])
@pytest.mark.parametrize("compute_dtype", [None, jnp.bfloat16])
def test_the_fold_returns_the_plain_forms_stats(rng, counted, compute_dtype,
                                                valid_k):
    x, c = _block(rng)
    if valid_k is not None:
        c = lane_pack.pad_rows(c, 128)
    plain = distance.partial_sums_counts(x, c, compute_dtype, valid_k=valid_k)
    assert counted() == (0, 1)
    sums, counts, cost = distance.partial_sums_counts(
        x, c, compute_dtype, valid_k=valid_k, valid_d=100)
    assert counted() == (1, 1)
    # the counts are the same whole numbers, the cost never saw the fold,
    # and a column of the product does not depend on its neighbours
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(plain[1]))
    assert float(jnp.sum(counts)) == x.shape[0]
    assert float(jnp.max(counts)) > 256
    assert float(cost) == float(plain[2])
    np.testing.assert_allclose(np.asarray(sums), np.asarray(plain[0]),
                               rtol=1e-6, atol=1e-6)
    # the column that carried the 1 leaves as the padding it is
    assert np.all(np.asarray(sums[:, 100:]) == 0)
    assert sums.dtype == counts.dtype == jnp.float32
    if valid_k is not None:
        assert np.all(np.asarray(sums[valid_k:]) == 0)
        assert np.all(np.asarray(counts[valid_k:]) == 0)


@pytest.mark.parametrize("rows, stored_d, valid_d, fold", [
    (256, 128, 100, True),
    (256, 128, 127, True),          # one spare lane is enough
    (256, 100, 100, False),         # lane_pad=False: nothing spare
    (256, 128, 128, False),         # a width that fills its lanes
    (256, 128, None, False),        # the caller states no logical width
    (1 << 24, 128, 100, True),      # 2**24 ones still sum exactly
    ((1 << 24) + 1, 128, 100, False),
    (17_000_000, 128, 100, False),
])
def test_the_fold_is_chosen_by_shape(rows, stored_d, valid_d, fold):
    assert distance.counts_fold(rows, stored_d, valid_d) is fold


@pytest.mark.parametrize("stored_d, valid_d, max_rows", [
    (100, 100, None),       # no spare lane
    (128, None, None),      # no logical width given
    (128, 100, 4095),       # a block of more rows than a float32 counts
])
def test_without_the_fold_the_onehot_is_reduced(rng, counted, monkeypatch,
                                                stored_d, valid_d, max_rows):
    x, c = _block(rng)
    x, c = x[:, :stored_d], c[:, :stored_d]
    plain = distance.partial_sums_counts(x, c)
    if max_rows is not None:
        monkeypatch.setattr(distance, "FOLD_MAX_ROWS", max_rows)
    got = distance.partial_sums_counts(x, c, valid_d=valid_d)
    assert counted() == (0, 2)
    for a, b in zip(got, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("ambient", [None, "highest", "BF16_BF16_F32_X3"])
def test_the_stats_product_pins_no_precision(rng, ambient):
    """Both products are left to the ambient ``jax_default_matmul_precision``
    whole: the TPU backend runs a ``{default,highest}`` pair at the passes of
    ``{highest,highest}`` (PERF.md, Findings, PR 32), so nothing is pinned."""
    x, c = _block(rng, n=64)
    with jax.default_matmul_precision(ambient):
        jaxpr = jax.make_jaxpr(lambda x, c: distance.partial_sums_counts(
            x, c, valid_d=100))(x, c)
    scores, stats = [eqn.params["precision"] for eqn in jaxpr.jaxpr.eqns
                     if eqn.primitive.name == "dot_general"]
    assert stats == scores
    assert (scores is None) == (ambient is None)


@pytest.mark.parametrize("comm", ["regroupallgather", "rotation"])
@pytest.mark.parametrize("lane_pad", [True, False])
def test_every_variants_traced_body_says_where_it_counts(session, counted,
                                                          comm, lane_pad):
    """The rotation variant's stats go through the same product as the
    E-step's: lane-padded points fold, unpadded ones reduce, and the
    centroids are the numpy reference's either way."""
    pts = datagen.dense_points(400, 17, seed=11, num_clusters=5)
    cen0 = datagen.initial_centroids(pts, 5, seed=5)
    cfg = km.KMeansConfig(5, 17, 4, comm, lane_pad=lane_pad)
    cen, _ = km.KMeans(session, cfg).fit(pts, cen0)
    assert counted() == ((1, 0) if lane_pad else (0, 1))
    ref = km.numpy_reference(pts.astype(np.float64),
                             cen0.astype(np.float64), 4)
    np.testing.assert_allclose(np.asarray(cen), ref, rtol=1e-4, atol=1e-5)
