"""Pairwise-distance and cluster-assignment kernels.

Reference parity: the compute hot spot of every Harp K-means variant — CenCalcTask
(ml/java kmeans regroupallgather, KMeansCollectiveMapper.java:128-144) computed
point→centroid Euclidean distances and partial centroid sums across Xeon threads;
the DAAL path used AVX-512 kernels (daal_kmeans step1 local:164).

TPU-native: both the distance matrix and the partial-sum accumulation are expressed
as matmuls so the MXU does all the FLOPs:

  * ``-2 * X @ C^T`` (N×D @ D×K) dominates the distance computation;
  * partial sums = ``onehot(assign)^T @ X`` (K×N @ N×D) — the scatter-add that Harp
    did with per-thread arrays becomes a second matmul, which also counts the
    points of each centroid where the stored feature axis has a lane to spare
    (``onehot_stats``).

Here both are plain XLA: each product is one fusion that reads the points once
(the score product with its mask, argmin and min; the stats product with the
one-hot built in its operand), and the N×K matrices never reach HBM. This is
the TWIN: on TPU, where the stored shapes are whole 128-lane tiles, the E-step
of ``models/kmeans.py`` is one Pallas kernel that reads each tile of the
points once for both products (``ops/kmeans_kernels.py`` ``kmeans_estep``,
chosen by ``use_kmeans_estep_pallas`` by backend, stored shape and dtype);
``partial_sums_counts`` runs everywhere else (the CPU, ``lane_pad=False``,
odd shapes), in the rotation variant and the minibatch step, and is what the
kernel's tests compare against.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from harp_tpu.ops import lane_pack
from harp_tpu.telemetry.scopes import scoped
from harp_tpu.utils import metrics


def pairwise_sq_dist(x: jax.Array, c: jax.Array,
                     compute_dtype=None, precision=None) -> jax.Array:
    """Squared Euclidean distances (N, K) between rows of x (N, D) and c (K, D).

    ``compute_dtype=jnp.bfloat16`` runs the cross-term matmul in bf16 with f32
    accumulation — the MXU-native recipe; the squared-norm terms stay f32 so
    only the (well-conditioned) cross term loses mantissa. On v5e this halves
    the dominant (N, K) HBM traffic. ``precision=jax.lax.Precision.HIGHEST``
    keeps true-f32 cross terms on TPU (whose DEFAULT f32 matmul truncates to
    bf16) — needed when downstream math is precision-sensitive (MDS SMACOF),
    irrelevant for argmin-only uses (K-means).
    """
    xf = x.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    x2 = jnp.sum(xf * xf, axis=1, keepdims=True)          # (N, 1), f32 norms
    c2 = jnp.sum(cf * cf, axis=1)[None, :]                # (1, K)
    xm = x if compute_dtype is None else x.astype(compute_dtype)
    cm = c if compute_dtype is None else c.astype(compute_dtype)
    xc = jax.lax.dot_general(
        xm, cm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision)                              # (N, K)
    return x2 - 2.0 * xc + c2


@scoped("kmeans.scores")
def pairwise_scores(x: jax.Array, c: jax.Array,
                    compute_dtype=None) -> jax.Array:
    """Assignment scores ‖c‖² − 2x·c (N, K): same argmin ordering as
    ``pairwise_sq_dist`` (the per-row ‖x‖² offset is constant), one x-read
    cheaper. Used by every K-means variant so argmin tie-breaking is
    formulation-identical across them."""
    cf = c.astype(jnp.float32)
    c2 = jnp.sum(cf * cf, axis=1)[None, :]
    xm = x if compute_dtype is None else x.astype(compute_dtype)
    cm = c if compute_dtype is None else c.astype(compute_dtype)
    xc = jax.lax.dot_general(xm, cm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return c2 - 2.0 * xc


def assign_clusters(x: jax.Array, c: jax.Array) -> jax.Array:
    """Nearest-centroid assignment (N,) int32."""
    return jnp.argmin(pairwise_sq_dist(x, c), axis=1).astype(jnp.int32)


# float32 holds every whole number up to 2**24: a count summed as a column of
# the stats product is exact for a block of at most that many rows
FOLD_MAX_ROWS = 1 << 24


def counts_fold(rows: int, stored_d: int, valid_d: Optional[int]) -> bool:
    """Whether the stats product can count its own rows: the stored feature
    axis has a lane past the ``valid_d`` logical ones to carry the 1, and
    the block's (static) row count keeps a float32 sum of ones exact."""
    return (valid_d is not None and valid_d < stored_d
            and rows <= FOLD_MAX_ROWS)


@scoped("kmeans.stats")
def onehot_stats(
    x: jax.Array, assign: jax.Array, k: int, compute_dtype=None,
    valid_d: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-centroid sums (k, D) and counts (k,), both float32, of the rows of
    x (N, D) under ``assign`` (N,): ``onehot(assign)^T @ x``, one MXU product
    with the one-hot built in its operand.

    ``valid_d``: the logical feature count where x is stored lane-padded
    (ops/lane_pack). Where ``counts_fold`` holds, the first spare column
    carries a 1 into the product, whose own output column is then the
    count (float32 sums of 1.0, exact), and that column of the sums is set
    back to exact zero. Otherwise the one-hot is reduced a second time.
    """
    xm = x if compute_dtype is None else x.astype(compute_dtype)
    onehot = jax.nn.one_hot(assign, k, dtype=xm.dtype)            # (N, k)
    fold = counts_fold(x.shape[0], x.shape[1], valid_d)
    # runs when jax traces, only: where this program's E-step counts
    metrics.DEFAULT.count("kmeans.stats.counts_folded" if fold
                          else "kmeans.stats.counts_reduced")
    if fold:
        spare = jax.lax.broadcasted_iota(
            jnp.int32, (1, x.shape[1]), 1) == valid_d
        xm = jnp.where(spare, 1, xm)
    sums = jax.lax.dot_general(                                   # (k, D)
        onehot, xm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if fold:
        return jnp.where(spare, 0.0, sums), sums[:, valid_d]
    # a bf16 one-hot cannot hold a whole-number sum past 256: reduce in f32
    return sums, jnp.sum(onehot.astype(jnp.float32), axis=0)


def partial_sums_counts(
    x: jax.Array, c: jax.Array, compute_dtype=None, x_sq_sum=None,
    valid_k: Optional[int] = None, valid_d: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One K-means E-step on this worker's block.

    Returns (sums (K, D), counts (K,), sq_dist_sum scalar) — the LOCAL table payload
    that Harp's CenCalcTask + CenMergeTask produced per worker.

    ``compute_dtype=jnp.bfloat16``: both MXU matmuls and the (N, K) one-hot run
    in bf16 with f32 accumulation; the accumulated sums/counts stay f32, so the
    M-step averages keep full precision (assignment flips only where two
    centroids are within bf16 epsilon — empirically nil on clustered data).

    ``x_sq_sum``: precomputed Σ‖x‖² (scalar). Pass it when calling in a loop —
    it is iteration-invariant and hoisting it removes a full read of x.

    ``valid_k``: when the centroid table carries phantom lane-padding rows
    (ops/lane_pack: K padded to an MXU-lane multiple), rows >= valid_k are
    masked out of the argmin (+inf score columns) so no point can assign to
    padding; their sums/counts come out exactly zero.

    ``valid_d``: the logical feature count where x is stored lane-padded; it
    lets the stats product count (``onehot_stats``).
    """
    # argmin over ‖x−c‖² == argmin over (‖c‖² − 2x·c): the per-row ‖x‖² term is
    # constant and never needs materializing. Two products, each one read of
    # x: the score GEMM with its mask, argmin and min, and the one-hot stats
    # product, which also counts where x has a spare lane (else the one-hot
    # is reduced once more); no (N, D)-sized temporary
    scores = pairwise_scores(x, c, compute_dtype)         # (N, K)
    with jax.named_scope("kmeans.scores"):
        if valid_k is not None:
            scores = lane_pack.mask_phantom_cols(scores, valid_k)
        assign = jnp.argmin(scores, axis=1)
        min_s = jnp.min(scores, axis=1)
    sums, counts = onehot_stats(x, assign, c.shape[0], compute_dtype, valid_d)
    if x_sq_sum is None:
        with jax.named_scope("kmeans.norms"):
            xf = x.astype(jnp.float32)
            x_sq_sum = jnp.sum(xf * xf)
    with jax.named_scope("kmeans.scores"):
        return sums, counts, jnp.sum(min_s) + x_sq_sum
