"""Pairwise-distance and cluster-assignment kernels.

Reference parity: the compute hot spot of every Harp K-means variant — CenCalcTask
(ml/java kmeans regroupallgather, KMeansCollectiveMapper.java:128-144) computed
point→centroid Euclidean distances and partial centroid sums across Xeon threads;
the DAAL path used AVX-512 kernels (daal_kmeans step1 local:164).

TPU-native: both the distance matrix and the partial-sum accumulation are expressed
as matmuls so the MXU does all the FLOPs:

  * ``-2 * X @ C^T`` (N×D @ D×K) dominates the distance computation;
  * partial sums = ``onehot(assign)^T @ X`` (K×N @ N×D) — the scatter-add that Harp
    did with per-thread arrays becomes a second matmul.

A fused pallas kernel (ops/pallas_kernels.py) avoids materializing the N×K distance
matrix in HBM for large N·K; this module is the XLA path and the reference
implementation.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from harp_tpu.ops import lane_pack
from harp_tpu.telemetry.scopes import scoped


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


def partial_sums_counts(
    x: jax.Array, c: jax.Array, compute_dtype=None, x_sq_sum=None,
    valid_k: Optional[int] = None,
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
    """
    # argmin over ‖x−c‖² == argmin over (‖c‖² − 2x·c): the per-row ‖x‖² term is
    # constant and never needs materializing — the E-step reads x exactly
    # twice (two MXU matmuls) and touches no (N, D)-sized temporaries.
    scores = pairwise_scores(x, c, compute_dtype)         # (N, K)
    # two kernels, interleaved as the equations always were: the score GEMM
    # with its mask, argmin and min, and the one-hot stats product
    if valid_k is not None:
        with jax.named_scope("kmeans.scores"):
            scores = lane_pack.mask_phantom_cols(scores, valid_k)
    with jax.named_scope("kmeans.stats"):
        xm = x if compute_dtype is None else x.astype(compute_dtype)
    with jax.named_scope("kmeans.scores"):
        assign = jnp.argmin(scores, axis=1)
        min_s = jnp.min(scores, axis=1)
    with jax.named_scope("kmeans.stats"):
        oh_dtype = x.dtype if compute_dtype is None else compute_dtype
        onehot = jax.nn.one_hot(assign, c.shape[0], dtype=oh_dtype)  # (N, K)
        sums = jax.lax.dot_general(                              # (K, D) on MXU
            onehot, xm, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        counts = jnp.sum(onehot.astype(jnp.float32), axis=0)
    if x_sq_sum is None:
        with jax.named_scope("kmeans.norms"):
            xf = x.astype(jnp.float32)
            x_sq_sum = jnp.sum(xf * xf)
    with jax.named_scope("kmeans.scores"):
        return sums, counts, jnp.sum(min_s) + x_sq_sum
