"""CSR (sparse-input) analytics — the reference's *csr* component family.

Reference parity: daal_kmeans/allreducecsr (KmeansDaalCollectiveMapper.java:43,
loadCSRNumericTable :155 — Lloyd's on CSR input with an allreduce of the
centroid stats), daal_cov/csrdistri (CSR covariance), and daal_pca/corcsrdistr
(correlation-method PCA from CSR input). Those were distinct DAAL kernels
because MKL has separate sparse BLAS; here they are one shared layout plus two
device expressions.

TPU-native design — two different sparse strategies for the two access
patterns:

* **K-means E-step** (``sparse_kmeans_stats``): block-densify-GEMM by
  default — scatter-free densification (one-hot·value reduce, via the
  shared ``ops/lane_pack.densify_rows`` engine) of a (block, D) tile,
  then MXU GEMMs for scores and M-step sums; 13× the gather strategy on
  chip (docstring there). A ``gather`` strategy
  (cᵀ-row gathers + segment_sum, nnz-proportional compute) is kept for
  the very-sparse-very-wide regime. Per-row ‖x‖² is precomputed once
  (the dense path's hoisted Σ‖x‖², VERDICT r3 item 4's recipe).
* **Covariance/PCA gram** (``sparse_gram_stats``): the same blocked
  densify, with the MXU running (D, B)×(B, D) at matrix rates. The scan
  keeps peak memory at (block, D), never (N, D).

Layout: padded neighbor lists (``als.pad_csr_lists`` shape contract):
``idx/val/mask (n_pad, m)`` with rows padded to a worker multiple and columns
to the max row nnz. Zipf-skewed data should pre-balance rows across workers
(the ALS capped-chunk layout is the heavier-duty option; K-means points are
typically bounded-degree feature vectors, where max-nnz padding is tight).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu.collectives import lax_ops
from harp_tpu.ops import lane_pack, linalg
from harp_tpu.parallel.mesh import WORKERS
from harp_tpu.session import HarpSession


def csr_worker_layout(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                      num_rows: int, num_workers: int
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray]:
    """COO → padded per-row neighbor lists, rows padded to a worker multiple.

    Returns (idx (n_pad, m), val, mask, real (n_pad,)). Row order is
    preserved (row i of the output is data row i), so results align with
    the dense path on the same matrix. ``real`` flags true DATA rows —
    an all-zero data row is real (it counts toward n and may own a
    centroid assignment); only the worker-multiple pad rows are not.
    """
    from harp_tpu.models.als import pad_csr_lists

    rows = np.asarray(rows)
    cols = np.asarray(cols)
    vals = np.asarray(vals, np.float32)
    if rows.size and (rows.min() < 0 or rows.max() >= num_rows):
        raise ValueError(f"row ids must be in [0, {num_rows})")
    if cols.size and cols.min() < 0:
        # a negative id would silently clamp in device gathers / drop in
        # scatters — the same trap the dim upper-bound checks close
        raise ValueError(f"column ids must be nonnegative; got {cols.min()}")
    if rows.size:
        # duplicate (row, col) entries SUM — densification semantics, so
        # every consumer (scores, grams, x_sq) agrees with the dense path
        span = int(cols.max()) + 1
        key = rows.astype(np.int64) * span + cols.astype(np.int64)
        uniq, inv = np.unique(key, return_inverse=True)
        if len(uniq) < len(key):
            vsum = np.zeros(len(uniq), np.float32)
            np.add.at(vsum, inv, vals)
            rows = (uniq // span).astype(rows.dtype)
            cols = (uniq % span).astype(cols.dtype)
            vals = vsum
    idx, val, mask = pad_csr_lists(rows, cols, vals, num_rows, num_workers)
    real = (np.arange(idx.shape[0]) < num_rows).astype(np.float32)
    return idx, val, mask, real



def _pad_to_blocks(n_l: int, block: int, *arrays):
    """Round the leading axis up to a block multiple (zero padding) and
    return (b, nb, padded arrays). Zero rows are inert in every consumer
    (values 0 → no gram/sum contribution; real=0 → no counts/cost)."""
    b = min(block, max(n_l, 1))
    n_up = -(-n_l // b) * b
    if n_up != n_l:
        arrays = tuple(
            jnp.pad(a, ((0, n_up - n_l),) + ((0, 0),) * (a.ndim - 1))
            for a in arrays)
    return b, n_up // b, arrays


def sparse_kmeans_stats(idx, val, mask, real, x_sq, centroids,
                        strategy: str = "densify", block: int = 1024,
                        ) -> Tuple[jax.Array, jax.Array]:
    """Fused sparse E-step: returns (stats (K, D+1), local cost).

    scores[i, k] = ‖c_k‖² − 2 Σ_m val[i,m]·c[k, idx[i,m]]; the Σ‖x‖² row
    constant drops from the argmin and returns in the cost (the dense
    E-step's exact formulation, kmeans.py estep — tie-breaking matches).

    Two strategies, picked by where the bytes go on TPU:

    * ``densify`` (default): scan over ``block``-row tiles — densify the
      tile's nonzeros into a (block, D) buffer, then score (GEMM against
      cᵀ) and accumulate the M-step (one-hotᵀ GEMM) on the MXU. Compute
      matches the dense E-step; the sparsity saves STORAGE (O(nnz)
      resident vs O(N·D)). Measured r4 on the chip (n=262k, d=256,
      density 5%): 119.9 iters/s vs gather's 9.1 (13×) — and the densify
      itself must avoid XLA scatter (one-hot·value reduce instead; the
      `.at[].add` version measured 13.7, scatter-serialization-bound).
    * ``gather``: nnz-proportional compute via cᵀ-row gathers + one
      segment_sum scatter. Fewer FLOPs, but 128-byte-granule gathers run
      ~25M rows/s on v5e (the measured wall) — only wins when the data is
      so sparse-and-wide that nnz·K reads beat N·D·4 streaming bytes.
    """
    k, d = centroids.shape
    c2 = jnp.sum(centroids * centroids, axis=1)            # (K,)
    ct = centroids.T                                       # (D, K)
    vm = val * mask
    if strategy == "densify":
        n_l, m = idx.shape
        b, nb, (idx, vm, real, x_sq) = _pad_to_blocks(
            n_l, block, idx, vm, real, x_sq)

        def body(carry, blk):
            sums_a, counts_a, cost_a = carry
            bidx, bvm, breal, bxsq = blk
            # scatter-free densify via the shared engine (`.at[].add`
            # measured 8.8× slower on this E-step — lane_pack module doc)
            dense = lane_pack.densify_rows(bidx, bvm, d)   # (b, D)
            scores = c2[None, :] - 2.0 * jax.lax.dot_general(
                dense, ct, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # (b, K)
            assign = jnp.argmin(scores, axis=1)
            min_s = jnp.min(scores, axis=1)
            onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32)
            onehot = onehot * breal[:, None]               # drop phantoms
            sums_a = sums_a + jax.lax.dot_general(
                onehot, dense, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            counts_a = counts_a + jnp.sum(onehot, axis=0)
            cost_a = cost_a + jnp.sum(breal * (min_s + bxsq))
            return (sums_a, counts_a, cost_a), None

        (sums, counts, cost), _ = jax.lax.scan(
            body,
            (jnp.zeros((k, d), jnp.float32), jnp.zeros((k,), jnp.float32),
             jnp.zeros((), jnp.float32)),
            (idx.reshape(nb, b, m), vm.reshape(nb, b, m),
             real.reshape(nb, b), x_sq.reshape(nb, b)))
        return jnp.concatenate([sums, counts[:, None]], axis=1), cost
    if strategy != "gather":
        raise ValueError(f"strategy must be densify|gather, got {strategy!r}")
    xc = jnp.einsum("nm,nmk->nk", vm, ct[idx],
                    preferred_element_type=jnp.float32)    # (n_l, K)
    scores = c2[None, :] - 2.0 * xc
    assign = jnp.argmin(scores, axis=1)                    # (n_l,)
    min_s = jnp.min(scores, axis=1)
    # M-step: scatter each nonzero into its row's centroid — one segment_sum
    # keyed (assign, col) over the flattened nnz
    keys = (assign[:, None] * d + idx).ravel()
    sums = jax.ops.segment_sum(vm.ravel(), keys,
                               num_segments=k * d).reshape(k, d)
    counts = jax.ops.segment_sum(jnp.ones_like(assign, jnp.float32), assign,
                                 num_segments=k)
    stats = jnp.concatenate([sums, counts[:, None]], axis=1)
    # phantom rows from the worker-multiple pad: their x=0 still assigns
    # somewhere — remove them from the counts and cost (``real`` comes from
    # the layout: an all-zero DATA row stays in, exactly like the dense path)
    stats = stats.at[:, -1].add(-jax.ops.segment_sum(
        1.0 - real, assign, num_segments=k))
    cost = jnp.sum(real * (min_s + x_sq))
    return stats, cost


def sparse_gram_stats(idx, val, mask, real, dim: int, block: int = 512,
                      axis_name: str = WORKERS):
    """Global (XᵀX, Σx, n) from the padded-CSR shard — the csrdistri core.

    Densifies ``block`` rows at a time inside a scan (peak (block, D)) and
    runs the gram on the MXU; column sums accumulate from the same
    densified tiles (free inside the fusion — r5).
    """
    n_l, m = idx.shape
    vm = val * mask
    b, nb, (idx, vm) = _pad_to_blocks(n_l, block, idx, vm)

    def body(carry, blk):
        acc, s_acc = carry
        bidx, bval = blk                         # (b, m)
        dense = lane_pack.densify_rows(bidx, bval, dim)
        # column sums ride the already-densified tile: the old
        # segment_sum(vm, idx) over ALL nnz was 73 of the 83 ms/pass on the
        # bench shape (8.4M serialized scatter rows — profiled r5); this
        # reduce is free inside the tile fusion
        return (acc + jax.lax.dot_general(
            dense, dense, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32),
            s_acc + jnp.sum(dense, axis=0)), None

    (gram_local, s_local), _ = jax.lax.scan(
        body, (jnp.zeros((dim, dim), jnp.float32),
               jnp.zeros((dim,), jnp.float32)),
        (idx.reshape(nb, b, m), vm.reshape(nb, b, m)))
    gram = jax.lax.psum(gram_local, axis_name)
    s = jax.lax.psum(s_local, axis_name)
    n_real = jax.lax.psum(jnp.sum(real), axis_name)
    return gram, s, n_real


@dataclasses.dataclass(frozen=True)
class SparseKMeansConfig:
    num_centroids: int = 10
    dim: int = 100
    iterations: int = 10
    strategy: str = "densify"   # densify | gather (sparse_kmeans_stats doc)


class SparseKMeans:
    """daal_kmeans/allreducecsr: Lloyd's on CSR points, stats allreduced.

    Produces the same centroid trajectory as the dense KMeans on the
    equivalent densified matrix (up to summation-order float noise — the
    tests assert allclose, not bit equality, because gather-matmul and
    dense-matmul reduce in different orders)."""

    def __init__(self, session: HarpSession, config: SparseKMeansConfig):
        self.session = session
        self.config = config
        self._fns = {}

    def prepare(self, rows, cols, vals, num_points: int):
        sess, cfg = self.session, self.config
        cols = np.asarray(cols)
        if cols.size and int(np.max(cols)) >= cfg.dim:
            raise ValueError(f"column id {int(np.max(cols))} >= dim {cfg.dim}")
        idx, val, mask, real = csr_worker_layout(
            rows, cols, vals, num_points, sess.num_workers)
        x_sq = (val * val * mask).sum(axis=1).astype(np.float32)   # (n_pad,)
        key = (idx.shape, cfg.strategy)
        if key not in self._fns:
            def fit_fn(i_, v_, m_, r_, xsq_, cen0):
                def body(cen, _):
                    stats, cost = sparse_kmeans_stats(i_, v_, m_, r_, xsq_,
                                                      cen, cfg.strategy)
                    full = lax_ops.allreduce(stats)
                    new_c = full[:, :-1] / jnp.maximum(full[:, -1:], 1.0)
                    return new_c, jax.lax.psum(cost, WORKERS)

                return jax.lax.scan(body, cen0, None, length=cfg.iterations)

            self._fns[key] = sess.spmd(
                fit_fn, in_specs=(sess.shard(),) * 5 + (sess.replicate(),),
                out_specs=(sess.replicate(), sess.replicate()))
        return key, (sess.scatter(idx), sess.scatter(val), sess.scatter(mask),
                     sess.scatter(real), sess.scatter(x_sq))

    def fit_prepared(self, state, centroids0: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Run on prepared device data (the KMeans.prepare/fit_prepared
        timing idiom: host layout + H2D stays out of timed regions)."""
        key, placed = state
        cen, costs = self._fns[key](
            *placed, self.session.replicate_put(
                jnp.asarray(centroids0, jnp.float32)))
        return np.asarray(cen), np.asarray(costs)

    def fit(self, rows, cols, vals, num_points: int,
            centroids0: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        return self.fit_prepared(self.prepare(rows, cols, vals, num_points),
                                 centroids0)


class CSRCovariance:
    """daal_cov/csrdistri: covariance + mean from CSR input."""

    def __init__(self, session: HarpSession):
        self.session = session
        self._fns = {}

    def _layout(self, rows, cols, vals, num_rows: int, dim: int):
        cols = np.asarray(cols)
        if cols.size and (cols.min() < 0 or int(cols.max()) >= dim):
            # jit scatters DROP out-of-bounds indices silently — validate
            # here so the contract matches SparseKMeans.prepare
            raise ValueError(f"column ids must be in [0, {dim}); got "
                             f"[{cols.min()}, {cols.max()}]")
        return csr_worker_layout(rows, cols, vals, num_rows,
                                 self.session.num_workers)

    @staticmethod
    def _cov_mean(i_, v_, m_, r_, dim):
        gram, s, n = sparse_gram_stats(i_, v_, m_, r_, dim)
        mean = s / jnp.maximum(n, 1.0)
        cov = (gram - n * jnp.outer(mean, mean)) / jnp.maximum(n - 1.0, 1.0)
        return cov, mean

    def _stats(self, rows, cols, vals, num_rows: int, dim: int):
        sess = self.session
        idx, val, mask, real = self._layout(rows, cols, vals, num_rows, dim)
        key = (idx.shape, dim)
        if key not in self._fns:
            self._fns[key] = sess.spmd(
                lambda i_, v_, m_, r_: self._cov_mean(i_, v_, m_, r_, dim),
                in_specs=(sess.shard(),) * 4,
                out_specs=(sess.replicate(), sess.replicate()))
        return self._fns[key](sess.scatter(idx), sess.scatter(val),
                              sess.scatter(mask), sess.scatter(real))

    def compute(self, rows, cols, vals, num_rows: int, dim: int
                ) -> Tuple[np.ndarray, np.ndarray]:
        cov, mean = self._stats(rows, cols, vals, num_rows, dim)
        return np.asarray(cov), np.asarray(mean)

    def compute_repeated(self, rows, cols, vals, num_rows: int, dim: int,
                         repeats: int) -> Tuple[np.ndarray, np.ndarray]:
        """Run ``repeats`` full covariance passes inside ONE compiled program
        (carry-dependent scan, same idiom as stats.PCA.fit_repeated) — the
        bench measures device work, not per-dispatch cost."""
        sess = self.session
        idx, val, mask, real = self._layout(rows, cols, vals, num_rows, dim)
        key = (idx.shape, dim, repeats, "rep")
        if key not in self._fns:
            def fn(i_, v_, m_, r_):
                def body(carry, _):
                    eps = carry[0]
                    cov, mean = self._cov_mean(i_, v_ + eps, m_, r_, dim)
                    return (cov[0, 0] * 1e-30, cov, mean), None
                init = (jnp.float32(0.0), jnp.zeros((dim, dim)),
                        jnp.zeros((dim,)))
                (_, cov, mean), _ = jax.lax.scan(body, init, None,
                                                 length=repeats)
                return cov, mean

            self._fns[key] = sess.spmd(
                fn, in_specs=(sess.shard(),) * 4,
                out_specs=(sess.replicate(), sess.replicate()))
        cov, mean = self._fns[key](sess.scatter(idx), sess.scatter(val),
                                   sess.scatter(mask), sess.scatter(real))
        return np.asarray(cov), np.asarray(mean)


class CSRPCA:
    """daal_pca/corcsrdistr: correlation-method PCA from CSR input.

    The correlation derives from the CSR covariance; the (D, D) eigh runs
    replicated exactly as the dense path (linalg.pca)."""

    def __init__(self, session: HarpSession):
        self.session = session
        self._cov = CSRCovariance(session)

    def fit(self, rows, cols, vals, num_rows: int, dim: int
            ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        cov, mean = self._cov._stats(rows, cols, vals, num_rows, dim)
        cov = np.asarray(cov)
        d = np.sqrt(np.maximum(np.diag(cov), 1e-30))
        corr = cov / np.outer(d, d)
        w, v = np.linalg.eigh(corr)
        order = np.argsort(-w)
        return w[order], v[:, order].T, np.asarray(mean)
