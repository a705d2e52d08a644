#!/usr/bin/env python
"""Benchmark harness — prints ONE compact JSON line for the driver and writes
the FULL result to BENCH_local.json (VERDICT r4 weak #6: the driver's record
was tail-truncated; the compact line carries the headline keys, the file
carries everything).

Covers the five BASELINE workload configs (BASELINE.json): K-means
regroupallgather (the flagship/primary metric), SGD-MF (rotate pipeline),
PCA/covariance (dense allreduce), CGS-LDA (rotation + blocked sampling), and
mini-batch NN — each anchored against an optimized CPU implementation
(numpy/BLAS — the same linear-algebra core DAAL uses) of the IDENTICAL
workload on this host: the reference publishes no absolute throughput
(BASELINE.md), and the north-star is "match DAAL-on-Xeon iteration
throughput". A subprocess on an 8-device virtual CPU mesh adds the 1→2→4→8
strong-scaling curve and the collective micro-benchmarks
(harp_tpu/benchmark/{scaling,collectives}.py).

Timing method (round 5): every device rate is measured TWO-POINT — the same
workload is compiled at a low and a high in-program iteration count and the
rate comes from the iteration-count delta, so whatever one call costs
besides its iterations (dispatch, the result fetch; recorded per row as
*_fixed_dispatch_s) cancels instead of being amortized into the rate. On
the machine r5 ran on that constant was 0.3-0.4 s per call and dominated any
row whose device time was < ~1 s. On a directly attached chip it is small
(chip_smoke.py prints one observation); whether the protocol survives is
ROADMAP D2. Each two-point sample is a median of N≥3 alternating runs and
ships a spread column; deltas inside the spread are noise by the data, not
by prose.

Usage: python bench.py [--small] [--only group1,group2,...] [--list-groups]

``--only`` re-measures a subset of row groups (names in ROW_GROUPS) without
the full ~all-rows run and MERGES the result into BENCH_local.json instead
of rewriting it; the gc-at-group-boundary behavior is identical to the full
run (a gc precedes every selected group), so a filtered re-measure sees the
same freshly-collected device state.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

V5E_BF16_PEAK = 197e12   # TPU v5e peak bf16 FLOP/s (MFU denominator)
V5E_HBM_GBPS = 819e9     # TPU v5e HBM bandwidth roofline (bytes/s)

# The DAAL-on-Xeon north star (BASELINE.md): the comparison machine is a
# 2x18-core Haswell E5-2699 v3. This host has exactly ONE (modern Zen) core,
# so a measured multicore anchor is impossible; instead every vs-CPU ratio
# also ships a CONSERVATIVE LOWER BOUND on the vs-Xeon ratio: divide by 36,
# i.e. assume the same BLAS anchor scales PERFECTLY linearly to all 36
# Haswell cores AND that a 2015 Haswell core matches this Zen core per-core.
# Both assumptions favor the Xeon (memory-bound kernels scale sublinearly;
# Haswell is slower per-core), so vs_xeon36_lb >= 1 genuinely supports
# "matches DAAL-on-Xeon throughput".
XEON_CORES = 36


def xeon_lb(vs_cpu: float) -> float:
    return round(vs_cpu / XEON_CORES, 2)


# shared two-point protocol: rate from the iteration-count delta so the
# per-call dispatch+fetch constant cancels (harp_tpu/benchmark/timing.py)
from harp_tpu.benchmark.timing import two_point  # noqa: E402


# --------------------------------------------------------------------------- #
# K-means (BASELINE configs[0] — flagship, primary metric)
# --------------------------------------------------------------------------- #

def tpu_kmeans(n, k, d, iters, compute_dtype="float32", lane_pad=True):
    from harp_tpu.io import datagen
    from harp_tpu.models import kmeans as km
    from harp_tpu.session import HarpSession

    sess = HarpSession()  # all visible devices (1 real chip under the driver)
    pts = datagen.dense_points(n - n % sess.num_workers or n, d, seed=7,
                               num_clusters=k)
    n_eff = pts.shape[0] - pts.shape[0] % sess.num_workers
    pts = pts[:n_eff]
    cen0 = datagen.initial_centroids(pts, k, seed=3)
    state = {}

    def build(ni):
        model = km.KMeans(sess, km.KMeansConfig(k, d, ni, "regroupallgather",
                                                compute_dtype=compute_dtype,
                                                lane_pad=lane_pad))
        pts_dev, cen_dev = model.prepare(pts, cen0)
        _, costs = model.fit_prepared(pts_dev, cen_dev)   # compile + warmup
        state[ni] = float(np.asarray(costs)[-1])  # the fetch waits for
        #   the run (block_until_ready would too; the cost is needed anyway)

        def timer():
            _, costs = model.fit_prepared(pts_dev, cen_dev)
            np.asarray(costs)
        return timer

    tp = two_point(build, max(iters // 4, 2), iters, 1.0)
    # two utilization views. The r5 two-point rate exposed that the old
    # "2 reads per iteration" HBM model was wrong: XLA fuses distance GEMM +
    # argmin + stats GEMM into ONE pass over the point tiles (the old model
    # read >100% of roofline). hbm: one point-block read per iteration;
    # mxu: the 2·2·N·K·D FLOPs of the two GEMMs — at the flagship shape the
    # iteration is MXU-bound (bf16 point storage ties f32, same FLOPs).
    # mxu counts USEFUL flops (real K and D) — with lane_pad the hardware
    # runs 128-wide tiles either way; the padded row's gain shows up as rate.
    # hbm counts STORED bytes: lane_pad feature-pads the resident block to a
    # 128 multiple, and the E-step streams the padded width.
    bytes_per_point = 2 if compute_dtype == "bfloat16" else 4
    d_stored = -(-d // 128) * 128 if lane_pad else d
    bytes_per_iter = 1.0 * n_eff * d_stored * bytes_per_point
    tp["hbm_one_pass_pct"] = round(100.0 * bytes_per_iter * tp["rate"] / (
        V5E_HBM_GBPS * sess.num_workers), 1)
    tp["mxu_tflops"] = round(4.0 * n_eff * k * d * tp["rate"] / 1e12
                             / sess.num_workers, 1)
    tp["final_cost"] = state[iters]
    tp["lane_pad"] = lane_pad
    return tp


def cpu_kmeans_iters_per_sec(n, k, d, iters):
    """BLAS-backed Lloyd iteration — the DAAL-equivalent CPU anchor."""
    rng = np.random.default_rng(7)
    pts = rng.random((n, d), dtype=np.float32)
    cen = pts[:k].copy()

    def one_iter(cen):
        x2 = (pts * pts).sum(1, keepdims=True)
        c2 = (cen * cen).sum(1)[None, :]
        dist = x2 - 2.0 * pts @ cen.T + c2
        a = dist.argmin(1)
        oh = np.zeros((n, k), np.float32)
        oh[np.arange(n), a] = 1.0
        sums = oh.T @ pts
        cnt = oh.sum(0)[:, None]
        return sums / np.maximum(cnt, 1.0)

    cen = one_iter(cen)     # warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        cen = one_iter(cen)
    return iters / (time.perf_counter() - t0)


def tpu_sparse_kmeans(n, k, d, density, iters):
    """daal_kmeans/allreducecsr at realistic sparsity."""
    from harp_tpu.io import datagen
    from harp_tpu.models import sparse as sp
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    n -= n % sess.num_workers
    rows, cols, vals = datagen.sparse_points(n, d, density, seed=11)
    dense0 = np.zeros((k, d), np.float32)
    head = rows < k
    dense0[rows[head], cols[head]] = vals[head]

    def build(ni):
        model = sp.SparseKMeans(sess, sp.SparseKMeansConfig(k, d, ni))
        state = model.prepare(rows, cols, vals, n)
        _, costs = model.fit_prepared(state, dense0)      # compile + warmup
        np.asarray(costs)

        def timer():
            _, costs = model.fit_prepared(state, dense0)
            np.asarray(costs)
        return timer

    tp = two_point(build, max(iters // 4, 2), iters, 1.0)
    tp["nnz"] = len(vals)
    return tp


# --------------------------------------------------------------------------- #
# SGD-MF (BASELINE configs[2] — rotate pipeline; dense masked-stripe layout)
# --------------------------------------------------------------------------- #

def tpu_sgd_mf(nu, ni, epochs, rank=32):
    """Steady-state training throughput (samples = ratings processed)."""
    from harp_tpu.io import datagen
    from harp_tpu.models import sgd_mf
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    rows, cols, vals = datagen.sparse_ratings(nu, ni, rank=16, density=0.01,
                                              seed=5)
    meta = {}

    def build(ne):
        cfg = sgd_mf.SGDMFConfig(rank=rank, lam=0.01, lr=0.05, epochs=ne,
                                 minibatches_per_hop=8)
        model = sgd_mf.SGDMF(sess, cfg)
        state = model.prepare(rows, cols, vals, nu, ni)
        meta["nnz"] = len(vals) - model.last_layout_stats.get(
            "duplicates_dropped", 0)
        meta["layout"] = model.last_layout_stats["layout"]
        _, _, rmse = model.train_prepared(state)          # compile + warm-up
        meta[ne] = float(np.asarray(rmse)[-1])

        def timer():
            _, _, rmse = model.train_prepared(state)
            np.asarray(rmse)
        return timer

    tp = two_point(build, max(epochs // 4, 2), epochs, 1.0)
    nnz = meta["nnz"]
    tp["rate"] *= nnz                        # epochs/s → ratings/s
    tp["final_rmse"] = round(meta[epochs], 4)
    tp["layout"] = meta["layout"]
    # two utilization views: mxu_busy = the dense slab GEMMs the program
    # actually issues (dense layout computes on NaN holes by design);
    # nnz_mfu = only the 6*nnz*rank flops a sparse-exact algorithm needs.
    eps = tp["rate"] / nnz
    tp["mxu_busy_pct"] = round(100 * 6.0 * nu * ni * rank * eps
                               / (V5E_BF16_PEAK * sess.num_workers), 2) \
        if meta["layout"] == "dense" else 0.0
    tp["nnz_effective_mfu_pct"] = round(100 * 6.0 * nnz * rank * eps / (
        V5E_BF16_PEAK * sess.num_workers), 3)
    return tp


def cpu_sgd_mf_samples_per_sec(nu, ni, epochs):
    """numpy minibatch-SGD anchor for the same workload shape."""
    from harp_tpu.io import datagen

    rows, cols, vals = datagen.sparse_ratings(nu, ni, rank=16, density=0.01,
                                              seed=5)
    rng = np.random.default_rng(0)
    k = 32
    w = (rng.standard_normal((nu, k)) / np.sqrt(k)).astype(np.float32)
    h = (rng.standard_normal((ni, k)) / np.sqrt(k)).astype(np.float32)
    bs = min(8192, len(vals))
    nb = -(-len(vals) // bs)            # include the tail minibatch
    processed = 0
    t0 = time.perf_counter()
    for _ in range(epochs):
        for b in range(nb):
            sl = slice(b * bs, min((b + 1) * bs, len(vals)))
            r, c, v = rows[sl], cols[sl], vals[sl]
            wr, hc = w[r], h[c]
            err = (v - np.einsum("ij,ij->i", wr, hc))[:, None]
            np.add.at(w, r, 0.05 * (err * hc - 0.01 * wr))
            np.add.at(h, c, 0.05 * (err * wr - 0.01 * hc))
            processed += len(v)
    return processed / (time.perf_counter() - t0)


# --------------------------------------------------------------------------- #
# ALS (BASELINE configs[2] names daal_als alongside SGD-MF — implicit, CSR)
# --------------------------------------------------------------------------- #

def tpu_als(nu, ni, iters, ablate_solve=False):
    from harp_tpu.io import datagen
    from harp_tpu.models import als
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    rows, cols, vals = datagen.sparse_ratings(nu, ni, rank=16, density=0.005,
                                              seed=9)
    vals = np.abs(vals)          # implicit mode consumes interaction COUNTS
    meta = {}

    def build(ni_):
        cfg = als.ALSConfig(rank=32, lam=0.1, alpha=40.0, iterations=ni_,
                            implicit=True, ablate_solve=ablate_solve)
        model = als.ALS(sess, cfg)
        state = model.prepare(rows, cols, vals, nu, ni, seed=0)
        _, _, rmse = model.train_prepared(state)          # compile + warm-up
        meta[ni_] = float(np.asarray(rmse)[-1])
        meta["layout"] = model.last_layout_stats.get("layout", "sparse")

        def timer():
            _, _, rmse = model.train_prepared(state)
            np.asarray(rmse)
        return timer

    tp = two_point(build, max(iters // 3, 2), iters, 1.0)
    tp["final_rmse"] = round(meta[iters], 4)
    tp["layout"] = meta["layout"]
    return tp


def tpu_als_stage(nu, ni, iters, full_row=None):
    """ALS per-iteration stage budget by solve ablation (ISSUE 9 satellite:
    the thinnest north-star margin, lb 5.22, gets a MEASURED stage row —
    the r3/r4 PERF one-off ablation as a reproducible bench sub-row).
    ``ablate_solve=True`` rides identity through the batched k×k SPD solve
    (results wrong, timing only), so full − ablated prices the solve and
    the remainder is gram/gather/allgather + bookkeeping."""
    full = full_row if full_row is not None else tpu_als(nu, ni, iters)
    ablated = tpu_als(nu, ni, iters, ablate_solve=True)
    f_ms, a_ms = full["per_iter_ms"], ablated["per_iter_ms"]
    return {
        "config": f"nu={nu} ni={ni} rank=32 implicit two-point",
        "full_ms_per_iter": f_ms,
        "solve_ablated_ms_per_iter": a_ms,
        "solve_ms_per_iter": round(f_ms - a_ms, 3),
        "solve_share_pct": round(100.0 * max(f_ms - a_ms, 0.0)
                                 / max(f_ms, 1e-9), 1),
        "note": ("solve-ablated results are wrong by construction "
                 "(ALSConfig.ablate_solve); this row prices stages only"),
    }


def cpu_als_iters_per_sec(nu, ni, iters):
    """Implicit (Hu-Koren) ALS anchor: batched normal equations over padded
    neighbor lists — the same formulation the device program uses, on BLAS."""
    from harp_tpu.io import datagen

    rows, cols, vals = datagen.sparse_ratings(nu, ni, rank=16, density=0.005,
                                              seed=9)
    vals = np.abs(vals)          # same implicit counts as the device side
    k, lam, alpha = 32, 0.1, 40.0

    def pad(r, c, v, n):
        order = np.argsort(r, kind="stable")
        r, c, v = r[order], c[order], v[order]
        cnt = np.bincount(r, minlength=n)
        m = max(int(cnt.max()), 1)
        idx = np.zeros((n, m), np.int64)
        val = np.zeros((n, m), np.float32)
        msk = np.zeros((n, m), np.float32)
        pos = np.arange(len(r)) - np.concatenate([[0], np.cumsum(cnt)])[r]
        idx[r, pos] = c
        val[r, pos] = v
        msk[r, pos] = 1.0
        return idx, val, msk

    u_lay = pad(rows, cols, vals, nu)
    i_lay = pad(cols, rows, vals, ni)
    rng = np.random.default_rng(0)
    u = (rng.random((nu, k)) / np.sqrt(k)).astype(np.float32)
    v = (rng.random((ni, k)) / np.sqrt(k)).astype(np.float32)
    eye = lam * np.eye(k, dtype=np.float32)

    def half(other, lay):
        idx, val, msk = lay
        x = other[idx] * msk[..., None]          # (n, M, K) masked neighbors
        wts = alpha * val * msk                  # C - 1
        a = (other.T @ other + eye
             + np.matmul(x.transpose(0, 2, 1) * wts[:, None, :], x))
        b = ((msk + wts)[..., None] * x).sum(1)  # Σ C·v over observed
        return np.linalg.solve(a, b[..., None])[..., 0]

    t0 = time.perf_counter()
    for _ in range(iters):
        u = half(v, u_lay)
        v = half(u, i_lay)
    return iters / (time.perf_counter() - t0)


# --------------------------------------------------------------------------- #
# PCA / covariance (BASELINE configs[1] — dense allreduce)
# --------------------------------------------------------------------------- #

def tpu_pca(n, d, repeats):
    from harp_tpu.io import datagen
    from harp_tpu.models import stats
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    n -= n % sess.num_workers
    x_dev = sess.scatter(datagen.dense_points(n, d, seed=2))
    model = stats.PCA(sess)
    meta = {}

    def build(nr):
        w, _, _ = model.fit_repeated(x_dev, nr)           # compile + warmup
        meta[nr] = float(w[0])

        def timer():
            model.fit_repeated(x_dev, nr)     # returns host arrays (forces)
        return timer

    tp = two_point(build, max(repeats // 4, 2), repeats, 1.0)
    tp["top_eigenvalue"] = round(meta[repeats], 5)
    return tp


def cpu_pca_fits_per_sec(n, d, repeats):
    from harp_tpu.io import datagen

    x = datagen.dense_points(n, d, seed=2).astype(np.float64)
    t0 = time.perf_counter()
    for _ in range(repeats):
        xc = x - x.mean(0)
        cov = (xc.T @ xc) / (n - 1)
        np.linalg.eigh(cov)
    return repeats / (time.perf_counter() - t0)


# --------------------------------------------------------------------------- #
# CGS-LDA (BASELINE configs[3] — rotation + blocked sampling)
# --------------------------------------------------------------------------- #

def tpu_lda(num_docs, vocab, doc_len, topics, epochs, vocab_sub_block=0):
    from harp_tpu.io import datagen
    from harp_tpu.models import lda
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    num_docs -= num_docs % sess.num_workers
    docs = datagen.lda_corpus(num_docs, vocab, max(2, topics // 2), doc_len,
                              seed=3)
    meta = {}

    def build(ne):
        cfg = lda.LDAConfig(num_topics=topics, vocab=vocab, epochs=ne,
                            vocab_sub_block=vocab_sub_block)
        model = lda.LDA(sess, cfg)
        state = model.prepare(docs, seed=1)      # host layout + H2D once
        _, _, ll = model.fit_prepared(state)     # compile + warmup
        meta[ne] = float(ll[-1])
        # per-(doc, sub-block) padding is the sub-block layout's cost —
        # report it NEXT to the throughput it buys
        meta["overhead"] = model.last_layout_stats["overhead"]

        def timer():
            model.fit_prepared(state)            # fetches ll etc. (forces)
        return timer

    tp = two_point(build, max(epochs // 4, 2), epochs, float(docs.size))
    tp["final_ll"] = meta[epochs]
    if vocab_sub_block:
        tp["vocab_sub_block"] = vocab_sub_block
        tp["token_padding_overhead"] = round(meta["overhead"], 3)
    # analytic flop estimate per token: the blocked-CGS sampling builds the
    # K-topic categorical (≈5 flops/topic), normalizes + cumsum-samples (≈3),
    # plus count updates (≈2) → ~8K+2. MFU documents that CGS is
    # GATHER/SAMPLE bound, not MXU work — honest, and honestly tiny.
    tp["mfu_pct"] = round(100 * tp["rate"] * (8.0 * topics + 2)
                          / (V5E_BF16_PEAK * sess.num_workers), 4)
    return tp


def cpu_lda_tokens_per_sec(num_docs, vocab, doc_len, topics, epochs):
    """Vectorized numpy blocked-CGS sweep — same blocked math as the device."""
    from harp_tpu.io import datagen

    docs = datagen.lda_corpus(num_docs, vocab, max(2, topics // 2), doc_len,
                              seed=3)
    rng = np.random.default_rng(1)
    d, l = docs.shape
    z = rng.integers(0, topics, (d, l))
    ndk = np.zeros((d, topics))
    np.add.at(ndk, (np.arange(d)[:, None], z), 1)
    nwk = np.zeros((vocab, topics))
    np.add.at(nwk, (docs, z), 1)
    nk = ndk.sum(0)
    alpha, beta = 0.1, 0.01
    t0 = time.perf_counter()
    for _ in range(epochs):
        cur = np.zeros((d, l, topics))
        np.put_along_axis(cur, z[..., None], 1.0, axis=2)
        p = ((ndk[:, None, :] - cur + alpha)
             * (nwk[docs] - cur + beta)
             / (nk[None, None, :] - cur + vocab * beta))
        p = np.maximum(p, 1e-12)
        p /= p.sum(-1, keepdims=True)
        u = rng.random((d, l, 1))
        z = (p.cumsum(-1) < u).sum(-1).clip(0, topics - 1)
        ndk = np.zeros((d, topics))
        np.add.at(ndk, (np.arange(d)[:, None], z), 1)
        nwk = np.zeros((vocab, topics))
        np.add.at(nwk, (docs, z), 1)
        nk = ndk.sum(0)
    return docs.size * epochs / (time.perf_counter() - t0)


# --------------------------------------------------------------------------- #
# Mini-batch NN (BASELINE configs[4] — mini-batch allreduce)
# --------------------------------------------------------------------------- #

def tpu_nn(n, d, epochs, layers=(256, 128), batch_size=512):
    from harp_tpu.io import datagen
    from harp_tpu.models import nn
    from harp_tpu.session import HarpSession
    import jax.numpy as jnp

    sess = HarpSession()
    n -= n % sess.num_workers
    x, y = datagen.classification_data(n, d, 16, seed=4)
    # place once: fit's internal scatter is a no-op on placed arrays, so the
    # timed run measures training, not host->device transfer
    x_dev = sess.scatter(jnp.asarray(x, jnp.float32))
    y_dev = sess.scatter(jnp.asarray(y, jnp.int32))
    meta = {}

    def build(ne):
        cfg = nn.NNConfig(layers=layers, num_classes=16, lr=0.05,
                          batch_size=batch_size, epochs=ne)
        model = nn.MLPClassifier(sess, cfg)
        losses = model.fit(x_dev, y_dev, seed=0)          # compile + warmup
        meta[ne] = float(losses[-1])

        def timer():
            model.fit(x_dev, y_dev, seed=0)   # returns host losses (forces)
        return timer

    tp = two_point(build, max(epochs // 4, 2), epochs, float(n))
    tp["final_loss"] = round(meta[epochs], 4)
    # exact MLP flops/sample: fwd 2·Σ(a·b) + bwd 4·Σ(a·b) (dW and dX GEMMs)
    dims = [d] + list(layers) + [16]
    param_mults = sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    tp["mfu_pct"] = round(100 * tp["rate"] * 6.0 * param_mults
                          / (V5E_BF16_PEAK * sess.num_workers), 2)
    tp["config"] = (f"n={n} d={d} layers={'x'.join(map(str, layers))} "
                    f"batch={batch_size}")
    return tp


def cpu_nn_samples_per_sec(n, d, epochs, layers=(256, 128), batch_size=512):
    from harp_tpu.io import datagen

    x, y = datagen.classification_data(n, d, 16, seed=4)
    rng = np.random.default_rng(0)
    dims = [d] + list(layers) + [16]
    ws = [rng.standard_normal((a, b)).astype(np.float32) * np.sqrt(2.0 / a)
          for a, b in zip(dims[:-1], dims[1:])]
    bs_ = [np.zeros(b, np.float32) for b in dims[1:]]
    bsz, lr = batch_size, 0.05
    t0 = time.perf_counter()
    for _ in range(epochs):
        for i in range(0, n - bsz + 1, bsz):
            xb, yb = x[i:i + bsz], y[i:i + bsz]
            acts = [xb]
            h = xb
            for w, b in zip(ws[:-1], bs_[:-1]):
                h = np.maximum(h @ w + b, 0.0)
                acts.append(h)
            logits = h @ ws[-1] + bs_[-1]
            e = np.exp(logits - logits.max(1, keepdims=True))
            probs = e / e.sum(1, keepdims=True)
            probs[np.arange(bsz), yb] -= 1.0
            g = probs / bsz
            for li in range(len(ws) - 1, -1, -1):
                gw = acts[li].T @ g
                gb = g.sum(0)
                if li:
                    g = (g @ ws[li].T) * (acts[li] > 0)
                ws[li] -= lr * gw
                bs_[li] -= lr * gb
    return n * epochs / (time.perf_counter() - t0)


def tpu_attention(l=16384, h=8, dh=64, reps=100, head_pack=None,
                  causal=True):
    """Long-context blocked attention (pallas flash at L >= 8192) at the
    per-chip length SP exists for. Causal, one chip; the multi-chip ring adds
    the ppermute hops on top.

    ``head_pack``: None = the dispatcher's auto gate (packed at Dh<=64);
    False pins the unpacked layout via HARP_FLASH_HEADPACK=0 so the r7
    block-sparse-grid leg can be priced separately from the lane-packing
    leg (the env var is restored after the measurement)."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.parallel import ring_attention as ra

    q = jax.random.normal(jax.random.key(0), (l, h, dh), jnp.float32)

    def build(nr):
        def run(q0):
            def body(c, _):
                o = ra.blocked_attention(c, c, c, causal=causal)
                return c + 1e-20 * o, ()    # carry dependence: no hoisting

            out, _ = jax.lax.scan(body, q0, None, length=nr)
            return out

        fn = jax.jit(run)
        np.asarray(fn(q))                    # compile + warm (D2H forces)

        def timer():
            # a tiny D2H fetch waits for the run (any element of the scan
            # carry needs every rep), as block_until_ready would
            np.asarray(fn(q)[0, 0])
        return timer

    prev = os.environ.get("HARP_FLASH_HEADPACK")
    try:
        if head_pack is False:
            os.environ["HARP_FLASH_HEADPACK"] = "0"
        tp = two_point(build, max(reps // 4, 2), reps, float(l))
    finally:
        if head_pack is False:
            if prev is None:
                os.environ.pop("HARP_FLASH_HEADPACK", None)
            else:
                os.environ["HARP_FLASH_HEADPACK"] = prev
    tp["config"] = (f"causal={causal} L={l} H={h} Dh={dh} "
                    f"head_pack={'auto' if head_pack is None else head_pack}")
    return tp


def tpu_kernel_svm(n, d, iterations):
    """Kernel-SVM dual training rate (VERDICT r4 weak #5: the r4 components
    shipped correctness-tested but unbenchmarked). One projected-gradient
    iteration = one ring-rotated Gram matvec: N²/W kernel evaluations per
    worker per iteration, never materializing the N×N Gram."""
    from harp_tpu.models import svm
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    rng = np.random.default_rng(21)
    n -= n % sess.num_workers
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = (x @ rng.standard_normal(d) + 0.3 * rng.standard_normal(n)
         > 0).astype(np.int32)
    y_signed = (2.0 * y - 1.0).astype(np.float32)
    cap = np.full((n,), 10.0, np.float32)

    def build(ni):
        model = svm.KernelSVM(sess, svm.KernelSVMConfig(
            kernel="rbf", sigma=2.0, c=10.0, iterations=ni))
        model._fit_padded(x, y_signed, cap)      # compile + warm

        def timer():
            model._fit_padded(x, y_signed, cap)
        return timer

    tp = two_point(build, max(iterations // 4, 2), iterations, 1.0)
    tp["kernel_evals_per_sec"] = round(tp["rate"] * n * n)
    tp["config"] = f"rbf n={n} d={d}"
    # convergence-budget view: the early stop ends the same program when
    # relative dual progress dies (one extra compile, small run)
    es = svm.KernelSVM(sess, svm.KernelSVMConfig(
        kernel="rbf", sigma=2.0, c=10.0, iterations=iterations,
        early_stop_tol=1e-5))
    es._fit_padded(x, y_signed, cap)
    tp["early_stop_iters_at_1e-5"] = int(es.n_iter_)
    # the RECORDED firing config (VERDICT r5 leftover: the row above shows
    # the stop never fired at the bench shape — this one provably does;
    # the firing iteration is dual-ascent math, device-independent)
    xf, yf = svm.early_stop_recorded_problem()
    esf = svm.KernelSVM(sess, svm.KernelSVMConfig(
        **svm.EARLY_STOP_RECORDED_CONFIG))
    esf.fit(xf, yf)
    tp["early_stop_recorded"] = {
        "config": "rbf sigma=2 c=1 n=128 d=3 seed=12 tol=1e-5 budget=2000 "
                  "(svm.EARLY_STOP_RECORDED_CONFIG)",
        "fired_at_iteration": int(esf.n_iter_),
        "budget": svm.EARLY_STOP_RECORDED_CONFIG["iterations"],
    }
    return tp


def tpu_mds(n, iterations):
    """WDA-MDS stress-majorization rate (SMACOF + weighted-V CG solve)."""
    from harp_tpu.models import mds
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    rng = np.random.default_rng(13)
    n -= n % sess.num_workers
    pts = rng.standard_normal((n, 3)).astype(np.float32)
    dist = np.sqrt(((pts[:, None] - pts[None, :]) ** 2).sum(-1))
    wts = 0.5 + rng.random((n, n)).astype(np.float32)   # non-uniform weights
    wts = (wts + wts.T) / 2
    meta = {}

    def build(ni):
        model = mds.WDAMDS(sess, mds.MDSConfig(dim=3, iterations=ni,
                                               cg_iters=8))
        state = model.prepare(dist, wts, seed=0)   # H2D of the N² matrices
        #   happens ONCE here, not in the timed region (it swamped the
        #   iteration delta in the first r5 run)
        _, stress = model.fit_prepared(state)            # compile + warm
        meta[ni] = float(stress[-1])

        def timer():
            model.fit_prepared(state)
        return timer

    tp = two_point(build, max(iterations // 4, 2), iterations, 1.0)
    tp["final_stress"] = meta[iterations]
    tp["config"] = f"n={n} dim=3 cg_iters=8"
    return tp


def tpu_distributed_sort(n, repeats):
    """Distributed sort rate (odd-even block transposition; on one chip this
    measures the XLA sort core the multi-worker path is built from)."""
    import jax
    import jax.numpy as jnp

    from harp_tpu.ops import linalg
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    n -= n % sess.num_workers
    x = np.random.default_rng(17).standard_normal(n).astype(np.float32)

    def build(nr):
        def looped(a):
            def body(c, _):
                out = linalg.distributed_sort(jnp.roll(c, 7))
                return out, ()
            out, _ = jax.lax.scan(body, a, None, length=nr)
            return out

        prog = sess.spmd(looped, in_specs=(sess.shard(),),
                         out_specs=sess.shard())
        dev = sess.scatter(x)
        np.asarray(prog(dev))                    # compile + warm (D2H forces)

        def timer():
            np.asarray(prog(dev)[:1])            # force, tiny fetch
        return timer

    tp = two_point(build, max(repeats // 4, 2), repeats, float(n))
    tp["config"] = f"n={n} f32"
    return tp


def tpu_csr_cov(n, d, density, repeats):
    """CSR covariance/PCA statistics rate (densify-GEMM gram path)."""
    from harp_tpu.io import datagen
    from harp_tpu.models import sparse as sp
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    n -= n % sess.num_workers
    rows, cols, vals = datagen.sparse_points(n, d, density, seed=23)
    cov = sp.CSRCovariance(sess)

    def build(nr):
        cov.compute_repeated(rows, cols, vals, n, d, nr)  # compile + warm

        def timer():
            cov.compute_repeated(rows, cols, vals, n, d, nr)
        return timer

    tp = two_point(build, max(repeats // 4, 2), repeats, 1.0)
    tp["nnz"] = len(vals)
    tp["config"] = f"n={n} d={d} density={density}"
    return tp


def kmeans_from_files(n=131072, d=64, k=64, iters=20, parts=8):
    """File-driven flagship workflow (VERDICT r4 missing #1): the
    reference's entire pipeline was files-in (README.md:148-160 — generated
    HDFS part-files consumed by KMeansLauncher). Times the host load stage
    (native C++ parser on local part-files vs the numpy fallback through
    the fsspec memory:// store) and the full load→split→scatter→fit wall.
    Host work has no per-dispatch constant to cancel, so these are plain
    medians-of-3."""
    import shutil
    import statistics
    import tempfile

    import jax.numpy as jnp

    from harp_tpu.io import datagen, loaders
    from harp_tpu.models import kmeans as km
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    n -= n % sess.num_workers
    pts = datagen.dense_points(n, d, seed=31, num_clusters=k)
    tmp = tempfile.mkdtemp(prefix="harp_bench_km_")
    try:
        for i, block in enumerate(np.array_split(pts, parts)):
            np.savetxt(os.path.join(tmp, f"part-{i:05d}"), block,
                       fmt="%.6f", delimiter=",")
        paths = loaders.list_files(tmp)
        bytes_total = sum(os.path.getsize(p) for p in paths)

        def timed3(fn, reduce):
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                fn()
                ts.append(time.perf_counter() - t0)
            return reduce(ts)

        def med(fn):
            return timed3(fn, statistics.median)

        def best(fn):
            # peak parse rate: min-of-3 — host-side bandwidth benchmarks
            # report best-case; one bench-process GC/VM hiccup should not
            # stand as the parser rate
            return timed3(fn, min)

        t_native = best(lambda: loaders.load_dense_csv(paths))
        # numpy fallback: the same bytes through the fsspec memory:// store
        # (URL paths bypass the native parser by design)
        import fsspec

        mem = fsspec.filesystem("memory")
        mem_paths = []
        for p in paths:
            mp = f"/bench_km/{os.path.basename(p)}"
            with open(p, "rb") as src, mem.open(mp, "wb") as dst:
                dst.write(src.read())
            mem_paths.append("memory://" + mp)
        t_numpy = best(lambda: loaders.load_dense_csv(mem_paths))

        # full workflow: list → threaded load → scatter → 20-iteration fit
        model = km.KMeans(sess, km.KMeansConfig(k, d, iters,
                                                "regroupallgather"))
        cen0 = datagen.initial_centroids(pts, k, seed=32)

        def full():
            loaded = loaders.load_dense_csv(loaders.list_files(tmp))
            pts_dev, cen_dev = model.prepare(loaded, cen0)
            _, costs = model.fit_prepared(pts_dev, cen_dev)
            np.asarray(costs)

        full()                                   # compile + warm
        t_full = med(full)
        try:
            mem.rm("/bench_km", recursive=True)
        except Exception:          # noqa: BLE001 — best-effort cleanup
            pass
        return {
            "config": f"n={n} d={d} k={k} iters={iters} parts={parts}",
            "csv_bytes": bytes_total,
            "load_native_mb_per_sec": round(bytes_total / t_native / 1e6, 1),
            "load_numpy_fallback_mb_per_sec": round(
                bytes_total / t_numpy / 1e6, 1),
            "native_vs_numpy": round(t_numpy / t_native, 2),
            "load_scatter_fit_wall_s": round(t_full, 3),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def tpu_collectives_quantized(small=False):
    """Quantized-collective busbw rows (ISSUE 6): int8/bf16 vs f32 wire
    formats for allreduce + the rotation hop at >= 2 payload sizes, on the
    session mesh (on-chip when the driver runs this; the committed record
    carries null-with-note rows when no TPU is reachable). busbw prices the
    QUANTIZED wire bytes (int8 payload + scales), so a codec's win shows as
    equal-or-better busbw at 1/4 (int8) or 1/2 (bf16) the moved volume —
    see collectives_quantized_note in the record."""
    from harp_tpu.benchmark import collectives as bc
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    sizes = (16, 64) if small else (64, 1024)
    return bc.bench_collectives_quantized(sess, sizes_kb=list(sizes),
                                          loops=20)


def tpu_telemetry_overhead(small=False):
    """Telemetry on/off delta on the kmeans fit loop (ISSUE 7 acceptance:
    < 2% on-chip, asserted here). Runs the fit_checkpointed dispatch shape —
    one compiled iteration per host step with the cost fetched at each
    boundary — with and without `telemetry.record_chunk` + the comm ledger,
    both sides timing and fetching identically, so the delta is exactly the
    telemetry layer. Returns None on a CPU-only host (null-with-note
    convention; the driver's on-chip run fills it)."""
    import statistics
    import tempfile

    import jax

    if all(d.platform == "cpu" for d in jax.devices()):
        return None
    from harp_tpu import telemetry
    from harp_tpu.io import datagen
    from harp_tpu.models import kmeans as km
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    n, k, d = (100_000, 100, 100) if small else (1_000_000, 100, 100)
    iters = 30 if small else 200
    pts = datagen.dense_points(n, d, seed=7, num_clusters=k)
    pts = pts[: len(pts) - len(pts) % sess.num_workers]
    cen0 = datagen.initial_centroids(pts, k, seed=3)
    model = km.KMeans(sess, km.KMeansConfig(k, d, 1))
    p, c0 = model.prepare(pts, cen0)
    step = model._fit

    def run(ledger=None, record=False):
        cen = c0
        t0 = time.perf_counter()
        for i in range(iters):
            it0 = time.perf_counter()
            cen, cost = step(p, cen)
            loss = [float(np.asarray(cost)[0])]       # the boundary D2H
            wall = time.perf_counter() - it0
            if record:
                telemetry.record_chunk("kmeans", start=i, losses=loss,
                                       wall_s=wall, ledger=ledger)
        return time.perf_counter() - t0

    run()                                             # compile + warm
    t_off = statistics.median(run() for _ in range(3))
    tele_dir = tempfile.mkdtemp(prefix="harp-bench-tele-")
    telemetry.configure(tele_dir, interval=16)
    ledger = telemetry.ledger_for("kmeans", comm="regroupallgather",
                                  scale=model.comm_scale(),
                                  exact=sess.num_workers == 8)
    try:
        t_on = statistics.median(run(ledger, record=True)
                                 for _ in range(3))
    finally:
        telemetry.disable()
    overhead_pct = round(100.0 * (t_on - t_off) / t_off, 3)
    # the acceptance contract rides IN the row (pass flag), and main() exits
    # nonzero on failure AFTER committing the record — the failing number
    # must land in BENCH_local.json, not vanish into a swallowed assert
    return {"config": f"n={len(pts)} k={k} d={d} iters={iters} "
                      f"dispatch=1-iter-chunks",
            "off_iters_per_sec": round(iters / t_off, 1),
            "on_iters_per_sec": round(iters / t_on, 1),
            "overhead_pct": overhead_pct,
            "contract": "overhead_pct < 2.0 (ISSUE 7 acceptance)",
            "pass": bool(overhead_pct < 2.0),
            "telemetry_dir": tele_dir}


def tpu_ring_dma_overlap(small=False):
    """Fused ring-DMA overlap ablation (ISSUE 9 acceptance): hidden comm
    time on two ring workloads — the LDA wt-block rotation
    (benchmark/lda_overlap, fused twins) and ring attention
    (benchmark/ring_overlap). Each row carries unfused / rotation-ablated /
    fused timings plus ``fused_hidden_fraction`` = the share of the
    measured hop cost the in-kernel ``make_async_remote_copy`` transport
    hides. Returns None on a CPU-only host (null-with-note convention; the
    driver's on-chip run fills it — the fused kernels only exist on TPU,
    the CPU fallback is transport-identical to ppermute by design)."""
    import jax

    if all(d.platform == "cpu" for d in jax.devices()):
        return None
    from harp_tpu.benchmark import lda_overlap, ring_overlap
    from harp_tpu.session import HarpSession

    workers = HarpSession().num_workers
    row = {
        "lda_rotation": lda_overlap.measure(epochs=4 if small else 8,
                                            reps=3, fused=True),
        "ring_attention": ring_overlap.measure(
            l_local=2048 if small else 8192, reps=3),
    }
    if workers < 2:
        row["note"] = (
            f"single-device mesh (workers={workers}): ring hops are "
            f"self-loops, so the ablation degenerates — a >=2-chip run is "
            f"needed for a meaningful overlap fraction")
    return row


def tpu_serving(small=False):
    """Online-serving load rows (ISSUE 10 acceptance): p50/p99 latency +
    QPS at >=3 traffic mixes against a 2-worker local serving gang
    (harp_tpu/serve/ router + continuous micro-batcher + resident
    dispatches; benchmark/serving_load.py). The per-mix latency rows are
    published THROUGH telemetry (record_timing -> steps.jsonl, same
    percentile format as the straggler reports); the returned row carries
    the telemetry event count as proof. Unlike the pure-device groups this
    one always measures — the router/batcher stack is host-side — but the
    row's `device` field says what the dispatches ran on, and a CPU-mesh
    row carries the re-measure note for the driver's on-chip run."""
    import tempfile

    from harp_tpu import telemetry
    from harp_tpu.benchmark import serving_load
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    tele_dir = tempfile.mkdtemp(prefix="harp-bench-serve-")
    telemetry.configure(tele_dir, interval=1)
    try:
        row = serving_load.measure(
            sess, requests_per_mix=300 if small else 900, num_clients=3,
            trace_sample=4)
    finally:
        telemetry.disable()
    rank_file = os.path.join(tele_dir, "rank0", "steps.jsonl")
    n_events = n_spans = 0
    if os.path.exists(rank_file):
        with open(rank_file) as f:
            for line in f:
                n_events += '"kind": "timing"' in line
                n_spans += '"kind": "span"' in line
    row["telemetry_timing_events"] = n_events
    # the r13 proof the spans flowed THROUGH telemetry: every sampled
    # request's breakdown is also a kind:"span" JSONL event
    row["telemetry_span_events"] = n_spans
    row["telemetry_dir"] = tele_dir
    return row


def tpu_serving_quant(small=False):
    """Quantized-serving rows (ISSUE 17 acceptance): f32 vs int8 resident
    gangs at the recsys bench shapes (2048 users x 512 items, rank 64,
    k=10) — per-mix QPS/p99 for both modes measured by the same closed-
    loop machinery, per-model resident_bytes + the f32/int8 reduction
    ratio, and the sampled top-k overlap through the full quantized
    request path (int8 dispatch wire + f16-encoded replies). The
    acceptance bars (resident reduction >= 3x on the top-k model, mean
    overlap >= 0.95) are gated AFTER the record commits, like
    telemetry_overhead. resident_bytes and overlap are device-independent;
    a CPU-mesh row carries the latency re-measure note."""
    from harp_tpu.benchmark import serving_quant
    from harp_tpu.session import HarpSession

    return serving_quant.measure(
        HarpSession(), requests_per_mix=200 if small else 600,
        overlap_sample=64 if small else 128, num_clients=3)


def tpu_serving_fleet(small=False):
    """Fleet-operations rows (ISSUE 14 acceptance): the recovery-blip run
    (a SEPARATE-PROCESS serving gang under retrying load absorbs a
    scripted ``kill@request=N`` — spare restored through the on-device
    reshard engine, zero failed requests, the recovery-window p99 blip
    measured against steady state), the live-refresh run (factor epochs
    pushed mid-traffic through the versioned snapshot swap — torn reads
    asserted zero by checking every reply against ITS version's
    reference), and the hot-key run (Zipfian load, router reply cache off
    vs on — hit rate, lookup skew, and the hot subset's tail). See
    harp_tpu/benchmark/serving_fleet.py for the scenario scripts."""
    from harp_tpu.benchmark import serving_fleet
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    return {
        "recovery": serving_fleet.measure_recovery(
            requests_per_client=60 if small else 120),
        # ISSUE 15: the SAME scripted kill with a pre-warmed artifact
        # store — the elastic replacement loads every dispatch instead of
        # compiling (its post-mortem trace_counts ride the row), plus the
        # rolling-restart cold-start comparison (spawn -> first reply,
        # artifacts off vs on, with the worker's published stage split)
        "recovery_aot": serving_fleet.measure_recovery(
            requests_per_client=60 if small else 120,
            prebuild_artifacts=True),
        "refresh": serving_fleet.measure_refresh(
            sess, requests_per_client=100 if small else 200),
        "hotkey": serving_fleet.measure_hotkey(
            sess, requests_per_client=150 if small else 400,
            zipf_alpha=1.2),
        "restart": serving_fleet.measure_restart(
            repeats=2 if small else 3),
        # ISSUE 16: QPS ramp with the demand-driven autoscaler closing the
        # loop — worker count must follow the ramp up AND back down, the
        # scale-up journaled with its placement version, zero trace
        # counts, and AOT-store loads (the elastic worker never compiles).
        # Subprocess on the 8-device virtual mesh (reshard_bench idiom):
        # the restore-built movers and the AOT store's traced layouts only
        # agree at the fleet's real mesh width, not on this process's
        # possibly-single device
        "autoscale": _autoscale_subprocess(small),
    }


def _autoscale_subprocess(small=False):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                         " --xla_force_host_platform_device_count=8"
                         ).strip()}
    out = subprocess.run(
        [sys.executable, "-m", "harp_tpu.benchmark.serving_fleet",
         f"--ramp_hold_s={5.0 if small else 8.0}"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        return {"error": out.stderr[-500:]}
    return json.loads(out.stdout.strip().splitlines()[-1])


def tpu_reshard(small=False):
    """On-device reshard rows (ISSUE 11): seconds + bytes moved for a
    world-size-changing factor-table redistribution vs the PR 8 host
    gather-and-resplit on the same maps (harp_tpu/benchmark/reshard_bench).
    Two legs: ``cpu_mesh`` is MEASURED in a subprocess on the 8-worker
    virtual CPU mesh (the engine is backend-agnostic — same plan, same
    traced program shape as on chip), committed per the CPU-session
    convention; ``gb_scale`` is the multi-chip on-chip row (a >=2-chip
    mesh moving a GB-scale table over ICI) and stays null-with-note until
    the driver's on-chip run."""
    import jax

    rows, rank = (65536, 32) if small else (262144, 64)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                         " --xla_force_host_platform_device_count=8"
                         ).strip()}
    out = subprocess.run(
        [sys.executable, "-m", "harp_tpu.benchmark.reshard_bench",
         f"--rows={rows}", f"--rank={rank}"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=1200)
    if out.returncode != 0:
        return {"cpu_mesh": {"error": out.stderr[-500:]}, "gb_scale": None}
    cpu_row = json.loads(out.stdout.strip().splitlines()[-1])
    row = {"cpu_mesh": cpu_row}
    tpu_devs = [d for d in jax.devices() if d.platform != "cpu"]
    if len(tpu_devs) >= 2:
        from harp_tpu.benchmark import reshard_bench

        row["gb_scale"] = reshard_bench.measure(
            num_workers=len(tpu_devs), rows=2_097_152, rank=128,
            old_world=max(len(tpu_devs) // 2, 1))
    else:
        row["gb_scale"] = None
        row["gb_scale_note"] = (
            f"GB-scale on-chip reshard needs a >=2-chip mesh; this session "
            f"sees {len(tpu_devs)} non-CPU device(s) — the driver's "
            f"on-chip run fills it (rows=2097152 rank=128 f32 ~= 1 GB "
            f"table, chunk-bounded ICI rounds)")
    return row


def tpu_ingest(small=False):
    """Streaming-ingestion rows (ISSUE 18 acceptance): GB-scale part-file
    stream through the io/pipeline engine — load MB/s for the bounded-queue
    drain, serialized vs prefetch-overlapped twin walls (overlap_efficiency,
    gated >= 1.3x where overlap is physically available — see the row's
    overlap_gate/overlap_note), end-to-end stream->assemble->Lloyd-fit wall,
    the per-stage telemetry timer table, and the distributed COO->CSR
    regroup on the jaxlint-pinned ingest_coo_regroup all_to_all schedule.
    The host-side stages (read/parse/chunk) measure for real on any host;
    the compute/H2D columns of a CPU-mesh row carry the usual on-chip
    re-measure convention."""
    from harp_tpu.benchmark import ingest as bench_ingest

    if small:
        return bench_ingest.bench_ingest(
            total_mb=48, parts=6, chunk_rows=16384, fit_iters=2)
    return bench_ingest.bench_ingest()


def p2p_event_rtt_us(rounds=200):
    """Host event-plane round trip (send → wait_event → reply → wait): the
    latency the true P2P transport (authenticated, loopback) delivers.
    BenchmarkMapper's bcast row timed the reference's control-plane links;
    this times ours."""
    import statistics as st
    import threading

    from harp_tpu.parallel.events import EventQueue
    from harp_tpu.parallel.p2p import P2PTransport

    q0, q1 = EventQueue(), EventQueue()
    # loopback benchmark: bind 127.0.0.1 explicitly so the authenticated
    # transports never open an externally reachable port (ADVICE r4)
    t0_ = P2PTransport(q0, rank=0, peers={}, secret=b"bench",
                       host="127.0.0.1")
    t1_ = P2PTransport(q1, rank=1, peers={0: t0_.address}, secret=b"bench",
                       host="127.0.0.1")
    t0_._peers[1] = t1_.address

    def echo():
        for _ in range(rounds):
            ev = q1.wait(timeout=5.0)
            if ev is None:
                return                  # a lost frame ends the echo cleanly
            t1_.send(0, ev.payload)

    th = threading.Thread(target=echo, daemon=True)
    th.start()
    lat = []
    payload = b"x" * 256
    try:
        for _ in range(rounds):
            t = time.perf_counter()
            t0_.send(1, payload)
            if q0.wait(timeout=5.0) is None:
                break                   # echo died — stop, don't poison
            lat.append((time.perf_counter() - t) * 1e6)   # full round trip
    finally:
        th.join(timeout=10.0)
        t0_.close()
        t1_.close()
    if len(lat) < rounds // 2:
        raise RuntimeError(f"p2p rtt bench lost frames: only {len(lat)}/"
                           f"{rounds} round trips completed")
    return round(st.median(lat), 1)


# --------------------------------------------------------------------------- #
# Scaling + collectives (subprocess on the 8-device virtual CPU mesh)
# --------------------------------------------------------------------------- #

def mesh_scaling_and_collectives(timeout=1800):
    # 1800 s: the 1→64 sweep compiles 7 mesh widths and time-shares up to 64
    # virtual devices on what may be a single host core
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                         " --xla_force_host_platform_device_count=64").strip()}
    try:
        out = subprocess.run(
            [sys.executable, "-m", "harp_tpu.benchmark.scaling"],
            cwd=REPO, env=env, capture_output=True, text=True,
            timeout=timeout)
        if out.returncode == 0:
            return json.loads(out.stdout.strip().splitlines()[-1])
        return {"error": out.stderr[-500:]}
    except Exception as e:             # noqa: BLE001 — bench must not die here
        return {"error": str(e)}


# Row GROUPS --only can select (comma-separated). Each group is
# self-contained (its CPU anchor rides along); dependent keys reuse an
# already-measured group's result when both are selected.
ROW_GROUPS = ("kmeans", "kmeans_padded128", "kmeans_csr", "sgd_mf", "als",
              "pca", "lda", "lda_large", "lda_clueweb_subblock", "nn",
              "nn_compute_bound", "attention", "attention_blocksparse",
              "kernel_svm", "mds", "sort", "csr_cov", "kmeans_from_files",
              "p2p", "mesh", "collectives_quantized", "telemetry_overhead",
              "ring_dma_overlap", "serving", "serving_quant", "reshard",
              "ingest")


def main():
    argv = sys.argv[1:]
    if "--list-groups" in argv:
        # the discoverable twin of --only's validator: one group name per
        # line, nothing else — `bench.py --only "$(bench.py --list-groups
        # | ...)"` composes, and tier-1 pins this list to ROW_GROUPS
        for g in ROW_GROUPS:
            print(g)
        sys.exit(0)
    small = "--small" in argv
    only = None
    for i, a in enumerate(argv):
        if a == "--only":
            if i + 1 >= len(argv):
                # a bare --only must NOT silently fall through to the full
                # run (which rewrites the whole committed record)
                sys.stderr.write(
                    f"--only needs a value; valid: {','.join(ROW_GROUPS)}\n")
                sys.exit(2)
            only = argv[i + 1]
        elif a.startswith("--only="):
            only = a.split("=", 1)[1]
    if only is not None:
        selected = tuple(s.strip() for s in only.split(",") if s.strip())
        unknown = [s for s in selected if s not in ROW_GROUPS]
        if unknown or not selected:
            sys.stderr.write(
                f"--only: unknown row group(s) {unknown or only!r}; "
                f"valid: {','.join(ROW_GROUPS)}\n")
            sys.exit(2)
    else:
        selected = ROW_GROUPS
    run = set(selected)

    def want(name):
        return name in run

    detail = {"timing_method": (
        "two-point: rate from the wall-clock delta between a low and a high "
        "in-program iteration count (median of 3 alternating runs each) — "
        "the constant dispatch+D2H cost per call cancels and is "
        "recorded separately as fixed_dispatch_s; spread_pct = (max-min)/"
        "median of the high-count samples")}
    compact = {}

    # gc between ROW GROUPS: accumulated device-buffer pressure inside the
    # long bench process measurably perturbs later rows (r5 found
    # nn_compute_bound varying by seconds until a gc preceded it). The
    # boundary gc runs before every selected group, so a --only re-measure
    # of a single row sees the same freshly-collected state it would in the
    # full run.
    import gc

    started = []

    def begin(name):
        if started:
            gc.collect()
        started.append(name)

    # iteration counts: HIGH enough that each two-point delta carries
    # >= ~1-2 s of device time — the delta must stand clear of per-call
    # jitter (timing.py low_resolution note); scan-based epoch
    # loops make compile time independent of the count
    n, k, d = (100_000, 100, 100) if small else (1_000_000, 100, 100)
    tpu_iters = 50 if small else 2000
    cpu_iters = 2 if small else 3

    km = None
    if want("kmeans"):
        begin("kmeans")
        km = tpu_kmeans(n, k, d, tpu_iters)
        # bf16 point storage halves the E-step's dominant bytes;
        # accumulations stay f32 (kmeans.py compute_dtype contract)
        km_bf16 = tpu_kmeans(n, k, d, tpu_iters, compute_dtype="bfloat16")
        cpu_ips = cpu_kmeans_iters_per_sec(n, k, d, cpu_iters)
        detail.update({
            "kmeans": km, "kmeans_bf16": km_bf16,
            "kmeans_cpu_anchor_iters_per_sec": round(cpu_ips, 3)})
        compact.update({
            "metric": f"kmeans_regroupallgather_iters_per_sec_n{n}_k{k}_d{d}",
            "value": round(km["rate"], 1),
            "unit": "iters/s",
            "vs_baseline": round(km["rate"] / cpu_ips, 2),
            "kmeans_vs_xeon36_lb": xeon_lb(km["rate"] / cpu_ips),
            "kmeans_spread_pct": km["spread_pct"],
            "kmeans_bf16_iters_per_sec": round(km_bf16["rate"], 1)})

    if want("kmeans_padded128"):
        # the r6 lane-packing row: K and D padded to 128-lane MXU tiles
        # with masked phantom centroids (KMeansConfig.lane_pad — the
        # default, so the padded rate IS the flagship rate; measured fresh
        # if the kmeans group was filtered out) vs the same config with
        # lane_pad=False (the pre-r6 100-wide tiles), same two-point
        # protocol. The delta is pure layout: identical math, masked pads.
        begin("kmeans_padded128")
        km_pad = km if km is not None else tpu_kmeans(n, k, d, tpu_iters)
        km_nopad = tpu_kmeans(n, k, d, tpu_iters, lane_pad=False)
        detail["kmeans_padded128"] = km_pad
        detail["kmeans_lane_pad_off"] = km_nopad
        detail["kmeans_lane_pad_speedup"] = round(
            km_pad["rate"] / max(km_nopad["rate"], 1e-9), 3)
        compact["kmeans_padded128_iters_per_sec"] = round(km_pad["rate"], 1)
        compact["kmeans_lane_pad_speedup"] = (
            detail["kmeans_lane_pad_speedup"])

    if want("kmeans_csr"):
        begin("kmeans_csr")
        skm_n, skm_d = (16384, 128) if small else (262144, 256)
        skm = tpu_sparse_kmeans(skm_n, k, skm_d, density=0.05,
                                iters=20 if small else 400)
        detail["kmeans_csr"] = skm
        compact["kmeans_csr_iters_per_sec"] = round(skm["rate"], 1)

    if want("sgd_mf"):
        begin("sgd_mf")
        nu = 4096 if small else 32768
        sgd_epochs = 20 if small else 400
        sgd = tpu_sgd_mf(nu, nu, epochs=sgd_epochs)
        sgd_cpu = cpu_sgd_mf_samples_per_sec(nu, nu, epochs=1)
        # rank-128 config: fills the MXU's 128-lane tiles
        sgd128 = tpu_sgd_mf(nu, nu, epochs=sgd_epochs, rank=128)
        detail.update({
            "sgd_mf": sgd, "sgd_mf_rank128": sgd128,
            "sgd_mf_cpu_anchor_samples_per_sec": round(sgd_cpu)})
        compact.update({
            "sgd_mf_samples_per_sec": round(sgd["rate"]),
            "sgd_mf_vs_xeon36_lb": xeon_lb(sgd["rate"] / sgd_cpu),
            "sgd_mf_rank128_samples_per_sec": round(sgd128["rate"])})

    if want("als"):
        begin("als")
        an = 2048 if small else 8192
        als = tpu_als(an, an, iters=6 if small else 120)
        als_cpu = cpu_als_iters_per_sec(an, an, iters=1)
        # r10: the measured stage budget (solve share by ablation) rides
        # the als group — the thinnest north-star margin gets a row, not
        # an assertion
        als_stages = tpu_als_stage(an, an, iters=6 if small else 120,
                                   full_row=als)
        detail.update({
            "als": als, "als_cpu_anchor_iters_per_sec": round(als_cpu, 4),
            "als_stage_budget": als_stages})
        compact.update({
            "als_iters_per_sec": round(als["rate"], 2),
            "als_vs_xeon36_lb": xeon_lb(als["rate"] / als_cpu),
            "als_solve_share_pct": als_stages["solve_share_pct"]})

    if want("pca"):
        begin("pca")
        pn, pd = (32768, 64) if small else (262144, 256)
        pca = tpu_pca(pn, pd, repeats=50 if small else 1000)
        pca_cpu = cpu_pca_fits_per_sec(pn, pd, repeats=2)
        detail.update({
            "pca": pca, "pca_cpu_anchor_fits_per_sec": round(pca_cpu, 3)})
        compact.update({
            "pca_fits_per_sec": round(pca["rate"], 1),
            "pca_vs_xeon36_lb": xeon_lb(pca["rate"] / pca_cpu)})

    if want("lda"):
        begin("lda")
        ld, lv, ll_, lk = ((256, 300, 32, 8) if small
                           else (2048, 2000, 128, 32))
        lda = tpu_lda(ld, lv, ll_, lk, epochs=20 if small else 800)
        lda_cpu = cpu_lda_tokens_per_sec(ld // 4, lv, ll_, lk, epochs=1)
        detail.update({
            "lda": lda, "lda_cpu_anchor_tokens_per_sec": round(lda_cpu)})
        compact.update({
            "lda_tokens_per_sec": round(lda["rate"]),
            "lda_vs_xeon36_lb": xeon_lb(lda["rate"] / lda_cpu),
            "lda_spread_pct": lda["spread_pct"]})

    if want("lda_large"):
        begin("lda_large")
        # a clueweb-regime corpus (8x the tokens, 4x the vocab, 2x the
        # topics): per-token fixed costs amortize, so this is the throughput
        # a real LDA workload sees (the small config is BASELINE's toy shape)
        lda_big = None if small else tpu_lda(8192, 8000, 256, 64, epochs=100)
        detail["lda_large"] = lda_big
        compact["lda_large_tokens_per_sec"] = (
            None if lda_big is None else round(lda_big["rate"]))

    if want("lda_clueweb_subblock"):
        begin("lda_clueweb_subblock")
        # the r6 vocab-sub-block row: same clueweb-regime corpus, tokens
        # bucketized per 128-wide vocab sub-block so the scatter GEMM's
        # FLOPs scale with 128 instead of vpb=8064 (the measured r5
        # crossover config) — the row that cashes the 540M tokens/s
        # no-scatter ceiling. token_padding_overhead rides in the detail.
        lda_sub = None if small else tpu_lda(8192, 8000, 256, 64, epochs=100,
                                             vocab_sub_block=128)
        detail["lda_clueweb_subblock"] = lda_sub
        compact["lda_clueweb_subblock_tokens_per_sec"] = (
            None if lda_sub is None else round(lda_sub["rate"]))

    if want("nn"):
        begin("nn")
        nn_n, nn_d = (8192, 64) if small else (65536, 128)
        nn = tpu_nn(nn_n, nn_d, epochs=4 if small else 4000)
        nn_cpu = cpu_nn_samples_per_sec(nn_n, nn_d, epochs=1)
        detail.update({
            "nn": nn, "nn_cpu_anchor_samples_per_sec": round(nn_cpu)})
        compact.update({
            "nn_samples_per_sec": round(nn["rate"]),
            "nn_vs_xeon36_lb": xeon_lb(nn["rate"] / nn_cpu)})

    if want("nn_compute_bound"):
        # compute-bound NN config (VERDICT r4 weak #1): bigger batch +
        # hidden sizes — still mini-batch allreduce SGD
        # (NNDaalCollectiveMapper.java:47), but the per-step GEMMs are large
        # enough that the MXU, not allreduce latency, sets the floor. The
        # begin() gc matters most here (biggest-footprint config; r5 saw
        # multi-second variance from accumulated HBM pressure without it).
        begin("nn_compute_bound")
        if small:
            nn_big, nn_big_cpu = None, None
        else:
            nn_big = tpu_nn(65536, 512, epochs=150, layers=(2048, 1024),
                            batch_size=8192)
            nn_big_cpu = cpu_nn_samples_per_sec(65536, 512, epochs=1,
                                                layers=(2048, 1024),
                                                batch_size=8192)
        detail.update({
            "nn_compute_bound": nn_big,
            "nn_compute_bound_cpu_anchor": (None if nn_big_cpu is None
                                            else round(nn_big_cpu))})
        compact.update({
            "nn_compute_bound_samples_per_sec": (
                None if nn_big is None else round(nn_big["rate"])),
            "nn_compute_bound_vs_xeon36_lb": (
                None if nn_big is None
                else xeon_lb(nn_big["rate"] / nn_big_cpu)),
            "nn_compute_bound_mfu_pct": (
                None if nn_big is None else nn_big["mfu_pct"])})

    attn = None
    if want("attention"):
        begin("attention")
        attn_l = 2048 if small else 16384
        attn = tpu_attention(l=attn_l, reps=100 if small else 200)
        detail.update({
            "attention": attn,
            "attention_config": (
                f"blocked causal L={attn_l} H=8 Dh=64 (1 chip)")})
        compact["attention_tokens_per_sec"] = round(attn["rate"])

    if want("attention_blocksparse"):
        # r7 rows, three legs of the flash rebuild at the r5 bench shape
        # (L=16k causal; VERDICT r5 #1 target >= 2M tokens/s at Dh=64):
        #  * blocksparse — trapezoid grid alone (head packing pinned OFF):
        #    comparable head-to-head with the r5 1.10M row, isolates the
        #    dead-block DMA removal;
        #  * headpacked — trapezoid + two-heads-per-128-lane packing: the
        #    Dh=64 DEFAULT dispatch, i.e. the SAME config the attention
        #    group times — reused when both groups run (one number, not two
        #    drifting copies of it), measured fresh only under --only;
        #  * dh128 — Dh=128 heads (no packing applies: lanes already full),
        #    quantifying what the Dh=64 padding cost either way.
        # --small pins L=2048, BELOW the use_flash_pallas L>=8192 crossover:
        # every leg would time the XLA scan and the legs' deltas would be
        # scheduler noise wearing kernel labels — emit null instead.
        begin("attention_blocksparse")
        if small:
            bs = hp = d128 = None
        else:
            bs = tpu_attention(l=16384, reps=200, head_pack=False)
            hp = attn if attn is not None else tpu_attention(l=16384,
                                                             reps=200)
            d128 = tpu_attention(l=16384, h=4, dh=128, reps=200)
        detail.update({
            "attention_causal_blocksparse": bs,
            "attention_headpacked": hp,
            "attention_dh128": d128})
        compact.update({
            "attention_causal_blocksparse_tokens_per_sec": (
                None if bs is None else round(bs["rate"])),
            "attention_headpacked_tokens_per_sec": (
                None if hp is None else round(hp["rate"])),
            "attention_dh128_tokens_per_sec": (
                None if d128 is None else round(d128["rate"]))})

    if want("kernel_svm"):
        # r4-component rows (VERDICT r4 weak #5: implemented but
        # unbenchmarked)
        begin("kernel_svm")
        svm_n, svm_d, svm_it = ((2048, 16, 200) if small
                                else (16384, 32, 1000))
        ksvm = tpu_kernel_svm(svm_n, svm_d, svm_it)
        detail["kernel_svm"] = ksvm
        compact["kernel_svm_iters_per_sec"] = round(ksvm["rate"], 1)

    if want("mds"):
        begin("mds")
        mds_row = tpu_mds(1024 if small else 4096,
                          iterations=100 if small else 600)
        detail["mds"] = mds_row
        compact["mds_iters_per_sec"] = round(mds_row["rate"], 1)

    if want("sort"):
        begin("sort")
        sort_row = tpu_distributed_sort(1 << 20 if small else 1 << 22,
                                        repeats=20 if small else 200)
        detail["distributed_sort"] = sort_row
        compact["sort_rows_per_sec"] = round(sort_row["rate"])

    if want("csr_cov"):
        begin("csr_cov")
        cc_n, cc_d = (16384, 128) if small else (262144, 256)
        csr_cov = tpu_csr_cov(cc_n, cc_d, density=0.05,
                              repeats=50 if small else 400)
        detail["csr_covariance"] = csr_cov
        compact["csr_cov_per_sec"] = round(csr_cov["rate"], 1)

    if want("kmeans_from_files"):
        begin("kmeans_from_files")
        km_files = kmeans_from_files(n=16384 if small else 131072,
                                     d=64, k=64, iters=20)
        detail["kmeans_from_files"] = km_files
        compact["load_native_mb_per_sec"] = km_files["load_native_mb_per_sec"]

    if want("p2p"):
        begin("p2p")
        try:
            rtt_us = p2p_event_rtt_us()
        except Exception as e:         # noqa: BLE001 — bench must not die here
            rtt_us = {"error": str(e)[:200]}
        detail["p2p_event_rtt_us"] = rtt_us
        compact["p2p_event_rtt_us"] = rtt_us

    if want("mesh"):
        begin("mesh")
        mesh = mesh_scaling_and_collectives()
        detail.update({
            "scaling_efficiency": mesh.get("scaling_efficiency", mesh),
            "collectives_8w_cpu_mesh": mesh.get("collectives", {})})

    if want("collectives_quantized"):
        begin("collectives_quantized")
        try:
            qrows = tpu_collectives_quantized(small)
        except Exception as e:     # noqa: BLE001 — bench must not die here
            qrows = {"error": str(e)[:200]}
        detail["collectives_quantized"] = qrows
        if isinstance(qrows, list):
            for r in qrows:
                if r["op"] == "allreduce" and r["codec"] in ("int8", "bf16"):
                    compact[f"allreduce_{r['codec']}_busbw_gbps"] = (
                        r["busbw_gbps"])

    if want("telemetry_overhead"):
        begin("telemetry_overhead")
        try:
            trow = tpu_telemetry_overhead(small)
        except Exception as e:     # noqa: BLE001 — bench must not die here
            trow = {"error": str(e)[:200]}
        detail["telemetry_overhead"] = trow
        if trow is None:
            detail["bench_schema_note_r9"] = (
                "r9 adds the telemetry_overhead group (bench.py --only "
                "telemetry_overhead): kmeans fit loop in 1-iteration "
                "dispatch chunks with and without harp_tpu.telemetry "
                "record_chunk + comm-ledger at every boundary; the row "
                "asserts the on/off delta < 2% (ISSUE 7 acceptance) — "
                "committed null because no TPU was reachable from this "
                "session (CPU-only devices); the driver's on-chip bench "
                "run fills it. The CPU-flavor contract (telemetry per-step "
                "cost < 2% of a measured kmeans step) IS asserted in "
                "tier-1: tests/test_telemetry.py "
                "test_telemetry_overhead_cpu_smoke.")
        elif isinstance(trow, dict) and "overhead_pct" in trow:
            compact["telemetry_overhead_pct"] = trow["overhead_pct"]
            compact["telemetry_overhead_pass"] = trow["pass"]

    if want("ring_dma_overlap"):
        begin("ring_dma_overlap")
        try:
            rrow = tpu_ring_dma_overlap(small)
        except Exception as e:     # noqa: BLE001 — bench must not die here
            rrow = {"error": str(e)[:200]}
        detail["ring_dma_overlap"] = rrow
        if rrow is None:
            detail["bench_schema_note_r10"] = (
                "r10 adds the ring_dma_overlap group (bench.py --only "
                "ring_dma_overlap): the fused ring-DMA overlap ablation on "
                "two ring workloads — LDA wt-block rotation "
                "(benchmark/lda_overlap fused twins) and ring attention "
                "(benchmark/ring_overlap) — each row carrying unfused / "
                "rotation-ablated / fused timings and "
                "fused_hidden_fraction. Committed null because no TPU was "
                "reachable from this session (CPU-only devices; the fused "
                "make_async_remote_copy kernels only lower on TPU, and "
                "the CPU fallback is transport-identical to ppermute by "
                "design so its delta is dispatch noise). The driver's "
                "on-chip run fills it; fused == unfused bitwise parity "
                "and the row schema ARE asserted in tier-1 "
                "(tests/test_ring_dma.py). The als group also gains "
                "als_stage_budget (solve share by ALSConfig.ablate_solve "
                "ablation) — measured whenever the als group runs; null "
                "for the same no-TPU reason until the driver's run.")
        elif isinstance(rrow, dict) and "ring_attention" in rrow:
            compact["ring_dma_lda_hidden_fraction"] = (
                rrow["lda_rotation"].get("fused_hidden_fraction"))
            compact["ring_dma_attn_hidden_fraction"] = (
                rrow["ring_attention"].get("fused_hidden_fraction"))

    if want("serving"):
        begin("serving")
        try:
            srow = tpu_serving(small)
        except Exception as e:     # noqa: BLE001 — bench must not die here
            srow = {"error": str(e)[:200]}
        detail["serving"] = srow
        if isinstance(srow, dict) and "mixes" in srow:
            mixed = srow["mixes"].get("mixed", {})
            compact.update({
                "serving_mixed_p50_ms": mixed.get("p50_ms"),
                "serving_mixed_p99_ms": mixed.get("p99_ms"),
                "serving_mixed_qps": mixed.get("qps"),
                "serving_device": srow.get("device")})
            rec = srow.get("reconciliation") or {}
            sb = srow.get("stage_breakdown") or {}
            compact.update({
                "serving_dispatch_p50_ms": sb.get("dispatch",
                                                  {}).get("p50_ms"),
                "serving_span_p50_ratio": rec.get("p50_ratio"),
                "serving_span_mean_ratio": rec.get("mean_ratio")})
        # r15 fleet rows (ISSUE 14): recovery blip (separate-process gang,
        # scripted kill, reshard-engine spare restore), live refresh under
        # load (versioned swap, torn reads asserted zero), hot-key cache
        # vs the unmitigated Zipfian baseline
        begin("serving_fleet")
        try:
            frow = tpu_serving_fleet(small)
        except Exception as e:     # noqa: BLE001 — bench must not die here
            frow = {"error": str(e)[:200]}
        detail["serving_fleet"] = frow
        if isinstance(frow, dict) and "recovery" in frow:
            rec_row = frow["recovery"]
            rec_aot = frow.get("recovery_aot", {})
            ref_row = frow.get("refresh", {})
            hot_row = frow.get("hotkey", {})
            rst_row = frow.get("restart", {})
            compact.update({
                "fleet_recovery_errors": rec_row.get("errors"),
                "fleet_recovery_s": rec_row.get("observed_recovery_s"),
                "fleet_recovery_p99_blip_ms":
                    (rec_row.get("recovery_window") or {}).get("p99_ms"),
                "fleet_recovery_aot_s": rec_aot.get("observed_recovery_s"),
                "restart_to_first_reply_s":
                    (rst_row.get("no_aot") or {}).get(
                        "restart_to_first_reply_s"),
                "restart_to_first_reply_aot_s":
                    (rst_row.get("aot") or {}).get(
                        "restart_to_first_reply_s"),
                "fleet_refresh_torn_reads": ref_row.get("torn_reads"),
                "fleet_refresh_errors": ref_row.get("errors"),
                "fleet_hotkey_hit_rate":
                    ((hot_row.get("cached") or {}).get("cache")
                     or {}).get("hit_rate"),
                "fleet_hotkey_hot_p99_speedup":
                    hot_row.get("hot_p99_speedup")})
            asc_row = frow.get("autoscale", {})
            asc_up = asc_row.get("scale_up") or {}
            compact.update({
                "fleet_autoscale_errors": asc_row.get("errors"),
                "fleet_autoscale_wrong": asc_row.get("wrong_results"),
                "fleet_autoscale_peak_workers":
                    asc_row.get("peak_workers"),
                "fleet_autoscale_final_workers":
                    asc_row.get("final_workers"),
                "fleet_autoscale_up_trace_count":
                    (sum(asc_up["trace_counts"].values())
                     if asc_up.get("trace_counts") else None)})

    if want("serving_quant"):
        begin("serving_quant")
        try:
            qsrow = tpu_serving_quant(small)
        except Exception as e:     # noqa: BLE001 — bench must not die here
            qsrow = {"error": str(e)[:200]}
        detail["serving_quant"] = qsrow
        detail["bench_schema_note_r17"] = (
            "r17 adds the serving_quant group (bench.py --only "
            "serving_quant): f32 vs int8 resident serving gangs at the "
            "recsys bench shapes (2048x512, rank 64, k=10) — per-mix "
            "QPS/p99 for both modes, per-model resident_bytes with the "
            "f32/int8 reduction ratio, and the sampled top-k overlap "
            "through the full quantized path (int8 dispatch wire, "
            "f16-encoded replies). resident_bytes and overlap are "
            "device-independent; on a CPU-mesh session the latency "
            "columns price CPU dispatches and the driver's on-chip run "
            "re-measures them (same schema, device='tpu').")
        if isinstance(qsrow, dict) and "resident_reduction" in qsrow:
            i8 = qsrow["modes"]["int8"]["mixes"].get("topk_heavy", {})
            f32 = qsrow["modes"]["f32"]["mixes"].get("topk_heavy", {})
            compact.update({
                "serving_quant_topk_reduction":
                    qsrow["resident_reduction"].get("topk"),
                "serving_quant_overlap_mean":
                    qsrow["topk_overlap"]["mean"],
                "serving_quant_int8_p99_ms": i8.get("p99_ms"),
                "serving_quant_f32_p99_ms": f32.get("p99_ms"),
                "serving_quant_device": qsrow.get("device")})

    if want("reshard"):
        begin("reshard")
        try:
            rsrow = tpu_reshard(small)
        except Exception as e:     # noqa: BLE001 — bench must not die here
            rsrow = {"error": str(e)[:200]}
        detail["reshard"] = rsrow
        cpu_mesh = rsrow.get("cpu_mesh") if isinstance(rsrow, dict) else None
        if isinstance(cpu_mesh, dict) and "reshard_seconds" in cpu_mesh:
            compact.update({
                "reshard_seconds": cpu_mesh["reshard_seconds"],
                "reshard_bytes_moved": cpu_mesh["reshard_bytes_moved"],
                "reshard_host_vs_device_speedup":
                    cpu_mesh["host_vs_device_speedup"]})

    if want("ingest"):
        begin("ingest")
        try:
            irow = tpu_ingest(small)
        except Exception as e:     # noqa: BLE001 — bench must not die here
            irow = {"error": str(e)[:200]}
        detail["ingest"] = irow
        detail["bench_schema_note_r19"] = (
            "r19 adds the ingest group (bench.py --only ingest): the "
            "streaming ingestion engine (io/pipeline) at the ~1 GB "
            "part-file size — stream_load_mb_per_sec for the full "
            "bounded-queue drain, the serialized (prefetch-off) vs "
            "overlapped twin walls with overlap_efficiency, the "
            "end-to-end stream->assemble->fit wall, the per-stage timer "
            "table (list/count/read/parse/chunk/regroup/h2d/compute), "
            "and the distributed COO->CSR regroup row (device all_to_all "
            "on the jaxlint-pinned ingest_coo_regroup budget schedule). "
            "The overlap >= 1.3x acceptance gate applies where overlap "
            "is physically available (overlap_gate='on': multi-core host "
            "or accelerator compute); on this 1-core CPU host the twins "
            "time-share one core, the measured ratio rides in the row "
            "and the driver's on-chip run re-measures it — same "
            "convention as the telemetry_overhead/ring_dma_overlap "
            "rows.")
        if isinstance(irow, dict) and "stream_load_mb_per_sec" in irow:
            compact.update({
                "ingest_load_mb_per_sec": irow["stream_load_mb_per_sec"],
                "ingest_overlap_efficiency": irow["overlap_efficiency"],
                "ingest_e2e_wall_s": irow["e2e_stream_fit_wall_s"]})

    detail["xeon_anchor_note"] = (
        f"vs_cpu = measured vs ONE modern Zen core (this host has 1 "
        f"core); vs_xeon36_lb = vs_cpu/{XEON_CORES}, a conservative "
        f"lower bound on the ratio vs BASELINE.md's 2x18-core Haswell "
        f"(assumes perfect 36x anchor scaling AND Haswell==Zen "
        f"per-core; both favor the Xeon)")

    # a filtered run MERGES into the existing record (re-measuring one row
    # must not wipe the others); a full run rewrites it
    path = os.path.join(REPO, "BENCH_local.json")
    full = {}
    if only is not None and os.path.exists(path):
        try:
            with open(path) as f:
                full = json.load(f)
        except Exception:              # noqa: BLE001 — corrupt file: rewrite
            full = {}
    full.update(detail)
    with open(path, "w") as f:
        json.dump(full, f, indent=1)

    # compact driver line: headline + one rate per workload; full numbers,
    # configs, spreads and notes live in BENCH_local.json
    compact.update({
        "timing": "two-point (fixed per-call dispatch cost cancelled); "
                  "full detail in BENCH_local.json",
        "detail_file": "BENCH_local.json",
    })
    if only is not None:
        compact["only"] = ",".join(selected)
    print(json.dumps(compact))

    # acceptance-gated rows fail the bench AFTER the record is committed —
    # the number is on disk either way, and CI sees the breach
    trow = detail.get("telemetry_overhead")
    if isinstance(trow, dict) and trow.get("pass") is False:
        sys.stderr.write(
            f"bench: telemetry_overhead contract FAILED "
            f"({trow['overhead_pct']}% >= 2%)\n")
        sys.exit(1)
    qsrow = detail.get("serving_quant")
    if isinstance(qsrow, dict) and "resident_reduction" in qsrow:
        red = qsrow["resident_reduction"].get("topk") or 0.0
        ovl = qsrow["topk_overlap"]["mean"]
        if red < 3.0 or ovl < 0.95:
            sys.stderr.write(
                f"bench: serving_quant contract FAILED (topk resident "
                f"reduction {red}x < 3x or overlap {ovl} < 0.95)\n")
            sys.exit(1)
    irow = detail.get("ingest")
    if (isinstance(irow, dict) and irow.get("overlap_gate") == "on"
            and irow.get("overlap_pass") is False):
        sys.stderr.write(
            f"bench: ingest overlap contract FAILED (efficiency "
            f"{irow['overlap_efficiency']}x < 1.3x with overlap gate on)\n")
        sys.exit(1)


if __name__ == "__main__":
    main()
