"""Alternating least squares — explicit and implicit (confidence-weighted).

Reference parity: daal_als (ALSDaalCollectiveMapper.java:49 — implicit ALS on CSR
with DAAL's 4 distributed train steps; Harp allgather:336 + bcast of step2/step3
partial results:396-490) and daal_als_batch.

TPU-native: the factor matrices stay REPLICATED between half-iterations (they are
small: entities × rank); each half-iteration a worker solves the normal equations
for its shard of users (then items) as one batched Cholesky solve on the MXU, and
one all_gather re-replicates the updated factor — DAAL's step1-4 dance collapses
to "batched local solve + allgather".

Duplicate (row, col) pairs are dropped (keep-first) in ``prepare`` for BOTH
layouts so the two paths always train on the identical entry set (the
sgd_mf contract); the count is in ``last_layout_stats["duplicates_dropped"]``.

Dual layout (the dense-SGD-MF pattern applied to ALS): ``layout="dense"``
stores the rating matrix as NaN-encoded bf16 planes and computes each side's
normal equations as two big GEMMs (conf @ VV and a weighted @ V) instead of
per-entry factor-row gathers (128-byte granules, the TPU sparse-access wall);
auto-selected when both planes fit HARP_ALS_DENSE_MAX_BYTES. Either way the
batched k×k solve dominates on TPU — see ALSConfig.solver for the measured
story.

Sparse layout (SURVEY §7 recipe, skew-robust): ragged observed-entry lists become
**capped chunks** — a row's entries split into chunks of at most
``chunk_factor × mean`` entries, each chunk computing a partial Gram/RHS that a
``segment_sum`` combines per row before the solve. A Zipf head row therefore
costs proportionally more chunks instead of inflating every row's padding
(the round-1 ``pad_csr_lists`` padded all rows to the global max row length);
rows are dealt to workers by balanced (serpentine-LPT) entry counts. The
reference ingested exactly such power-law CSR data
(HarpDAALDataSource.regroupCOOList:399).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu import telemetry
from harp_tpu.collectives import lax_ops
from harp_tpu.parallel.mesh import WORKERS
from harp_tpu.session import HarpSession


@dataclasses.dataclass(frozen=True)
class ALSConfig:
    rank: int = 10
    lam: float = 0.1            # L2 (DAAL: lambda)
    alpha: float = 40.0         # implicit confidence weight (DAAL: alpha)
    iterations: int = 10
    implicit: bool = True
    balance: bool = True        # serpentine-LPT row→worker assignment
    chunk_factor: float = 2.0   # chunk cap = ceil(chunk_factor * mean entries)
    solver: str = "auto"        # auto | pallas | cholesky | newton — how the
    #   batched k×k SPD normal equations are solved. The solve DOMINATED ALS
    #   on TPU through r3 (measured ablation, PERF.md: the bench iteration
    #   was 70 ms with the solve and 9.6 ms without): XLA's batched-solve
    #   lowering serializes on k and underfills the MXU, so Cholesky ≈
    #   Newton–Schulz ≈ 30 ms per (8192, 32, 32)-batch solve pair despite
    #   the solve being only ~180 MFLOP. "pallas" is the r4 fix — a
    #   lane-vectorized batched Cholesky (ops/pallas_kernels.spd_solve_pallas:
    #   batch on the 128-lane axis, unrolled outer-product factorization +
    #   substitutions, pure full-width VPU work) that makes the solve
    #   HBM-bound. "auto" = pallas on TPU at k ≤ 64, else cholesky (exact
    #   XLA path); "newton" (pure batched GEMMs, Precision.HIGHEST — TPU's
    #   default bf16 multiply floors its quadratic convergence at ~1e-1) is
    #   kept as the measured alternative.
    newton_iters: int = 30
    layout: str = "auto"        # auto | dense | sparse — "dense" stores the
    #   rating matrix as NaN-encoded bf16 planes and computes each side's
    #   normal equations as two big GEMMs (conf @ VV and weighted @ V): the
    #   sparse path's factor-row gathers are 128 B granules (~25M rows/s,
    #   the same wall dense SGD-MF hit), while the dense A-GEMM runs the
    #   MXU at matrix-matrix rates. NOTE the bf16 planes QUANTIZE the stored
    #   ratings to ~3 significant digits (8-bit mantissa: integer counts
    #   above 256 and finely-graded explicit ratings round) — fine for
    #   implicit confidence weights, a real numeric change for explicit
    #   regression targets. "auto" therefore picks dense only in IMPLICIT
    #   mode (when this worker's plane share fits dense_max_bytes) and
    #   keeps explicit-rating runs on the exact f32 sparse path; request
    #   layout="dense" explicitly to accept the quantization there
    dense_max_bytes: int = 2 * 1024 ** 3  # per-WORKER budget for the two
    #   bf16 plane shards (the SGDMFConfig.dense_max_bytes convention)
    ablate_solve: bool = False  # timing ablation ONLY (r10, the ALS stage
    #   budget bench row): skip the batched k×k SPD solve — x = b rides
    #   through identity — so bench.py can price the solve stage by
    #   difference (the r3/r4 PERF ablation, now a reproducible row instead
    #   of a one-off). Results are WRONG; never use outside timing.


def pad_csr_lists(rows, cols, vals, num_rows, num_workers):
    """(entity → padded neighbor list): idx (R_pad, M), val (R_pad, M), mask.

    Round-1 layout (pads every row to the global max row length) — kept for
    callers with uniform data; ALS itself uses :func:`pad_csr_chunks`."""
    order = np.argsort(rows, kind="stable")
    r, c, v = rows[order], cols[order], vals[order]
    rpw = -(-num_rows // num_workers)
    r_pad = rpw * num_workers
    counts = np.bincount(r, minlength=r_pad)
    m = max(int(counts.max()), 1)
    idx = np.zeros((r_pad, m), np.int32)
    val = np.zeros((r_pad, m), np.float32)
    mask = np.zeros((r_pad, m), np.float32)
    starts = np.concatenate([[0], np.cumsum(counts)])
    pos = np.arange(len(r)) - starts[r]          # slot within each row bucket
    idx[r, pos] = c
    val[r, pos] = v
    mask[r, pos] = 1.0
    return idx, val, mask


def pad_csr_chunks(rows, cols, vals, num_rows, num_workers,
                   chunk_factor: float = 2.0, balance: bool = True):
    """Skew-robust CSR layout: capped chunks + per-row segment ids.

    Returns (idx (W, NC, C), val, mask, chunk_row (W, NC) local row slot,
    (row_bin, row_slot), rpw, stats). Padded chunks point at slot 0 with an
    all-zero mask.
    """
    from harp_tpu.models.sgd_mf import identity_assign, serpentine_assign

    nnz = len(rows)
    counts_global = np.bincount(rows, minlength=num_rows)
    if balance and nnz:
        row_bin, row_slot = serpentine_assign(counts_global, num_workers)
    else:
        row_bin, row_slot = identity_assign(num_rows, num_workers)
    rpw = -(-num_rows // num_workers)
    cap = max(1, int(np.ceil(chunk_factor * max(nnz, 1)
                             / max(num_rows, 1))))
    # order entries by (worker, row slot); chunks are consecutive runs of cap
    owner = row_bin[rows]
    slot = row_slot[rows]
    order = np.lexsort((slot, owner))
    o_own, o_slot = owner[order], slot[order]
    o_cols, o_vals = cols[order], vals[order]
    # position of each entry within its row  →  chunk id within the row
    row_key = o_own.astype(np.int64) * rpw + o_slot
    starts = np.concatenate([[0], np.cumsum(np.bincount(
        row_key, minlength=num_workers * rpw))])
    pos_in_row = np.arange(nnz) - starts[row_key]
    chunk_of_entry = pos_in_row // cap
    pos_in_chunk = pos_in_row % cap
    # number the chunks per worker
    n_chunks_per_row = -(-counts_global // cap)      # per global row id
    chunks_per_worker = np.zeros(num_workers, np.int64)
    np.add.at(chunks_per_worker, row_bin, n_chunks_per_row)
    nc = max(int(chunks_per_worker.max()), 1)
    # chunk index within worker: cumulative chunks of earlier slots + chunk id
    chunk_base = np.zeros((num_workers, rpw), np.int64)
    np.add.at(chunk_base, (row_bin, row_slot), n_chunks_per_row)
    chunk_base = np.cumsum(chunk_base, axis=1) - chunk_base
    entry_chunk = chunk_base[o_own, o_slot] + chunk_of_entry

    idx = np.zeros((num_workers, nc, cap), np.int32)
    val = np.zeros((num_workers, nc, cap), np.float32)
    mask = np.zeros((num_workers, nc, cap), np.float32)
    chunk_row = np.zeros((num_workers, nc), np.int32)
    idx[o_own, entry_chunk, pos_in_chunk] = o_cols
    val[o_own, entry_chunk, pos_in_chunk] = o_vals
    mask[o_own, entry_chunk, pos_in_chunk] = 1.0
    chunk_row[o_own, entry_chunk] = o_slot
    stats = {"padded": int(idx.size), "nnz": nnz,
             "overhead": idx.size / max(nnz, 1), "chunk_cap": cap}
    return idx, val, mask, chunk_row, (row_bin, row_slot), rpw, stats


def _resolve_solver(cfg: ALSConfig) -> str:
    if cfg.solver not in ("auto", "pallas", "cholesky", "newton"):
        raise ValueError(f"solver must be auto|pallas|cholesky|newton, got "
                         f"{cfg.solver!r}")
    if cfg.solver != "auto":
        return cfg.solver
    from harp_tpu.ops.pallas_kernels import use_spd_solve_pallas

    # measured on v5e (PERF.md r4): the lane-vectorized pallas Cholesky
    # breaks the XLA batched-solve plateau; where it doesn't apply,
    # cholesky ties or beats newton at every batch shape tried and is exact
    return "pallas" if use_spd_solve_pallas(cfg.rank) else "cholesky"


def _spd_solve(a, b, cfg: ALSConfig):
    """Solve the batched SPD systems ``a @ x = b`` (a: (N, K, K), b: (N, K)).

    newton: X_{t+1} = X_t (2I − A X_t) from X_0 = I / ||A||_inf — for SPD A
    the row-sum norm bounds λ_max, so ||I − X_0 A||_2 = 1 − λ_min/||A||_inf
    < 1 and the error squares every round: ~log2(cond) + 5 rounds reach f32
    accuracy (30 rounds cover cond ≤ ~3e7; ALS regularizes with λI so cond
    ≤ λ_max/λ). Every op is a batched GEMM — but measured on v5e this buys
    nothing over Cholesky: batched (8192, 32, 32) operands underfill the
    MXU for both, ~30 ms per solve pair either way (ALSConfig.solver note,
    PERF.md r3). Kept as the measured alternative and for platforms where
    batched triangular solves lower worse."""
    if cfg.ablate_solve:
        # stage-budget ablation: keep A's construction live (consume it so
        # XLA cannot dead-code the gram/normal-equation stages) but skip
        # the solve itself — identity plus a free first-column touch
        return b + 0.0 * a[..., 0]
    solver = _resolve_solver(cfg)
    if solver == "pallas":
        from harp_tpu.ops import pallas_kernels

        # explicit request off-TPU runs the kernel in interpret mode (slow
        # but exact — the path CI and the CPU mesh exercise); 'auto' never
        # resolves here off-TPU
        interpret = jax.default_backend() != "tpu"
        return pallas_kernels.spd_solve_pallas(a, b, interpret=interpret)
    if solver == "cholesky":
        return jax.scipy.linalg.solve(a, b[..., None], assume_a="pos")[..., 0]
    k = a.shape[-1]
    eye = jnp.eye(k, dtype=a.dtype)
    norminf = jnp.max(jnp.sum(jnp.abs(a), axis=-1), axis=-1)
    x = (1.0 / norminf)[..., None, None] * eye
    # full f32 multiply precision is LOAD-BEARING here: TPU's default
    # bf16-multiply f32 matmul floors the NS error at ~1e-1 (measured — the
    # iteration squares its error each round, so truncation noise persists)
    hi = jax.lax.Precision.HIGHEST

    def step(x, _):
        ax = jnp.matmul(a, x, precision=hi)
        x = jnp.matmul(x, 2.0 * eye - ax, precision=hi)
        return x, ()

    x, _ = jax.lax.scan(step, x, None, length=cfg.newton_iters)
    return jnp.matmul(x, b[..., None], precision=hi)[..., 0]


def _half_step(factor_other, idx, val, mask, chunk_row, rpw: int,
               cfg: ALSConfig):
    """Solve this worker's block of one side's normal equations.

    factor_other: replicated (E_other, K) in the OTHER side's permuted slot
    order (idx entries are pre-remapped on the host). idx/val/mask:
    (NC, C) capped chunks; chunk_row: (NC,) local row slot per chunk.
    Returns the updated local block (rpw, K)."""
    k = cfg.rank
    vi = factor_other[idx] * mask[..., None]     # (NC, C, K)
    if cfg.implicit:
        # Hu, Koren, Volinsky: A = V'V + V'(C−I)V + λI;  b = V'C·p (p=1 observed)
        conf = cfg.alpha * val * mask            # c − 1
        a_part = jnp.einsum("cmk,cm,cml->ckl", vi, conf, vi)
        b_part = jnp.einsum("cmk,cm->ck", vi, (1.0 + conf) * mask)
    else:
        # explicit: normal equations over observed entries only
        a_part = jnp.einsum("cmk,cml->ckl", vi, vi)
        b_part = jnp.einsum("cmk,cm->ck", vi, val * mask)
    a = jax.ops.segment_sum(a_part, chunk_row, num_segments=rpw)
    b = jax.ops.segment_sum(b_part, chunk_row, num_segments=rpw)
    if cfg.implicit:
        gram = jax.lax.dot_general(              # V'V over ALL entities
            factor_other, factor_other, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        a = a + gram[None]
    a = a + cfg.lam * jnp.eye(k, dtype=a.dtype)[None]
    return _spd_solve(a, b, cfg)


def _train(u_data, i_data, u0, v0, u_rpw: int, i_rpw: int, cfg: ALSConfig,
           axis_name: str = WORKERS):
    u_idx, u_val, u_mask, u_crow = u_data
    i_idx, i_val, i_mask, i_crow = i_data

    def iteration(carry, _):
        u, v = carry                             # both replicated (E, K)
        # users half-step: local block solve, then re-replicate
        u_block = _half_step(v, u_idx, u_val, u_mask, u_crow, u_rpw, cfg)
        u = lax_ops.allgather(u_block, axis_name)
        v_block = _half_step(u, i_idx, i_val, i_mask, i_crow, i_rpw, cfg)
        v = lax_ops.allgather(v_block, axis_name)
        # monitor: squared error on observed entries of the user-side chunks
        pred = jnp.einsum("cmk,ck->cm", v[u_idx] * u_mask[..., None],
                          u_block[u_crow])
        tgt = u_val if not cfg.implicit else (u_mask * 1.0)
        sse = jax.lax.psum(jnp.sum(u_mask * (tgt - pred) ** 2), axis_name)
        cnt = jax.lax.psum(jnp.sum(u_mask), axis_name)
        return (u, v), jnp.sqrt(sse / jnp.maximum(cnt, 1.0))

    (u, v), rmse = jax.lax.scan(iteration, (u0, v0), None,
                                length=cfg.iterations)
    return u, v, rmse


# --------------------------------------------------------------------------- #
# Dense layout: normal equations as GEMMs (the dense-SGD-MF trick for ALS)
# --------------------------------------------------------------------------- #

def _half_step_dense(factor_other, val_plane, rpw: int, cfg: ALSConfig):
    """One side's normal equations from a dense NaN-encoded value plane.

    ``val_plane``: (rpw, E_other) bf16, NaN = unobserved (0 is a VALID
    observed value in explicit mode). A_u = Σ_i w_ui v_i v_iᵀ collapses to
    one (rpw, E) @ (E, K²) GEMM against the factor's row-wise outer products
    — MXU matrix-matrix rates instead of 128-byte row gathers. bf16 operands,
    f32 accumulation (the dense SGD-MF precision contract)."""
    k = cfg.rank
    obs = jnp.isfinite(val_plane)
    vz = jnp.where(obs, val_plane, 0).astype(jnp.bfloat16)
    f_b = factor_other.astype(jnp.bfloat16)
    e = factor_other.shape[0]
    vv = (f_b[:, :, None] * f_b[:, None, :]).reshape(e, k * k)
    f32 = jnp.float32
    if cfg.implicit:
        # Hu-Koren: A = V'V + V'(C−I)V + λI, C−I = alpha*r on observed
        conf = (cfg.alpha * vz).astype(jnp.bfloat16)
        a = jax.lax.dot_general(conf, vv, (((1,), (0,)), ((), ())),
                                preferred_element_type=f32)
        gram = jax.lax.dot_general(factor_other, factor_other,
                                   (((0,), (0,)), ((), ())),
                                   preferred_element_type=f32)
        a = a.reshape(rpw, k, k) + gram[None]
        bw = jnp.where(obs, 1.0 + cfg.alpha * vz.astype(f32), 0.0)
        b = jax.lax.dot_general(bw.astype(jnp.bfloat16), f_b,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=f32)
    else:
        a = jax.lax.dot_general(obs.astype(jnp.bfloat16), vv,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=f32)
        a = a.reshape(rpw, k, k)
        b = jax.lax.dot_general(vz, f_b, (((1,), (0,)), ((), ())),
                                preferred_element_type=f32)
    a = a + cfg.lam * jnp.eye(k, dtype=a.dtype)[None]
    return _spd_solve(a, b, cfg)


def _train_dense(u_plane, i_plane, u0, v0, u_rpw: int, i_rpw: int,
                 cfg: ALSConfig, axis_name: str = WORKERS):
    """Dense-layout training loop: same allgather choreography as _train,
    with the dense half-step and a GEMM-based RMSE monitor."""

    def iteration(carry, _):
        u, v = carry
        u_block = _half_step_dense(v, u_plane, u_rpw, cfg)
        u = lax_ops.allgather(u_block, axis_name)
        v_block = _half_step_dense(u, i_plane, i_rpw, cfg)
        v = lax_ops.allgather(v_block, axis_name)
        obs = jnp.isfinite(u_plane)
        pred = jax.lax.dot_general(
            u_block.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        tgt = (jnp.where(obs, u_plane, 0).astype(jnp.float32)
               if not cfg.implicit else 1.0)
        sse = jax.lax.psum(jnp.sum(jnp.where(obs, (tgt - pred) ** 2, 0.0)),
                           axis_name)
        cnt = jax.lax.psum(jnp.sum(obs.astype(jnp.float32)), axis_name)
        return (u, v), jnp.sqrt(sse / jnp.maximum(cnt, 1.0))

    (u, v), rmse = jax.lax.scan(iteration, (u0, v0), None,
                                length=cfg.iterations)
    return u, v, rmse


class ALS:
    """Distributed ALS over a HarpSession mesh (daal_als parity)."""

    def __init__(self, session: HarpSession, config: ALSConfig):
        self.session = session
        self.config = config
        self._fns = {}
        self.last_layout_stats: dict = {}

    def prepare(self, rows, cols, vals, num_users: int, num_items: int,
                seed: int = 0):
        """Host layout + H2D ONCE; returns an opaque state for
        :meth:`fit_prepared` (the KMeans/SGDMF prepare idiom — keeps host
        prep and transfers out of timed regions)."""
        from harp_tpu.models.sgd_mf import _validate_coo

        sess, cfg = self.session, self.config
        w = sess.num_workers
        rows = np.asarray(rows)
        cols = np.asarray(cols)
        vals = np.asarray(vals, np.float32)
        _validate_coo(rows, cols, num_users, num_items, vals)  # incl. NaN
        if cfg.implicit and len(vals) and not (vals.min() >= 0):
            # Hu-Koren confidence c = 1 + alpha*r assumes r >= 0 (interaction
            # counts); a negative r can make the normal equations indefinite
            # and the Cholesky solve silently produce NaNs
            raise ValueError(
                "implicit ALS requires nonnegative interaction values "
                f"(confidence counts); got min {vals.min():.4f} — use "
                "implicit=False for signed ratings, or feed counts")
        # keep-first dedupe for BOTH layouts so they train on the identical
        # entry set (shared sgd_mf.dedupe_coo contract; the sparse path
        # would otherwise SUM duplicates while the dense plane kept one)
        from harp_tpu.models.sgd_mf import dedupe_coo

        rows, cols, vals, self._duplicates_dropped = dedupe_coo(
            rows, cols, vals, num_items)
        if self._pick_layout(num_users, num_items) == "dense":
            return self._prepare_dense(rows, cols, vals, num_users,
                                       num_items, seed)
        u_layout = pad_csr_chunks(rows, cols, vals, num_users, w,
                                  cfg.chunk_factor, cfg.balance)
        i_layout = pad_csr_chunks(cols, rows, vals, num_items, w,
                                  cfg.chunk_factor, cfg.balance)
        u_idx, u_val, u_mask, u_crow, u_assign, u_rpw, u_stats = u_layout
        i_idx, i_val, i_mask, i_crow, i_assign, i_rpw, i_stats = i_layout
        self.last_layout_stats = {
            "layout": "sparse",
            "users": u_stats, "items": i_stats,
            "overhead": max(u_stats["overhead"], i_stats["overhead"]),
            "duplicates_dropped": self._duplicates_dropped,
        }
        # chunk idx entries address the OTHER side's replicated factor, which
        # lives in permuted slot order after allgather — remap on the host
        ib, isl = i_assign
        u_idx = (ib[u_idx].astype(np.int64) * i_rpw + isl[u_idx]).astype(np.int32)
        ub, usl = u_assign
        i_idx = (ub[i_idx].astype(np.int64) * u_rpw + usl[i_idx]).astype(np.int32)

        rng = np.random.default_rng(seed)
        scale = 1.0 / np.sqrt(cfg.rank)
        u0 = (scale * rng.random((w * u_rpw, cfg.rank))).astype(np.float32)
        v0 = (scale * rng.random((w * i_rpw, cfg.rank))).astype(np.float32)
        # zero phantom padding slots: the implicit-mode gram V'V sums over ALL
        # rows of the replicated factor, so random init there would bias the
        # first half-iteration's normal equations
        u_slots = ub.astype(np.int64)[:num_users] * u_rpw + usl[:num_users]
        v_slots = ib.astype(np.int64)[:num_items] * i_rpw + isl[:num_items]
        used_u = np.zeros(w * u_rpw, bool)
        used_u[u_slots] = True
        u0[~used_u] = 0.0
        used_v = np.zeros(w * i_rpw, bool)
        used_v[v_slots] = True
        v0[~used_v] = 0.0

        key = (u_idx.shape, i_idx.shape, u_rpw, i_rpw)
        if key not in self._fns:
            self._fns[key] = sess.spmd(
                lambda a, b, c, d, e, f, g, h, i, j: _train(
                    (a[0], b[0], c[0], d[0]), (e[0], f[0], g[0], h[0]),
                    i, j, u_rpw, i_rpw, cfg),
                in_specs=(sess.shard(),) * 8 + (sess.replicate(),) * 2,
                out_specs=(sess.replicate(),) * 3)
        placed = (sess.scatter(u_idx), sess.scatter(u_val),
                  sess.scatter(u_mask), sess.scatter(u_crow),
                  sess.scatter(i_idx), sess.scatter(i_val),
                  sess.scatter(i_mask), sess.scatter(i_crow),
                  sess.replicate_put(u0), sess.replicate_put(v0))
        return key, placed, u_slots, v_slots

    def _pick_layout(self, num_users: int, num_items: int) -> str:
        cfg = self.config
        if cfg.layout not in ("auto", "dense", "sparse"):
            raise ValueError(f"layout must be auto|dense|sparse, got "
                             f"{cfg.layout!r}")
        if cfg.layout != "auto":
            return cfg.layout
        if not cfg.implicit:
            # bf16 planes quantize explicit training targets (see the
            # ALSConfig.layout note) — auto never changes results silently
            return "sparse"
        w = self.session.num_workers
        u_rpw = -(-num_users // w)
        i_rpw = -(-num_items // w)
        # each worker holds one (u_rpw, i_pad) and one (i_rpw, u_pad) bf16
        # shard — the budget is per-worker HBM, so dense stays available on
        # big meshes where the global planes dwarf a single chip
        per_worker = (u_rpw * (i_rpw * w) + i_rpw * (u_rpw * w)) * 2
        return "dense" if per_worker <= cfg.dense_max_bytes else "sparse"

    def _prepare_dense(self, rows, cols, vals, num_users: int,
                       num_items: int, seed: int):
        """Dense NaN-encoded plane layout (see ALSConfig.layout). Entries
        arrive already deduped (keep-first, prepare's contract). Factor rows
        stay in natural entity order (no slot permutation); padding rows sit
        past num_users/num_items and are zeroed so the implicit gram V'V is
        unbiased."""
        import ml_dtypes

        sess, cfg = self.session, self.config
        w = sess.num_workers
        u_rpw = -(-num_users // w)
        i_rpw = -(-num_items // w)
        u_pad, i_pad = w * u_rpw, w * i_rpw
        # build straight in bf16 (host peak = exactly the budgeted bytes);
        # entries are already deduped, and the item plane is the transpose
        # by construction — no second fill pass
        u_plane = np.full((u_pad, i_pad), np.nan, ml_dtypes.bfloat16)
        u_plane[rows, cols] = vals.astype(ml_dtypes.bfloat16)
        i_plane = np.ascontiguousarray(u_plane.T)
        self.last_layout_stats = {
            "layout": "dense",
            "plane_bytes": 2 * u_pad * i_pad * 2,
            "duplicates_dropped": self._duplicates_dropped,
            "overhead": (u_pad * i_pad) / max(len(rows), 1),
        }
        rng = np.random.default_rng(seed)
        scale = 1.0 / np.sqrt(cfg.rank)
        u0 = (scale * rng.random((u_pad, cfg.rank))).astype(np.float32)
        v0 = (scale * rng.random((i_pad, cfg.rank))).astype(np.float32)
        u0[num_users:] = 0.0
        v0[num_items:] = 0.0
        key = ("dense", u_rpw, i_rpw, w, cfg.implicit)
        if key not in self._fns:
            self._fns[key] = sess.spmd(
                lambda up, ip, u, v: _train_dense(up, ip, u, v, u_rpw,
                                                  i_rpw, cfg),
                in_specs=(sess.shard(), sess.shard(),
                          sess.replicate(), sess.replicate()),
                out_specs=(sess.replicate(),) * 3)
        placed = (sess.scatter(jnp.asarray(u_plane, jnp.bfloat16)),
                  sess.scatter(jnp.asarray(i_plane, jnp.bfloat16)),
                  sess.replicate_put(u0), sess.replicate_put(v0))
        return (key, placed, np.arange(num_users), np.arange(num_items))

    def train_prepared(self, state):
        """Run the compiled train program; factors stay ON DEVICE. Returns
        (u_dev, v_dev, rmse ndarray) — the benchmark timing surface (the
        rmse fetch forces execution; the factor D2H is a one-time cost)."""
        import time as _time

        key, placed, _, _ = state
        t0 = _time.perf_counter()
        u, v, rmse = self._fns[key](*placed)
        rmse = np.asarray(rmse)
        # telemetry at the rmse fetch that was already here (per-iteration
        # events, wall amortized over the scanned program); the manifest row
        # pins the explicit path only — implicit jobs get no comm row
        telemetry.record_chunk(
            "als", start=0, losses=rmse.tolist(),
            wall_s=_time.perf_counter() - t0,
            ledger=(telemetry.ledger_for("als")
                    if not self.config.implicit else None))
        return u, v, rmse

    def fit_prepared(self, state
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Run the compiled train program on prepared state; returns
        (U (num_users, K), V (num_items, K), rmse-per-iteration)."""
        u, v, rmse = self.train_prepared(state)
        _, _, u_slots, v_slots = state
        u_final = np.asarray(u)[u_slots]
        v_final = np.asarray(v)[v_slots]
        return u_final, v_final, rmse

    def fit(self, rows, cols, vals, num_users: int, num_items: int,
            seed: int = 0) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Returns (U (num_users, K), V (num_items, K), rmse-per-iteration)."""
        return self.fit_prepared(self.prepare(rows, cols, vals, num_users,
                                              num_items, seed))
