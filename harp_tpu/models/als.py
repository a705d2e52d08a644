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
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu import telemetry
from harp_tpu.collectives import lax_ops
from harp_tpu.models.dense_planes import dense_plane, place_dense_planes
from harp_tpu.ops import pallas_kernels
from harp_tpu.ops.lane_pack import LANES, round_up
from harp_tpu.parallel.mesh import WORKERS
from harp_tpu.session import HarpSession
from harp_tpu.utils import metrics


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
    #   HBM-bound. "auto" = pallas on TPU wherever a lane tile of the
    #   (k, k, 128) working set fits VMEM (k up to ~250), else cholesky
    #   (exact XLA path); "newton" (pure batched GEMMs, Precision.HIGHEST — TPU's
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
    # measured on v5e (PERF.md r4): the lane-vectorized pallas Cholesky
    # breaks the XLA batched-solve plateau; where it doesn't apply,
    # cholesky ties or beats newton at every batch shape tried and is exact
    return ("pallas" if pallas_kernels.use_spd_solve_pallas(cfg.rank)
            else "cholesky")


def _interpret(cfg: ALSConfig) -> bool:
    """An explicit ``solver="pallas"`` off-TPU runs the kernel in interpret
    mode (slow but exact — the path CI and the CPU mesh exercise); 'auto'
    resolves to the kernel only where it is compiled."""
    return cfg.solver == "pallas" and jax.default_backend() != "tpu"


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
    solver = _resolve_solver(cfg)
    if solver == "pallas":
        return pallas_kernels.spd_solve_pallas(a, b,
                                               interpret=_interpret(cfg))
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
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        a = a + gram[None]
    a = a + cfg.lam * jnp.eye(k, dtype=a.dtype)[None]
    return _spd_solve(a, b, cfg)


def _train(u_data, i_data, u0, v0, u_rpw: int, i_rpw: int, cfg: ALSConfig,
           axis_name: str = WORKERS):
    u_idx, u_val, u_mask, u_crow = u_data
    i_idx, i_val, i_mask, i_crow = i_data
    telemetry.traced("als.fit")          # runs when jax traces, only

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

# What one dense half-step may hold beside the resident planes. A side's
# normal equations are P float32 a row, P = 5,824 packed entries of the
# 104 x 104 at rank 100 (pallas_kernels.spd_pack_rows; 1.7 GB for
# MovieLens-10M's users), and its outer-product operand P bfloat16 a row of
# the OTHER side (0.8 GB for the items' half-step there), so a half-step
# runs in row blocks, each contracting the other side in chunks; both sizes
# are derived from the shapes and this budget.
DENSE_SCRATCH_BYTES = 2 * 1024 ** 3
_CHUNK_COLS = 16384         # widest contraction chunk (P x 16384 bf16 =
#   0.19 GB at rank 100)


def _row_block(rows: int, per_row: int, fixed: int = 0) -> Tuple[int, int]:
    """``(block, blocks)``: the fewest equal row blocks, a multiple of the
    512 lanes the solve kernel's widest tile carries its batch on, whose
    ``per_row`` bytes a row fit :data:`DENSE_SCRATCH_BYTES` beside
    ``fixed``. One block is the side as it stands, whatever its length."""
    tile = pallas_kernels.SPD_SOLVE_TILES[0]
    most = max(tile, (DENSE_SCRATCH_BYTES - fixed) // per_row // tile * tile)
    blocks = -(-rows // most)
    if blocks == 1:
        return rows, 1
    return round_up(-(-rows // blocks), tile), blocks


def _dense_blocks(rows: int, other: int, k: int) -> Tuple[int, int, int, int]:
    """``(row block, row blocks, chunk, chunks)`` of one dense half-step
    over a ``(rows, other)`` plane at rank ``k``. A row of a block costs its
    packed normal equations twice (the GEMM's result and the solver's
    operand) and two bf16 weights per chunk column; a chunk costs its
    packed outer products."""
    packed = pallas_kernels.spd_pack_size(round_up(k, 8))
    chunks = -(-other // _CHUNK_COLS)
    chunk = other if chunks == 1 else round_up(-(-other // chunks), LANES)
    block, blocks = _row_block(rows, 8 * packed + 4 * chunk,
                               fixed=2 * packed * chunk)
    return block, blocks, chunk, chunks


def _spd_solve_lanes(at, bt, cfg: ALSConfig):
    """Solve batch-last systems: ``at`` (P, B) float32, the packed entries
    the kernel reads (``pallas_kernels.spd_pack_rows``), ``bt`` (Kp, B), the
    rank padded to Kp by an identity block → x (B, K). The Pallas kernel
    reads them as they lie; any other solver takes the full matrices
    batch-first."""
    k = cfg.rank
    pallas = _resolve_solver(cfg) == "pallas"
    # runs when jax traces, only: which solve this program's half-steps run
    metrics.DEFAULT.count("als.solve.pallas" if pallas else "als.solve.xla")
    if pallas:
        xt = pallas_kernels.spd_solve_lanes(at, bt, interpret=_interpret(cfg))
        return xt[:k].T
    full = pallas_kernels.spd_unpack(at, bt.shape[0])
    return _spd_solve(jnp.transpose(full, (2, 0, 1))[:, :k, :k], bt[:k].T,
                      cfg)


def _half_step_dense(factor_other, val_plane, rpw: int, cfg: ALSConfig,
                     blocks: Optional[Tuple[int, int, int, int]] = None):
    """One side's normal equations from a dense NaN-encoded value plane.

    ``val_plane``: (rpw, E_other) bf16, NaN = unobserved (0 is a VALID
    observed value in explicit mode). A_u = Σ_i w_ui v_i v_iᵀ collapses to
    one GEMM of the factor's row-wise outer products against the weights —
    MXU matrix-matrix rates instead of 128-byte row gathers. bf16 operands,
    f32 accumulation (the dense SGD-MF precision contract); V'V from the
    float32 factors at ``Precision.HIGHEST``.

    v vᵀ is symmetric and the solve kernel reads a block-upper triangle of
    it, so only those P of the Kp² products are ever formed
    (``pallas_kernels.spd_pack_rows``; 5,824 of 10,816 at rank 100). The
    product is taken TRANSPOSED, (P, E) x (rows, E)ᵀ → (P, rows): the
    systems leave the MXU packed and batch-last, as the solve kernel reads
    them, with the rank padded to a sublane multiple by zero outer products
    (the regulariser puts 1 on the padded diagonal). Runs in row blocks, each
    contracting E in chunks (:func:`_dense_blocks`; ``blocks`` overrides
    them); the last block and chunk are taken flush with the end, and what a
    chunk then shares with the one before is weighted 0."""
    k = cfg.rank
    kp = round_up(k, 8)
    e = factor_other.shape[0]
    rb, n_rb, ce, n_ce = blocks or _dense_blocks(rpw, e, k)
    packed = pallas_kernels.spd_pack_size(kp)            # P
    f32, bf16 = jnp.float32, jnp.bfloat16
    # run when jax traces, only
    metrics.DEFAULT.count("als.row_blocks", n_rb)
    metrics.DEFAULT.count("als.gram.rows", packed)
    with jax.named_scope("als.outer"):
        f_t = jnp.pad(factor_other.T, ((0, kp - k), (0, 0))).astype(bf16)
    with jax.named_scope("als.gram"):
        shift = jnp.diag(jnp.where(jnp.arange(kp) < k, cfg.lam, 1.0)
                         .astype(f32))
        if cfg.implicit:
            # Hu-Koren: A = V'V + V'(C−I)V + λI, C−I = alpha*r on observed
            gram = jax.lax.dot_general(
                factor_other, factor_other, (((0,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=f32)
            shift = shift + jnp.pad(gram, ((0, kp - k), (0, kp - k)))
        shift = pallas_kernels.spd_pack(shift)

    def normal_equations(r0, c0, lo):
        """(P, rb) and (Kp, rb) of the plane's block at (r0, c0); columns
        before ``lo`` belong to the chunk before."""
        blk = jax.lax.dynamic_slice(val_plane, (r0, c0), (rb, ce))
        f_c = jax.lax.dynamic_slice_in_dim(f_t, c0, ce, 1)
        with jax.named_scope("als.outer"):
            vv = pallas_kernels.spd_pack_outer(f_c)
        obs = jnp.isfinite(blk) & (c0 + jnp.arange(ce) >= lo)[None, :]
        vz = jnp.where(obs, blk, 0).astype(bf16)
        if cfg.implicit:
            w_a = (cfg.alpha * vz).astype(bf16)
            w_b = jnp.where(obs, 1.0 + cfg.alpha * vz.astype(f32),
                            0.0).astype(bf16)
        else:
            w_a, w_b = obs.astype(bf16), vz
        with jax.named_scope("als.gram"):
            a = jax.lax.dot_general(vv, w_a, (((1,), (1,)), ((), ())),
                                    preferred_element_type=f32)
        with jax.named_scope("als.rhs"):
            b = jax.lax.dot_general(f_c, w_b, (((1,), (1,)), ((), ())),
                                    preferred_element_type=f32)
        return a, b

    def block(i, out):
        r0 = jnp.minimum(i * rb, rpw - rb)

        def chunk(c, acc):
            da, db = normal_equations(r0, jnp.minimum(c * ce, e - ce), c * ce)
            return acc[0] + da, acc[1] + db

        with jax.named_scope("als.gram"):
            if n_ce == 1:
                a, b = normal_equations(r0, 0, 0)
            else:
                a, b = jax.lax.fori_loop(
                    0, n_ce, chunk,
                    (jnp.zeros((packed, rb), f32), jnp.zeros((kp, rb), f32)))
        with jax.named_scope("als.solve"):
            x = _spd_solve_lanes(a + shift[:, None], b, cfg)
            return jax.lax.dynamic_update_slice_in_dim(out, x, r0, 0)

    # what the compiler makes of the loop itself (it keeps the carried bf16
    # factors in fast memory, slice by slice) has no name of its own
    with jax.named_scope("als.gram"):
        return jax.lax.fori_loop(0, n_rb, block, jnp.zeros((rpw, k), f32))


def _monitor_dense(u_block, v, u_plane, cfg: ALSConfig):
    """Squared error and count over the observed cells of this worker's
    plane (against 1 in implicit mode), in row blocks: the predictions of
    all rows at once are another plane in float32."""
    rpw, e = u_plane.shape
    rb, n_rb = _row_block(rpw, 8 * e)
    v_b = v.astype(jnp.bfloat16)

    def block(i, acc):
        lo = i * rb
        r0 = jnp.minimum(lo, rpw - rb)
        blk = jax.lax.dynamic_slice_in_dim(u_plane, r0, rb, 0)
        rows = jax.lax.dynamic_slice_in_dim(u_block, r0, rb, 0)
        obs = jnp.isfinite(blk) & (r0 + jnp.arange(rb) >= lo)[:, None]
        pred = jax.lax.dot_general(
            rows.astype(jnp.bfloat16), v_b, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        tgt = (jnp.where(obs, blk, 0).astype(jnp.float32)
               if not cfg.implicit else 1.0)
        return (acc[0] + jnp.sum(jnp.where(obs, (tgt - pred) ** 2, 0.0)),
                acc[1] + jnp.sum(obs.astype(jnp.float32)))

    zero = jnp.zeros((), jnp.float32)
    return jax.lax.fori_loop(0, n_rb, block, (zero, zero))


def _train_dense(u_plane, i_plane, u0, v0, u_rpw: int, i_rpw: int,
                 cfg: ALSConfig, axis_name: str = WORKERS):
    """Dense-layout training loop: same allgather choreography as _train,
    with the dense half-step and a GEMM-based RMSE monitor."""
    telemetry.traced("als.fit")          # runs when jax traces, only

    def iteration(carry, _):
        u, v = carry
        u_block = _half_step_dense(v, u_plane, u_rpw, cfg)
        u = lax_ops.allgather(u_block, axis_name)
        v_block = _half_step_dense(u, i_plane, i_rpw, cfg)
        v = lax_ops.allgather(v_block, axis_name)
        with jax.named_scope("als.monitor"):
            sse, cnt = _monitor_dense(u_block, v, u_plane, cfg)
            sse = jax.lax.psum(sse, axis_name)
            cnt = jax.lax.psum(cnt, axis_name)
            rmse = jnp.sqrt(sse / jnp.maximum(cnt, 1.0))
        return (u, v), rmse

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
        with telemetry.phase("als.prepare"):
            return self._prepare(rows, cols, vals, num_users, num_items, seed)

    def _prepare(self, rows, cols, vals, num_users: int, num_items: int,
                 seed: int):
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
        sess, cfg = self.session, self.config
        w = sess.num_workers
        u_rpw = -(-num_users // w)
        i_rpw = -(-num_items // w)
        u_pad, i_pad = w * u_rpw, w * i_rpw
        # the two planes, as models/ccd.py also keeps them: the users' built
        # on the host, the items' transposed on the device
        u_plane = dense_plane(rows, cols, vals, u_pad, i_pad)
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
        key = self._dense_program(u_rpw, i_rpw)
        placed = (*place_dense_planes(sess, self._fns, u_plane),
                  sess.replicate_put(u0), sess.replicate_put(v0))
        return (key, placed, np.arange(num_users), np.arange(num_items))

    def _dense_program(self, u_rpw: int, i_rpw: int):
        """Key of the dense SPMD program at these rows per worker (built on
        first use): planes sharded by rows, factors replicated."""
        sess, cfg = self.session, self.config
        key = ("dense", u_rpw, i_rpw, sess.num_workers, cfg.implicit)
        if key not in self._fns:
            self._fns[key] = sess.spmd(
                lambda up, ip, u, v: _train_dense(up, ip, u, v, u_rpw,
                                                  i_rpw, cfg),
                in_specs=(sess.shard(), sess.shard(),
                          sess.replicate(), sess.replicate()),
                out_specs=(sess.replicate(),) * 3)
        return key

    def train_prepared(self, state):
        """Run the compiled train program; factors stay ON DEVICE. Returns
        (u_dev, v_dev, rmse ndarray) — the benchmark timing surface (the
        rmse fetch forces execution; the factor D2H is a one-time cost).
        The last two entries of ``state[1]`` are the factors the call starts
        from: a caller that trains in several calls hands back what the call
        before returned."""
        key, placed, _, _ = state
        # the lines from here to the dispatch keep the numbers they had: a
        # kernel's payload, and with it the compile cache's key of the step,
        # carries the line of every frame above it (PERF.md section 7, row 11)
        with telemetry.phase("als.call") as call:
            step = self._fns[key]
            with telemetry.phase("step.dispatch"):
                u, v, rmse = step(*placed)
            telemetry.record_program("als.fit", step, placed)
            with telemetry.phase("step.fetch"):
                rmse = np.asarray(rmse)
            # telemetry at the rmse fetch that was already here
            # (per-iteration events, wall amortized over the scanned
            # program); the manifest row pins the explicit path only —
            # implicit jobs get no comm row
            telemetry.record_chunk(
                "als", start=0, losses=rmse.tolist(),
                wall_s=call.elapsed(),
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
