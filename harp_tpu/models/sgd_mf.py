"""SGD matrix factorization — the model-rotation flagship (Model B).

Reference parity: Harp's SGD-MF (ml/java sgd/SGDCollectiveMapper.java:54 and the
DAAL-2019 variant experimental/daal_sgd/SGDDaalCollectiveMapper.java:75 — BASELINE's
"harp-daal SGD-MF"). The reference design: rating rows are data-local, the item
factor matrix H is split into ``numModelSlices`` tables that ring-rotate among
workers (Rotator, dymoro/Rotator.java:30); within each rotation hop a timer-bounded
``Scheduler`` (dymoro/Scheduler.java:85-160) randomly schedules (row-split,
col-slice) blocks onto threads running asynchronous SGD point updates.

TPU-native re-expression:

* **Rotation** is a ``ppermute`` ring schedule (`collectives.rotation.Rotator`);
  after B hops every H block has visited every worker and is home again. The whole
  multi-epoch loop is ONE compiled XLA program.
* **The timer-bounded async scheduler** is host-driven and data-dependent — hostile
  to XLA (SURVEY §7 "hard parts"). Reformulated as **bounded staleness**: each hop
  runs a fixed number of mini-batch SGD steps over that (worker, block) bucket of
  ratings. Convergence-equivalent, not step-equivalent; Harp itself only claims
  statistical semantics for its racy Hogwild-style updates. The per-hop budget can
  be auto-tuned between epochs by :class:`HopBudgetTuner` /
  :meth:`SGDMF.fit_adaptive` — the analog of the reference's
  ``adjustMiniBatch``/``setTimer`` (SGDCollectiveMapper.java:281-287, :623):
  buckets are padded to a multiple of ``minibatches_per_hop``, so every divisor
  is a valid budget over the SAME device-resident data (a "banded" shape family
  — switching budgets swaps compiled programs, never re-lays-out or re-uploads).

Two data layouts, selected by density (``SGDMFConfig.layout``):

* **dense** (masked dense-stripe): when the per-worker rating slab fits HBM, store
  the (rows × cols) block as ONE dense bf16 matrix whose missing entries are
  NaN-encoded (no separate mask slab) and express each minibatch as three
  GEMMs — ``pred = W_s @ H_b^T``, ``dW = G @ H_b``, ``dH = G^T @ W_s`` with
  ``G = where(isnan(V), 0, V - pred)``. This burns redundant FLOPs on missing
  entries but runs entirely on the MXU with **zero gathers/scatters**, which
  on TPU is ~50× faster than an index-chasing loop at MovieLens/Netflix-like
  densities (the per-row gather granularity, not HBM bandwidth, is the sparse
  ceiling). Same update rule as the sparse path — same minibatch gradient
  formula, same L2 term (missing entries contribute exactly zero to G, and
  the regularizer is scaled by true per-row/per-col counts, precomputed
  host-side) — but the slab stores ratings in bf16 (~8-bit mantissa), so
  values/residuals are quantized: the two layouts are convergence-equivalent,
  not bit-identical. Input NaN values are rejected at validation — NaN is the
  missing-entry sentinel. The slab and the factors are STORED with every
  stripe, column block and the rank padded to the fused hop kernel's tiles
  (:class:`DenseGeometry`: the mini-batches, the first model and every table
  that leaves the device keep the logical sizes), so that on TPU a hop is
  ``pallas_kernels.dense_mf_hop_pallas``' single pass over the resident
  block, which the kernel reads straight out of the worker's whole slab;
  where no tile fits VMEM, and off the TPU, the XLA stripe scan runs on the
  same arrays, behind a ``jnp.take`` of the block
  (``last_layout_stats["slab_pick"]``).
* **sparse** (padded COO buckets): for data too sparse/large to densify. Ratings
  are pre-sorted on the host into a (W workers × B column-blocks) grid of padded
  COO buckets; the inner loop is gather → rank-K dot → two scatter-adds. Hot
  rows/columns are spread by **balanced (serpentine-LPT) id assignment** so one
  power-law row or column cannot blow up the shared bucket padding (the
  reference's marquee datasets — clueweb — are exactly Zipf-distributed; its
  regroup of VSets achieved the same load-spreading by hash partitioning,
  HarpDAALDataSource.regroupCOOList:399).

Duplicate (row, col) pairs are dropped (keep-first) in ``prepare`` for BOTH
layouts so the two paths always train on the identical entry set; the count is
reported in ``last_layout_stats["duplicates_dropped"]``.

RMSE per epoch is accumulated on the fly (pre-update residuals) and combined with an
allreduce — the reference's test-RMSE allreduce (SGDCollectiveMapper.java:615-641).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu import telemetry
from harp_tpu.collectives import lax_ops, quantize, rotation
from harp_tpu.ops import ring_dma
from harp_tpu.ops import lane_pack, pallas_kernels
from harp_tpu.parallel.mesh import fetch
from harp_tpu.session import HarpSession
from harp_tpu.telemetry.scopes import scoped
from harp_tpu.utils import metrics


@dataclasses.dataclass(frozen=True)
class SGDMFConfig:
    """Mirrors the reference CLI (r, lambda, epsilon/lr, numIterations,
    numModelSlices → here the slice count is the worker count by construction)."""

    rank: int = 16
    lam: float = 0.05          # L2 regularization (reference: lambda)
    lr: float = 0.05           # learning rate (reference: epsilon)
    epochs: int = 10
    minibatches_per_hop: int = 4  # bounded-staleness stand-in for the dymoro timer
    num_slices: int = 1        # 2 = double-buffered pipeline (reference:
    #                            numModelSlices=2, dymoro comm/compute overlap)
    layout: str = "auto"       # auto | dense | sparse
    quant: Optional[str] = None  # None | "int8" | "bf16": quantize the H-block
    #                              rotation hops' WIRE format with error
    #                              feedback carried in the rotation scan
    #                              (collectives/quantize.py). Dequantize-
    #                              after-transport: updates run f32; the
    #                              trajectory is convergence-equivalent to
    #                              f32, not bit-identical (tests pin a
    #                              per-codec RMSE tolerance).
    dense_max_bytes: int = 6_000_000_000  # per-worker slab budget for auto-dense
    balance: bool = True       # serpentine-LPT id balancing for the sparse layout
    reshard: str = "auto"      # r12: HOW a world-size-changing resume moves
    #   the factor tables onto this session's layout (arXiv:2112.01075):
    #   "device" = collective redistribution on the mesh (collectives/
    #   reshard.py alltoall schedule — bitwise, chunk-bounded rounds, no
    #   host gather of a sharded leaf), "ring" = the ppermute schedule
    #   (rides lax_ops.rotate, so DCN link-class chunking composes),
    #   "host" = the PR 8 numpy gather-and-resplit (kept as the parity
    #   oracle and small-world fallback), "auto" = device when the mesh has
    #   >1 worker, host on a 1-worker mesh (nothing to redistribute).
    reshard_chunk_bytes: int = 0   # 0 = collectives.reshard default (1 MiB)
    fused_dma: Optional[bool] = None  # the H-block rotation hops' wire.
    #   None: the program decides: the hop fuses INTO the dense hop kernel
    #   wherever that can run (TPU, the fused kernel live, a ring of more
    #   than one, one slice, an unquantized wire; dense_mf_hop_pallas
    #   ring_hop: each tile of H leaves VMEM for the neighbour's HBM as the
    #   last stripe finishes it), ppermute elsewhere. True: the same, and
    #   every other path hops through the ring-DMA engine (ops/ring_dma.hop;
    #   off-TPU its tagged fallback keeps the jaxpr budget's fused_dma rows
    #   honest). False: ppermute. Bitwise the same factors on every wire
    #   (the engine moves bytes, it never rounds); a quantized wire (quant=)
    #   takes precedence over fusion (rotation.py module doc).
    #   last_layout_stats["ring_hop"] says which wire the hops ride.


# --------------------------------------------------------------------------- #
# Host-side layout planning
# --------------------------------------------------------------------------- #

def serpentine_assign(counts: np.ndarray, num_bins: int
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Balanced id→bin assignment: sort ids by descending weight, deal them out
    in serpentine (boustrophedon) order. Each bin receives exactly
    ``ceil(n/num_bins)`` or ``floor`` ids, and loads are near-LPT balanced.

    Returns ``(bin_of_id, local_slot_of_id)``. This is the skew-defense for the
    sparse layout: a Zipf head row/column lands alone in a lightly-loaded bin
    instead of inflating the global bucket padding.
    """
    n = len(counts)
    order = np.argsort(-np.asarray(counts), kind="stable")
    ranks = np.empty(n, np.int64)
    ranks[order] = np.arange(n)
    chunk, pos = np.divmod(ranks, num_bins)
    bins = np.where(chunk % 2 == 0, pos, num_bins - 1 - pos)
    return bins.astype(np.int32), chunk.astype(np.int32)


def identity_assign(n: int, num_bins: int) -> Tuple[np.ndarray, np.ndarray]:
    """Contiguous-range assignment (the round-1 behavior)."""
    per = -(-n // num_bins)
    ids = np.arange(n)
    return (ids // per).astype(np.int32), (ids % per).astype(np.int32)


def dedupe_coo(rows, cols, vals, num_cols):
    """Keep-FIRST dedupe of (row, col) pairs — the shared contract for every
    dual-layout model (SGD-MF and ALS): sparse and dense paths must train on
    the identical entry set, so duplicates are resolved once, here, before
    layout dispatch. Returns (rows, cols, vals, dropped_count)."""
    if not len(rows):
        return rows, cols, vals, 0
    keys = rows.astype(np.int64) * num_cols + cols
    _, first = np.unique(keys, return_index=True)
    if len(first) == len(rows):
        return rows, cols, vals, 0
    dropped = len(rows) - len(first)
    first.sort()
    return rows[first], cols[first], vals[first], dropped


def _validate_coo(rows, cols, num_rows, num_cols, vals=None):
    if vals is not None and len(vals) and np.isnan(vals).any():
        raise ValueError("rating values must not be NaN (NaN encodes missing "
                         "entries in the dense layout)")
    if len(rows):
        if rows.min() < 0 or rows.max() >= num_rows:
            raise ValueError(
                f"row indices must be in [0, {num_rows}); got "
                f"[{rows.min()}, {rows.max()}]")
        if cols.min() < 0 or cols.max() >= num_cols:
            raise ValueError(
                f"col indices must be in [0, {num_cols}); got "
                f"[{cols.min()}, {cols.max()}]")


def bucketize(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    num_workers: int,
    num_rows: int,
    num_cols: int,
    minibatches: int,
    num_col_blocks: int = 0,
    row_assign: Optional[Tuple[np.ndarray, np.ndarray]] = None,
    col_assign: Optional[Tuple[np.ndarray, np.ndarray]] = None,
    validate: bool = True,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, int, int]:
    """Host-side layout: COO ratings → (W, B, M) padded buckets.

    Bucket (w, b) holds the ratings whose row lives on worker w and whose column
    lives in H block b, with row/col indices localized to the block. This replaces
    the reference's regroup of VSets (SGDCollectiveMapper regroup-vw:384): the
    shuffle happens once on the host, the device program is static.
    ``num_col_blocks`` defaults to W (one H block per worker); the 2-slice
    pipeline uses 2W. ``row_assign``/``col_assign`` are optional (bin, slot)
    id maps (see :func:`serpentine_assign`); default is contiguous ranges.
    """
    if validate:
        _validate_coo(rows, cols, num_rows, num_cols)
    w = num_workers
    b_blocks = num_col_blocks or w
    rpw = -(-num_rows // w)        # rows per worker (ceil)
    cpb = -(-num_cols // b_blocks)  # cols per block
    if row_assign is None:
        row_assign = identity_assign(num_rows, w)
    if col_assign is None:
        col_assign = identity_assign(num_cols, b_blocks)
    owner, r_slot = row_assign[0][rows], row_assign[1][rows]
    block, c_slot = col_assign[0][cols], col_assign[1][cols]
    # One sort-based pass: order entries by (owner, block), then lay each bucket
    # out contiguously — O(nnz log nnz), not O(W^2 * nnz).
    bucket = owner.astype(np.int64) * b_blocks + block
    order = np.argsort(bucket, kind="stable")
    counts = np.bincount(bucket, minlength=w * b_blocks)
    m = max(int(counts.max()), 1) if counts.size else 1
    m = -(-m // minibatches) * minibatches   # pad so hops split evenly
    r_idx = np.zeros((w, b_blocks, m), np.int32)
    c_idx = np.zeros((w, b_blocks, m), np.int32)
    val = np.zeros((w, b_blocks, m), np.float32)
    mask = np.zeros((w, b_blocks, m), np.float32)
    starts = np.concatenate([[0], np.cumsum(counts)])
    rs, cs, vs = r_slot[order], c_slot[order], vals[order]
    for b in range(w * b_blocks):
        lo, hi = starts[b], starts[b + 1]
        if lo == hi:
            continue
        wi, bi = divmod(b, b_blocks)
        k = hi - lo
        r_idx[wi, bi, :k] = rs[lo:hi]
        c_idx[wi, bi, :k] = cs[lo:hi]
        val[wi, bi, :k] = vs[lo:hi]
        mask[wi, bi, :k] = 1.0
    return r_idx, c_idx, val, mask, rpw, cpb


@dataclasses.dataclass(frozen=True)
class DenseGeometry:
    """The dense layout's two geometries (``meta[6]`` of a dense state).

    **Logical** is the job: a worker's ``rpw = nmb * s_rows`` rows in ``nmb``
    stripes, column blocks of ``cpb``, ``rank`` factor columns. The
    mini-batches (one stripe x block tile each), the first model's draw,
    ``meta``'s ``rpw``/``cpb`` and id maps, ``_finalize``'s tables and a
    checkpoint's tables are all logical.

    **Stored** is what the device arrays hold, and only ``_prepare_dense``,
    the step program and the two conversions (``_to_logical``/``_to_stored``,
    ``_stored_maps``) speak it: every stripe padded at its end to
    ``s_store`` rows, every block to ``cpb_store`` columns, the factors to
    ``rank_store`` columns — the fused hop kernel's tiles
    (``pallas_kernels.dense_mf_col_tile``). Pad cells are NaN (missing), pad
    rows' and columns' counts 0, pad factor entries 0: they take no gradient
    and no regulariser, add nothing to a product or to the RMSE, and stay 0.
    Stripes are padded at the finest granularity (``nmb``), so a coarser
    budget of ``fit_adaptive`` merges whole stored stripes."""

    nmb: int
    s_rows: int
    cpb: int
    rank: int
    s_store: int
    cpb_store: int
    rank_store: int

    @property
    def rpw(self) -> int:
        return self.nmb * self.s_rows

    @property
    def rpw_store(self) -> int:
        return self.nmb * self.s_store

    def store_row(self, r_loc):
        """Stored row (within a worker) of logical row ``r_loc``."""
        return r_loc // self.s_rows * self.s_store + r_loc % self.s_rows


def _slab_pick(fused: bool, n_blocks: int) -> str:
    """Where a dense hop picks its resident block out of the worker's slab:
    ``in_kernel`` (the fused hop's index map reads the block index: nothing
    is copied), ``copy`` (the XLA stripe scan behind a ``jnp.take``, which
    materialises the block every hop) or ``static`` (the XLA scan over a
    slab of one block)."""
    if fused:
        return "in_kernel"
    return "copy" if n_blocks > 1 else "static"


def _repad(a: np.ndarray, rows: int, rows_to: int, k_to: int) -> np.ndarray:
    """``(..., n * rows, K)`` -> ``(..., n * rows_to, k_to)``: every run of
    ``rows`` rows cut or zero-padded at its end to ``rows_to``, the last
    axis to ``k_to``."""
    a = np.asarray(a)
    runs = a.reshape(-1, rows, a.shape[-1])
    out = np.zeros((len(runs), rows_to, k_to), a.dtype)
    r, k = min(rows, rows_to), min(a.shape[-1], k_to)
    out[:, :r, :k] = runs[:, :r, :k]
    return out.reshape(*a.shape[:-2], -1, k_to)


# --------------------------------------------------------------------------- #
# Model
# --------------------------------------------------------------------------- #

class SGDMF:
    """Distributed SGD matrix factorization over a HarpSession mesh."""

    def __init__(self, session: HarpSession, config: SGDMFConfig):
        self.session = session
        self.config = config
        self._compiled = {}       # layout/shape key -> compiled SPMD program
        self._warm: dict = {}     # key -> AOT-compiled executable (fit_adaptive)
        self.last_layout_stats: dict = {}

    # -- schedule (shared by both layouts) ----------------------------------- #

    def _bucket_id(self, wid, t, w):
        """Which (globally-numbered) column block is resident at hop t.

        1-slice: plain ring — block (wid - t) mod W. 2-slice: the dymoro
        pipeline (Rotator, numModelSlices=2): resident slice s = t%2 has been
        shifted t//2 times; compute on it while the other slice's ppermute is
        in flight."""
        if self.config.num_slices == 2:
            s = t % 2
            return s * w + (wid - t // 2) % w
        return (wid - t) % w

    def _build(self, w: int, num_data_args: int,
               make_update_bucket: Callable, epochs: int,
               body_hops: bool = False,
               w_carry: Tuple[Callable, Callable] = (lambda w: w,
                                                     lambda w: w)):
        """Shared rotation/epoch harness for both layouts.

        ``make_update_bucket(local_data)`` receives the worker-local shards of
        the data arrays (leading worker axis stripped) and returns
        ``update_bucket(w_local, h_block, sse, cnt, bucket_id)`` — the only
        part that differs between the sparse and dense programs.

        ``w_carry``: ``(enter, leave)``, the form ``update_bucket`` takes and
        returns ``w_local`` in. ``enter`` is applied once to the call's
        ``(rows, K)`` table, ``leave`` once to the last epoch's carry: the
        hops and epochs between them carry whatever ``enter`` made.

        ``body_hops``: the update itself performs the ring hop (the fused
        dense kernel's in-kernel remote-copy epilogue returns the NEXT
        resident block), so the rotation scan runs shift=0 — the schedule
        is unchanged, only the transport moved into the kernel.
        """
        cfg = self.config
        two_slice = cfg.num_slices == 2

        def fit_fn(*args):
            telemetry.traced("sgd_mf.fit")   # runs when jax traces, only
            data, (w0, h0) = args[:num_data_args], args[num_data_args:]
            # this worker's block of each data array; a relayout of the slab
            # on the way into the loops reads under this name
            with jax.named_scope("sgdmf.select"):
                local_data = tuple(d[0] for d in data)
            update_bucket = make_update_bucket(local_data)

            def hop_body(carry, h_block, t):
                w_local, sse, cnt = carry
                with jax.named_scope("sgdmf.select"):
                    wid = lax_ops.worker_id()
                    bucket_id = self._bucket_id(wid, t, w)
                w_local, h_block, sse, cnt = update_bucket(
                    w_local, h_block, sse, cnt, bucket_id)
                return (w_local, sse, cnt), h_block

            rotator = rotation.Rotator(
                w, cfg.num_slices,
                comm=(quantize.CommConfig(quant=cfg.quant)
                      if cfg.quant is not None else None),
                fused_dma=bool(cfg.fused_dma) and not body_hops,
                shift=0 if body_hops else 1)

            def epoch(state, _):
                w_local, h = state
                with jax.named_scope("sgdmf.rmse"):
                    carry0 = (w_local, jnp.zeros(()), jnp.zeros(()))
                slices = h if two_slice else (h,)
                # the hop loop's own plumbing (its counter, which hop this
                # is) reads as part of picking the resident bucket
                with jax.named_scope("sgdmf.select"):
                    (w_local, sse, cnt), out = rotator.run(hop_body, carry0,
                                                           slices)
                h = out if two_slice else out[0]
                with jax.named_scope("sgdmf.rmse"):
                    sse = jax.lax.psum(sse, lax_ops.WORKERS)
                    cnt = jax.lax.psum(cnt, lax_ops.WORKERS)
                    return (w_local, h), jnp.sqrt(sse / jnp.maximum(cnt, 1.0))

            # two-slice h0 arrives as this worker's (1, 2, cpb, K) chunk:
            # slice A block w and slice B block W+w
            h_init = (h0[0, 0], h0[0, 1]) if two_slice else h0
            enter, leave = w_carry
            w_init = enter(w0)
            # the epoch loop's plumbing stacks the per-epoch RMSE
            with jax.named_scope("sgdmf.rmse"):
                (w_local, h_fin), rmse = jax.lax.scan(
                    epoch, (w_init, h_init), None, length=epochs)
            if two_slice:
                h_fin = jnp.stack(h_fin, axis=0)[None]   # (1, 2, cpb, K)
            return leave(w_local), h_fin, rmse

        sess = self.session
        return sess.spmd(
            fit_fn,
            in_specs=(sess.shard(),) * (num_data_args + 2),
            out_specs=(sess.shard(), sess.shard(), sess.replicate()),
        )

    # -- sparse (padded COO bucket) program ----------------------------------- #

    def _build_sparse(self, w: int, nmb: int, mbs: int, epochs: int):
        lr, lam = self.config.lr, self.config.lam

        def make_update_bucket(data):
            r_idx, c_idx, val, mask = data

            def update_bucket(w_local, h_block, sse, cnt, bucket_id):
                """Run the minibatched SGD updates of one (worker, block)
                bucket against the resident H block."""
                with jax.named_scope("sgdmf.select"):
                    r = jnp.take(r_idx, bucket_id, axis=0).reshape(nmb, mbs)
                    c = jnp.take(c_idx, bucket_id, axis=0).reshape(nmb, mbs)
                    v = jnp.take(val, bucket_id, axis=0).reshape(nmb, mbs)
                    msk = jnp.take(mask, bucket_id, axis=0).reshape(nmb, mbs)

                def mb_step(state, xs):
                    wl, hb, sse, cnt = state
                    rm, cm, vm, mm = xs
                    wr = wl[rm]                      # (mbs, K)
                    hc = hb[cm]
                    pred = jnp.sum(wr * hc, axis=-1)
                    err = (vm - pred) * mm
                    wl = wl.at[rm].add(
                        lr * (err[:, None] * hc - lam * wr * mm[:, None]))
                    hb = hb.at[cm].add(
                        lr * (err[:, None] * wr - lam * hc * mm[:, None]))
                    return (wl, hb, sse + jnp.sum(err * err),
                            cnt + jnp.sum(mm)), None

                with jax.named_scope("sgdmf.stripes"):
                    (w_local, h_block, sse, cnt), _ = jax.lax.scan(
                        mb_step, (w_local, h_block, sse, cnt), (r, c, v, msk))
                return w_local, h_block, sse, cnt

            return update_bucket

        return self._build(w, 4, make_update_bucket, epochs)

    # -- dense (masked stripe-GEMM) program ------------------------------------ #

    def _hop_tile(self, g: DenseGeometry, nmb: int) -> int:
        """Column tile of the fused hop kernel for the budget ``nmb`` over
        the stored arrays; 0 = the XLA stripe scan runs on them."""
        shape = (g.cpb_store, g.rpw_store // nmb, g.rank_store)
        return (pallas_kernels.dense_mf_col_tile(*shape)
                if pallas_kernels.use_dense_mf_pallas(*shape) else 0)

    def _ring_wire(self, fused: bool, w: int) -> str:
        """The wire a dense hop's H block rides to the right neighbour:
        ``"in_kernel"`` (the fused hop kernel streams it out behind its last
        stripe), ``"ring_dma"`` (the engine's own hop after the update:
        ``fused_dma=True`` where the kernel cannot send), ``"ppermute"``, or
        ``"none"`` on a ring of one. The kernel sends wherever it can: on
        the TPU backend (remote DMA has no other lowering), with the fused
        kernel live, on the one-slice schedule (the two-slice pipeline
        overlaps its own hops) and an unquantized wire (``quant`` takes the
        encode path), unless ``fused_dma=False``."""
        cfg = self.config
        if w == 1:
            return "none"
        if (fused and cfg.fused_dma is not False and cfg.num_slices == 1
                and cfg.quant is None and ring_dma.use_ring_dma()):
            return "in_kernel"
        if cfg.fused_dma and cfg.quant is None:
            return "ring_dma"
        return "ppermute"

    def _build_dense(self, w: int, nmb: int, g: DenseGeometry, epochs: int):
        lr, lam = self.config.lr, self.config.lam
        # the program runs on the STORED geometry throughout
        nmb_fine, rpw, cpb = g.nmb, g.rpw_store, g.cpb_store
        s_rows = rpw // nmb
        bf = jnp.bfloat16
        col_tile = self._hop_tile(g, nmb)
        fused = col_tile > 0
        slab_pick = _slab_pick(fused, self.config.num_slices * w)
        # the kernel that sends its H block returns the block it received,
        # so _build runs the rotation scan with shift=0 (body_hops)
        wire = self._ring_wire(fused, w)
        ring_hop = wire == "in_kernel"

        def make_update_bucket(data):
            # missing entries are NaN-encoded in the value slab — no separate
            # mask slab (halves slab memory and cuts a quarter of the epoch's
            # HBM traffic; measured +14% samples/s, identical SSE)
            v_slab, row_cnt, col_cnt = data

            @scoped("sgdmf.stripes")
            def _run_stripes_pallas(w_t, h_block, sse, cnt, block, rcnt,
                                    ccnt, col_tile, ring_hop):
                # fused hop kernel: pred/G stay in VMEM → one slab read per
                # hop instead of XLA's ~5 slab-sized passes (pallas_kernels
                # module doc). It takes the WHOLE slab and picks the
                # resident block in its own index map: no copy of the block
                # stands in front of it. Factors ride transposed (K, rows):
                # W is CARRIED so (w_carry below), the H block, which the
                # rotator ships as (cpb, K), is transposed here.
                # With ring_hop the kernel ALSO ships the updated H to the
                # ring neighbor (VMEM → remote HBM, ops/ring_dma) and the
                # returned block is the received one — the rotation scan
                # then runs shift=0 (body_hops).
                outs = pallas_kernels.dense_mf_hop_pallas(
                    v_slab, block, w_t, h_block.T,
                    rcnt.reshape(nmb, s_rows), ccnt, lr, lam,
                    col_tile=col_tile, ring_hop=ring_hop)
                w_t, hop_sse, h_t = outs[0], outs[2], outs[-1 if ring_hop
                                                           else 1]
                return w_t, h_t.T, sse + hop_sse, cnt + jnp.sum(ccnt)

            @scoped("sgdmf.stripes")
            def _run_stripes(w_local, h_block, sse, cnt, vb, rcnt, ccnt):
                def stripe(state, xs):
                    hb, sse = state
                    w_s, v_s, rc_s, cc_s = xs
                    # pred/G/dW/dH are three MXU GEMMs; bf16 inputs, f32
                    # accumulation (matches the fused pallas hop bit-for-bit)
                    hb_b = hb.astype(bf)
                    pred = jax.lax.dot_general(
                        w_s.astype(bf), hb_b, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)  # (s, cpb)
                    g = jnp.where(jnp.isnan(v_s), jnp.asarray(0.0),
                                  v_s.astype(jnp.float32) - pred
                                  ).astype(bf)               # bf16, masked
                    dw = jax.lax.dot_general(
                        g, hb_b, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)  # (s, K)
                    dh = jax.lax.dot_general(
                        g, w_s.astype(bf), (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)  # (cpb, K)
                    w_s = w_s + lr * (dw - lam * rc_s[:, None] * w_s)
                    hb = hb + lr * (dh - lam * cc_s[:, None] * hb)
                    sse = sse + jnp.sum(g.astype(jnp.float32) ** 2)
                    return (hb, sse), w_s

                (h_block, sse), w_new = jax.lax.scan(
                    stripe,
                    (h_block, sse),
                    (w_local.reshape(nmb, s_rows, -1),
                     vb.reshape(nmb, s_rows, cpb),
                     rcnt.reshape(nmb, s_rows),
                     ccnt))
                cnt = cnt + jnp.sum(ccnt)
                return w_new.reshape(rpw, -1), h_block, sse, cnt

            def update_bucket(w_local, h_block, sse, cnt, bucket_id):
                # runs when jax traces, only: which update this program's
                # hops run, and where the resident block is picked
                metrics.DEFAULT.count(
                    "sgd_mf.hops.fused" if fused else "sgd_mf.hops.xla")
                if wire != "none":
                    metrics.DEFAULT.count("sgd_mf.ring." + wire)
                if slab_pick != "static":
                    metrics.DEFAULT.count(
                        "sgd_mf.picks.in_kernel" if fused
                        else "sgd_mf.picks.copied")
                # single-block mesh (W=1, 1 slice): static index — the
                # dynamic-slice would copy the full slab (GBs) every hop
                single = v_slab.shape[0] == 1

                def pick(a):
                    return a[0] if single else jnp.take(a, bucket_id, axis=0)

                with jax.named_scope("sgdmf.select"):
                    # the fused kernel reads its block out of the whole slab
                    vb = None if fused else pick(v_slab)      # (rpw, cpb)
                    rcnt, ccnt = pick(row_cnt), pick(col_cnt)
                    # col counts are stored at the finest stripe granularity
                    # (nmb_fine, cpb); coarser budgets sum adjacent fine
                    # stripes
                    ccnt = ccnt.reshape(nmb, nmb_fine // nmb, cpb).sum(axis=1)
                if fused:
                    return _run_stripes_pallas(
                        w_local, h_block, sse, cnt,
                        0 if single else bucket_id, rcnt, ccnt, col_tile,
                        ring_hop)
                return _run_stripes(w_local, h_block, sse, cnt, vb, rcnt,
                                    ccnt)

            return update_bucket

        if not fused:
            return self._build(w, 3, make_update_bucket, epochs)
        # the kernel takes and returns W as (K, rpw): the call carries it so
        # across its hops and epochs, transposed once on the way in and once
        # on the way out (every caller sees the (rows, K) tables)
        transpose = scoped("sgdmf.stripes")(jnp.transpose)
        return self._build(w, 3, make_update_bucket, epochs,
                           body_hops=ring_hop, w_carry=(transpose, transpose))

    def _program(self, layout: str, nmb: int, epochs: int, geom: Tuple):
        """Compile (or fetch) the SPMD program for a given per-hop budget.

        ``geom`` is the layout geometry captured at prepare time — buckets are
        padded to a multiple of ``minibatches_per_hop``, so every divisor
        ``nmb`` yields a valid program over the same device arrays."""
        w = self.session.num_workers
        if layout == "sparse":
            (m_total,) = geom
            if m_total % nmb:
                raise ValueError(f"budget {nmb} does not divide bucket {m_total}")
            key = ("sparse", w, nmb, m_total // nmb, self.config.num_slices,
                   epochs)
            if key not in self._compiled:
                self._compiled[key] = self._build_sparse(
                    w, nmb, m_total // nmb, epochs)
        else:
            if geom.nmb % nmb:
                raise ValueError(f"budget {nmb} does not divide band {geom.nmb}")
            key = ("dense", w, nmb, geom, self.config.num_slices, epochs)
            if key not in self._compiled:
                self._compiled[key] = self._build_dense(w, nmb, geom, epochs)
        return key

    # -- preparation ----------------------------------------------------------- #

    def _dense_geometry(self, num_rows: int, num_cols: int
                        ) -> Tuple[DenseGeometry, int]:
        w = self.session.num_workers
        n_blocks = self.config.num_slices * w
        nmb = self.config.minibatches_per_hop
        s_rows = -(-(-(-num_rows // w)) // nmb)   # stripes must split evenly
        cpb = -(-num_cols // n_blocks)
        rank = self.config.rank
        # stored sizes follow the fused hop's tiles on every backend: a
        # stripe's rows ride the 128 lanes of the transposed W blocks, the
        # rank their 8 sublanes; a block is a whole number of 256-column
        # tiles, since a hop at 128-column tiles takes a third longer than
        # at 256 and 512 gains nothing on 256 (PERF.md, Findings, PR 26)
        return DenseGeometry(
            nmb, s_rows, cpb, rank,
            s_store=lane_pack.round_up(s_rows, lane_pack.LANES),
            cpb_store=lane_pack.round_up(cpb, 2 * lane_pack.LANES),
            rank_store=lane_pack.round_up(rank, 8)), n_blocks

    def _choose_layout(self, num_rows: int, num_cols: int) -> str:
        cfg = self.config
        if cfg.layout in ("dense", "sparse"):
            return cfg.layout
        g, n_blocks = self._dense_geometry(num_rows, num_cols)
        slab_elems = g.rpw_store * g.cpb_store * n_blocks
        # budget densify's PEAK: the NaN-encoded bf16 value slab plus the
        # transient bf16 mask slab alive at the same time (4 B/elem total);
        # and the int32 scatter-index limit must hold for auto to pick dense
        slab_bytes = 4 * slab_elems
        return ("dense" if slab_bytes <= cfg.dense_max_bytes
                and slab_elems < 2 ** 31 else "sparse")

    def prepare(self, rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                num_rows: int, num_cols: int, seed: int = 0):
        """Bucketize + place data and init factors on the mesh ONCE.

        Returns an opaque state tuple for :meth:`fit_prepared` — keeps host
        prep and H2D transfer out of timed regions (KMeans.prepare idiom)."""
        cfg = self.config
        if cfg.num_slices not in (1, 2):
            raise ValueError("num_slices must be 1 or 2")
        if cfg.layout not in ("auto", "dense", "sparse"):
            raise ValueError(f"layout must be auto|dense|sparse, got "
                             f"{cfg.layout!r}")
        with telemetry.phase("sgd_mf.prepare"):
            _validate_coo(rows, cols, num_rows, num_cols, vals)
            # keep-first dedupe for BOTH layouts: identical training sets
            rows, cols, vals, dropped = dedupe_coo(rows, cols, vals, num_cols)
            layout = self._choose_layout(num_rows, num_cols)
            if layout == "dense":
                state = self._prepare_dense(rows, cols, vals, num_rows,
                                            num_cols, seed)
            else:
                state = self._prepare_sparse(rows, cols, vals, num_rows,
                                             num_cols, seed)
        self.last_layout_stats["duplicates_dropped"] = dropped
        return state

    def _init_factors(self, rng, w_rows: int, h_rows: int):
        scale = 1.0 / np.sqrt(self.config.rank)
        w0 = (scale * rng.standard_normal(
            (w_rows, self.config.rank))).astype(np.float32)
        h0 = (scale * rng.standard_normal(
            (h_rows, self.config.rank))).astype(np.float32)
        return w0, h0

    def _place_h0(self, h0: np.ndarray, w: int, cpb: int):
        """Scatter H blocks to their home workers (2-slice: worker-major
        (W, 2, cpb, K) so each worker starts with slice-A block w and slice-B
        block W+w)."""
        sess = self.session
        if self.config.num_slices == 2:
            return sess.scatter(np.ascontiguousarray(
                h0.reshape(2, w, cpb, -1).transpose(1, 0, 2, 3)))
        return sess.scatter(h0)

    def _prepare_sparse(self, rows, cols, vals, num_rows, num_cols, seed):
        cfg = self.config
        sess = self.session
        w = sess.num_workers
        n_blocks = cfg.num_slices * w
        if cfg.balance and len(rows):
            row_assign = serpentine_assign(
                np.bincount(rows, minlength=num_rows), w)
            col_assign = serpentine_assign(
                np.bincount(cols, minlength=num_cols), n_blocks)
        else:
            row_assign = identity_assign(num_rows, w)
            col_assign = identity_assign(num_cols, n_blocks)
        r_idx, c_idx, val, mask, rpw, cpb = bucketize(
            rows, cols, vals, w, num_rows, num_cols, cfg.minibatches_per_hop,
            num_col_blocks=n_blocks, row_assign=row_assign,
            col_assign=col_assign, validate=False)
        nnz = max(len(vals), 1)
        self.last_layout_stats = {
            "layout": "sparse", "padded": int(r_idx.size),
            "nnz": len(vals), "overhead": r_idx.size / nnz,
        }
        geom = (r_idx.shape[2],)

        rng = np.random.default_rng(seed)
        w0, h0 = self._init_factors(rng, w * rpw, n_blocks * cpb)
        return ("sparse", (sess.scatter(r_idx), sess.scatter(c_idx),
                           sess.scatter(val), sess.scatter(mask)),
                sess.scatter(w0), self._place_h0(h0, w, cpb),
                (num_rows, num_cols, row_assign, col_assign, rpw, cpb, geom))

    def _prepare_dense(self, rows, cols, vals, num_rows, num_cols, seed):
        cfg = self.config
        sess = self.session
        w = sess.num_workers
        nmb = cfg.minibatches_per_hop
        g, n_blocks = self._dense_geometry(num_rows, num_cols)
        rpw, cpb = g.rpw, g.cpb
        row_assign = identity_assign(w * rpw, w)
        col_assign = identity_assign(num_cols, n_blocks)

        # who holds a rating is logical; where it lies there is stored
        owner = rows // rpw
        r_loc = g.store_row(rows % rpw)
        block = cols // cpb
        c_loc = cols % cpb
        rpw_st, cpb_st = g.rpw_store, g.cpb_store
        # flat slab index within a worker: ((b * rpw) + r) * cpb + c
        flat = (block.astype(np.int64) * rpw_st + r_loc) * cpb_st + c_loc

        # group per worker, pad to a common capacity for the SPMD densify
        order = np.argsort(owner, kind="stable")
        counts = np.bincount(owner, minlength=w)
        cap = max(int(counts.max()), 1)
        idx_p = np.zeros((w, cap), np.int64)
        val_p = np.zeros((w, cap), np.float32)
        msk_p = np.zeros((w, cap), np.float32)
        starts = np.concatenate([[0], np.cumsum(counts)])
        fo, vo = flat[order], vals[order]
        for wi in range(w):
            lo, hi = starts[wi], starts[wi + 1]
            idx_p[wi, :hi - lo] = fo[lo:hi]
            val_p[wi, :hi - lo] = vo[lo:hi]
            msk_p[wi, :hi - lo] = 1.0

        slab_elems = n_blocks * rpw_st * cpb_st
        if slab_elems >= 2 ** 31:
            # device indices are int32 (jax x64 off): a bigger slab would
            # silently wrap and drop entries in the scatter
            raise ValueError(
                f"dense slab has {slab_elems} elements per worker (>= 2^31); "
                "use layout='sparse' or more workers")

        def densify(idx, val, msk):
            # scatter directly in bf16 — indices are unique (deduped in
            # prepare), so add == set and no f32 transient doubles the peak
            # memory that _choose_layout budgeted. Missing entries become NaN
            # (the mask slab is transient, freed after this program).
            telemetry.traced("sgd_mf.densify")   # runs when jax traces, only
            idx, val, msk = idx[0], val[0], msk[0]
            bf = jnp.bfloat16
            v = jnp.zeros((slab_elems,), bf).at[idx].add(
                (val * msk).astype(bf))
            m = jnp.zeros((slab_elems,), bf).at[idx].add(msk.astype(bf))
            v = jnp.where(m > 0, v, jnp.asarray(jnp.nan, bf))
            return v.reshape((1, n_blocks, rpw_st, cpb_st))

        # one-shot prepare-time program, routed through session.run — the
        # documented build-and-invoke-once entry point (jaxlint JL103). It
        # still traces per prepare call (prepare runs once per layout);
        # programs that must keep their trace cache hold the session.spmd
        # callable instead.
        v_slab = sess.run(
            densify,
            sess.scatter(idx_p), sess.scatter(val_p), sess.scatter(msk_p),
            in_specs=(sess.shard(), sess.shard(), sess.shard()),
            out_specs=sess.shard(),
        )

        # regularizer counts (host): per-(worker, block, row) and
        # per-(worker, block, stripe, col)
        wb = owner.astype(np.int64) * n_blocks + block
        row_cnt = np.bincount(wb * rpw_st + r_loc,
                              minlength=w * n_blocks * rpw_st
                              ).reshape(w, n_blocks, rpw_st
                                        ).astype(np.float32)
        stripe = r_loc // g.s_store
        col_cnt = np.bincount((wb * nmb + stripe) * cpb_st + c_loc,
                              minlength=w * n_blocks * nmb * cpb_st
                              ).reshape(w, n_blocks, nmb, cpb_st
                                        ).astype(np.float32)

        col_tile = self._hop_tile(g, nmb)
        self.last_layout_stats = {
            "layout": "dense", "padded": int(w) * slab_elems,
            "nnz": len(vals), "overhead": w * slab_elems / max(len(vals), 1),
            # stored over logical slab cells, and which update the hops of
            # the configured budget run
            "pad_overhead": rpw_st * cpb_st / (rpw * cpb),
            "fused_hop": col_tile > 0, "col_tile": col_tile,
            "slab_pick": _slab_pick(col_tile > 0, n_blocks),
            "ring_hop": self._ring_wire(col_tile > 0, w),
        }

        # the first model is drawn at the LOGICAL sizes (what the
        # configuration's `init` states), then embedded
        rng = np.random.default_rng(seed)
        meta = (num_rows, num_cols, row_assign, col_assign, rpw, cpb, g)
        w0, h0 = self._to_stored(
            *self._init_factors(rng, w * rpw, n_blocks * cpb), meta)
        return ("dense",
                (v_slab, sess.scatter(row_cnt), sess.scatter(col_cnt)),
                sess.scatter(w0), self._place_h0(h0, w, cpb_st), meta)

    # -- logical <-> stored (DenseGeometry; the sparse layout has one) --------- #

    @staticmethod
    def _to_logical(w_tab, h_tab, meta):
        """Host factor tables as the device holds them -> the logical tables
        (``(W * rpw, rank)``, H in its fetched shape with ``cpb`` rows a
        block)."""
        g = meta[6]
        if not isinstance(g, DenseGeometry):
            return w_tab, h_tab
        return (_repad(w_tab, g.s_store, g.s_rows, g.rank),
                _repad(h_tab, g.cpb_store, g.cpb, g.rank))

    @staticmethod
    def _to_stored(w_tab, h_tab, meta):
        """Logical host factor tables -> what the device holds."""
        g = meta[6]
        if not isinstance(g, DenseGeometry):
            return w_tab, h_tab
        return (_repad(w_tab, g.s_rows, g.s_store, g.rank_store),
                _repad(h_tab, g.cpb, g.cpb_store, g.rank_store))

    @staticmethod
    def _stored_maps(meta):
        """``(row_assign, col_assign, rpw, cpb)`` addressing the arrays on
        the device (``meta[2:6]`` address the logical tables)."""
        row_assign, col_assign, rpw, cpb, g = meta[2:7]
        if not isinstance(g, DenseGeometry):
            return row_assign, col_assign, rpw, cpb
        return ((row_assign[0], g.store_row(row_assign[1])), col_assign,
                g.rpw_store, g.cpb_store)

    # -- training -------------------------------------------------------------- #

    def _finalize(self, out_w, out_h, meta):
        """Device factor blocks → (num_rows, K)/(num_cols, K) in original id
        order (undo the worker/block permutation)."""
        num_rows, num_cols, row_assign, col_assign, rpw, cpb = meta[:6]
        # fetch gathers sharded blocks across a gang
        out_w, out_h = self._to_logical(fetch(out_w), fetch(out_h), meta)
        if self.config.num_slices == 2:
            # (W, 2, cpb, K) worker-major → block-id-major (2W*cpb, K)
            w_, _, cpb_, k = out_h.shape
            out_h = out_h.transpose(1, 0, 2, 3).reshape(2 * w_ * cpb_, k)
        w_flat = out_w.reshape(-1, out_w.shape[-1])
        rb, rl = row_assign
        w_final = w_flat[rb[:num_rows].astype(np.int64) * rpw
                         + rl[:num_rows]]
        cb, cl = col_assign
        h_final = out_h[cb[:num_cols].astype(np.int64) * cpb + cl[:num_cols]]
        return w_final, h_final

    def train_prepared(self, state):
        """Run the compiled training program; factors stay ON DEVICE.

        Returns (w_dev, h_dev, rmse ndarray). The rmse fetch waits for the
        run to finish, but the factor blocks (MBs) are not transferred —
        this is the timing surface benchmarks use: steady-state epoch
        throughput, not the one-time D2H of the final model
        (``benchmark/configs/sgdmf-k100.driver.py``). :meth:`fit_prepared`
        adds the fetch + de-permutation."""
        layout, data, w0, h0, meta = state
        # the lines from here to the dispatch keep the numbers they had: a
        # kernel's payload, and with it the compile cache's key of the step,
        # carries the line of every frame above it (PERF.md section 7, row 11)
        with telemetry.phase("sgd_mf.call") as call:
            key = self._program(layout, self.config.minibatches_per_hop,
                                self.config.epochs, meta[6])
            step, args = self._compiled[key], (*data, w0, h0)
            with telemetry.phase("step.dispatch"):
                out_w, out_h, rmse = step(*args)
            telemetry.record_program("sgd_mf.fit", step, args)
            with telemetry.phase("step.fetch"):
                rmse = np.asarray(rmse)
            # telemetry at the fetch that was already here: one event per
            # epoch, wall amortized over the scanned program (step_log
            # docstring)
            telemetry.record_chunk(
                "sgd_mf", start=0, losses=rmse.tolist(),
                wall_s=call.elapsed(),
                ledger=telemetry.ledger_for("sgd_mf",
                                            quant=self.config.quant))
        return out_w, out_h, rmse

    def fit_prepared(self, state) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Run training on already-placed device data (no host prep)."""
        out_w, out_h, rmse = self.train_prepared(state)
        w_final, h_final = self._finalize(out_w, out_h, state[4])
        return w_final, h_final, rmse

    def fit_adaptive(self, state, tuner: Optional["HopBudgetTuner"] = None,
                     epochs: Optional[int] = None
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                "HopBudgetTuner"]:
        """Train with an auto-tuned per-hop budget (reference:
        ``adjustMiniBatch``/``setTimer``, SGDCollectiveMapper.java:281-287).

        Runs one compiled epoch per host step, measures it, and lets the
        tuner pick the next budget among the divisors of
        ``minibatches_per_hop``. Programs for each budget are compiled once
        (ahead of the timed region) and reuse the same device data — the
        banded-shape property of the bucket padding."""
        import time as _time

        layout, data, w0, h0, meta = state
        geom = meta[6]
        nmb_fine = self.config.minibatches_per_hop
        cands = [d for d in range(1, nmb_fine + 1) if nmb_fine % d == 0]
        tuner = tuner or HopBudgetTuner(cands)
        epochs = epochs if epochs is not None else self.config.epochs
        w_cur, h_cur = w0, h0
        rmses = []
        for _ in range(epochs):
            nmb = tuner.next_budget()
            key = self._program(layout, nmb, 1, geom)
            if key not in self._warm:
                # AOT-compile outside the timed region and call the compiled
                # executable directly — the jit wrapper's dispatch cache is NOT
                # populated by lower().compile(), so calling the wrapper would
                # re-compile inside the timing. One throwaway call (outputs
                # discarded; the program is pure) absorbs first-execution
                # costs (the executable's load onto the device).
                exe = self._compiled[key].lower(*data, w_cur, h_cur).compile()
                np.asarray(exe(*data, w_cur, h_cur)[2])
                self._warm[key] = exe
            fn = self._warm[key]
            t0 = _time.perf_counter()
            w_cur, h_cur, r = fn(*data, w_cur, h_cur)
            r = np.asarray(r)        # the fetch waits for the epoch
            tuner.record(nmb, _time.perf_counter() - t0)
            rmses.append(r[0])
        w_final, h_final = self._finalize(w_cur, h_cur, meta)
        return w_final, h_final, np.asarray(rmses), tuner

    def warmup_epoch(self, state) -> None:
        """Compile + run the one-epoch program once, outputs discarded (the
        program is pure), so a subsequent timed ``fit_checkpointed`` region
        measures steady state rather than compilation."""
        layout, data, w0, h0, meta = state
        key = self._program(layout, self.config.minibatches_per_hop, 1,
                            meta[6])
        np.asarray(self._compiled[key](*data, w0, h0)[2])

    def fit_checkpointed(self, state, checkpointer, epochs: Optional[int] = None,
                         save_every: int = 1
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
        """Train with periodic checkpointing and automatic resume.

        Runs one compiled epoch per host step (the fit_adaptive granularity);
        every ``save_every`` epochs the factor state is saved through
        ``checkpointer`` (utils.checkpoint.Checkpointer). If the checkpoint
        directory already holds state, training RESUMES from the newest epoch
        — a capability upgrade over the reference, which restarts from
        iteration 0 (SURVEY §5; KMUtil.storeCentroids saved final models
        only). Returns (W, H, rmse-per-epoch-run, first_epoch) where
        ``first_epoch`` is where this call started (0 for a fresh run).

        The training math is deterministic given (data, factors), so an
        interrupted + resumed run produces exactly the trajectory of an
        uninterrupted run at the same per-epoch program granularity.

        World-size-agnostic: the checkpoint stores the factors in this
        world's permuted block layout PLUS the (bin, slot) id maps and a
        manifest meta naming the writing world. Resuming under a different
        worker count (the supervisor's shrink/re-place relaunch) restores
        with the SAVED shapes and re-shards both factor tables onto this
        session's layout ON DEVICE (collectives.reshard: chunk-bounded
        all_to_all rounds, bitwise the numpy oracle, no host gather of a
        sharded leaf; ``SGDMFConfig.reshard`` selects the ring/host
        alternatives) — exact for every id the ratings reference,
        including across a 1-slice/2-slice layout change. Same-world
        resume takes the historical bitwise path untouched.
        """
        from harp_tpu.parallel import faults
        from harp_tpu.utils import checkpoint as ckpt_lib

        layout, data, w0, h0, meta = state
        num_rows, num_cols, row_assign, col_assign = meta[:4]
        geom = meta[6]
        nmb = self.config.minibatches_per_hop
        epochs = epochs if epochs is not None else self.config.epochs
        w_cur, h_cur = w0, h0
        start = 0
        world = self.session.num_workers
        # the id maps ride in every checkpoint so a DIFFERENT world can
        # de-permute to canonical id order (maps are deterministic given the
        # data, but only for the world that computed them)
        assign_leaves = {
            "row_bin": np.asarray(row_assign[0][:num_rows], np.int32),
            "row_slot": np.asarray(row_assign[1][:num_rows], np.int32),
            "col_bin": np.asarray(col_assign[0][:num_cols], np.int32),
            "col_slot": np.asarray(col_assign[1][:num_cols], np.int32),
        }
        # meta-less (pre-elastic) steps hold only the factor pair — restore
        # them through the legacy template so same-world resume of an old
        # work dir keeps working (a world CHANGE on one raises the clear
        # no-metadata error in _repartition_saved)
        # (a checkpoint holds the LOGICAL tables: stored padding stays on
        # the device, so a step reads the same under any layout)
        w_like, h_like = self._to_logical(
            np.zeros(w0.shape, w0.dtype), np.zeros(h0.shape, h0.dtype), meta)
        legacy_like = {"w": w_like, "h": h_like}
        # verified resume, single read: manifest-checksummed steps only (a
        # corrupt newest checkpoint falls back to the previous step,
        # utils.checkpoint). `like` only conveys tree structure + dtypes:
        # host zeros, not a full (gang-collective) D2H gather of the
        # factors. A step written at another world size restores through a
        # template with the SAVED shapes (its manifest meta), then
        # re-partitions below.
        resume, saved, ck_meta = checkpointer.restore_latest_valid(
            like_from_meta=lambda m: (ckpt_lib.meta_like(m) if m
                                      else legacy_like),
            return_meta=True)
        if resume is not None:
            start = resume
            if ck_meta is not None and ck_meta.get("model") not in (
                    None, "sgd_mf"):
                # the template followed the SAVED shapes, so the leaf-count
                # guard cannot catch a wrong-model work dir anymore — the
                # recorded model name does
                raise ValueError(
                    f"checkpoint in this work dir was written by model "
                    f"{ck_meta['model']!r}, not sgd_mf — wrong work dir?")
            if start > epochs:
                raise ValueError(
                    f"checkpoint at epoch {start} exceeds the requested "
                    f"{epochs} epochs — the saved model is already trained "
                    f"past this budget (pass a fresh checkpoint directory "
                    f"or a larger epochs)")
            # shape equality is NOT world equality (64 rows block to 8x8 or
            # 4x16): trust the recorded world, fall back to shapes for
            # meta-less legacy steps
            if (int(ck_meta["world"]) != world if ck_meta
                    and "world" in ck_meta
                    else np.shape(saved["w"]) != legacy_like["w"].shape):
                saved = self._repartition_saved(saved, ck_meta, state)
            else:
                w_tab, h_tab = self._to_stored(saved["w"], saved["h"], meta)
                saved = {**saved, "w": w_tab, "h": h_tab}
            # the device reshard path hands back already-placed arrays in
            # this session's sharding — no host round trip to undo
            w_cur = (saved["w"] if isinstance(saved["w"], jax.Array)
                     else jax.device_put(np.asarray(saved["w"]),
                                         w0.sharding))
            h_cur = (saved["h"] if isinstance(saved["h"], jax.Array)
                     else jax.device_put(np.asarray(saved["h"]),
                                         h0.sharding))
        key = self._program(layout, nmb, 1, geom)
        fn = self._compiled[key]
        rmses = []
        # telemetry: per-epoch step events at the existing np.asarray(r)
        # host sync (one epoch per host step here — real per-step timing)
        ledger = telemetry.ledger_for("sgd_mf", quant=self.config.quant)
        import time as _time

        for epoch in range(start, epochs):
            # iteration-boundary fault hook (parallel.faults)
            faults.fire(epoch + 1, checkpointer)
            t0 = _time.perf_counter()
            w_cur, h_cur, r = fn(*data, w_cur, h_cur)
            rmse_e = float(np.asarray(r)[0])
            wall = _time.perf_counter() - t0
            rmses.append(rmse_e)
            telemetry.record_chunk("sgd_mf", start=epoch, losses=[rmse_e],
                                   wall_s=wall, ledger=ledger)
            if (epoch + 1) % save_every == 0 or epoch + 1 == epochs:
                with telemetry.phase("sgd_mf.checkpoint"):
                    w_tab, h_tab = self._to_logical(
                        fetch(w_cur), fetch(h_cur), meta)
                    save_state = {"w": w_tab, "h": h_tab, **assign_leaves}
                    checkpointer.save(
                        epoch + 1, save_state,
                        meta=ckpt_lib.state_meta(
                            save_state, model="sgd_mf", world=world,
                            num_rows=num_rows, num_cols=num_cols,
                            num_slices=self.config.num_slices,
                            layout=layout))
        if hasattr(checkpointer, "wait"):
            checkpointer.wait()     # surface a failed async final write
        w_final, h_final = self._finalize(w_cur, h_cur, meta)
        return w_final, h_final, np.asarray(rmses), start

    def _reshard_mode(self) -> str:
        from harp_tpu.collectives import reshard as rs

        return rs.resolve_mode(self.config.reshard,
                               self.session.num_workers)

    def _repartition_saved(self, saved: dict, ck_meta: Optional[dict],
                           state) -> dict:
        """Factor state written at another world size → this session's
        layout. Default (``SGDMFConfig.reshard``): the DEVICE collective
        redistribution of collectives/reshard.py — the saved leaves go
        host→device once (the H2D any resume pays) and every row moves to
        its new (bin, slot) home in chunk-bounded all_to_all (or ring
        ppermute) rounds ON the mesh; no sharded leaf is ever gathered to
        host, and the returned leaves are device arrays already in this
        session's sharding. ``reshard="host"`` keeps the PR 8 numpy
        gather-and-resplit (collectives.repartition) as the parity oracle.
        Both paths are exact for every id the ratings reference; padded
        slots keep this run's fresh init (training math never reads them —
        their counts are zero, so neither gradients nor the regularizer
        move them). 2-slice layouts re-shard like 1-slice through the
        worker-major half-slice placement (reshard.block_layout), on
        either side of the resize. Run once at resume: the reshard step
        program is its own jaxlint-pinned trace target
        (reshard_factor_a2a/_ring); no collective is added to any TRAINING
        step program, so those budgets stay bitwise."""
        from harp_tpu.collectives import repartition as rep
        from harp_tpu.collectives import reshard as rs

        layout, data, w0, h0, meta = state
        num_rows, num_cols = meta[:2]
        # the saved tables are logical, their new home is the device's
        row_assign, col_assign, rpw, cpb = self._stored_maps(meta)
        if ck_meta is None or "world" not in ck_meta:
            raise ValueError(
                "checkpoint does not match this session's factor shapes and "
                "carries no world metadata (written by a pre-elastic "
                "version?) — resume at the original worker count")
        old_world = int(ck_meta["world"])
        old_ns = int(ck_meta.get("num_slices", 1))
        new_ns = self.config.num_slices
        if (int(ck_meta.get("num_rows", num_rows)) != num_rows
                or int(ck_meta.get("num_cols", num_cols)) != num_cols):
            raise ValueError(
                f"checkpoint was written for a "
                f"{ck_meta.get('num_rows')}x{ck_meta.get('num_cols')} "
                f"rating matrix; this run prepared {num_rows}x{num_cols} — "
                f"not the same dataset")
        w = self.session.num_workers

        def stored_rank(a):
            a = np.asarray(a)
            return np.pad(a, [(0, 0)] * (a.ndim - 1)
                          + [(0, w0.shape[-1] - a.shape[-1])])

        saved_w = stored_rank(saved["w"])
        saved_h = stored_rank(saved["h"])
        old_rpw = saved_w.shape[0] // old_world
        # 2-slice checkpoints hold H as fetched: worker-major
        # (W_old, 2, cpb_old, K) — already flat device order when raveled
        old_cpb = (saved_h.shape[2] if saved_h.ndim == 4
                   else saved_h.shape[0] // (old_ns * old_world))
        old_w_lay = rs.block_layout(
            (np.asarray(saved["row_bin"]), np.asarray(saved["row_slot"])),
            old_rpw, old_world, 1)
        old_h_lay = rs.block_layout(
            (np.asarray(saved["col_bin"]), np.asarray(saved["col_slot"])),
            old_cpb, old_world, old_ns)
        new_w_lay = rs.block_layout(row_assign, rpw, w, 1)
        new_h_lay = rs.block_layout(col_assign, cpb, w, new_ns)
        mode = self._reshard_mode()
        if mode in ("device", "ring"):
            schedule = "alltoall" if mode == "device" else "ring"
            chunk = (self.config.reshard_chunk_bytes
                     or rs.DEFAULT_CHUNK_BYTES)
            w_new = rs.reshard_factor(
                self.session, saved_w, old_w_lay, old_world, new_w_lay,
                num_rows, w0, chunk_bytes=chunk, schedule=schedule)
            h_new = rs.reshard_factor(
                self.session, saved_h, old_h_lay, old_world, new_h_lay,
                num_cols, h0, chunk_bytes=chunk, schedule=schedule)
            return {**saved, "w": w_new, "h": h_new}
        # host oracle: bin-major flat arrays on both sides (2-slice device
        # order worker-major <-> bin-major via the half-slice transpose)
        def to_bin_major(a):
            return (a.transpose(1, 0, 2, 3).reshape(-1, a.shape[-1])
                    if a.ndim == 4 else a)

        def from_bin_major(flat, ns, w_, rpb):
            if ns == 1:
                return flat
            k = flat.shape[-1]
            return (flat.reshape(ns, w_, rpb, k).transpose(1, 0, 2, 3))

        fill_h = to_bin_major(fetch(h0))
        w_new = rep.repartition_factor(
            saved_w, (saved["row_bin"], saved["row_slot"]), old_rpw,
            row_assign, rpw, num_rows, fetch(w0))
        h_new = rep.repartition_factor(
            to_bin_major(saved_h),
            (saved["col_bin"], saved["col_slot"]), old_cpb,
            col_assign, cpb, num_cols, fill_h)
        return {**saved, "w": w_new,
                "h": from_bin_major(h_new, new_ns, w, cpb)}

    def fit(self, rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
            num_rows: int, num_cols: int, seed: int = 0
            ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Train; returns (W (num_rows, K), H (num_cols, K), rmse-per-epoch)."""
        return self.fit_prepared(self.prepare(rows, cols, vals, num_rows,
                                              num_cols, seed))


class HopBudgetTuner:
    """Chooses the per-hop minibatch budget from measured epoch times.

    Policy (mirrors the intent of the reference's adaptive timer,
    SGDCollectiveMapper.adjustMiniBatch:623): more minibatches per hop =
    more sequential SGD steps = better convergence per epoch, but smaller
    device ops. Sweep each candidate once, then exploit the LARGEST budget
    whose time is within ``slack`` of the fastest, refining the estimate of
    the chosen budget with an EWMA each epoch."""

    def __init__(self, candidates, slack: float = 0.2):
        if not candidates:
            raise ValueError("need at least one candidate budget")
        self.candidates = sorted(set(int(c) for c in candidates))
        self.slack = slack
        self.times: dict = {}
        self._sweep = list(self.candidates)

    def next_budget(self) -> int:
        return self._sweep[0] if self._sweep else self.chosen

    @property
    def chosen(self) -> int:
        if not self.times:
            return self.candidates[-1]
        best = min(self.times.values())
        ok = [c for c in self.candidates
              if self.times.get(c, np.inf) <= best * (1 + self.slack)]
        return max(ok) if ok else self.candidates[-1]

    def record(self, budget: int, seconds: float) -> None:
        if self._sweep and self._sweep[0] == budget:
            self._sweep.pop(0)
        prev = self.times.get(budget)
        self.times[budget] = (seconds if prev is None
                              else 0.7 * prev + 0.3 * seconds)


def numpy_rmse(w_f: np.ndarray, h_f: np.ndarray, rows, cols, vals) -> float:
    pred = np.einsum("ij,ij->i", w_f[rows], h_f[cols])
    return float(np.sqrt(np.mean((vals - pred) ** 2)))
