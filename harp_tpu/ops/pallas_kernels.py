"""Pallas TPU kernels — fused hot ops the XLA autofuser can't produce.

Reference parity: the role Intel DAAL's hand-tuned AVX-512 kernels played
(SURVEY §2.5 — third_party/daal-2018 libJavaAPI.so behind every ml/daal
algorithm): the dense SGD-MF hop, flash attention and the batched SPD solve.

Each kernel has a dispatch predicate (``use_*``) that selects it on the TPU
backend at the shapes it tiles and the XLA path elsewhere; CPU tests run the
kernels in interpret mode. A kernel Mosaic refuses raises the compiler's own
error — nothing here catches it and retries through XLA.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from harp_tpu.ops import lane_pack


# --------------------------------------------------------------------------- #
# Dense SGD-MF fused hop (the flagship rotate workload's inner loop)
# --------------------------------------------------------------------------- #
#
# XLA's lowering of the masked stripe-GEMM hop (models/sgd_mf._build_dense)
# materializes pred and G — two (s_rows, cpb) bf16 intermediates — to HBM and
# re-reads G for the dW/dH GEMMs: ~5 slab-sized HBM passes per epoch (16.7 ms
# an epoch at MovieLens-10M's shape: ledger, PR 25, `sgdmf-k100.ml10m`). This
# kernel fuses the whole stripe update: pred and G live only in VMEM, so the
# epoch's HBM traffic collapses to one slab read plus factor-sized I/O.
# Factors are carried TRANSPOSED — (K, rows) — so every block's lane dimension
# is a 128-multiple (K rides the sublane dimension, where 8 | K suffices);
# the dense fused program carries W in this form across its hops and epochs
# (models/sgd_mf._build_dense: transposed once a call, not once a hop).
#
# That puts a stripe's ROWS on the lanes of every W block, so a stripe must
# be a whole number of 128-lane tiles; the dense layout stores its stripes,
# column blocks and rank at such sizes (models/sgd_mf.DenseGeometry).
#
# Grid: (nmb stripes, n_ct column tiles), sequential on TPU with j innermost.
# W is pre-update for a whole stripe, so its layout work is done ONCE A
# STRIPE, at the stripe's first tile: the (K, s) float32 block is cast to
# bf16 and kept in VMEM scratch in both forms the products take, (K, s) for
# dH and (s, K) for the prediction, whose left operand Mosaic would
# otherwise transpose in front of every step's first MXU pass (PR 37).
# Per step: pred = W_sᵀ·H_j (MXU, bf16), G = where(isnan(V), 0, V − pred),
# dWᵀ += H_j·Gᵀ (accumulated in VMEM scratch across j; as dW += G·H_jᵀ and
# transposed at the stripe's end where the tile is 256 lanes: the faster
# form there, dense_mf_hop_pallas), dHᵀ = W_sᵀ·G applied
# to H_j IMMEDIATELY (tile j is touched once per stripe, so in-stripe update
# order matches the XLA path), W written once at the stripe's last tile.
# H lives ENTIRELY in VMEM for the whole kernel (full-array out block,
# initialized from the input at step 0): stripe i+1 reads stripe i's updates
# with no HBM round trip and no reliance on write-back/prefetch ordering.
#
# The kernel is handed the worker's WHOLE slab, (n_blocks, rpw, cpb), and
# the resident block's index as a prefetched scalar: the slab's index map
# reads the leading (squeezed) block coordinate from it, so the tile DMAs
# are the ones a single block would get, from an offset base. Which block is
# resident changes every hop with (wid - t) % W, a value only the device
# knows: picked in front of the kernel (`jnp.take`) XLA materialises the
# block, 481 MB read and 481 MB written a hop at MovieLens-20M's shape on
# four chips, which took longer than the hop itself (PERF.md, Findings, PR
# 28). The body sees an (s, col_tile) tile.


def _dense_mf_hop_kernel(block_ref, v_ref, wt_ref, rc_ref, cc_ref, ht_in_ref,
                         wt_out_ref, ht_ref, sse_ref, *refs,
                         lr: float, lam: float, col_tile: int, n_ct: int,
                         nmb: int = 1, ring: Optional[dict] = None,
                         dw_rows: bool = False):
    del block_ref                                 # the index maps read it
    if ring is not None:
        hn_ref, dw_ref, wb_ref, wbt_ref, send_sem, recv_sem = refs
    else:
        dw_ref, wb_ref, wbt_ref = refs
    i = pl.program_id(0)
    j = pl.program_id(1)
    bf = jnp.bfloat16

    @pl.when((i == 0) & (j == 0))
    def _init():
        sse_ref[...] = jnp.zeros_like(sse_ref)
        ht_ref[...] = ht_in_ref[...]              # H resident in VMEM

    @pl.when(j == 0)
    def _stripe_start():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        wt = wt_ref[...]                          # (K, s) f32, pre-update
        wb_ref[...] = wt.astype(bf)               # dH's left operand
        wbt_ref[...] = wt.T.astype(bf)            # (s, K): the prediction's

    cols = pl.ds(j * col_tile, col_tile)
    ht = ht_ref[:, cols]                          # (K, CT) f32, current
    ht_b = ht.astype(bf)
    pred = jax.lax.dot_general(wbt_ref[...], ht_b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (s, CT)
    # NaN test in f32: mosaic has no bf16 vector compare (cast is free VPU)
    vf = v_ref[...].astype(jnp.float32)           # (s, CT); NaN = missing
    g = jnp.where(jnp.isnan(vf), jnp.zeros_like(pred),
                  vf - pred).astype(bf)
    dw_lhs, dw_rhs = (g, ht_b) if dw_rows else (ht_b, g)
    dw_ref[...] += jax.lax.dot_general(
        dw_lhs, dw_rhs, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # (s, K) or (K, s)
    dh = jax.lax.dot_general(
        wb_ref[...], g, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)       # (K, CT)
    cc = cc_ref[0:1, :]                           # (1, CT): stripe i's counts
    ht_ref[:, cols] = ht + lr * (dh - lam * cc * ht)
    gf = g.astype(jnp.float32)
    sse_ref[...] += jnp.full((1, 128), jnp.sum(gf * gf) / 128.0, jnp.float32)

    @pl.when(j == n_ct - 1)
    def _stripe_end():
        rc = rc_ref[0:1, :]                       # (1, s): stripe i's counts
        wt = wt_ref[...]                          # float32, pre-update
        dw = dw_ref[...].T if dw_rows else dw_ref[...]
        wt_out_ref[...] = wt + lr * (dw - lam * rc * wt)

    if ring is not None:
        from harp_tpu.ops import ring_dma

        # The fused rotation hop. H is resident in VMEM for the whole
        # kernel, and column tile j is final once the LAST stripe has
        # updated it, at this step (nmb - 1, j): its copy to the right
        # neighbour's h_t_next (VMEM -> remote HBM) starts right after the
        # store above and runs while the MXU takes tiles j+1, ...; the last
        # step waits for every send and receive. The transfer overlaps the
        # last stripe, where a ppermute after the kernel waits for the whole
        # block (on four v5e chips half of that wait went: PERF.md, Findings)
        # and costs writing H to HBM and a staging copy on either side.
        # The handshake opens the last stripe (ops/ring_dma.stream_hop); the
        # program sends this way wherever it can (models/sgd_mf._ring_wire).
        ring_dma.stream_hop(ht_ref, hn_ref, send_sem, recv_sem,
                            i == nmb - 1, j, col_tile, n_ct,
                            ring["axis_name"], ring["num_workers"])


# what the kernel may ask of VMEM (v5e: 128 MiB physical)
DENSE_MF_VMEM_LIMIT = 100 * 1024 * 1024


def dense_mf_hop_vmem_bytes(k: int, cpb: int, s: int, col_tile: int) -> int:
    """VMEM the fused hop needs at these shapes, from above: 2.5 copies of
    the resident H, 7 of a stripe's (K, s) factor block (in and out double
    buffered, the dW scratch, a bf16 value and a temporary), the stripe's two
    bf16 operands in scratch ((K, s), and (s, K) on 128 lanes), 6 bytes a
    cell of the (s, col_tile) slab tile (its two bf16 buffers plus what
    mosaic keeps of pred/G), 4 MiB. PR 26 fitted it to the least
    ``vmem_limit_bytes`` at which the v5e compiler accepts the kernel (ten
    shapes, s 1024-17920, cpb 2048-32768, K 16-128, every tile: 2 to 10 MiB
    over it at each); the kernel that keeps the stripe's operands in scratch
    asks for less than that one did, so this now stands 4 to 23 MiB over the
    least limit at each of twelve such shapes (PERF.md, Findings, PR 37)."""
    return (10 * k * cpb + 28 * k * s + (2 * k + 256) * s
            + 6 * s * col_tile) + (4 << 20)


def dense_mf_col_tile(cpb: int, s_rows: int, k: int) -> int:
    """The fused hop's column tile at these (stored) shapes: the widest of
    512 / 256 / 128 lanes that divides the column block and whose VMEM
    estimate fits :data:`DENSE_MF_VMEM_LIMIT`; 0 where the shapes do not
    tile (stripe rows ride the lanes of the transposed W blocks, rank the
    sublanes) or nothing fits."""
    if s_rows % lane_pack.LANES or k % 8:
        return 0
    return next((ct for ct in (4 * lane_pack.LANES, 2 * lane_pack.LANES,
                               lane_pack.LANES)
                 if cpb % ct == 0 and dense_mf_hop_vmem_bytes(
                     k, cpb, s_rows, ct) <= DENSE_MF_VMEM_LIMIT), 0)


def dense_mf_hop_pallas(v_slab: jax.Array, block, w_t: jax.Array,
                        h_t: jax.Array, rc2: jax.Array, cc2: jax.Array,
                        lr: float, lam: float, col_tile: int = 256,
                        interpret: bool = False, ring_hop: bool = False,
                        axis_name: str = "workers"):
    """One dense-MF hop over block ``block`` of the worker's slab. v_slab
    (n_blocks, rpw, cpb) bf16 NaN-encoded, the WHOLE slab; block an int32
    scalar (traced or not) in [0, n_blocks); w_t (K, rpw) f32; h_t (K, cpb)
    f32; rc2 (nmb, s_rows) and cc2 (nmb, cpb) the picked block's regularizer
    counts. Returns (w_t_new, h_t_new, sse), w_t_new in w_t's buffer where
    the caller lets go of it. nmb = rc2.shape[0].

    The block is picked in the slab's index map, from the prefetched scalar
    (kernel comment): no copy of the block stands in front of the kernel,
    and a block the index does not name is never read. A ring of one passes
    its (1, rpw, cpb) slab and 0.

    ``ring_hop`` (TPU only, inside shard_map over ``axis_name``): also
    ring-ship the UPDATED H block to the right neighbor from inside the
    kernel, each column tile as the last stripe finishes it (kernel
    comment; ops/ring_dma.stream_hop), and return
    ``(w_t_new, h_t_new, sse, h_t_next)``: ``h_t_next`` is the block this
    worker receives, what ``lax_ops.rotate(h, 1)`` would deliver; the
    caller's rotation scan must then run shift=0."""
    if ring_hop and interpret:
        raise ValueError("ring_hop=True has no interpret-mode lowering "
                         "(remote DMA is not emulated off-TPU)")

    nmb, s = rc2.shape
    k, rpw = w_t.shape
    _, slab_rows, cpb = v_slab.shape
    if rpw != nmb * s or slab_rows != rpw or h_t.shape[1] != cpb:
        raise ValueError("dense_mf_hop_pallas: inconsistent shapes")
    if cpb % col_tile or s % 128 or k % 8 or col_tile % 128:
        raise ValueError("dense_mf_hop_pallas: tiling constraints violated")
    n_ct = cpb // col_tile
    ring = None
    if ring_hop:
        from harp_tpu.collectives import lax_ops as _lax_ops

        ring = {"axis_name": axis_name,
                "num_workers": _lax_ops.num_workers(axis_name)}
    # dW accumulates as G·H_jᵀ, (s, K), where a tile is two 128-lane chunks
    # of contraction and as H_j·Gᵀ, (K, s), elsewhere: the faster form at
    # each of eight shapes on the chip (-4 to -9 % of a hop at tile 256,
    # +4 % at 512, +23 % at 128: PERF.md, Findings, PR 37), same sums
    dw_rows = col_tile == 2 * lane_pack.LANES
    kernel = functools.partial(_dense_mf_hop_kernel, lr=lr, lam=lam,
                               col_tile=col_tile, n_ct=n_ct, nmb=nmb,
                               ring=ring, dw_rows=dw_rows)
    # per-stripe count rows ride in 8-sublane-replicated blocks: mosaic
    # cannot vector-load a single DYNAMIC sublane row, so give each stripe an
    # aligned (8, ·) block and read its (static) first row in-kernel
    rc8 = jnp.broadcast_to(rc2[:, None, :], (nmb, 8, s)).reshape(nmb * 8, s)
    cc8 = jnp.broadcast_to(cc2[:, None, :],
                           (nmb, 8, cpb)).reshape(nmb * 8, cpb)
    # every index map takes the prefetched block index last; the slab's
    # alone reads it
    out_specs = [
        pl.BlockSpec((k, s), lambda i, j, b: (0, i)),           # w_t_new
        pl.BlockSpec((k, cpb), lambda i, j, b: (0, 0)),         # h_t_new
        pl.BlockSpec((1, 128), lambda i, j, b: (0, 0)),         # sse
    ]
    out_shape = [
        jax.ShapeDtypeStruct((k, rpw), jnp.float32),
        jax.ShapeDtypeStruct((k, cpb), jnp.float32),
        jax.ShapeDtypeStruct((1, 128), jnp.float32),
    ]
    scratch_shapes = [pltpu.VMEM((s, k) if dw_rows else (k, s),
                                 jnp.float32),                  # dW
                      pltpu.VMEM((k, s), jnp.bfloat16),         # W_s
                      pltpu.VMEM((s, k), jnp.bfloat16)]         # W_sᵀ
    params = {"vmem_limit_bytes": DENSE_MF_VMEM_LIMIT}
    if ring is not None:
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))     # h_t_next
        out_shape.append(jax.ShapeDtypeStruct((k, cpb), jnp.float32))
        scratch_shapes += [pltpu.SemaphoreType.DMA] * 2
        from harp_tpu.ops import ring_dma as _rd

        params["collective_id"] = _rd.COLLECTIVE_IDS["dense_mf_ring"]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                                  # block
        grid=(nmb, n_ct),
        in_specs=[
            pl.BlockSpec((None, s, col_tile),
                         lambda i, j, b: (b[0], i, j)),         # v_slab
            pl.BlockSpec((k, s), lambda i, j, b: (0, i)),       # w_t
            pl.BlockSpec((8, s), lambda i, j, b: (i, 0)),       # rc8
            pl.BlockSpec((8, col_tile), lambda i, j, b: (i, j)),  # cc8
            pl.BlockSpec((k, cpb), lambda i, j, b: (0, 0)),     # h_t full
        ],
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # W in place (operand 2 -> result 0): a stripe's block is read before
        # it is written and no other stripe's is touched, so a caller that
        # carries W through a loop keeps ONE buffer and copies nothing
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(**params),
        interpret=interpret,
        name="dense_mf_hop",
    )(jnp.asarray(block, jnp.int32).reshape(1), v_slab, w_t, rc8, cc8, h_t)
    if ring is not None:
        w_t_new, h_t_new, sse128, h_next = outs
        return w_t_new, h_t_new, jnp.sum(sse128), h_next
    w_t_new, h_t_new, sse128 = outs
    return w_t_new, h_t_new, jnp.sum(sse128)


# --------------------------------------------------------------------------- #
# Flash attention (the long-context inner loop)
# --------------------------------------------------------------------------- #
#
# The XLA blocked_attention path (parallel/ring_attention.py) already keeps
# the (L, L) score tensor out of HBM, but its lax.scan lowering re-reads the
# FULL query block and round-trips the (H, L) running stats + (H, L, Dv)
# accumulator through HBM on every KV step — measured 4.6 TFLOP/s effective
# at L=16k. This kernel holds one query tile's stats/accumulator in VMEM
# scratch across a KV-innermost grid, so HBM traffic collapses to one pass
# over Q/K/V plus the output write.
#
# r7 — the grid is BLOCK-SPARSE BY CONSTRUCTION for causal. The r5 kernel
# predicated fully-masked causal blocks off with pl.when (exact — they
# contributed p = 0; 938k → 1.10M tokens/s at L=16k), but the static mosaic
# grid still VISITED them and their block DMAs still landed — half the KV
# traffic of a causal pass moved dead bytes. Now the (q-tile, kv-block)
# pairs are flattened host-side into a trapezoid (_flash_grid_layout): q
# tile iq visits exactly n_kv_live(iq) = ceil(((iq+1)·bq)/bk) KV blocks, and
# the scalar-prefetched index maps (PrefetchScalarGridSpec) steer each grid
# step's DMA from the flat step id — blocks above the diagonal are never
# visited and never fetched. At bq=256/bk=512/L=16k that is 1056 KV-block
# fetches instead of 2048 (the exact L(L+2·bq)/2 trapezoid).
#
# r7 — HEAD PACKING fills the 128 MXU lanes at Dh ≤ 64. Unpacked, a Dh=64
# head pads its contraction to 128 lanes and half the dot-product lanes
# compute zeros. Packed, head pairs share one 128-lane tile ([q_even|q_odd]
# on lanes 0-63/64-127) and K/V expand IN-KERNEL to a block-diagonal
# (2·bk, 128) tile ([k_even|0] over [0|k_odd]), so one (bq,128)×(128,2bk)
# dot computes BOTH heads' scores with every contraction lane live, and the
# two heads' score columns stay separable (cols [0,bk) vs [bk,2bk)). The
# running max/denominator ride the same (bq,128) scratch with one head per
# lane half. Q/K/V/O also ship at 64 real lanes per head instead of a
# zero-padded 128 — HBM traffic halves on top of the MXU fill.

_PACK_LANES = 64       # lane split point: head-even on [0,64), head-odd on
#   [64,128). Packing engages only for dh, dv <= 64 and even H.


def _flash_grid_layout(n_q: int, n_kv: int, bq: int, bk: int, causal: bool):
    """Flat (q-tile, kv-block) visit order for the flash grid.

    Returns int32 arrays ``(iq_of, j_of)`` of length T — the flat grid's
    step → (q tile, kv block) map, consumed by the kernel's scalar-prefetch
    index maps. Causal: a trapezoid — q tile iq visits only the
    ``min(n_kv, ceil(((iq+1)·bq)/bk))`` KV blocks at or below the diagonal,
    so fully-masked blocks are never part of the grid (no visit, no DMA).
    Non-causal: the full rectangle in KV-innermost order. The accounting
    tests assert directly on these arrays — they ARE the index map.
    """
    import numpy as np

    iq_of, j_of = [], []
    for iq in range(n_q):
        m = n_kv if not causal else min(n_kv, -(-((iq + 1) * bq) // bk))
        iq_of.extend([iq] * m)
        j_of.extend(range(m))
    return (np.asarray(iq_of, np.int32), np.asarray(j_of, np.int32))


def _flash_kernel(iq_ref, j_ref, q_ref, k_ref, v_ref, *refs,
                  bq: int, bk: int, n_kv: int, causal: bool, scale: float,
                  l_real: int, packed: bool, return_stats: bool,
                  ring: Optional[dict] = None, n_heads: int = 1,
                  n_steps: int = 1):
    """One flat-grid step: fold KV block j_of[t] into q tile iq_of[t].

    Scratch m/d are (bq, 128): unpacked they are row-replicated; packed,
    lanes [0,64) carry the even head and [64,128) the odd head.

    ``ring`` (the r10 remote-copy epilogue; requires ``return_stats``):
    {"axis_name", "num_workers"} — two extra ANY-space inputs carry the
    full packed K/V (aliases of the blocked operands), two extra ANY-space
    outputs receive the NEXT hop's K/V. At the FIRST grid step the kernel
    barriers the ring and STARTS both whole-array remote copies; it WAITS
    at the LAST grid step — so the neighbor's KV streams in over the ICI
    DMA engines while this whole flash pass computes, which is exactly how
    the ring-attention hop hides (arXiv:2310.01889) — and the payload never
    takes the ppermute staging round trip through HBM."""
    if ring is not None:
        (o_ref, m_out_ref, d_out_ref, kn_ref, vn_ref,
         m_ref, d_ref, acc_ref, send_sems, recv_sems) = refs[2:]
        kh_ref, vh_ref = refs[:2]
    elif return_stats:
        o_ref, m_out_ref, d_out_ref, m_ref, d_ref, acc_ref = refs
    else:
        o_ref, m_ref, d_ref, acc_ref = refs
    hh = pl.program_id(0)
    t = pl.program_id(1)
    iq = iq_ref[t]
    j = j_ref[t]

    if ring is not None:
        from harp_tpu.ops import ring_dma

        ax, nw = ring["axis_name"], ring["num_workers"]

        @pl.when((hh == 0) & (t == 0))
        def _ring_start():
            ring_dma.ring_ready(ax, nw, 1)
            ring_dma.start_hop(kh_ref, kn_ref, send_sems.at[0],
                               recv_sems.at[0], ax, nw, 1)
            ring_dma.start_hop(vh_ref, vn_ref, send_sems.at[1],
                               recv_sems.at[1], ax, nw, 1)

        @pl.when((hh == n_heads - 1) & (t == n_steps - 1))
        def _ring_wait():
            # rebuild the identical descriptors to wait (ring_dma.hop_op
            # doc): the DMAs have had the whole pass to land
            ring_dma.hop_op(kh_ref, kn_ref, send_sems.at[0],
                            recv_sems.at[0], ax, nw, 1).wait()
            ring_dma.hop_op(vh_ref, vn_ref, send_sems.at[1],
                            recv_sems.at[1], ax, nw, 1).wait()

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                   # (bq, DL)
    kb = k_ref[0]                                  # (bk, DL)
    vb = v_ref[0]
    if packed:
        # expand the [k_even|k_odd] lane-packed block to the block-diagonal
        # (2bk, 128) form: rows [0,bk) keep even-head lanes, rows [bk,2bk)
        # keep odd-head lanes. The zeros never touch HBM — built in VMEM.
        lo = jax.lax.broadcasted_iota(jnp.int32, kb.shape, 1) < _PACK_LANES
        kb = jnp.concatenate([jnp.where(lo, kb, jnp.zeros_like(kb)),
                              jnp.where(lo, jnp.zeros_like(kb), kb)], axis=0)
        lov = jax.lax.broadcasted_iota(jnp.int32, vb.shape, 1) < _PACK_LANES
        vb = jnp.concatenate([jnp.where(lov, vb, jnp.zeros_like(vb)),
                              jnp.where(lov, jnp.zeros_like(vb), vb)], axis=0)
    s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # s: (bq, bk) unpacked; (bq, 2bk) packed with head-even cols [0, bk)
    ragged = n_kv * bk != l_real     # L padded up: mask padded KEY rows
    if causal or ragged:
        q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        c_idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        k_pos = j * bk + (c_idx % bk if packed else c_idx)
        mask = (q_pos >= k_pos) if causal else (q_pos >= 0)
        if ragged:
            mask = jnp.logical_and(mask, k_pos < l_real)
        s = jnp.where(mask, s, -1e30)
    m_prev = m_ref[...]                            # (bq, 128)
    if packed:
        lane = jax.lax.broadcasted_iota(jnp.int32, m_prev.shape, 1)
        m0 = jnp.max(s[:, :bk], axis=1)[:, None]   # (bq, 1) head-even
        m1 = jnp.max(s[:, bk:], axis=1)[:, None]   # (bq, 1) head-odd
        m_cur = jnp.where(lane < _PACK_LANES,
                          jnp.broadcast_to(m0, m_prev.shape),
                          jnp.broadcast_to(m1, m_prev.shape))
    else:
        m_cur = jnp.broadcast_to(jnp.max(s, axis=1)[:, None], m_prev.shape)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)                # (bq, 128)
    if packed:
        m_cols = jnp.concatenate(
            [jnp.broadcast_to(m_new[:, :1], (bq, bk)),
             jnp.broadcast_to(m_new[:, _PACK_LANES:_PACK_LANES + 1],
                              (bq, bk))], axis=1)
        p = jnp.exp(s - m_cols)                    # (bq, 2bk)
        d0 = jnp.sum(p[:, :bk], axis=1)[:, None]
        d1 = jnp.sum(p[:, bk:], axis=1)[:, None]
        d_blk = jnp.where(lane < _PACK_LANES,
                          jnp.broadcast_to(d0, m_prev.shape),
                          jnp.broadcast_to(d1, m_prev.shape))
        acc_scale = alpha          # per-lane: each half scales its own head
    else:
        p = jnp.exp(s - m_new[:, :1])              # (bq, bk)
        d_blk = jnp.broadcast_to(jnp.sum(p, axis=1)[:, None], m_prev.shape)
        acc_scale = jnp.broadcast_to(alpha[:, :1], acc_ref.shape)
    d_ref[...] = d_ref[...] * alpha + d_blk
    # v cast to f32: p is f32 (exp of scores) and mosaic dots need matching
    # operand dtypes — bf16 would otherwise fail lowering. Packed: p's col
    # halves hit v's block-diagonal rows, so head outputs land in disjoint
    # lane halves of acc.
    acc_ref[...] = acc_ref[...] * acc_scale + jax.lax.dot_general(
        p, vb.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    # the last LIVE block for this q tile (not n_kv-1: the trapezoid ends at
    # the diagonal) — recomputed from iq, mirroring _flash_grid_layout
    j_last = (jnp.minimum(n_kv, ((iq + 1) * bq + bk - 1) // bk) - 1
              if causal else n_kv - 1)

    @pl.when(j == j_last)
    def _finish():
        den = jnp.maximum(d_ref[...], 1e-30)
        if packed:
            o_ref[0] = acc_ref[...] / den
        else:
            o_ref[0] = acc_ref[...] / jnp.broadcast_to(den[:, :1],
                                                       acc_ref.shape)
        if return_stats:
            m_out_ref[0] = m_ref[...]
            d_out_ref[0] = d_ref[...]


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = False, bq: int = 256, bk: int = 512,
                           interpret: bool = False,
                           head_pack: Optional[bool] = None,
                           return_stats: bool = False,
                           ring_hop: bool = False,
                           axis_name: str = "workers"):
    """Single-chip flash attention: q/k (L, H, Dh), v (L, H, Dv) →
    (L, H, Dv).

    ANY L is accepted — the sequence pads up to a block multiple inside the
    wrapper and padded KEY rows are masked inside the kernel (padded QUERY
    rows are sliced off the output), so the win covers ragged lengths too
    (VERDICT r4 #10). Dh and Dv pad to lane multiples independently
    (Dv ≠ Dh is fine — cross-attention/Ulysses value heads). Dispatched by
    ``parallel.ring_attention.blocked_attention`` on TPU.

    ``causal=True`` runs the block-sparse trapezoid grid (r7): above-diagonal
    KV blocks are not in the grid at all — never visited, never DMA'd.

    ``head_pack``: None = auto (:func:`use_flash_head_pack`); True forces the
    two-heads-per-128-lane packed layout (raises if shapes don't allow it);
    False forces the unpacked layout.

    ``return_stats``: also return the streaming-softmax stats
    ``(out, m (L, H), den (L, H))`` so a caller can MERGE this result with
    other KV blocks' partial attention (the ring-attention hop composition:
    num = out·den). Stats rows for padded queries are sliced off with the
    output.

    ``ring_hop`` (requires ``return_stats``; TPU only — must be called
    inside shard_map over ``axis_name``): the r10 fused ring epilogue. The
    kernel ALSO ships this hop's K/V to the right ring neighbor via
    in-kernel ``make_async_remote_copy`` (start at the first grid step
    after a neighbor barrier, wait at the last — the DMA hides behind the
    whole flash pass) and the call returns two extra arrays
    ``(k_next, v_next)``, each (L, H, D): the NEXT hop's resident KV,
    bitwise the ``lax_ops.rotate`` result, without the ppermute staging
    round trip through HBM. ``parallel.ring_attention.ring_attention_mha``
    is the consumer.
    """
    if ring_hop and not return_stats:
        raise ValueError("ring_hop=True requires return_stats=True (the "
                         "ring merge needs the streaming-softmax stats)")
    if ring_hop and interpret:
        raise ValueError(
            "ring_hop=True has no interpret-mode lowering (remote DMA is "
            "not emulated off-TPU) — ring_attention_mha's fused path "
            "routes off-TPU hops through ops/ring_dma.hop instead")

    l, h, dh = q.shape
    dv = v.shape[-1]
    pack_ok = h % 2 == 0 and dh <= _PACK_LANES and dv <= _PACK_LANES
    if head_pack is None:
        packed = pack_ok and use_flash_head_pack(h, dh, dv)
    elif head_pack:
        if not pack_ok:
            raise ValueError(
                f"head_pack=True needs even H and Dh/Dv <= {_PACK_LANES}, "
                f"got H={h} Dh={dh} Dv={dv}")
        packed = True
    else:
        packed = False
    bq = min(bq, l)
    bk = min(bk, l)
    # q and kv axes pad INDEPENDENTLY to their own block multiples (a shared
    # lcm multiple explodes when a clamped block size is coprime with the
    # other — L=257 would have padded 256x)
    l_pad_q = -(-l // bq) * bq
    l_pad_kv = -(-l // bk) * bk
    scale = 1.0 / float(dh) ** 0.5
    n_q = l_pad_q // bq
    n_kv = l_pad_kv // bk
    iq_of, j_of = _flash_grid_layout(n_q, n_kv, bq, bk, causal)
    if packed:
        h_dim = h // 2
        d_q = d_k = d_v = 2 * _PACK_LANES

        def pack_heads(x, d_real, l_pad):
            # (L, H, d) → (HP, L_pad, 128): head 2i on lanes [0,64),
            # head 2i+1 on [64,128) — no zero-padded 128-lane per-head tile
            # ever reaches HBM
            x = jnp.pad(x, ((0, l_pad - l), (0, 0),
                            (0, _PACK_LANES - d_real)))
            return jnp.transpose(
                x.reshape(l_pad, h_dim, 2 * _PACK_LANES), (1, 0, 2))

        qt = pack_heads(q, dh, l_pad_q)
        kt = pack_heads(k, dh, l_pad_kv)
        vt = pack_heads(v, dv, l_pad_kv)
    else:
        h_dim = h
        d_q = d_k = -(-dh // 128) * 128
        d_v = -(-dv // 128) * 128
        qt = jnp.pad(jnp.transpose(q, (1, 0, 2)),
                     ((0, 0), (0, l_pad_q - l), (0, d_q - dh)))
        kt = jnp.pad(jnp.transpose(k, (1, 0, 2)),
                     ((0, 0), (0, l_pad_kv - l), (0, d_k - dh)))
        vt = jnp.pad(jnp.transpose(v, (1, 0, 2)),
                     ((0, 0), (0, l_pad_kv - l), (0, d_v - dv)))
    ring = None
    if ring_hop:
        from harp_tpu.collectives import lax_ops as _lax_ops

        ring = {"axis_name": axis_name,
                "num_workers": _lax_ops.num_workers(axis_name)}
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, n_kv=n_kv,
                               causal=causal, scale=scale, l_real=l,
                               packed=packed, return_stats=return_stats,
                               ring=ring, n_heads=h_dim, n_steps=len(iq_of))
    in_specs = [
        pl.BlockSpec((1, bq, d_q), lambda hh, t, iqr, jr: (hh, iqr[t], 0)),
        pl.BlockSpec((1, bk, d_k), lambda hh, t, iqr, jr: (hh, jr[t], 0)),
        pl.BlockSpec((1, bk, d_v), lambda hh, t, iqr, jr: (hh, jr[t], 0)),
    ]
    out_shape = [jax.ShapeDtypeStruct((h_dim, l_pad_q, d_v), jnp.float32)]
    out_specs = [pl.BlockSpec((1, bq, d_v),
                              lambda hh, t, iqr, jr: (hh, iqr[t], 0))]
    if return_stats:
        for _ in range(2):                         # running max, denominator
            out_shape.append(
                jax.ShapeDtypeStruct((h_dim, l_pad_q, 128), jnp.float32))
            out_specs.append(pl.BlockSpec(
                (1, bq, 128), lambda hh, t, iqr, jr: (hh, iqr[t], 0)))
    scratch_shapes = [
        pltpu.VMEM((bq, 128), jnp.float32),        # running max
        pltpu.VMEM((bq, 128), jnp.float32),        # running denominator
        pltpu.VMEM((bq, d_v), jnp.float32),        # output accumulator
    ]
    call_kwargs = {}
    if ring is not None:
        # the packed K/V ride AGAIN as un-blocked ANY-space operands (the
        # DMA source must see the whole array, the blocked specs only see
        # per-step tiles) and two ANY-space outputs receive the neighbor's
        # blocks; per-direction double-buffered send/recv semaphore pairs
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        out_shape += [jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                      jax.ShapeDtypeStruct(vt.shape, vt.dtype)]
        out_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        scratch_shapes += [pltpu.SemaphoreType.DMA((2,)),
                           pltpu.SemaphoreType.DMA((2,))]
        from harp_tpu.ops import ring_dma as _rd

        call_kwargs["compiler_params"] = pltpu.CompilerParams(
            collective_id=_rd.COLLECTIVE_IDS["flash_ring"])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # iq_of, j_of
        grid=(h_dim, len(iq_of)),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    args = [jnp.asarray(iq_of), jnp.asarray(j_of), qt, kt, vt]
    if ring is not None:
        args += [kt, vt]
    outs = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret, **call_kwargs,
    )(*args)
    if packed:
        o = jnp.transpose(outs[0], (1, 0, 2)).reshape(
            l_pad_q, h, _PACK_LANES)[:l, :, :dv]
    else:
        o = jnp.transpose(outs[0], (1, 0, 2))[:l, :, :dv]
    if not return_stats:
        return o

    def unpack_stat(raw):
        if packed:
            st = jnp.stack([raw[..., 0], raw[..., _PACK_LANES]], axis=-1)
            return jnp.transpose(st, (1, 0, 2)).reshape(l_pad_q, h)[:l]
        return jnp.transpose(raw[..., 0])[:l]

    if ring is None:
        return o, unpack_stat(outs[1]), unpack_stat(outs[2])

    def unpack_kv(raw, d_real):
        # inverse of the pack/transpose: the DMA moved the packed layout
        # verbatim, so slicing the zero padding back off recovers the
        # neighbor's (L, H, D) block bitwise
        if packed:
            return jnp.transpose(raw, (1, 0, 2)).reshape(
                l_pad_kv, h, _PACK_LANES)[:l, :, :d_real]
        return jnp.transpose(raw, (1, 0, 2))[:l, :, :d_real]

    return (o, unpack_stat(outs[1]), unpack_stat(outs[2]),
            unpack_kv(outs[3], dh), unpack_kv(outs[4], dv))


def use_flash_pallas(l: int) -> bool:
    """Dispatch predicate for the flash kernel: default ON for TPU at
    L ≥ 8192 (measured crossover — at L=4096 the XLA scan edges it 0.91×,
    from 8192 up the kernel wins 2.5×; per-tile scratch setup and the
    D-pad waste amortize with sequence length); any L — the kernel pads and
    masks ragged lengths internally (r5)."""
    if jax.default_backend() != "tpu":
        return False
    return l >= 8192


def use_flash_head_pack(h: int, dh: int, dv: int) -> bool:
    """Head-packing gate for the flash kernel: pack two heads per 128-lane
    tile when BOTH head dims fit a 64-lane half and H is even — at Dh=64
    the unpacked layout computes zeros on half the MXU contraction lanes
    AND ships a zero-padded 128-lane tile per head through HBM; packing
    fixes both. At Dh > 64 the lanes are already full."""
    return h % 2 == 0 and 0 < dh <= _PACK_LANES and 0 < dv <= _PACK_LANES


# --------------------------------------------------------------------------- #
# Batched small-SPD Cholesky solve (the ALS normal-equations bottleneck)
# --------------------------------------------------------------------------- #
#
# XLA lowers batched (N, K, K) `solve(..., assume_a="pos")` through a
# triangular-solve path that serializes on K and underfills the MXU: measured
# 30 ms per (8192, 32, 32) solve pair on v5e — yet the solve is only ~180
# MFLOP, i.e. the lowering runs at ~0.006 TFLOP/s. The fix is a LAYOUT move,
# not a FLOP move: put the BATCH on the 128-lane axis ((K, K, B) tiles,
# matrices ride sublanes/leading dim) so every step of an outer-product
# Cholesky + the two substitutions is a full-width VPU elementwise op across
# B independent systems. No MXU involvement at all — the MXU was never the
# right unit for K x K systems of this size; the VPU at full lane occupancy
# is.
#
# The operand is PACKED (:func:`spd_pack_rows`): the factorisation reads row
# j of its working copy only from the start of j's sublane group on, so a
# caller hands over exactly those entries, the block-upper triangle, as the
# rows of a (P, B) array, and the normal equations never exist in full: 5,824
# rows of the 10,816 at K = 104. The kernel's first act lays them out as the
# (K, K, B) working copy, a static aligned copy per sublane group.
#
# Only the sublane groups of 8 columns are unrolled; the columns of a group
# and the trailing update of a column (the rows below it: a dynamic index on
# the leading dim is free) are loops, and the update touches only the groups
# from the column's own on: at K = 104 that is 52 k vreg updates per 128
# systems where the whole-matrix rank-1 update of the r4 kernel made 140 k,
# in a program of 13 + 13 loop bodies where the r4 kernel unrolled every
# column (with the columns unrolled the executable took 8 s to load from the
# compile cache at every start, PERF.md, Findings, PR 27).
#
# Reference role: DAAL's cblas/LAPACK POTRF+POTRS behind
# daal_als/ALSDaalCollectiveMapper.java:49's train steps.

# what the kernel may ask of VMEM (v5e: 128 MiB physical)
SPD_SOLVE_VMEM_LIMIT = 100 * 1024 * 1024
SPD_SOLVE_NAME = "als_spd_solve"
# systems per grid step, widest first
SPD_SOLVE_TILES = (4 * lane_pack.LANES, 2 * lane_pack.LANES, lane_pack.LANES)


# imported here: a line added above the kernels of this file changes the
# source lines their payloads carry, and with them the compile cache's key of
# every step that holds one (PERF.md, Findings, PR 29)
import numpy as np  # noqa: E402


def spd_pack_rows(kp: int):
    """``(rows_j, rows_c)``: the entries of a symmetric ``(kp, kp)`` matrix
    the solve kernel reads, in the order its packed operand holds them. Row
    ``p`` is the pair ``(j, c)`` with ``c >= 8 * (j // 8)``, ordered by ``j``
    then ``c``: row ``j``'s segment is contiguous, starts at a multiple of 8
    and is ``kp - 8 * (j // 8)`` long. Every ``c >= j`` is there once (and
    the few ``c < j`` of j's own group); ``kp`` = 8 packs to the whole
    matrix. Static NumPy arrays, from ``kp`` alone."""
    if kp <= 0 or kp % 8:
        raise ValueError(f"spd_pack_rows: kp = {kp} is no multiple of 8")
    j, c = np.divmod(np.arange(kp * kp, dtype=np.int32), kp)
    keep = c >= 8 * (j // 8)
    return j[keep], c[keep]


def _spd_group_offset(kp: int, g: int) -> int:
    """The packed row at which sublane group ``g`` starts: 8 rows of
    ``kp - 8 h`` entries for every group ``h`` before it."""
    return 8 * g * kp - 32 * g * (g - 1)


def spd_pack_size(kp: int) -> int:
    """P, the rows of the packed operand: 5,824 at ``kp`` = 104."""
    return _spd_group_offset(kp, kp // 8)


def spd_pack(full: jax.Array) -> jax.Array:
    """``full[rows_j, rows_c]`` of :func:`spd_pack_rows`, ``(kp, kp, ...)``
    → ``(P, ...)``, as one static slice a sublane group (an element-wise
    gather is the slow way to say it on the TPU)."""
    kp = full.shape[0]
    return jnp.concatenate([
        full[g:g + 8, g:].reshape(8 * (kp - g), *full.shape[2:])
        for g in range(0, kp, 8)])


def spd_pack_outer(f: jax.Array) -> jax.Array:
    """The packed rows of the outer products of ``f`` (kp, n) with itself,
    ``f[rows_j] * f[rows_c]`` → ``(P, n)``, a sublane group's slab at a
    time: :func:`spd_pack` of the whole ``(kp, kp, n)`` product would have
    XLA form it first (two gathers of P rows each is the third way)."""
    kp = f.shape[0]
    return jnp.concatenate([
        (f[g:g + 8, None] * f[None, g:]).reshape(8 * (kp - g), *f.shape[1:])
        for g in range(0, kp, 8)])


def spd_unpack(at: jax.Array, kp: int) -> jax.Array:
    """The full systems ``(kp, kp, N)``, both triangles, from the packed
    ``(P, N)``: what a solver other than the kernel reads. An entry below
    its row's sublane group is read from its mirror image."""
    rows_j, rows_c = spd_pack_rows(kp)
    where = np.empty((kp, kp), np.int32)
    where[rows_c, rows_j] = np.arange(len(rows_j))
    where[rows_j, rows_c] = np.arange(len(rows_j))    # a kept entry: itself
    return at[where.reshape(-1)].reshape(kp, kp, *at.shape[1:])


def spd_solve_vmem_bytes(k: int, tile_b: int) -> int:
    """VMEM one grid step of the solve holds, from above: the packed (P, B)
    operand block twice (the pipeline's two buffers), the (K, K, B) working
    copy the factorisation overwrites, a dozen (K, B) rows (b, x, the
    reciprocal diagonal, each double-buffered or live), 2 MiB."""
    kp = lane_pack.round_up(k, 8)
    return 4 * tile_b * (2 * spd_pack_size(kp) + kp * kp + 12 * kp) + (2 << 20)


def spd_solve_tile(k: int) -> int:
    """Systems per grid step: the widest of 512 / 256 / 128 lanes whose
    estimate fits :data:`SPD_SOLVE_VMEM_LIMIT`, 0 where none does. Wider is
    faster (8,192 systems of 100 x 100 on a v5e: 5.70 / 3.94 / 3.18 ms at
    128 / 256 / 512; XLA's ``solve(assume_a="pos")`` 113 ms; PERF.md,
    Findings, PR 27): a row's loop overhead is shared by more systems."""
    return next((t for t in SPD_SOLVE_TILES
                 if spd_solve_vmem_bytes(k, t) <= SPD_SOLVE_VMEM_LIMIT), 0)


def _chol_solve_kernel(a_ref, b_ref, x_ref, l_ref, dinv_ref, col_ref, *,
                       k: int):
    """One batch tile: A packed (P, B) (:func:`spd_pack_rows`), SPD, b (k, B)
    → x (k, B); k a multiple of 8.

    Right-looking Cholesky on a working copy ``l_ref`` (k, k, B), of which
    only ``l_ref[j, 8 * (j // 8):]`` is ever read: the packed rows land
    there, and what lies below (never initialised, updated all the same by
    the trailing loop) reaches no result. At column j, row j of
    the running Schur complement (= its column j: the trailing block is kept
    symmetric) scaled by 1/sqrt(d_j) is column j of L; it overwrites row j,
    which nothing reads again, so ``l_ref[j, c]`` ends as ``L[c, j]``. Row
    i > j then loses ``L[i, j] * L[:, j]`` on the sublane groups that hold
    columns >= j. ``x_ref`` carries the right-hand side through the forward
    substitution in the same pass (``x_ref[j]`` ends as ``y_j * sqrt(d_j)``);
    the backward substitution is a masked dot of row p with what is solved.

    The sublane GROUPS of 8 columns are unrolled, the 8 columns of a group
    and the row groups below a column are loops: a row is a dynamic index on
    the leading dim, a group a static aligned slice. Mosaic neither loads
    nor stores a single DYNAMIC sublane, so the one sublane of a column
    inside its group is taken by a mask (``pick``) and written by a select,
    and the column's multipliers L[i, j] are laid out a group per leading
    index in ``col_ref`` (k/8, 8, B), where row i's is a static sublane."""
    tb = b_ref.shape[-1]
    groups = k // 8
    for g in range(groups):                   # row j's segment → l_ref[j, c0:]
        c0, off = 8 * g, _spd_group_offset(k, g)
        l_ref[c0:c0 + 8, c0:, :] = a_ref[off:off + 8 * (k - c0), :].reshape(
            8, k - c0, tb)
    x_ref[...] = b_ref[...].astype(jnp.float32)
    lane8 = jax.lax.broadcasted_iota(jnp.int32, (8, tb), 0)

    def pick(group, t):
        """Sublane t of an (8, B) group, as (1, B)."""
        return jnp.sum(jnp.where(lane8 == t, group, 0.0), axis=0,
                       keepdims=True)

    for g in range(groups):                   # a sublane group of columns
        c0 = 8 * g
        head = slice(c0, c0 + 8)
        sub = c0 + jax.lax.broadcasted_iota(jnp.int32, (k - c0, tb), 0)

        def column(t, carry, g=g, c0=c0, head=head, sub=sub):
            j = c0 + t
            dinv = jax.lax.rsqrt(pick(l_ref[j, head, :], t))  # SPD: diag > 0
            lj = jnp.where(sub >= j, l_ref[j, c0:, :] * dinv, 0.0)
            l_ref[j, c0:, :] = lj
            dinv_ref[head, :] = jnp.where(lane8 == t, dinv, dinv_ref[head, :])
            below = jnp.where(sub > j, lj, 0.0)           # L[i, j], i > j
            # forward substitution: r_i -= L[i, j] y_j below the diagonal
            x_ref[c0:, :] = x_ref[c0:, :] - below * (
                pick(x_ref[head, :], t) * dinv)
            for gi in range(g, groups):
                col_ref[gi] = below[8 * (gi - g):8 * (gi - g + 1), :]

            def trailing(gi, carry):
                for s in range(8):            # rows up to j lose nothing
                    i = 8 * gi + s
                    l_ref[i, c0:, :] = (l_ref[i, c0:, :]
                                        - col_ref[gi, s:s + 1, :] * lj)
                return carry

            return jax.lax.fori_loop(g, groups, trailing, carry)

        jax.lax.fori_loop(0, 8, column, 0)

    # backward substitution  Lᵀ x = y: x_p = (y_p − Σ_{c>p} L[c, p] x_c)/L[p, p]
    for c0 in range(k - 8, -1, -8):
        head = slice(c0, c0 + 8)
        sub = c0 + jax.lax.broadcasted_iota(jnp.int32, (k - c0, tb), 0)

        def unknown(step, carry, c0=c0, head=head, sub=sub):
            t = 7 - step
            p = c0 + t
            dinv = pick(dinv_ref[head, :], t)
            solved = jnp.where(sub > p, l_ref[p, c0:, :] * x_ref[c0:, :], 0.0)
            xp = (pick(x_ref[head, :], t) * dinv
                  - jnp.sum(solved, axis=0, keepdims=True)) * dinv
            x_ref[head, :] = jnp.where(lane8 == t, xp, x_ref[head, :])
            return carry

        jax.lax.fori_loop(0, 8, unknown, 0)


def spd_solve_lanes(at: jax.Array, bt: jax.Array, tile_b: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """Solve batch-last SPD systems: at (P, N) float32, the packed entries of
    the (K, K) matrices (:func:`spd_pack_rows`), bt (K, N) → (K, N); K a
    multiple of 8. The layout the kernel reads: a caller that builds its
    systems packed and batch-last (ALS's dense half-step) pays no relayout.
    N is padded up to the lane tile with identity systems."""
    k, n = bt.shape
    if k % 8 or at.shape != (spd_pack_size(k), n):
        raise ValueError(f"spd_solve_lanes: at {at.shape} vs bt {bt.shape}")
    packed = at.shape[0]
    tile_b = tile_b or spd_solve_tile(k)
    if not tile_b:
        raise ValueError(f"spd_solve_lanes: k = {k} does not fit VMEM")
    npad = lane_pack.round_up(n, tile_b)
    if npad != n:
        at = jnp.concatenate([at, jnp.broadcast_to(
            spd_pack(jnp.eye(k, dtype=at.dtype))[:, None],
            (packed, npad - n))], axis=1)
        bt = jnp.pad(bt, ((0, 0), (0, npad - n)))
    xt = pl.pallas_call(
        functools.partial(_chol_solve_kernel, k=k),
        grid=(npad // tile_b,),
        in_specs=[
            pl.BlockSpec((packed, tile_b), lambda i: (0, i)),
            pl.BlockSpec((k, tile_b), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((k, tile_b), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((k, npad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k, k, tile_b), jnp.float32),
                        pltpu.VMEM((k, tile_b), jnp.float32),
                        pltpu.VMEM((k // 8, 8, tile_b), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=min(SPD_SOLVE_VMEM_LIMIT, max(
                32 << 20, spd_solve_vmem_bytes(k, tile_b)))),
        interpret=interpret,
        name=SPD_SOLVE_NAME,
    )(at.astype(jnp.float32), bt.astype(jnp.float32))
    return xt[:, :n]


def spd_solve_pallas(a: jax.Array, b: jax.Array, tile_b: Optional[int] = None,
                     interpret: bool = False) -> jax.Array:
    """Solve batched SPD systems ``a @ x = b``: a (N, K, K), b (N, K) → (N, K).

    Pads K up to a sublane multiple (identity diagonal, zero rhs — padded
    components solve to 0 and never couple) and moves the batch onto the
    lanes, packing on the way (:func:`spd_pack`): the (N, K, K) → (P, N)
    move is one HBM-bound XLA pass that writes only the entries the kernel reads
    (:func:`spd_solve_lanes` takes operands that are built that way)."""
    n, k = b.shape
    if a.shape != (n, k, k):
        raise ValueError(f"spd_solve_pallas: a {a.shape} vs b {b.shape}")
    kp = lane_pack.round_up(k, 8)
    if kp != k:
        a = jnp.pad(a, ((0, 0), (0, kp - k), (0, kp - k)))
        a = a + jnp.diag((jnp.arange(kp) >= k).astype(a.dtype))[None]
        b = jnp.pad(b, ((0, 0), (0, kp - k)))
    xt = spd_solve_lanes(jnp.transpose(jax.vmap(spd_pack)(a), (1, 0)),
                         jnp.transpose(b, (1, 0)), tile_b, interpret)
    return jnp.transpose(xt, (1, 0))[:, :k]


def use_spd_solve_pallas(k: int) -> bool:
    """Dispatch predicate: default ON for TPU wherever a lane tile of the
    working set fits VMEM (:func:`spd_solve_tile`: K up to ~300)."""
    if jax.default_backend() != "tpu":
        return False
    return spd_solve_tile(k) > 0


def use_dense_mf_pallas(cpb: int, s_rows: int, k: int) -> bool:
    """Dispatch predicate for the fused dense-MF hop: default ON for TPU
    where a column tile fits (:func:`dense_mf_col_tile`)."""
    if jax.default_backend() != "tpu":
        return False
    return dense_mf_col_tile(cpb, s_rows, k) > 0
