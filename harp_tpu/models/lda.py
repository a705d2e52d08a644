"""LDA via collapsed Gibbs sampling with model rotation.

Reference parity: ml/java lda (LDAMPCollectiveMapper.java:51 — SparseLDA CGS with
the word-topic table ring-rotating via Rotator:257 and doc-topic tables local;
likelihood via allreduce:731 — BASELINE's "harp-java CGS-LDA, dynamic scheduler +
asynchronous rotation") and contrib/lda (CVB0).

TPU-native reformulation (SURVEY §7 "hard parts" — async semantics under SPMD):

* Docs are sharded over workers; the word-topic count matrix is split into W
  vocab blocks that ring-rotate (``ppermute``) — Harp's Rotator schedule. Words
  are dealt to blocks by **balanced (serpentine-LPT) corpus frequency** so a
  Zipf head word cannot blow up the per-(doc, block) token padding (the
  reference's clueweb vocabulary is exactly Zipf; set ``balance=False`` for the
  round-1 contiguous id ranges).
* Strictly sequential per-token Gibbs is hostile to SPMD, so sampling is
  **blocked**: during a hop, every token of the resident vocab block draws its
  topic from the CURRENT counts in parallel; count deltas are applied after the
  block (one-hot matmuls on the MXU). This is the standard blocked/stale-count
  approximation used by every distributed CGS (including Harp itself across
  workers — its staleness is per-rotation too, LDAMPCollectiveMapper rotates
  between updates); convergence is statistical, not token-sequential.
* Topic totals n_k are refreshed by psum once per hop — bounded staleness,
  replacing Harp's asynchronously drifting totals.
* The count WRITE rides the one-hot-GEMM scatter engine (ops/lane_pack —
  the shared software answer to TPU's missing per-lane HBM scatter), and
  ``vocab_sub_block=128`` additionally buckets tokens per 128-wide vocab
  SUB-block so the scatter GEMM is 128 lanes wide regardless of vocab size
  (FLOPs ∝ 128·K per token instead of vpb·K — the r5 large-vocab crossover
  remover; costs per-(doc, sub-block) padding, see bucketize_tokens_subblock).
* The reference splits the word-topic table into numModelSlices=2 pipelined
  slices (LDAMPCollectiveMapper wTableMap[k]) so rotation overlaps sampling.
  Both schedules exist here: ``num_model_slices=1`` (single-slice
  rotate_scan; XLA's async collective scheduler overlaps the block ppermute
  with the next hop's leading compute) and ``num_model_slices=2``
  (half-width blocks on collectives.rotation.pipelined_rotation — while one
  half-slice is being sampled the other is in flight, the reference's exact
  schedule).

Likelihood monitor: the REFERENCE formula, exactly (CalcLikelihoodTask.run:56 +
the topic-sum completion in printLikelihood, LDAMPCollectiveMapper.java:731-748
— MALLET's word-topic model-likelihood part):

    LL = Σ_{w,k: n_wk>0} [lgamma(β + n_wk) − lgamma(β)]
         − Σ_k lgamma(Vβ + n_k) + K·lgamma(Vβ)

allreduced per epoch, so BASELINE's time-to-likelihood rows are directly
measurable. :func:`full_model_log_likelihood` additionally adds the doc-topic
term of the full MALLET formula (the reference omits it) for model comparison,
and :func:`sequential_cgs_reference` is the single-device token-sequential CGS
oracle the convergence-parity test measures against.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu import telemetry
from harp_tpu.collectives import lax_ops, quantize, rotation
from harp_tpu.ops import lane_pack
from harp_tpu.parallel.mesh import WORKERS, fetch
from harp_tpu.session import HarpSession


@dataclasses.dataclass(frozen=True)
class LDAConfig:
    """Reference CLI parity (numTopics, alpha, beta, numIterations)."""

    num_topics: int = 10
    vocab: int = 100
    alpha: float = 0.1
    beta: float = 0.01
    epochs: int = 20
    method: str = "cgs"         # "cgs" (ml/java lda) or "cvb0" (contrib/lda)
    balance: bool = True        # serpentine-LPT word→block assignment
    wt_access: str = "auto"     # auto | gemm_scatter | gemm | gather — how
    #   tokens read/write the word-topic block.
    #   * "gather": row-gather read + segment_sum write (the r≤4 default).
    #     The r5 stage budget showed the segment_sum is 82% of the hop
    #     (2.25 of 2.73 ms/epoch on the bench config — XLA scatter
    #     serializes at ~8.5 ns/row).
    #   * "gemm_scatter" (r5): row-gather read, but the count WRITE becomes
    #     chunked one-hot GEMMs on the MXU — oh (chunk, vpb) in bf16
    #     (0/1 exact) against delta (chunk, K) in bf16 (CGS deltas are
    #     ±1/0, exact) with f32 accumulation, so counts stay exact while
    #     the scatter rides the MXU at tens of TF/s instead of the scatter
    #     unit. CGS only (CVB0's soft deltas are not bf16-exact).
    #   * "gemm": BOTH sides as full-width f32 one-hot matmuls (legacy).
    #   "auto" picks gemm_scatter for cgs — UNLESS the vocab block is wider
    #   than wt_gemm_scatter_max_vpb (below) and the sub-block layout is
    #   off, in which case it falls back to gather (ADVICE r5: the one-hot
    #   GEMM write costs vpb·K FLOPs per token, so a vpb~1M block would
    #   regress far below the segment_sum path; the r6 auto had no guard).
    #   The one-hot-GEMM implementation itself lives in ops/lane_pack.py
    #   (the shared scatter engine; bitwise-equal to the r5 in-module copy).
    wt_gemm_scatter_max_vpb: int = 65536   # auto-mode crossover guard: the
    #   widest vocab block auto still routes to gemm_scatter. The measured
    #   r5 crossover config (V=8000, K=64 → vpb=8064, vpb·K ≈ 516k FLOPs/
    #   token) still wins ~1.9x over segment_sum; the FLOP cost scales
    #   linearly in vpb while the scatter-unit cost does not, so 8x past
    #   the measured-winning width is where auto stops gambling. Explicit
    #   wt_access="gemm_scatter" is never overridden, and the vocab_sub_block
    #   layout ignores the guard (its one-hot is 128 lanes wide regardless
    #   of vpb — that layout exists precisely for the wide-vocab regime).
    vocab_sub_block: int = 0    # 0 = off; else (r6) the vocab-SUB-block token
    #   layout: tokens are bucketized per (vocab block, sub-block of this
    #   width), so the scatter's one-hot GEMM is `vocab_sub_block` lanes wide
    #   (one batched GEMM over all sub-blocks) instead of vpb wide — FLOPs
    #   ∝ 128 instead of V/(W·slices), which is what pushes large-vocab
    #   configs (vpb·K ≈ 512k, the measured r5 crossover) back toward the
    #   540M tokens/s no-scatter floor. Cost: per-(doc, sub-block) token
    #   padding (tracked in last_layout_stats). 128 = the MXU lane width.
    #   Requires method='cgs' and wt_access auto/gemm_scatter.
    num_model_slices: int = 1   # 1 = plain rotate_scan; 2 = the reference's
    #   numModelSlices=2 double-buffered schedule (half-width vocab blocks on
    #   pipelined_rotation: sample one half-slice while the other rotates)
    minibatches_per_hop: int = 4  # sequential doc-group sub-steps per hop:
    #   fully-parallel draws let every token of a word resample against the
    #   SAME stale word-topic row each round (a word's tokens can never
    #   coordinate on a topic), which parks the chain at a diffuse fixed
    #   point; refreshing counts between doc-groups restores near-sequential
    #   mixing (the analog of the reference's per-thread token batches under
    #   the dymoro timer, Scheduler.java:110-121)
    quant: Optional[str] = None  # None | "int8" | "bf16": quantize the
    #   per-hop topic-total allreduce's WIRE format with error feedback
    #   carried through the rotation + epoch scans (collectives/quantize.py).
    #   The per-hop (K,) delta psum is LDA's allreduce hot hop (W·epochs
    #   calls per fit); sampling probabilities then run on slightly-perturbed
    #   totals — convergence-equivalent, not bit-identical (the parity test
    #   uses the deterministic CVB0 method so the comparison is pure
    #   quantization error, not CGS chain divergence). The circulating
    #   word-topic block stays exact: its counts ARE the model — unless
    #   quant_wt opts it in too (below).
    quant_wt: bool = False      # r10 (requires quant): ALSO quantize the
    #   circulating word-topic BLOCK rotation payload — the (vpb, K) hop
    #   that is LDA's dominant wire volume (the topic-total allreduce quant
    #   above moves only K floats/hop). int8/bf16 per the quant codec, with
    #   the error-feedback residual threaded through the EPOCH carry
    #   (rotation.rotate_scan/pipelined_rotation ``ef_state``), so an epoch
    #   boundary never drops the pending encode error. Counts become
    #   fractional on the wire (EF keeps the time-average exact) — the
    #   parity test again uses CVB0 so the delta is pure wire error.
    fused_dma: bool = False     # r10: the wt-block rotation hops ride the
    #   fused ring-DMA engine (ops/ring_dma) instead of ppermute — on TPU
    #   the block moves HBM → remote HBM in-kernel with no staging copies;
    #   bitwise-identical schedule on every backend. A quantized wt wire
    #   (quant_wt) takes precedence over fusion (rotation.py module doc).
    reshard: str = "auto"       # r12: HOW a world-size-changing resume moves
    #   the chain state (token assignments z + word-topic counts wt) onto
    #   this session's blocking: "device" = collectives/reshard.py bounded
    #   all_to_all rounds on the mesh (z rows ride the token-key
    #   permutation, wt rows ride the (word_block, word_slot) maps — no
    #   host gather of a sharded leaf), "ring" = the ppermute schedule,
    #   "host" = the PR 8 numpy re-match/rebuild (parity oracle + 1-worker
    #   fallback), "auto" = device when the mesh has >1 worker.
    reshard_chunk_bytes: int = 0  # 0 = collectives.reshard default (1 MiB)


def bucketize_tokens(docs: np.ndarray, num_blocks: int, vpb: int,
                     word_block: Optional[np.ndarray] = None,
                     word_slot: Optional[np.ndarray] = None,
                     ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Host-side layout: (D, L) tokens → (D, W, Lb) grouped by home vocab block.

    Each hop then processes exactly the resident block's tokens (padded to the
    max per-(doc, block) count Lb) instead of sampling every token every hop.
    The stored token ids are block-LOCAL slots. ``word_block``/``word_slot``
    are optional id maps (see sgd_mf.serpentine_assign); default contiguous.
    """
    d, l = docs.shape
    rows = np.arange(d)[:, None]
    if word_block is None:
        block = np.minimum(docs // vpb, num_blocks - 1)
        slot = docs - block * vpb
    else:
        block = word_block[docs]
        slot = word_slot[docs]
    counts = np.zeros((d, num_blocks), np.int64)
    np.add.at(counts, (rows, block), 1)
    lb = max(int(counts.max()), 1)
    # padding slots hold local id 0 (in-range); mask zeroes their effect
    docs_b = np.zeros((d, num_blocks, lb), docs.dtype)
    mask_b = np.zeros((d, num_blocks, lb), np.float32)
    order = np.argsort(block, axis=1, kind="stable")
    sorted_block = np.take_along_axis(block, order, axis=1)
    sorted_slot = np.take_along_axis(slot, order, axis=1)
    bucket_starts = np.concatenate(
        [np.zeros((d, 1), np.int64), np.cumsum(counts, axis=1)[:, :-1]], axis=1)
    pos = np.arange(l)[None, :] - bucket_starts[rows, sorted_block]
    docs_b[rows, sorted_block, pos] = sorted_slot
    mask_b[rows, sorted_block, pos] = 1.0
    return docs_b, mask_b, lb


def bucketize_tokens_subblock(docs: np.ndarray, num_blocks: int, vpb: int,
                              sub: int, word_block: np.ndarray,
                              word_slot: np.ndarray
                              ) -> Tuple[np.ndarray, np.ndarray, int, int]:
    """Vocab-SUB-block layout: bucket tokens per (vocab block, ``sub``-wide
    sub-block of block-local slots), padded to the max per-(doc, sub-block)
    count Lbs. Returns ``(docs_b (D, NB, NS*Lbs), mask_b, lb, lbs)`` with
    ``lb = NS*Lbs`` and NS = vpb // sub; stored ids stay FULL block-local
    slots (gather and sampling are layout-agnostic), but within a (doc,
    block) row the tokens are grouped by sub-block, so the scatter can
    reshape its deltas to (NS, ·, K) and run one batched ``sub``-lane-wide
    one-hot GEMM (ops/lane_pack.gemm_scatter) instead of a vpb-wide one."""
    if vpb % sub:
        raise ValueError(f"vpb {vpb} must be a multiple of sub {sub}")
    ns = vpb // sub
    sub_of, _ = lane_pack.sub_block_split(word_slot, sub)
    fine_block = (word_block * ns + sub_of).astype(word_block.dtype)
    docs_f, mask_f, lbs = bucketize_tokens(
        docs, num_blocks * ns, vpb, fine_block, word_slot)
    d = docs.shape[0]
    docs_b = docs_f.reshape(d, num_blocks, ns * lbs)
    mask_b = mask_f.reshape(d, num_blocks, ns * lbs)
    return docs_b, mask_b, ns * lbs, lbs


class LDA:
    """Distributed CGS-LDA over a HarpSession mesh."""

    def __init__(self, session: HarpSession, config: LDAConfig):
        if config.method not in ("cgs", "cvb0"):
            raise ValueError(f"method must be 'cgs' or 'cvb0', got "
                             f"{config.method!r}")
        if config.num_model_slices not in (1, 2):
            raise ValueError(f"num_model_slices must be 1 or 2, got "
                             f"{config.num_model_slices}")
        if config.wt_access == "gemm_scatter" and config.method != "cgs":
            raise ValueError(
                "wt_access='gemm_scatter' requires method='cgs' (CVB0's "
                "soft deltas are not bf16-exact)")
        if config.quant_wt and config.quant is None:
            raise ValueError(
                "quant_wt=True requires quant='int8'|'bf16' (it selects "
                "WHICH payloads ride the quantized wire, not the codec)")
        if config.vocab_sub_block:
            if config.vocab_sub_block < 1:
                raise ValueError(
                    f"vocab_sub_block must be positive, got "
                    f"{config.vocab_sub_block}")
            if config.method != "cgs" or config.wt_access not in (
                    "auto", "gemm_scatter"):
                raise ValueError(
                    "vocab_sub_block requires method='cgs' with "
                    "wt_access='auto'/'gemm_scatter' (the sub-block layout "
                    "exists to narrow the gemm_scatter one-hot)")
        self.session = session
        self.config = config
        self._fns = {}
        self.last_layout_stats: dict = {}

    def _effective_minibatches(self, d_local: int) -> int:
        """Largest divisor of docs-per-worker within the configured budget —
        the sub-step count the compiled program actually runs."""
        return max(g for g in range(1, min(self.config.minibatches_per_hop,
                                           d_local) + 1) if d_local % g == 0)

    def _build(self, w: int, v_pad: int, lb: int, d_local: int,
               lbs: int = 0):
        cfg = self.config
        k = cfg.num_topics
        ns = cfg.num_model_slices
        nb = w * ns                           # rotating vocab blocks in total
        vpb = v_pad // nb                     # vocab per block
        comm = (quantize.CommConfig(quant=cfg.quant)
                if cfg.quant is not None else None)
        nmb = self._effective_minibatches(d_local)
        dg = d_local // nmb
        if cfg.wt_access not in ("auto", "gemm_scatter", "gemm", "gather"):
            raise ValueError(f"wt_access must be auto|gemm_scatter|gemm|"
                             f"gather, got {cfg.wt_access!r}")
        # legacy full f32 one-hot path: explicit, or auto for CVB0 on
        # narrow blocks (cvb0's soft deltas cannot take the bf16 route)
        onehot_bytes = dg * lb * vpb * 4
        use_gemm = (cfg.wt_access == "gemm"
                    or (cfg.wt_access == "auto" and cfg.method == "cvb0"
                        and vpb <= 8192
                        and onehot_bytes <= 256 * 1024 * 1024))
        # gemm_scatter: bf16 one-hot GEMM count writes (exact for CGS's
        # ±1/0 deltas — lane_pack's 'exact_pm1' policy) instead of the
        # segment_sum that is 82% of the hop. Chunked by the engine so the
        # transient one-hot stays ≤ ~64 MB (zero-delta pad rows contribute
        # nothing). Auto guards on the block width (ADVICE r5): past
        # wt_gemm_scatter_max_vpb the vpb·K one-hot FLOPs lose to the
        # segment_sum — fall back to gather — except under the sub-block
        # layout, whose one-hot width is vocab_sub_block, not vpb.
        use_gemm_scatter = (cfg.wt_access == "gemm_scatter"
                            or (cfg.wt_access == "auto"
                                and cfg.method == "cgs"
                                and (bool(cfg.vocab_sub_block)
                                     or vpb <= cfg.wt_gemm_scatter_max_vpb)))
        # vocab-sub-block layout: the scatter runs as ONE batched GEMM over
        # (NS, dg·Lbs, K) deltas against `sub`-lane-wide one-hots — FLOPs
        # ∝ sub (=128), not vpb. Tokens arrive grouped by sub-block
        # (bucketize_tokens_subblock), ids stay full block-local slots.
        sub_w = cfg.vocab_sub_block
        use_sub = bool(sub_w) and use_gemm_scatter
        if use_sub:
            if not lbs or lb % lbs or vpb % sub_w:
                raise ValueError(
                    f"sub-block build needs lb {lb} = NS*lbs ({lbs}) and "
                    f"sub {sub_w} | vpb {vpb} (prepare() sets these)")
            ns_sub = vpb // sub_w
            scatter_chunk = lane_pack.scatter_chunk(dg * lbs, sub_w,
                                                    batch=ns_sub)
        else:
            ns_sub = 1
            scatter_chunk = lane_pack.scatter_chunk(dg * lb, vpb)
        # record the resolved write path (the auto guard makes it
        # shape-dependent, so tests/benches read it instead of re-deriving)
        self.last_layout_stats["wt_path"] = (
            "gemm" if use_gemm
            else "gemm_scatter_subblock" if use_sub
            else "gemm_scatter" if use_gemm_scatter
            else "gather")

        def fit_fn(docs_b, mask_b, z0, wt_block0, seed):
            # docs_b/mask_b/z0: (D_local, NB, Lb) — tokens pre-bucketed by home
            # vocab block (host-side, bucketize_tokens; ids are block-local
            # slots), so each hop touches only the resident block's tokens
            # instead of sampling all tokens and discarding (w-1)/w of draws.
            soft = cfg.method == "cvb0"

            def group_update(wt_block, tt_local, key, wl_g, ms_g, zs_g, dt_g):
                """Resample one doc-group's resident-block tokens from the
                CURRENT counts: p(z=k) ∝ (n_dk−cur+α)(n_wk−cur+β)/(n_k−cur+Vβ)."""
                if soft:
                    cur = zs_g * ms_g[..., None]              # (dg, Lb, K)
                else:
                    cur = (jax.nn.one_hot(zs_g, k, dtype=jnp.float32)
                           * ms_g[..., None])
                nd = dt_g[:, None, :] - cur                   # exclude self
                oh = None

                def apply_scatter(wt_b, delta):
                    """The count-write path."""
                    if use_gemm:
                        return wt_b + jax.lax.dot_general(
                            oh, delta.reshape(-1, k),
                            (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
                    if use_sub:
                        # tokens are grouped (dg, NS, Lbs); key the one-hot
                        # on the within-sub slot and scatter all sub-blocks
                        # in one batched `sub`-lane GEMM
                        _, sub_slot = lane_pack.sub_block_split(
                            wl_g.reshape(dg, ns_sub, lbs), sub_w)
                        ids_s = sub_slot.transpose(1, 0, 2).reshape(
                            ns_sub, dg * lbs)
                        d_s = delta.reshape(dg, ns_sub, lbs, k).transpose(
                            1, 0, 2, 3).reshape(ns_sub, dg * lbs, k)
                        upd = lane_pack.gemm_scatter(
                            ids_s, d_s, sub_w, chunk=scatter_chunk,
                            policy="exact_pm1")
                        return wt_b + upd.reshape(vpb, k)
                    if use_gemm_scatter:
                        return wt_b + lane_pack.gemm_scatter(
                            wl_g.reshape(-1), delta.reshape(-1, k), vpb,
                            chunk=scatter_chunk, policy="exact_pm1")
                    return wt_b + jax.ops.segment_sum(
                        delta.reshape(-1, k), wl_g.reshape(-1),
                        num_segments=vpb)
                if use_gemm:
                    oh = jax.nn.one_hot(wl_g.reshape(-1), vpb,
                                        dtype=jnp.float32)   # (dg*Lb, vpb)
                    nw = (oh @ wt_block).reshape(cur.shape) - cur
                else:
                    nw = wt_block[wl_g] - cur
                nk = tt_local[None, None, :] - cur
                # PRODUCT space, not log space: p ∝ (nd+α)(nw+β)/(nk+Vβ)
                # directly. The log form cost 3 transcendentals per (token,
                # topic) and jax.random.categorical's gumbel trick 2 more —
                # ~5K logs/token of pure VPU-transcendental work at K
                # topics; inverse-CDF sampling needs ZERO (measured r4:
                # 39 → 68M tokens/s on the bench config). All factors are
                # nonnegative (counts exclude self) and bounded by doc
                # length/corpus counts, so f32 products are safe — the
                # sequential oracle uses the identical form.
                p = (jnp.maximum(nd + cfg.alpha, 0.0)
                     * jnp.maximum(nw + cfg.beta, 0.0)
                     / jnp.maximum(nk + cfg.vocab * cfg.beta, 1e-10))
                if soft:
                    # CVB0 (contrib/lda CVB0 LdaMapCollective): deterministic
                    # mean-field update — soft assignment = normalized
                    # probabilities (softmax(log p) ≡ p/Σp, minus the logs)
                    zs_new = (p / jnp.maximum(p.sum(-1, keepdims=True),
                                              1e-30)) * ms_g[..., None]
                    new = zs_new
                else:
                    key, sub = jax.random.split(key)
                    cdf = jnp.cumsum(p, axis=-1)
                    u = jax.random.uniform(sub, p.shape[:-1] + (1,),
                                           jnp.float32) * cdf[..., -1:]
                    zs_new = jnp.clip(jnp.sum((cdf < u), axis=-1), 0, k - 1)
                    new = (jax.nn.one_hot(zs_new, k, dtype=jnp.float32)
                           * ms_g[..., None])
                delta = new - cur                             # (dg, Lb, K)
                wt_block = apply_scatter(wt_block, delta)
                d_k = delta.sum(axis=(0, 1))
                return (wt_block, tt_local + d_k, d_k, key,
                        zs_new, dt_g + delta.sum(axis=1))

            def sample_resident(carry, wt_block, src):
                """Sample every token whose home block ``src`` is resident."""
                if comm is None:
                    doc_topic, z, topic_tot, key = carry
                else:
                    doc_topic, z, topic_tot, key, qres = carry
                w_local = jnp.take(docs_b, src, axis=1)       # (D, Lb) slots
                mask_s = jnp.take(mask_b, src, axis=1)
                z_s = jnp.take(z, src, axis=1)

                def grp(carry2, xs):
                    wt_b, tt_loc, hop_d, key = carry2
                    wl_g, ms_g, zs_g, dt_g = xs
                    wt_b, tt_loc, d_k, key, zs_new, dt_new = group_update(
                        wt_b, tt_loc, key, wl_g, ms_g, zs_g, dt_g)
                    return (wt_b, tt_loc, hop_d + d_k, key), (zs_new, dt_new)

                z_shape = ((nmb, dg, lb, k) if soft else (nmb, dg, lb))
                (wt_block, _, hop_delta, key), (zs_new, dt_new) = jax.lax.scan(
                    grp,
                    (wt_block, topic_tot, jnp.zeros(k), key),
                    (w_local.reshape(nmb, dg, lb),
                     mask_s.reshape(nmb, dg, lb),
                     z_s.reshape(z_shape),
                     doc_topic.reshape(nmb, dg, k)))
                doc_topic = dt_new.reshape(d_local, k)
                zs_new = zs_new.reshape(z_s.shape)
                if soft:
                    z = jnp.where((jnp.arange(nb) == src)[None, :, None, None],
                                  zs_new[:, None, :, :], z)
                else:
                    z = jnp.where((jnp.arange(nb) == src)[None, :, None],
                                  zs_new[:, None, :], z)
                # bounded-staleness topic totals: refresh by psum once per hop
                if comm is None:
                    topic_tot = topic_tot + jax.lax.psum(hop_delta,
                                                         lax_ops.WORKERS)
                    return (doc_topic, z, topic_tot, key), wt_block
                # quantized wire format for the hop allreduce; EF residual
                # rides the rotation (and epoch) carry
                delta_sum, qres = lax_ops.allreduce(hop_delta, comm=comm,
                                                    residual=qres)
                topic_tot = topic_tot + delta_sum
                return (doc_topic, z, topic_tot, key, qres), wt_block

            def hop_body(carry, wt_block, t):
                # single-slice schedule: at hop t the resident block's home
                # worker is (wid - t) — Harp's plain Rotator ring
                src = (lax_ops.worker_id() - t) % w
                return sample_resident(carry, wt_block, src)

            def micro_body(carry, wt_half, t):
                # numModelSlices=2 schedule (LDAMPCollectiveMapper wTableMap):
                # even micro-steps sample an a-half-block (ids [0, w)), odd
                # ones a b-half-block (ids [w, 2w)); each advances around the
                # ring every SECOND micro-step, so while this half is being
                # sampled the other is in flight (pipelined_rotation)
                src = (t % 2) * w + (lax_ops.worker_id() - t // 2) % w
                return sample_resident(carry, wt_half, src)

            key = jax.random.fold_in(jax.random.PRNGKey(0),
                                     seed + lax_ops.worker_id())
            if cfg.method == "cvb0":
                doc_topic = (z0 * mask_b[..., None]).sum(axis=(1, 2))
            else:
                doc_topic = (jax.nn.one_hot(z0, k, dtype=jnp.float32)
                             * mask_b[..., None]).sum(axis=(1, 2))
            topic_tot = jax.lax.psum(doc_topic.sum(axis=0), lax_ops.WORKERS)

            lgamma = jax.scipy.special.gammaln
            v_beta = cfg.vocab * cfg.beta

            def ref_ll(wt, topic_tot):
                # REFERENCE log-likelihood (CalcLikelihoodTask.run:56 +
                # printLikelihood:731-748): nonzero word-topic cells only,
                # then the topic-sum completion terms. Exact for CGS (integer
                # counts); under CVB0 counts are fractional soft mass, so the
                # >0.5 cell test makes this an approximate monitor there
                nz = wt > 0.5
                ll_w = jax.lax.psum(
                    jnp.sum(jnp.where(nz, lgamma(wt + cfg.beta)
                                      - lgamma(cfg.beta), 0.0)),
                    lax_ops.WORKERS)
                return (ll_w - jnp.sum(lgamma(topic_tot + v_beta))
                        + k * lgamma(v_beta))

            # quant_wt: the wt-block hop rides the quantized wire; its EF
            # residual lives in the EPOCH carry (ef_state threading) so the
            # pending encode error survives epoch boundaries
            quant_wt = comm is not None and cfg.quant_wt
            wt_comm = comm if quant_wt else None

            def epoch(state, _):
                if quant_wt:
                    *core, wt_res = state
                    state = tuple(core)
                if comm is None:
                    doc_topic, z, topic_tot, wt, key = state
                    hop_carry = (doc_topic, z, topic_tot, key)
                else:
                    doc_topic, z, topic_tot, wt, key, qres = state
                    hop_carry = (doc_topic, z, topic_tot, key, qres)
                if ns == 1:
                    if quant_wt:
                        hop_carry, wt, wt_res = rotation.rotate_scan(
                            hop_body, hop_carry, wt, w,
                            comm=wt_comm, ef_state=wt_res,
                            fused_dma=cfg.fused_dma)
                    else:
                        hop_carry, wt = rotation.rotate_scan(
                            hop_body, hop_carry, wt, w,
                            fused_dma=cfg.fused_dma)
                else:
                    # local (2*vpb, K) block = [a-half; b-half]; 2w micro-steps
                    # bring both halves home again
                    if quant_wt:
                        hop_carry, sa, sb, wt_res = rotation.pipelined_rotation(
                            micro_body, hop_carry, wt[:vpb], wt[vpb:], 2 * w,
                            comm=wt_comm, ef_state=wt_res,
                            fused_dma=cfg.fused_dma)
                    else:
                        hop_carry, sa, sb = rotation.pipelined_rotation(
                            micro_body, hop_carry, wt[:vpb], wt[vpb:], 2 * w,
                            fused_dma=cfg.fused_dma)
                    wt = jnp.concatenate([sa, sb], axis=0)
                if comm is None:
                    doc_topic, z, topic_tot, key = hop_carry
                    out = (doc_topic, z, topic_tot, wt, key)
                else:
                    doc_topic, z, topic_tot, key, qres = hop_carry
                    out = (doc_topic, z, topic_tot, wt, key, qres)
                if quant_wt:
                    out = out + (wt_res,)
                ll = ref_ll(wt, topic_tot)
                return out, ll

            state0 = ((doc_topic, z0, topic_tot, wt_block0, key)
                      if comm is None else
                      (doc_topic, z0, topic_tot, wt_block0, key,
                       jnp.zeros((k,), jnp.float32)))
            if quant_wt:
                wt_res0 = (rotation.ef_zero(wt_block0) if ns == 1 else
                           (rotation.ef_zero(wt_block0[:vpb]),
                            rotation.ef_zero(wt_block0[vpb:])))
                state0 = state0 + (wt_res0,)
            state, ll = jax.lax.scan(epoch, state0, None, length=cfg.epochs)
            doc_topic, z, _, wt = state[:4]
            return doc_topic, wt, z, ll

        sess = self.session
        return sess.spmd(
            fit_fn,
            in_specs=(sess.shard(), sess.shard(), sess.shard(), sess.shard(),
                      sess.replicate()),
            out_specs=(sess.shard(), sess.shard(), sess.shard(),
                       sess.replicate()),
        )

    def prepare(self, docs: np.ndarray, seed: int = 0):
        """Bucketize + place tokens and initial counts on the mesh ONCE.

        Returns an opaque state for :meth:`fit_prepared` — keeps host layout
        and H2D transfer out of timed regions (KMeans.prepare idiom)."""
        sess, cfg = self.session, self.config
        w = sess.num_workers
        nb = w * cfg.num_model_slices
        vpb = -(-cfg.vocab // nb)
        if cfg.vocab_sub_block:
            # sub-block layout: the block width must split into whole
            # sub-blocks (extra slots are never-touched zero-count rows)
            vpb = lane_pack.round_up(vpb, cfg.vocab_sub_block)
        v_pad = vpb * nb
        num_docs = docs.shape[0]
        if num_docs % w:
            raise ValueError(f"num_docs {num_docs} must divide over {w} workers")
        if docs.size and (docs.min() < 0 or docs.max() >= cfg.vocab):
            raise ValueError(
                f"token ids must be in [0, {cfg.vocab}); got "
                f"[{docs.min()}, {docs.max()}]")

        from harp_tpu.models.sgd_mf import identity_assign, serpentine_assign

        if cfg.balance:
            word_block, word_slot = serpentine_assign(
                np.bincount(docs.reshape(-1), minlength=cfg.vocab), nb)
        else:
            word_block, word_slot = identity_assign(cfg.vocab, nb)

        if cfg.vocab_sub_block:
            docs_b, mask_b, lb, lbs = bucketize_tokens_subblock(
                docs, nb, vpb, cfg.vocab_sub_block, word_block, word_slot)
        else:
            docs_b, mask_b, lb = bucketize_tokens(docs, nb, vpb, word_block,
                                                  word_slot)
            lbs = 0
        d_local = num_docs // w
        nmb_eff = self._effective_minibatches(d_local)
        if nmb_eff == 1 and cfg.minibatches_per_hop > 1:
            # fully-parallel draws park the chain at a diffuse fixed point
            # (module doc: a word's tokens never coordinate); this happens
            # when docs-per-worker has no divisor within the budget (e.g. a
            # prime d_local) — surface it LOUDLY, not only in layout stats
            import warnings

            warnings.warn(
                f"LDA sub-stepping degraded to 1 (fully-parallel draws): "
                f"docs-per-worker {d_local} has no divisor <= "
                f"minibatches_per_hop={cfg.minibatches_per_hop}. Mixing "
                f"will be poor — pad num_docs so docs/worker gains a small "
                f"divisor (e.g. a multiple of "
                f"{cfg.minibatches_per_hop * w}).",
                RuntimeWarning, stacklevel=3)
        self.last_layout_stats = {
            "padded": int(docs_b.size), "tokens": int(docs.size),
            "overhead": docs_b.size / max(docs.size, 1),
            # sub-steps actually used: largest divisor of docs-per-worker that
            # fits the configured budget (prime d_local can degrade this to 1,
            # which weakens mixing — check this field if convergence stalls)
            "minibatches_per_hop": nmb_eff,
            # sub-block layout accounting (0/absent-width when off): the
            # bench reports this padding next to the throughput it buys
            "sub_block": cfg.vocab_sub_block,
            "sub_blocks_per_block": (vpb // cfg.vocab_sub_block
                                     if cfg.vocab_sub_block else 0),
        }
        rng = np.random.default_rng(seed)
        z0 = rng.integers(0, cfg.num_topics, docs_b.shape).astype(np.int32)
        # initial word-topic counts, laid out as NB stacked vocab blocks of
        # block-local slots
        wt = np.zeros((nb, vpb, cfg.num_topics), np.float32)
        blk = np.broadcast_to(np.arange(nb)[None, :, None],
                              docs_b.shape).reshape(-1)
        np.add.at(wt, (blk, docs_b.reshape(-1)),
                  np.eye(cfg.num_topics, dtype=np.float32)[z0.reshape(-1)]
                  * mask_b.reshape(-1, 1))
        if cfg.num_model_slices == 2:
            # worker i's shard = [a-block i; b-block w+i] stacked — the two
            # half-slices pipelined_rotation double-buffers
            wt = wt.reshape(2, w, vpb, cfg.num_topics).transpose(1, 0, 2, 3)
        wt = wt.reshape(v_pad, cfg.num_topics)
        if cfg.method == "cvb0":
            # soft assignments: one-hot init (same counts as the CGS init)
            z0 = (np.eye(cfg.num_topics, dtype=np.float32)[z0]
                  * mask_b[..., None])

        key = (w, v_pad, lb, num_docs, cfg.method, cfg.num_model_slices, lbs)
        if key not in self._fns:
            self._fns[key] = self._build(w, v_pad, lb, num_docs // w, lbs)
        return (key,
                (sess.scatter(jnp.asarray(docs_b, jnp.int32)),
                 sess.scatter(jnp.asarray(mask_b, jnp.float32)),
                 sess.scatter(jnp.asarray(z0)),
                 sess.scatter(jnp.asarray(wt))),
                jnp.asarray(seed, jnp.int32),
                (word_block, word_slot, vpb))

    def _out_rows(self, w: int, word_block: np.ndarray,
                  word_slot: np.ndarray, vpb: int) -> np.ndarray:
        """Row of each original vocab id in the scattered wt output: block
        b lives on worker b % w; with 2 slices the shard stacks [a; b]."""
        ns = self.config.num_model_slices
        owner = (word_block % w).astype(np.int64)
        sl = word_block // w
        return (owner * ns + sl) * vpb + word_slot

    def fit_prepared(self, state
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Run training on already-placed device data (no host prep)."""
        import time as _time

        key, data, seed, (word_block, word_slot, vpb) = state
        t0 = _time.perf_counter()
        doc_topic, wt_out, z, ll = self._fns[key](*data, seed)
        ll = np.asarray(ll)
        wall = _time.perf_counter() - t0
        # telemetry at the ll fetch that was already here (per-epoch events,
        # wall amortized over the scanned program)
        telemetry.record_chunk(
            "lda", start=0, losses=ll.tolist(), wall_s=wall,
            ledger=telemetry.ledger_for(
                "lda", quant=self.config.quant,
                sub_block=bool(self.config.vocab_sub_block)))
        # un-permute word rows back to original vocab ids; fetch() gathers
        # sharded outputs across gang processes (run.py gang CLI)
        wt_out = fetch(wt_out)
        wt_final = wt_out[self._out_rows(key[0], word_block, word_slot, vpb)]
        return fetch(doc_topic), wt_final, ll

    def fit(self, docs: np.ndarray, seed: int = 0
            ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Train on a (num_docs, doc_len) token matrix.

        Returns (doc_topic (D, K), word_topic (V, K), log-likelihood per epoch
        in the reference formula)."""
        return self.fit_prepared(self.prepare(docs, seed))

    def fit_checkpointed(self, state, checkpointer, save_every: int = 1,
                         epochs: Optional[int] = None
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
        """Train with periodic model checkpointing and automatic resume.

        Every ``save_every`` epochs the chain state — topic assignments ``z``
        and the word-topic counts ``wt`` (THE model: the reference dumped it
        per-N iterations via ``printModel``, LDAMPCollectiveMapper.java:125,
        351) — is saved; a populated checkpoint directory resumes from the
        newest epoch. Chunk boundaries stay on the ``save_every`` grid (plus
        the final epoch), and each chunk's RNG key derives from
        ``seed + start_epoch``, so a run killed at any checkpoint and resumed
        is bitwise identical to an uninterrupted fit_checkpointed run at the
        same ``save_every`` (the trajectory differs from a single full-scan
        :meth:`fit_prepared` only in the per-chunk RNG folding). Returns
        (doc_topic, word_topic-unpermuted, ll-for-run-epochs, start_epoch).

        World-size-agnostic: besides the chain state the checkpoint stores
        the blocked corpus layout (token slots + mask + vocab id maps) and a
        manifest meta naming the writing world. A resume under a different
        worker count (the supervisor's shrink/re-place relaunch) restores
        with the SAVED shapes and re-matches every token's assignment onto
        this session's blocking by its (doc, vocab-id) key
        (collectives.repartition.rematch_tokens — exact up to the
        exchangeability of same-word-same-doc occurrences, under which all
        Gibbs counts are invariant), then rebuilds the word-topic counts at
        the new layout. Same-world resume takes the historical bitwise path
        untouched."""
        sess, cfg = self.session, self.config
        key, data, seed, (word_block, word_slot, vpb) = state
        docs_b, mask_b, z_cur, wt_cur = data
        from harp_tpu.parallel import faults
        from harp_tpu.utils import checkpoint as ckpt_lib

        w, v_pad, lb, num_docs = key[:4]
        lbs = key[6] if len(key) > 6 else 0
        total = epochs if epochs is not None else cfg.epochs
        start = 0
        # the blocked-layout leaves ride in EVERY checkpoint so a DIFFERENT
        # world can recover (doc, vocab-id) per token; the corpus is static,
        # so these fetches happen once. Deliberate size tradeoff: each step
        # dir stays fully self-contained (the keep-last-N pruning and the
        # corrupt-step-skip fallback both assume any single step restores
        # alone), at the cost of re-writing the static layout (~2x the z
        # payload for CGS) per save
        layout_leaves = {
            "docs": fetch(docs_b),
            "mask": fetch(mask_b).astype(np.uint8),
            "word_block": np.asarray(word_block, np.int32),
            "word_slot": np.asarray(word_slot, np.int32),
        }
        # meta-less (pre-elastic) steps hold only {z, wt} — restore them
        # through the legacy template so same-world resume of an old work
        # dir keeps working (a world CHANGE on one raises the clear
        # no-metadata error in _repartition_chain)
        legacy_like = {"z": np.zeros(z_cur.shape, z_cur.dtype),
                       "wt": np.zeros(wt_cur.shape, wt_cur.dtype)}
        # verified resume, single read: manifest-checksummed steps only (a
        # corrupt newest checkpoint falls back to the previous step,
        # utils.checkpoint). `like` only conveys tree structure + dtypes:
        # host zeros, not a full D2H gather of the device arrays (advisor
        # r3). A step written at another world size restores through a
        # template with the SAVED shapes (its manifest meta).
        resume, saved, ck_meta = checkpointer.restore_latest_valid(
            like_from_meta=lambda m: (ckpt_lib.meta_like(m) if m
                                      else legacy_like),
            return_meta=True)
        if resume is not None:
            start = resume
            if ck_meta is not None and ck_meta.get("model") not in (None,
                                                                    "lda"):
                # the template followed the SAVED shapes, so the leaf-count
                # guard cannot catch a wrong-model work dir anymore — the
                # recorded model name does
                raise ValueError(
                    f"checkpoint in this work dir was written by model "
                    f"{ck_meta['model']!r}, not lda — wrong work dir?")
            if start > total:
                raise ValueError(
                    f"checkpoint at epoch {start} exceeds the requested "
                    f"{total} epochs (pass a fresh directory or a larger "
                    f"budget)")
            if (int(ck_meta["world"]) != w if ck_meta and "world" in ck_meta
                    else np.shape(saved["z"]) != tuple(z_cur.shape)):
                saved = self._repartition_chain(saved, ck_meta,
                                                layout_leaves, vpb,
                                                tuple(z_cur.shape))
            # the device reshard path hands back already-placed arrays in
            # this session's sharding — no host round trip to undo
            z_cur = (saved["z"] if isinstance(saved["z"], jax.Array)
                     else sess.scatter(jnp.asarray(saved["z"])))
            wt_cur = (saved["wt"] if isinstance(saved["wt"], jax.Array)
                      else sess.scatter(jnp.asarray(saved["wt"])))
        chunk_fns = {}
        lls = []
        doc_topic = None
        # telemetry: step events at the chunk boundaries' existing ll fetch
        ledger = telemetry.ledger_for(
            "lda", quant=cfg.quant, sub_block=bool(cfg.vocab_sub_block))
        import time as _time

        ep = start
        while ep < total:
            # iteration-boundary fault hook (parallel.faults)
            faults.fire(ep + 1, checkpointer)
            # stay on the save_every grid so an interrupted run's chunk
            # boundaries (hence per-chunk RNG keys) match an uninterrupted one
            chunk = min(save_every - ep % save_every, total - ep)
            if chunk not in chunk_fns:
                sub = LDA(sess, dataclasses.replace(cfg, epochs=chunk))
                chunk_fns[chunk] = sub._build(w, v_pad, lb, num_docs // w,
                                              lbs)
            t0 = _time.perf_counter()
            doc_topic, wt_cur, z_cur, ll = chunk_fns[chunk](
                docs_b, mask_b, z_cur, wt_cur,
                jnp.asarray(int(seed) + ep, jnp.int32))
            chunk_lls = np.asarray(ll).tolist()
            wall = _time.perf_counter() - t0
            lls.extend(chunk_lls)
            telemetry.record_chunk("lda", start=ep, losses=chunk_lls,
                                   wall_s=wall, ledger=ledger)
            ep += chunk
            with telemetry.phase("lda.checkpoint"):
                save_state = {"z": fetch(z_cur), "wt": fetch(wt_cur),
                              **layout_leaves}
                checkpointer.save(ep, save_state, meta=ckpt_lib.state_meta(
                    save_state, model="lda", world=w,
                    num_model_slices=cfg.num_model_slices, vpb=vpb,
                    vocab=cfg.vocab, method=cfg.method))
        if hasattr(checkpointer, "wait"):
            checkpointer.wait()       # surface a failed async final write
        wt_out = fetch(wt_cur)
        wt_final = wt_out[self._out_rows(w, word_block, word_slot, vpb)]
        if doc_topic is not None:
            dt = fetch(doc_topic)
        else:
            # checkpoint already covered every requested epoch: no chunk ran,
            # so rebuild doc_topic from the restored assignments z (counts of
            # each doc's unmasked tokens per topic — same formula as the
            # in-program init) instead of fabricating zeros
            z_h = fetch(z_cur)
            m_h = fetch(mask_b)
            if cfg.method == "cvb0":
                dt = (z_h * m_h[..., None]).sum(axis=(1, 2))
            else:
                dt = (np.eye(cfg.num_topics, dtype=np.float32)[z_h]
                      * m_h[..., None]).sum(axis=(1, 2))
        return dt, wt_final, np.asarray(lls, np.float32), start


    def _reshard_mode(self) -> str:
        from harp_tpu.collectives import reshard as rs

        return rs.resolve_mode(self.config.reshard,
                               self.session.num_workers)

    def _repartition_chain(self, saved: dict, ck_meta, new_layout: dict,
                           vpb: int, new_z_shape: tuple) -> dict:
        """Chain state written at another world size → this session's
        blocked layout. Every token's topic assignment is re-matched onto
        the new blocking by its (doc, vocab-id) key; word-topic counts
        follow their (word_block, word_slot) maps. Default
        (``LDAConfig.reshard``): both leaves move ON DEVICE through
        collectives/reshard.py — the token match is computed host-side on
        the INDEX arrays only (doc/vocab ids, not the payload), then z rows
        and wt rows ride chunk-bounded all_to_all rounds on the mesh;
        ``reshard="host"`` keeps the PR 8 numpy path (rematch_tokens + a
        count rebuild) as the parity oracle. (doc-topic, word-topic,
        topic-total) counts transfer EXACTLY either way, the only freedom
        being the exchangeable order of same-word-same-doc occurrences;
        2-slice blockings re-shard through the same worker-major half-slice
        placement the factors use. Once per resume — no collective enters
        any TRAINING step program (jaxlint JL201/JL203 budgets stay
        bitwise; the reshard program has its own pinned targets)."""
        from harp_tpu.collectives import repartition as rep
        from harp_tpu.collectives import reshard as rs

        cfg = self.config
        sess = self.session
        if ck_meta is None or "world" not in ck_meta:
            raise ValueError(
                "checkpoint does not match this session's chain shapes and "
                "carries no world metadata (written by a pre-elastic "
                "version?) — resume at the original worker count")
        if int(ck_meta.get("vocab", cfg.vocab)) != cfg.vocab \
                or str(ck_meta.get("method", cfg.method)) != cfg.method:
            raise ValueError(
                f"checkpoint chain (vocab={ck_meta.get('vocab')}, "
                f"method={ck_meta.get('method')}) does not describe this "
                f"model (vocab={cfg.vocab}, method={cfg.method})")
        old_world = int(ck_meta["world"])
        old_ns = int(ck_meta.get("num_model_slices", 1))
        new_ns = cfg.num_model_slices
        w = sess.num_workers
        saved_z = np.asarray(saved["z"])
        nb_old = saved_z.shape[1]
        vpb_old = int(ck_meta["vpb"])
        nb_new = int(new_z_shape[1])

        def inverse(wb, ws, nb, width):
            inv = np.full((nb, width), -1, np.int64)
            inv[np.asarray(wb, np.int64),
                np.asarray(ws, np.int64)] = np.arange(len(wb))
            return inv

        inv_old = inverse(saved["word_block"], saved["word_slot"], nb_old,
                          vpb_old)
        inv_new = inverse(new_layout["word_block"], new_layout["word_slot"],
                          nb_new, vpb)
        od, ob, op = np.nonzero(np.asarray(saved["mask"]) > 0)
        v_old = inv_old[ob, np.asarray(saved["docs"])[od, ob, op]]
        nd, nb_i, np_i = np.nonzero(np.asarray(new_layout["mask"]) > 0)
        slots_new = np.asarray(new_layout["docs"])[nd, nb_i, np_i]
        v_new = inv_new[nb_i, slots_new]
        if len(v_old) and v_old.min() < 0 or len(v_new) and v_new.min() < 0:
            raise ValueError(
                "blocked corpus references slots outside its vocab id maps "
                "— the checkpoint layout leaves are inconsistent")
        k = cfg.num_topics
        mode = self._reshard_mode()
        if mode in ("device", "ring"):
            schedule = "alltoall" if mode == "device" else "ring"
            chunk = cfg.reshard_chunk_bytes or rs.DEFAULT_CHUNK_BYTES
            # token match on the INDEX arrays (the rematch_tokens lexsort,
            # payload-free): the k-th (doc, vocab) occurrence on the old
            # side pairs with the k-th on the new side
            old_order = np.lexsort((v_old, od))
            new_order = np.lexsort((v_new, nd))
            if not (np.array_equal(od[old_order], nd[new_order])
                    and np.array_equal(v_old[old_order], v_new[new_order])):
                raise ValueError(
                    "checkpoint token multiset does not match the prepared "
                    "corpus — the resumed run was prepared on different "
                    "data than the checkpoint was written from")
            lb_old, lb_new = saved_z.shape[2], int(new_z_shape[2])
            src_pos = ((od * nb_old + ob) * lb_old + op)[old_order]
            dst_pos = ((nd * nb_new + nb_i) * lb_new + np_i)[new_order]
            row_elems = k if cfg.method == "cvb0" else 1
            plan = rs.plan_moves(
                src_pos, dst_pos, saved_z.shape[0] * nb_old * lb_old,
                int(new_z_shape[0]) * nb_new * lb_new, w,
                row_elems * saved_z.dtype.itemsize, chunk, schedule)
            z_new = rs.reshard(
                sess, saved_z, plan,
                sess.scatter(np.zeros(new_z_shape, saved_z.dtype)))
            # wt rows follow their word: moving row v verbatim IS the
            # rebuild (counts per (word, topic) are blocking-invariant)
            old_wt_lay = rs.block_layout(
                (np.asarray(saved["word_block"]),
                 np.asarray(saved["word_slot"])), vpb_old, old_world,
                old_ns)
            new_wt_lay = rs.block_layout(
                (np.asarray(new_layout["word_block"]),
                 np.asarray(new_layout["word_slot"])), vpb, w, new_ns)
            wt_new = rs.reshard_factor(
                sess, np.asarray(saved["wt"]), old_wt_lay, old_world,
                new_wt_lay, cfg.vocab,
                sess.scatter(np.zeros((nb_new * vpb, k), np.float32)),
                chunk_bytes=chunk, schedule=schedule)
            return {**saved, "z": z_new, "wt": wt_new}
        matched = rep.rematch_tokens(
            od, v_old, saved_z[od, ob, op], nd, v_new)
        z_new = np.zeros(new_z_shape, saved_z.dtype)
        z_new[nd, nb_i, np_i] = matched
        # rebuild word-topic counts at the new blocking (prepare's formula)
        contrib = (matched if cfg.method == "cvb0"
                   else np.eye(k, dtype=np.float32)[matched])
        wt = np.zeros((nb_new, vpb, k), np.float32)
        np.add.at(wt, (nb_i, slots_new), contrib)
        if new_ns == 2:
            # device order stacks worker-major half-slices (prepare's
            # 2-slice placement) — mirror it so the scatter lands right
            wt = wt.reshape(2, nb_new // 2, vpb, k).transpose(1, 0, 2, 3)
        return {**saved, "z": z_new, "wt": wt.reshape(nb_new * vpb, k)}


# --------------------------------------------------------------------------- #
# Oracles (host)
# --------------------------------------------------------------------------- #

def reference_log_likelihood(word_topic: np.ndarray, beta: float,
                             vocab: int) -> float:
    """The reference's likelihood formula on host counts (CalcLikelihoodTask +
    printLikelihood completion) — for tests and offline evaluation."""
    return _ref_ll_np(word_topic, beta, vocab)


def _lgamma(x):
    try:
        from scipy.special import gammaln
        return gammaln(x)
    except ImportError:
        from math import lgamma
        return np.vectorize(lgamma)(x)


def _ref_ll_np(word_topic: np.ndarray, beta: float, vocab: int) -> float:
    k = word_topic.shape[1]
    nz = word_topic > 0.5
    ll = float(np.sum(np.where(nz, _lgamma(word_topic + beta)
                               - _lgamma(beta), 0.0)))
    topic_tot = word_topic.sum(axis=0)
    ll -= float(np.sum(_lgamma(topic_tot + vocab * beta)))
    ll += k * float(_lgamma(np.asarray(vocab * beta)))
    return ll


def full_model_log_likelihood(doc_topic: np.ndarray, word_topic: np.ndarray,
                              alpha: float, beta: float, vocab: int) -> float:
    """Full MALLET model log-likelihood: the reference's word part plus the
    doc-topic term it omits (ParallelTopicModel.modelLogLikelihood)."""
    k = doc_topic.shape[1]
    ll = _ref_ll_np(word_topic, beta, vocab)
    nz = doc_topic > 0.5
    ll += float(np.sum(np.where(nz, _lgamma(doc_topic + alpha)
                                - _lgamma(alpha), 0.0)))
    ll -= float(np.sum(_lgamma(doc_topic.sum(axis=1) + k * alpha)))
    ll += doc_topic.shape[0] * float(_lgamma(np.asarray(k * alpha)))
    return ll


def sequential_cgs_reference(docs: np.ndarray, cfg: LDAConfig, seed: int = 0
                             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Single-device token-sequential CGS — the convergence-parity oracle.

    Returns (doc_topic, word_topic, per-epoch reference log-likelihood)."""
    rng = np.random.default_rng(seed)
    d, l = docs.shape
    k, v = cfg.num_topics, cfg.vocab
    z = rng.integers(0, k, (d, l))
    ndk = np.zeros((d, k))
    nwk = np.zeros((v, k))
    nk = np.zeros(k)
    for di in range(d):
        for li in range(l):
            t = z[di, li]
            ndk[di, t] += 1
            nwk[docs[di, li], t] += 1
            nk[t] += 1
    lls = []
    for _ in range(cfg.epochs):
        for di in range(d):
            for li in range(l):
                wi, t = docs[di, li], z[di, li]
                ndk[di, t] -= 1
                nwk[wi, t] -= 1
                nk[t] -= 1
                p = ((ndk[di] + cfg.alpha) * (nwk[wi] + cfg.beta)
                     / (nk + v * cfg.beta))
                t = rng.choice(k, p=p / p.sum())
                z[di, li] = t
                ndk[di, t] += 1
                nwk[wi, t] += 1
                nk[t] += 1
        lls.append(_ref_ll_np(nwk, cfg.beta, v))
    return ndk, nwk, np.asarray(lls)
