"""One group of collapsed-Gibbs LDA draws over a token stream, fused.

A sweep of the stream layout (``models/lda.py``) visits ``G`` groups of whole
documents in order. Every token of a group draws its topic against the counts
as they stood at the start of the group::

    p(z_i = k) ∝ (n_dk + α) · (n_wk + β) · r_k,   r_k = 1 / (n_k + Wβ)

with the token's own assignment left out of all three counts, by inverse
CDF: ``z_i = #{k < K : c_k < u_i · c}``, ``u_i`` the stated counter hash
below. The running sum ``c_k`` is float32 and added in a stated order, so
that any implementation that keeps it draws the same topics: the ``KP``
topics (zero ``p`` past ``K``) form eight chains of 128, topic ``128 σ + m``
the ``m``-th of chain ``σ``; each chain is summed topic by topic from zero
(``a_σm = a_σ(m-1) + p``, the chain's total ``t_σ = a_σ127``), the offsets
topic by topic too (``o_0 = 0``, ``o_σ = o_(σ-1) + t_(σ-1)``), and ``c_k =
o_σ + a_σm``, ``c = o_7 + t_7``. The group's count changes are applied before
the next group.

The layout. A count table holds one ``(8, 128)`` int32 tile a row: topic
``k`` of a row sits at slot ``(s, v)`` with ``k = 128 (v % 8) + 16 s + v //
8`` (:data:`SLOT_TOPIC`). Within a group the tokens are sorted by word and cut
into tiles of :data:`TILE` tokens, each inside one aligned block of
:data:`WBLOCK` words; the group's documents are a contiguous range of
document rows.

The kernel (:func:`sample_pallas`, named ``lda_sample``) runs one group. It
holds the group's document rows in VMEM twice (the group-start counts it
reads, and the new counts it writes), a tile's word block twice (read,
and written in place) and the change of the word block's run so far. For
each part of :data:`PART` tokens it

1. copies every token's word row and document row into a staging buffer and
   turns each of the eight slot rows of the 128 tokens over (XLU), so that a
   vreg holds 8 topics for 128 tokens: topic ``128 σ + m`` at vreg ``m``,
   sublane ``σ``, one token a lane;
2. forms ``p`` vreg by vreg and sums the eight chains, one a sublane, topic
   by topic; then the offsets, and in a second pass over the saved ``p``
   runs each chain's sum again and counts the topics with ``o_σ + a_σm``
   below ``u · c``: the draw, one token a lane;
3. adds the word block's change, one-hot products on the MXU (the one-hot
   GEMM of ``ops/lane_pack.gemm_scatter`` at 128 words, ±1 operands in
   bfloat16, float32 sums: exact), into a contiguous VMEM accumulator,
   slot row by slot row; the accumulator is folded into the word block's
   sublane-strided rows, and into the totals, once per run (the consecutive
   tiles of one word block within the group), at the run's last tile;
   and writes the document rows' new counts into the VMEM copy, which goes
   back to HBM once a group: each token's slot is computed on the vector
   unit (:func:`topic_slot`) and the tokens add their one-hot rows eight a
   loop step.

Nothing of size tokens × K leaves VMEM. :func:`sample_xla` is the same group
in ``jax.numpy`` (row gathers, :func:`chained_draw` and scatter-adds over
chunks of tokens), for the CPU, the mesh and shapes the kernel does not take;
:func:`use_lda_sample_pallas` decides between them by backend and shape.

The random stream (``fmix32`` is MurmurHash3's 32-bit finaliser, all
arithmetic on uint32 with wrap-around)::

    seed_key  = fmix32(seed_lo ^ fmix32(seed_hi ^ 0x9E3779B9))
    sweep_key = fmix32(seed_key ^ fmix32(sweep + 0x9E3779B9))
    u_i       = (fmix32(sweep_key ^ fmix32(i)) >> 8) · 2^-24

``i`` the token's index in the corpus; sweep 0 gives the first model,
``z_i = min(⌊u_i · K⌋, K − 1)`` in float32, and sweep ``s ≥ 1`` the draws of
the ``s``-th sweep.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from harp_tpu.ops.lane_pack import LANES, SUBLANES

NAME = "lda_sample"
KP = SUBLANES * LANES        # topic slots a table row holds
CHAINS = SUBLANES            # running sums of 128 topics, one a sublane
TILE = 1024                  # tokens a grid step
PART = LANES                 # tokens a lane pass: one a lane
WBLOCK = 128                 # words of a word block: the one-hot's width
# what the kernel may ask of VMEM (v5e: 128 MiB); the two copies of a
# group's document rows take all but the fixed part
VMEM_LIMIT = 112 * 1024 * 1024
_FIXED_VMEM = 12 * 1024 * 1024
_ROW_BYTES = KP * 4
_UNROLL = 8                  # loop bodies unrolled by hand
GOLDEN = 0x9E3779B9
_M1, _M2 = 0x85EBCA6B, 0xC2B2AE35


def _slot_topic() -> np.ndarray:
    s, v = np.meshgrid(np.arange(SUBLANES), np.arange(LANES), indexing="ij")
    return (LANES * (v % CHAINS) + 16 * s + v // CHAINS).reshape(-1)


SLOT_TOPIC = _slot_topic()           # slot 128 s + v -> topic
TOPIC_SLOT = np.argsort(SLOT_TOPIC)  # topic -> slot


def topic_slot(z):
    """The flat slot ``128 s + v`` of topics ``z`` (:data:`TOPIC_SLOT`), by
    shifts and masks: topics are non-negative."""
    m = z & 127
    return 128 * (m >> 4) + 8 * (m & 15) + (z >> 7)


# --------------------------------------------------------------------------- #
# the random stream
# --------------------------------------------------------------------------- #

def fmix32(h):
    h = h ^ (h >> 16)
    h = h * np.uint32(_M1)
    h = h ^ (h >> 13)
    h = h * np.uint32(_M2)
    return h ^ (h >> 16)


def seed_words(seed: int) -> np.ndarray:
    """``(seed_lo, seed_hi)``: any whole number, as two uint32 words."""
    seed = int(seed) % (1 << 64)
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def sweep_key(seed: jax.Array, sweep) -> jax.Array:
    """The uint32 key of ``sweep`` under ``seed`` (:func:`seed_words`)."""
    g = jnp.uint32(GOLDEN)
    key = fmix32(seed[0] ^ fmix32(seed[1] ^ g))
    return fmix32(key ^ fmix32(jnp.asarray(sweep).astype(jnp.uint32) + g))


def uniforms(key: jax.Array, index: jax.Array) -> jax.Array:
    """``u`` in [0, 1) of the tokens at corpus positions ``index``."""
    bits = fmix32(key ^ fmix32(index.astype(jnp.uint32)))
    return (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def first_topics(key: jax.Array, index: jax.Array, k: int) -> jax.Array:
    """The first model's topics: ``min(⌊u · K⌋, K − 1)`` at sweep 0."""
    u = uniforms(key, index) * jnp.float32(k)
    return jnp.minimum(u.astype(jnp.int32), k - 1)


# --------------------------------------------------------------------------- #
# which implementation
# --------------------------------------------------------------------------- #

def use_lda_sample_pallas(backend: str, k: int, workers: int,
                          docs_per_group: int) -> bool:
    """The kernel on a TPU, for one worker, up to :data:`KP` topics, where a
    group's document rows fit VMEM twice; the twin everywhere else."""
    return (backend == "tpu" and workers == 1 and k <= KP
            and 2 * docs_per_group * _ROW_BYTES + _FIXED_VMEM <= VMEM_LIMIT)


def reciprocals(nk: jax.Array, k: int, vocab: int, beta: float
                ) -> jax.Array:
    """``(2, KP)`` in topic order: ``1 / (n_k − j + Wβ)`` for j = 0, 1, zero
    at the padded topics ``k ≥ K``. ``nk`` is in slot order."""
    n = nk.reshape(-1)[TOPIC_SLOT]
    wb = jnp.float32(vocab * beta)
    out = jnp.stack([1.0 / (n.astype(jnp.float32) + wb),
                     1.0 / ((n - 1).astype(jnp.float32) + wb)])
    return jnp.where(jnp.arange(KP)[None] < k, out, 0.0)


# --------------------------------------------------------------------------- #
# the kernel
# --------------------------------------------------------------------------- #

def _tile(i, meta):
    """The window's tile a grid step works on: past the group's last tile
    the last one again, so that no block is fetched or written anew."""
    return jnp.minimum(i, jnp.maximum(meta[1] - 1, 0))


def _sample_kernel(meta, twb, tcnt,
                   wl_s, dl_s, wl_v, zo_v, u_v, wt_in, dt_hbm, rk,
                   zn_out, wt_out, dt_out, nk_out, moved_out,
                   din, dacc, wacc, sw, sd, pbuf, zrow, zsm, sems,
                   *, k, alpha, beta, dg):
    i = pl.program_id(0)
    n = meta[1]
    ii = _tile(i, meta)
    after = jnp.minimum(ii + 1, twb.shape[0] - 1)

    @pl.when(i == 0)
    def _():
        cp = pltpu.make_async_copy(dt_hbm.at[pl.ds(meta[2], dg)], din,
                                   sems.at[0])
        cp.start()
        cp.wait()
        ndocs = meta[3]

        def clear(r, c):
            # rows past the group's documents keep their counts, so that
            # writing the whole window back changes nothing there
            dacc[r] = jnp.where(r < ndocs, 0, din[r])
            return c

        jax.lax.fori_loop(0, dg, clear, 0)
        nk_out[...] = jnp.zeros_like(nk_out)
        moved_out[...] = jnp.zeros_like(moved_out)

    # a run of tiles on one word block starts from its group-start counts
    # and an empty change
    @pl.when((i == 0) | ((i < n) & (twb[ii] != twb[jnp.maximum(ii - 1, 0)])))
    def _():
        wt_out[...] = wt_in[...]
        wacc[...] = jnp.zeros_like(wacc)

    sig = jax.lax.broadcasted_iota(jnp.int32, (16, SUBLANES, LANES), 1)
    jdx = jax.lax.broadcasted_iota(jnp.int32, (16, SUBLANES, LANES), 0)
    sub8 = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
    lane8 = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    words = jax.lax.broadcasted_iota(jnp.int32, (WBLOCK, LANES), 0)

    def part(q):
        base = q * PART
        valid = tcnt[i] - base

        def stage(j8, c):
            for jj in range(_UNROLL):
                j = j8 * _UNROLL + jj
                sw[j] = wt_in[wl_s[base + j]]
                sd[j] = din[dl_s[base + j]]
            return c

        jax.lax.fori_loop(0, PART // _UNROLL, stage, 0)
        zo = zo_v[q:q + 1, :]
        total = jnp.zeros((SUBLANES, LANES), jnp.float32)
        for s in range(SUBLANES):
            kid = LANES * sig + 16 * s + jdx
            own = kid == zo
            owni = own.astype(jnp.int32)
            nw = jnp.transpose(sw[:, s, :]).reshape(16, SUBLANES, LANES)
            nd = jnp.transpose(sd[:, s, :]).reshape(16, SUBLANES, LANES)
            r = jnp.where(own, rk[1, 16 * s:16 * s + 16],
                          rk[0, 16 * s:16 * s + 16])
            p = (((nd - owni).astype(jnp.float32) + alpha)
                 * ((nw - owni).astype(jnp.float32) + beta)) * r
            pbuf[16 * s:16 * s + 16] = p
            for j in range(16):         # the stated order: topic by topic
                total = total + p[j]
        # each chain's offset, the chain totals before it added in order
        offset = jnp.zeros((SUBLANES, LANES), jnp.float32)
        whole = total[0:1, :]
        for c in range(1, CHAINS):
            offset = jnp.where(sub8 == c, jnp.broadcast_to(
                whole, (SUBLANES, LANES)), offset)
            whole = whole + total[c:c + 1, :]
        target = jnp.broadcast_to(u_v[q:q + 1, :] * whole, (SUBLANES, LANES))

        def run(m8, carry):
            acc, below = carry
            for mm in range(_UNROLL):
                acc = acc + pbuf[m8 * _UNROLL + mm]
                below = below + ((acc + offset) < target).astype(jnp.int32)
            return acc, below

        _, below = jax.lax.fori_loop(
            0, KP // CHAINS // _UNROLL, run,
            (jnp.zeros((SUBLANES, LANES), jnp.float32),
             jnp.zeros((SUBLANES, LANES), jnp.int32)))
        live = lane < valid
        zn = jnp.where(live, jnp.minimum(jnp.sum(below, axis=0, keepdims=True),
                                         k - 1), zo)
        zn_out[q:q + 1, :] = zn
        moved_out[...] += (zn != zo).astype(jnp.int32)

        # the new topics' slots go to SMEM for the document counts below,
        # -1 on the pad lanes (no slot); the copy runs behind the word
        # block's products
        zrow[0:1, :] = jnp.where(live, topic_slot(zn), -1)
        cp = pltpu.make_async_copy(zrow.at[0], zsm, sems.at[1])
        cp.start()

        # the word block's change: one-hot (words, tokens) against the
        # tokens' ±1 rows, one product a slot row, added up contiguously
        onehot = ((words == wl_v[q:q + 1, :]) & live).astype(jnp.bfloat16)
        for s in range(SUBLANES):
            kid = LANES * sig + 16 * s + jdx
            delta = ((kid == zn).astype(jnp.int32)
                     - (kid == zo).astype(jnp.int32)).astype(jnp.bfloat16)
            change = jax.lax.dot_general(
                onehot, delta.reshape(LANES, LANES), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(jnp.int32)
            wacc[s] += change

        # the documents' new counts, eight tokens a step over the whole
        # part: a pad's document is row 0 (the layout pads with zeros) and
        # its slot -1, so it adds zeros to a real row of the window
        cp.wait()
        flat = sub8 * LANES + lane8

        def count(j8, c):
            for jj in range(_UNROLL):
                j = j8 * _UNROLL + jj
                d = dl_s[base + j]
                dacc[d] = dacc[d] + (flat == zsm[j]).astype(jnp.int32)
            return c

        jax.lax.fori_loop(0, PART // _UNROLL, count, 0)

    @pl.when(i < n)
    def _():
        for q in range(TILE // PART):
            pl.when(q * PART < tcnt[i])(functools.partial(part, q))

    # the run's change goes into the word block's strided rows and the
    # totals once, at its last tile
    @pl.when((i < n) & ((i == n - 1) | (twb[after] != twb[ii])))
    def _():
        for s in range(SUBLANES):
            wt_out[:, s, :] += wacc[s]
            nk_out[s:s + 1, :] += jnp.sum(wacc[s], axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        cp = pltpu.make_async_copy(dacc, dt_out.at[pl.ds(meta[2], dg)],
                                   sems.at[0])
        cp.start()
        cp.wait()


def sample_pallas(meta, twb, tcnt, wl, dl, z, u, wt, dt, nk, *, k: int,
                  vocab: int, alpha: float, beta: float, dg: int,
                  interpret: bool = False):
    """One group on the kernel. ``meta`` = ``(first tile, tiles, first
    document row, documents)``; ``twb`` / ``tcnt`` the word block and token
    count of the group's window of tiles; ``wl`` / ``dl`` / ``z`` the stream's
    word-in-block, document-in-group and topic of every slot; ``u`` the
    window's uniforms; ``wt`` ``(words, 8, 128)`` and ``dt`` ``(document rows
    + dg, 8, 128)`` the tables, ``nk`` ``(8, 128)`` the totals. Returns the
    window's new topics, both tables, the totals' change (slot order) and the
    count of tokens that changed topic."""
    max_tiles = twb.shape[0]
    rk_t = reciprocals(nk, k, vocab, beta)
    # (2, m, σ, lanes): topic 128 σ + m, the same for every token
    rk = jnp.broadcast_to(rk_t.reshape(2, CHAINS, KP // CHAINS)
                          .transpose(0, 2, 1)[..., None],
                          (2, KP // CHAINS, CHAINS, LANES))
    rows = TILE // LANES

    def tok1(i, m, *_):
        return (m[0] + _tile(i, m),)

    def tok2(i, m, *_):
        return (m[0] + _tile(i, m), 0)

    def win2(i, m, *_):
        return (_tile(i, m), 0)

    def block(i, m, w, *_):
        return (w[_tile(i, m)], 0, 0)

    smem = pltpu.SMEM
    kernel = functools.partial(_sample_kernel, k=k, alpha=alpha, beta=beta,
                               dg=dg)
    zn, wt, dt, dnk, moved = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(max_tiles,),
            in_specs=[
                pl.BlockSpec((TILE,), tok1, memory_space=smem),
                pl.BlockSpec((TILE,), tok1, memory_space=smem),
                pl.BlockSpec((rows, LANES), tok2),
                pl.BlockSpec((rows, LANES), tok2),
                pl.BlockSpec((rows, LANES), win2),
                pl.BlockSpec((WBLOCK, SUBLANES, LANES), block),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((2, KP // CHAINS, CHAINS, LANES),
                             lambda i, *_: (0, 0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((rows, LANES), win2),
                pl.BlockSpec((WBLOCK, SUBLANES, LANES), block),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((SUBLANES, LANES), lambda i, *_: (0, 0)),
                pl.BlockSpec((1, LANES), lambda i, *_: (0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((dg, SUBLANES, LANES), jnp.int32),
                pltpu.VMEM((dg, SUBLANES, LANES), jnp.int32),
                pltpu.VMEM((SUBLANES, WBLOCK, LANES), jnp.int32),
                pltpu.VMEM((PART, SUBLANES, LANES), jnp.int32),
                pltpu.VMEM((PART, SUBLANES, LANES), jnp.int32),
                pltpu.VMEM((KP // CHAINS, CHAINS, LANES), jnp.float32),
                pltpu.VMEM((SUBLANES, LANES), jnp.int32),
                pltpu.SMEM((LANES,), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((max_tiles * rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct(wt.shape, jnp.int32),
            jax.ShapeDtypeStruct(dt.shape, jnp.int32),
            jax.ShapeDtypeStruct((SUBLANES, LANES), jnp.int32),
            jax.ShapeDtypeStruct((1, LANES), jnp.int32),
        ],
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=NAME,
    )(meta, twb, tcnt, wl, dl, wl.reshape(-1, LANES), z.reshape(-1, LANES),
      u.reshape(-1, LANES), wt, dt, rk)
    return zn.reshape(-1), wt, dt, dnk, jnp.sum(moved)


# --------------------------------------------------------------------------- #
# the jax.numpy twin
# --------------------------------------------------------------------------- #

def chained_draw(p: jax.Array, u: jax.Array, k: int) -> jax.Array:
    """The draw of each row of ``p`` ``(tokens, KP)`` (topic order, zero past
    ``K``) at its ``u``, the running sum added in the stated order."""
    chains = p.reshape(p.shape[0], CHAINS, KP // CHAINS).transpose(2, 1, 0)

    def add(a, x):
        a = a + x
        return a, a

    last, run = jax.lax.scan(add, jnp.zeros_like(chains[0]), chains)
    offsets, whole = [jnp.zeros_like(last[0])], last[0]
    for c in range(1, CHAINS):
        offsets.append(whole)
        whole = whole + last[c]
    below = (jnp.stack(offsets)[None] + run) < (u * whole)[None, None]
    z = jnp.sum(below, axis=(0, 1), dtype=jnp.int32)
    return jnp.minimum(z, k - 1)


def sample_xla(meta, twb, tcnt, wl, dl, z, u, wt, dt, nk, *, k: int,
               vocab: int, alpha: float, beta: float, chunk: int,
               reduce: Optional[Callable] = None):
    """The same group as :func:`sample_pallas`, in ``jax.numpy``: row
    gathers and :func:`chained_draw` over chunks of ``chunk`` slots (never
    the window's tokens × K at once), the count changes by scatter-adds.
    ``reduce`` sums the word table's and the totals' changes over the
    workers (None: one worker)."""
    first, n, doc0 = meta[0], meta[1], meta[2]
    span = twb.shape[0] * TILE
    at = first * TILE
    wl_w = jax.lax.dynamic_slice(wl, (at,), (span,))
    dl_w = jax.lax.dynamic_slice(dl, (at,), (span,))
    zo_w = jax.lax.dynamic_slice(z, (at,), (span,))
    tile = jnp.arange(span) // TILE
    live = (jnp.arange(span) % TILE < tcnt[tile]) & (tile < n)
    word = twb[tile] * WBLOCK + wl_w
    doc = doc0 + dl_w
    rk_t = reciprocals(nk, k, vocab, beta)
    topic_slot = jnp.asarray(TOPIC_SLOT)
    flat_wt = wt.reshape(wt.shape[0], KP)
    flat_dt = dt.reshape(dt.shape[0], KP)
    topics = jnp.arange(KP, dtype=jnp.int32)

    def draw(_, xs):
        w, d, zo, uu = xs
        own = topics[None] == zo[:, None]
        owni = own.astype(jnp.int32)
        nw = flat_wt[w][:, topic_slot] - owni
        nd = flat_dt[d][:, topic_slot] - owni
        p = ((nd.astype(jnp.float32) + alpha)
             * (nw.astype(jnp.float32) + beta)) * jnp.where(own, rk_t[1],
                                                            rk_t[0])
        return None, chained_draw(p, uu, k)

    parts = span // chunk
    _, zn = jax.lax.scan(draw, None, (word.reshape(parts, chunk),
                                      doc.reshape(parts, chunk),
                                      zo_w.reshape(parts, chunk),
                                      u.reshape(parts, chunk)))
    zn = jnp.where(live, zn.reshape(-1), zo_w)
    slot_of = jnp.asarray(TOPIC_SLOT, jnp.int32)
    new_s, old_s = slot_of[zn], slot_of[zo_w]
    drop_w = wt.shape[0] * KP               # out of range: dropped
    idx_new = jnp.where(live, word * KP + new_s, drop_w)
    idx_old = jnp.where(live, word * KP + old_s, drop_w)
    change = (jnp.zeros((wt.shape[0] * KP,), jnp.int32)
              .at[idx_new].add(1, mode="drop")
              .at[idx_old].add(-1, mode="drop"))
    dnk = change.reshape(wt.shape[0], KP).sum(axis=0)
    if reduce is not None:
        change, dnk = reduce(change), reduce(dnk)
    wt = wt + change.reshape(wt.shape)
    drop_d = dt.shape[0] * KP
    dt = (dt.reshape(-1)
          .at[jnp.where(live, doc * KP + new_s, drop_d)].add(1, mode="drop")
          .at[jnp.where(live, doc * KP + old_s, drop_d)].add(-1, mode="drop")
          ).reshape(dt.shape)
    moved = jnp.sum(live & (zn != zo_w), dtype=jnp.int32)
    return zn, wt, dt, dnk.reshape(SUBLANES, LANES), moved
