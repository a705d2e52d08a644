"""The K-means E-step as one pass over the points, fused.

One E-step on a worker's block (``ops/distance.partial_sums_counts`` states
the contract) scores every point against every centroid, ``‖c‖² − 2 x·c``,
takes the row's least score and the FIRST centroid that attains it, and sums
the points and counts the rows of each centroid: ``onehot' x``. Left to XLA
that is two fusions, each reading the points and each splitting them into
bfloat16 terms for itself (and a third pass once a call for ``Σ‖x‖²``).

Here (:func:`estep_pallas`, kernel ``kmeans_estep``) a grid step holds a tile
of the points as stored, ``(tile, D)``, and walks it in chunks of ``chunk``
rows. Per chunk the points are split ONCE into bfloat16 terms that serve both
products; scores, least score, index and one-hot live and die in VMEM. The
centroids' passes, ``‖c‖²`` and the ``(K, D)`` float32 sums stay RESIDENT for
the whole call. Nothing N-sized is written.

Layout: a point is a LANE. The scores are formed transposed, ``c · x'``
``(K, chunk)``, so that the least score is an elementwise minimum over
vector registers and one reduce over eight sublanes, the one-hot ``(K,
chunk)`` needs no transpose, and the stats product is a plain ``(K, chunk) x
(chunk, D)`` matmul. Each product is ONE dot whose contraction (scores) or
output (stats) runs over its passes side by side, so the MXU sums the score
passes itself and each result is popped once. Phantom centroid rows take no
part: the kernel works on the live rows' whole bfloat16 sublane tiles (112
of 128 at 100 centroids), and the MXU's time follows them.

Precision is stated by the operands' types, as ``ops/mds_kernels.py`` does:
every product the MXU sees is bfloat16 by bfloat16, exact in float32, summed
in float32. How many terms an operand is split into is what the XLA twin
would run under the ambient ``jax_default_matmul_precision`` (read where
``lax.dot_general`` reads it): three at ``highest`` (hi + mid + lo = x
exactly, cut by masks on the bits: the six products of ``bf16_6x`` for the
scores, the three terms of the points beside an exact one-hot for the
stats), two at ``high``, one at the default, and one wherever the operands
are bfloat16 already (``compute_dtype="bfloat16"``).

Counts: where the stored feature axis has a lane past the ``valid_d`` logical
ones (``distance.counts_fold``) that lane of the first term carries a 1, so
the stats product counts its own rows and the column leaves as exact zero;
elsewhere the one-hot is summed in float32. A last tile's overhang holds
unspecified rows: where the row count is no whole number of tiles they are
masked by row index (zero one-hot, nothing added to either sum).

``use_kmeans_estep_pallas`` decides between this kernel and its XLA twin
(``distance.partial_sums_counts``) by backend, stored shape and dtype alone.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from harp_tpu.ops.distance import FOLD_MAX_ROWS, counts_fold
from harp_tpu.ops.lane_pack import LANES, SUBLANES, round_up

NAME = "kmeans_estep"
# what the kernel may ask of VMEM (v5e: 128 MiB physical)
VMEM_LIMIT = 100 * 1024 * 1024
# the points' tile (two are in flight) and a chunk's temporaries, in bytes
TILE_BYTES = 8 * 1024 * 1024
CHUNK_BYTES = 48 * 1024 * 1024
# rows of a chunk, whole lane tiles: the scores' float32 temporaries are
# (K, chunk). On the chip at (8 M, 128) x (100 of 128, 128), `highest`:
# 14.26 / 11.96 / 11.22 / 10.90 ms at 640 / 1,280 / 2,560 / 6,400 rows of a
# 12,800-row tile; a masked last tile costs 0.3 ms (PERF.md, Findings, PR 35)
MAX_CHUNK = 6400
MIN_CHUNK = 2048            # what a tile that divides the block must allow
MIN_ROWS = 1024             # under this the twin: nothing to win
_DEFAULT = jax.lax.Precision.DEFAULT


# -- shapes ------------------------------------------------------------------ #

def _row_bytes(stored_d: int, k_pad: int) -> int:
    """A chunk's temporaries a row, from above (three terms, six passes):
    four float32 arrays and the bfloat16 terms, twice, and passes of the
    points; five float32 arrays of the scores."""
    return stored_d * (4 * 4 + 2 * (2 * 3 + 6)) + k_pad * 4 * 5


def _divisor(tile: int, most: int) -> int:
    """The most rows a chunk, whole lane tiles and no more than ``most``,
    that divide ``tile``."""
    return max(c for c in range(LANES, most + 1, LANES) if tile % c == 0)


def estep_tiles(rows: int, stored_d: int = LANES, k_pad: int = LANES,
                itemsize: int = 4) -> Tuple[int, int]:
    """``(tile, chunk)`` over a block of ``rows`` points, whole lane tiles
    both: a tile of at most :data:`TILE_BYTES`, a chunk whose temporaries
    fit :data:`CHUNK_BYTES`. Of the tiles, a quarter of the tallest or
    more, that divide ``rows`` (no overhang, so nothing is masked) the one
    with the most rows a chunk, then the tallest, if it allows chunks of
    :data:`MIN_CHUNK` rows; else the tallest tile (one where the block is
    smaller) with the last masked by row index. ``(0, 0)`` where not even a
    lane tile of rows fits."""
    most_tile = TILE_BYTES // (stored_d * itemsize) // LANES * LANES
    most_chunk = min(MAX_CHUNK, most_tile,
                     CHUNK_BYTES // _row_bytes(stored_d, k_pad)
                     // LANES * LANES)
    if most_chunk < LANES:
        return 0, 0
    chunk, tile = max(((_divisor(tile, most_chunk), tile)
                       for tile in range(most_tile, most_tile // 4 - 1,
                                         -LANES) if rows % tile == 0),
                      default=(0, 0))
    if chunk >= min(MIN_CHUNK, most_chunk):
        return tile, chunk
    tile = min(most_tile, round_up(rows, LANES))
    return tile, _divisor(tile, most_chunk)


def estep_vmem_bytes(tile: int, chunk: int, stored_d: int, k_pad: int,
                     itemsize: int) -> int:
    """VMEM the kernel needs, from above: the points' tile double buffered,
    the residents (six passes of the centroids, the sums) twice, a chunk's
    temporaries, 4 MiB."""
    return (2 * itemsize * tile * stored_d
            + 2 * (6 * 2 + 4 + 4) * k_pad * stored_d
            + chunk * _row_bytes(stored_d, k_pad)) + (4 << 20)


def ambient_terms() -> Optional[int]:
    """bfloat16 terms a float32 operand is split into under the ambient
    matmul precision, read as ``lax.dot_general`` reads it; None where the
    ambient setting names an algorithm the kernel does not issue by hand."""
    ambient = jax.config.jax_default_matmul_precision
    if ambient is None:
        return 1
    try:
        ambient = jax.lax.Precision(ambient)
    except ValueError:
        return None
    return {jax.lax.Precision.HIGHEST: 3, jax.lax.Precision.HIGH: 2}.get(
        ambient, 1)


def use_kmeans_estep_pallas(rows: int, stored_d: int, k_pad: int,
                            dtype) -> bool:
    """Dispatch predicate: ON for TPU where the stored feature axis and the
    centroid table are whole 128-lane tiles, the operands float32 or
    bfloat16, the block at least :data:`MIN_ROWS` rows whose count float32
    holds exactly, and a tile and the residents fit VMEM."""
    if jax.default_backend() != "tpu":
        return False
    if stored_d % LANES or k_pad % LANES or not (
            MIN_ROWS <= rows <= FOLD_MAX_ROWS):
        return False
    if jnp.dtype(dtype) not in (jnp.float32, jnp.bfloat16):
        return False
    if ambient_terms() is None:
        return False
    itemsize = jnp.dtype(dtype).itemsize
    tile, chunk = estep_tiles(rows, stored_d, k_pad, itemsize)
    return tile > 0 and estep_vmem_bytes(
        tile, chunk, stored_d, k_pad, itemsize) <= VMEM_LIMIT


# -- the split ----------------------------------------------------------------- #

def _upper(x):
    """``x`` (float32) cut to its upper 16 bits: exact in bfloat16."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def split_terms(x, terms: int):
    """``x`` as ``terms`` bfloat16 arrays. Float32: every term but the last
    is cut by a mask on the bits (exact, and no compiler that keeps excess
    precision can fold it away), the last is what is left, rounded: at three
    terms nothing is left to round, ``hi + mid + lo = x`` exactly. bfloat16
    is its own one term."""
    if x.dtype == jnp.bfloat16:
        return [x]
    out = []
    for _ in range(terms - 1):
        hi = _upper(x)
        out.append(hi.astype(jnp.bfloat16))
        x = x - hi
    return out + [x.astype(jnp.bfloat16)]


def _pairs(terms: int):
    """``(centroid term, point term)`` of the score product's passes, the
    smallest first: those of total order under ``terms`` (one at one term,
    bf16_3x's three at two, bf16_6x's six at three)."""
    pairs = [(a, b) for a in range(terms) for b in range(terms)
             if a + b < terms]
    return sorted(pairs, key=lambda p: (-(p[0] + p[1]), -p[0]))


def _fold_lanes(x):
    """Lane tile onto lane tile: plain vector adds, no cross-lane work."""
    return functools.reduce(jnp.add, [
        x[:, l:l + LANES] for l in range(0, x.shape[1], LANES)])


# -- the kernel ---------------------------------------------------------------- #

def _estep_kernel(x_ref, c_ref, c2_ref, sums_ref, counts_ref, cost_ref, *,
                  rows: int, tile: int, chunk: int, terms: int, live_k: int,
                  spare: Optional[int]):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _start():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)
        cost_ref[...] = jnp.zeros_like(cost_ref)

    d = sums_ref.shape[1]
    ragged = rows % tile != 0
    pairs = _pairs(terms)
    c = c_ref[0:live_k, :]                                # (live K, passes D)
    c2 = c2_ref[0:live_k, :]                              # (live K, 1)
    ids = jax.lax.broadcasted_iota(jnp.int32, (live_k, chunk), 0).astype(
        jnp.float32)
    if spare is not None:
        spare_lane = jax.lax.broadcasted_iota(jnp.int32, (1, d), 1) == spare

    def one(j, carry):
        sums, counts, least, sq = carry
        first = pl.multiple_of(j * chunk, chunk)
        x = x_ref[pl.ds(first, chunk), :]                 # (chunk, D)
        if ragged:
            # compare and select in float32: mosaic has no bf16 select
            left = rows - (i * tile + first)     # rows of the block from here
            x = jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, (chunk, 1), 0) < left, x.astype(jnp.float32), 0.0
                          ).astype(x.dtype)
            valid = jax.lax.broadcasted_iota(
                jnp.int32, (1, chunk), 1) < left
        xf = x.astype(jnp.float32)
        sq = sq + jnp.sum((xf * xf).reshape(chunk // SUBLANES, SUBLANES, d),
                          axis=0)
        if spare is not None:
            # the lane that counts: it meets a zero in every centroid
            x = jnp.where(spare_lane, 1.0, xf).astype(x.dtype)
        xt = split_terms(x, terms)
        # scores transposed, (live K, chunk): a point a lane. ONE product
        # whose contraction runs over every pass, so the MXU sums them
        s = c2 + jax.lax.dot_general(
            c, jnp.concatenate([xt[b] for _, b in pairs], axis=1),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_DEFAULT)
        low = jnp.min(s, axis=0, keepdims=True)           # (1, chunk)
        # the FIRST centroid that attains it: jnp.argmin's tie rule
        idx = jnp.min(jnp.where(s == low, ids, float(live_k)), axis=0,
                      keepdims=True)
        hit = ids == idx
        if ragged:
            hit = hit & valid
            low = jnp.where(valid, low, 0.0)
        onehot = jnp.where(hit, 1.0, 0.0)
        if spare is None:
            counts = counts + _fold_lanes(onehot)
        # one product again: the terms side by side, (live K, terms D)
        by_term = jax.lax.dot_general(
            onehot.astype(jnp.bfloat16), jnp.concatenate(xt, axis=1),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_DEFAULT)
        sums = sums + functools.reduce(jnp.add, [
            by_term[:, t * d:(t + 1) * d] for t in reversed(range(len(xt)))])
        return sums, counts, least + _fold_lanes(low), sq

    # a tile's sums first, then onto the call's: two short float32 chains
    sums, counts, least, sq = jax.lax.fori_loop(
        0, tile // chunk, one,
        (jnp.zeros((live_k, d), jnp.float32),
         jnp.zeros((live_k, LANES), jnp.float32),
         jnp.zeros((1, LANES), jnp.float32),
         jnp.zeros((SUBLANES, d), jnp.float32)))
    sums_ref[0:live_k, :] += sums
    counts_ref[0:live_k, :] += counts
    cost_ref[0:1, 0:LANES] += least
    cost_ref[SUBLANES:2 * SUBLANES, :] += sq


def estep_pallas(x: jax.Array, c: jax.Array, compute_dtype=None,
                 valid_k: Optional[int] = None, valid_d: Optional[int] = None,
                 tiles: Optional[Tuple[int, int]] = None,
                 interpret: bool = False
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One E-step on this worker's block, the contract of
    ``distance.partial_sums_counts`` with ``x_sq_sum`` computed here:
    ``x`` (N, D) points as stored (float32 or bfloat16, D whole lane tiles,
    zeros past ``valid_d``), ``c`` (K, D) float32 centroids (K whole lane
    tiles, rows past ``valid_k`` phantoms). Returns ``(sums (K, D), counts
    (K,), Σ min score + Σ‖x‖²)``, float32. ``tiles``: ``(tile, chunk)``,
    :func:`estep_tiles` by default."""
    rows, d = x.shape
    k = c.shape[0]
    if c.shape != (k, d) or d % LANES or k % LANES:
        raise ValueError("kmeans estep: inconsistent shapes")
    tile, chunk = tiles or estep_tiles(rows, d, k, x.dtype.itemsize)
    if tile % chunk or chunk % LANES:
        raise ValueError("kmeans estep: tiling constraints violated")
    narrow = jnp.bfloat16 in (x.dtype, jnp.dtype(compute_dtype or x.dtype))
    terms = 1 if narrow else ambient_terms()
    cf = c.astype(jnp.float32)
    if valid_d is not None and valid_d < d:
        # the points hold zeros there: the spare lane's 1 must meet a zero
        cf = jnp.where(jnp.arange(d)[None, :] < valid_d, cf, 0.0)
    c2 = jnp.sum(cf * cf, axis=1, keepdims=True)
    if valid_k is not None and valid_k < k:
        c2 = jnp.where(jnp.arange(k)[:, None] < valid_k, c2, jnp.inf)
    spare = valid_d if counts_fold(rows, d, valid_d) else None
    # phantom rows take no part: whole bfloat16 sublane tiles of live rows
    live_k = k if valid_k is None else min(k, round_up(valid_k, 16))
    passes = _pairs(terms)
    c_terms = split_terms(-2.0 * cf, terms)
    c_passes = jnp.concatenate([c_terms[a] for a, _ in passes], axis=1)
    kernel = functools.partial(_estep_kernel, rows=rows, tile=tile,
                               chunk=chunk, terms=terms, live_k=live_k,
                               spare=spare)
    sums, counts, cost = pl.pallas_call(
        kernel,
        grid=(-(-rows // tile),),
        in_specs=[
            pl.BlockSpec((tile, d), lambda i: (i, 0)),              # points
            pl.BlockSpec((k, len(passes) * d), lambda i: (0, 0)),   # -2c
            pl.BlockSpec((k, 1), lambda i: (0, 0)),                 # ‖c‖²
        ],
        out_specs=[
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((k, LANES), lambda i: (0, 0)),
            pl.BlockSpec((2 * SUBLANES, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((k, LANES), jnp.float32),
            jax.ShapeDtypeStruct((2 * SUBLANES, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=NAME,
    )(x, c_passes, c2)
    if spare is None:
        counts = jnp.sum(counts, axis=1)
    else:
        counts = sums[:, spare]
        sums = jnp.where(jnp.arange(d)[None, :] == spare, 0.0, sums)
    return sums, counts, jnp.sum(cost)
