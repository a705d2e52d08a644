"""The E-step of full-covariance EM for Gaussian mixtures as one pass over the
points, fused.

One iteration at the parameters ``(π_k, μ_k, Σ_k)`` factors each covariance,
``Σ_k + reg I = L_k L_k'``, and whitens: ``A_k = L_k⁻¹``, ``b_k = A_k μ_k``.
Every point then needs, for every component, ``‖A_k x − b_k‖²``, the
log-sum-exp of ``log π_k − ½ (D log 2π + log det Σ_k + ‖A_k x − b_k‖²)``
over the components, the responsibilities ``r_nk`` and three statistics:
``N_k = Σ r``, ``Σ r x`` and ``Σ r x x'``. Left to XLA the whitened
coordinates are an ``(N, K, D)`` array, 240 GB at 6 M x 100 x 100.

Here (:func:`estep_pallas`, kernel ``em_estep``) nothing of size ``N·K·D``
or ``N·K`` leaves VMEM. The model arrives as ONE stacked operand ``W``,
``(K_pad·D_pad, D_store)``: component ``k``'s rows ``k·D_pad + i`` hold
``A_k``'s row ``i`` and, in the spare lane ``D``, ``−b_k[i]``. The points are
stored with a 1 in that lane (:func:`stored_points`), so ``W x`` IS ``A_k x −
b_k``, every component's at once. A grid step holds a tile of the points and
walks it in chunks; per chunk:

1. the points are split ONCE into exact bfloat16 terms that serve both
   products;
2. (a) by groups of :data:`GROUP` components, ``W_g x'`` ``(GROUP·D_pad,
   chunk)`` on the MXU (a point a lane), squared and summed by ``D_pad``
   rows into the Mahalanobis terms, ``(K_pad, chunk)``;
3. the log-sum-exp over the components and the responsibilities, in VMEM;
4. (b) by the same groups, ``Z_g = r_k x̃'`` stacked, ``(GROUP·D_pad,
   chunk)``, and ``Z_g x̃`` on the MXU into the resident statistics
   ``(K_pad·D_pad, D_store)``: row ``i < D`` of component ``k`` is ``Σ r x_i
   x'``, row ``D`` (the constant lane's) ``Σ r x'`` with ``N_k`` in lane
   ``D``.

A tile's statistics are summed first and added onto the call's after it:
two short float32 chains. ``Σ log z`` rides beside them.

Precision, float32's: every operand of both products is split into three
bfloat16 terms cut by masks on the bits (``hi + mid + lo = x`` exactly,
``ops/kmeans_kernels.split_terms``), and each product is ONE dot whose
contraction runs over the six pairs of total order under three (bf16_6x's
passes), side by side, so the MXU sums them: every product exact, the sums
float32, as a float32 product at ``highest``. :data:`TERMS` is that count;
the XLA twin (:func:`estep_xla`) states the same with
``Precision.HIGHEST`` on its dots.

``use_em_estep_pallas`` decides between the kernel and its twin by backend
and shape alone.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from harp_tpu.ops.kmeans_kernels import _pairs, split_terms
from harp_tpu.ops.lane_pack import LANES, SUBLANES, round_up

NAME = "em_estep"
TERMS = 3                   # bfloat16 terms of a float32 operand
GROUP = 8                   # components a group: whole float32 sublane tiles
# what the kernel may ask of VMEM (v5e: 128 MiB physical)
VMEM_LIMIT = 112 * 1024 * 1024
# the points' tile (two are in flight) and a chunk's rows. On the chip at
# 6 M x 100 x 100 (tile, chunk): 1.0423 s an E-step at (9600, 1920), 1.0652
# at (9600, 640), 1.0676 at (3200, 640), 1.0885 at (1920, 384), 1.1928 at
# (3200, 128); 1.1689 and 1.3717 with all 104 components a group (PERF.md,
# section 5)
TILE_BYTES = 8 * 1024 * 1024
MAX_CHUNK = 2048
MIN_ROWS = 1024                     # under this the twin: nothing to win
PHANTOM = -1e30                     # log weight of a padded component
_HIGHEST = jax.lax.Precision.HIGHEST
_DEFAULT = jax.lax.Precision.DEFAULT
_TWIN_BYTES = 64 * 1024 * 1024      # the twin's (block, K_pad·D_pad) scratch


# -- shapes ------------------------------------------------------------------ #

def padded(k: int, d: int) -> Tuple[int, int, int]:
    """``(K_pad, D_pad, D_store)``: components in whole groups, a component's
    rows of the stacked operand (the D coordinates and the constant's row, in
    whole float32 sublane tiles) and the stored feature lanes (the D
    coordinates and the constant lane, in whole lane tiles)."""
    return (round_up(k, GROUP), round_up(d + 1, SUBLANES),
            round_up(d + 1, LANES))


def _divisor(n: int, most: int) -> int:
    """The most rows, whole lane tiles and no more than ``most``, that divide
    ``n``; 0 where none does."""
    return max((c for c in range(LANES, most + 1, LANES) if n % c == 0),
               default=0)


def estep_tiles(rows: int, d_store: int) -> Tuple[int, int]:
    """``(tile, chunk)`` over a block of ``rows`` points, whole lane tiles
    both: of the tiles of at most :data:`TILE_BYTES`, a quarter of the
    tallest or more, that divide ``rows`` (nothing masked) the one with the
    tallest chunk of at most :data:`MAX_CHUNK` rows that divides it, then the
    tallest, where that chunk has at least 256 rows; else the tallest tile
    (one where the block is smaller), its overhang masked by row index."""
    most_tile = max(LANES, TILE_BYTES // (4 * d_store) // LANES * LANES)
    chunk, tile = max(((_divisor(tile, MAX_CHUNK), tile)
                       for tile in range(most_tile, most_tile // 4 - 1,
                                         -LANES) if rows % tile == 0),
                      default=(0, 0))
    if chunk >= 2 * LANES:
        return tile, chunk
    tile = min(most_tile, round_up(rows, LANES))
    return tile, _divisor(tile, MAX_CHUNK) or LANES


def estep_vmem_bytes(tile: int, chunk: int, k_pad: int, d_pad: int,
                     d_store: int) -> int:
    """VMEM the kernel needs, from above: the points' tile twice, the stacked
    operand's passes once, the statistics three times (the output's two
    buffers and the tile's sum), a chunk's temporaries (its points, terms and
    passes; the scores; the larger of a group's whitened product and its
    weighted points with their terms and passes), 4 MiB."""
    passes = len(_pairs(TERMS))
    rows = GROUP * d_pad
    chunk_bytes = (chunk * d_store * (4 * 3 + 2 * (TERMS + 2 * passes))
                   + k_pad * chunk * 4 * 6
                   + rows * chunk * max(4 * 2, 4 + 2 * (TERMS + passes))
                   + rows * d_store * 4 * 2)
    return (2 * 4 * tile * d_store + 2 * passes * k_pad * d_pad * d_store
            + 3 * 4 * k_pad * d_pad * d_store + chunk_bytes + (4 << 20))


def use_em_estep_pallas(rows: int, k: int, d: int) -> bool:
    """Dispatch predicate: ON for TPU where the worker's block holds at least
    :data:`MIN_ROWS` points and a tile, the residents and a chunk's
    temporaries fit VMEM."""
    if jax.default_backend() != "tpu" or rows < MIN_ROWS:
        return False
    k_pad, d_pad, d_store = padded(k, d)
    tile, chunk = estep_tiles(rows, d_store)
    return estep_vmem_bytes(tile, chunk, k_pad, d_pad,
                            d_store) <= VMEM_LIMIT


# -- the operands -------------------------------------------------------------- #

def stored_points(points, d_store: int):
    """``points`` (N, D) as the kernel and its twin read them: float32,
    ``d_store`` lanes, 1 in lane D (the constant that turns ``W x`` into
    ``A x − b`` and counts the rows of ``N_k``), 0 past it. numpy in, numpy
    out."""
    n, d = points.shape
    out = np.empty((n, d_store), np.float32)
    out[:, :d] = points
    out[:, d] = 1.0
    out[:, d + 1:] = 0.0
    return out


def stacked_operand(a, b, k_pad: int, d_pad: int, d_store: int):
    """``W`` (K_pad·D_pad, D_store), float32, from the whitening ``a`` (K, D,
    D) and ``b`` (K, D): ``W x̃`` is ``A_k x − b_k`` in rows ``k·D_pad + i``,
    i < D, and 0 in every other row."""
    k, d = b.shape
    w = jnp.concatenate([a, -b[:, :, None]], axis=2)          # (K, D, D + 1)
    w = jnp.pad(w, ((0, k_pad - k), (0, d_pad - d), (0, d_store - d - 1)))
    return w.reshape(k_pad * d_pad, d_store)


def padded_const(const, k_pad: int):
    """The per-component constant ``log π − ½ (D log 2π + log det)``,
    (K_pad, 1), padded components at :data:`PHANTOM`."""
    k = const.shape[0]
    return jnp.pad(const, (0, k_pad - k),
                   constant_values=PHANTOM).reshape(k_pad, 1)


# -- the kernel ---------------------------------------------------------------- #

def _fold_lanes(x):
    """Lane tile onto lane tile: plain vector adds, no cross-lane work."""
    return functools.reduce(jnp.add, [
        x[:, l:l + LANES] for l in range(0, x.shape[1], LANES)])


def _estep_kernel(x_ref, w_ref, c_ref, s_ref, ll_ref, acc_ref, maha_ref,
                  r_ref, part_ref, *, rows: int, tile: int, chunk: int,
                  k_pad: int, d_pad: int, group: int, terms: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _start():
        s_ref[...] = jnp.zeros_like(s_ref)
        ll_ref[...] = jnp.zeros_like(ll_ref)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    pairs = _pairs(terms)
    groups = k_pad // group
    g_rows = group * d_pad
    ragged = rows % tile != 0

    def one(j, ll):
        first = pl.multiple_of(j * chunk, chunk)
        x = x_ref[pl.ds(first, chunk), :]                      # (chunk, Ds)
        if ragged:
            left = rows - (i * tile + first)     # rows of the block from here
            x = jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, (chunk, 1), 0) < left, x, 0.0)
            valid = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) < left
        xt = split_terms(x, terms)
        # (a)'s operand: the passes side by side along the contraction
        x_pass = jnp.concatenate([xt[b] for _, b in pairs], axis=1)
        # (b)'s: the same passes stacked along it
        x_rows = jnp.concatenate([xt[b] for _, b in pairs], axis=0)
        x_t = jnp.transpose(x)[0:d_pad, :]                     # (Dp, chunk)

        def whiten(g, carry):
            at = pl.multiple_of(g * g_rows, g_rows)
            y = jax.lax.dot_general(
                w_ref[pl.ds(at, g_rows), :], x_pass,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_DEFAULT)
            y = y * y                                          # (G·Dp, chunk)
            for c in range(group):
                part_ref[c:c + 1, :] = jnp.sum(
                    y[c * d_pad:(c + 1) * d_pad, :], axis=0, keepdims=True)
            maha_ref[pl.ds(pl.multiple_of(g * group, group), group), :] = (
                part_ref[...])
            return carry

        jax.lax.fori_loop(0, groups, whiten, 0)
        logp = c_ref[...] - 0.5 * maha_ref[...]                # (Kp, chunk)
        top = jnp.max(logp, axis=0, keepdims=True)
        e = jnp.exp(logp - top)
        total = jnp.sum(e, axis=0, keepdims=True)
        logz = top + jnp.log(total)                            # (1, chunk)
        r = e / total
        if ragged:
            r = jnp.where(valid, r, 0.0)
            logz = jnp.where(valid, logz, 0.0)
        r_ref[...] = r

        def moments(g, carry):
            part_ref[...] = r_ref[pl.ds(pl.multiple_of(g * group, group),
                                        group), :]
            z = jnp.concatenate([part_ref[c:c + 1, :] * x_t
                                 for c in range(group)], axis=0)
            zt = split_terms(z, terms)
            at = pl.multiple_of(g * g_rows, g_rows)
            acc_ref[pl.ds(at, g_rows), :] += jax.lax.dot_general(
                jnp.concatenate([zt[a] for a, _ in pairs], axis=1), x_rows,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=_DEFAULT)
            return carry

        jax.lax.fori_loop(0, groups, moments, 0)
        return ll + _fold_lanes(logz)

    ll = jax.lax.fori_loop(0, tile // chunk, one,
                           jnp.zeros((1, LANES), jnp.float32))
    s_ref[...] += acc_ref[...]
    ll_ref[0:1, :] += ll


def estep_pallas(x: jax.Array, w: jax.Array, const: jax.Array,
                 k_pad: int, d_pad: int, tile: int, chunk: int,
                 interpret: bool = False
                 ) -> Tuple[jax.Array, jax.Array]:
    """One E-step on this worker's block: ``x`` (rows, D_store) as
    :func:`stored_points` stores them, ``w`` (K_pad·D_pad, D_store) float32
    as :func:`stacked_operand` makes it, ``const`` (K_pad, 1). Returns the
    statistics ``(K_pad·D_pad, D_store)`` (the module docstring) and ``Σ log
    z`` over the block, float32."""
    rows, d_store = x.shape
    if (w.shape != (k_pad * d_pad, d_store) or const.shape != (k_pad, 1)
            or k_pad % GROUP or d_pad % SUBLANES
            or d_store % LANES or tile % chunk or chunk % LANES):
        raise ValueError("em estep: inconsistent shapes or tiling")
    pairs = _pairs(TERMS)
    w_terms = split_terms(w.astype(jnp.float32), TERMS)
    w_pass = jnp.concatenate([w_terms[a] for a, _ in pairs], axis=1)
    kernel = functools.partial(
        _estep_kernel, rows=rows, tile=tile, chunk=chunk, k_pad=k_pad,
        d_pad=d_pad, group=GROUP, terms=TERMS)
    stats, ll = pl.pallas_call(
        kernel,
        grid=(-(-rows // tile),),
        in_specs=[
            pl.BlockSpec((tile, d_store), lambda i: (i, 0)),       # points
            pl.BlockSpec((k_pad * d_pad, len(pairs) * d_store),    # W's passes
                         lambda i: (0, 0), pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((k_pad, 1), lambda i: (0, 0)),            # constants
        ],
        out_specs=[
            pl.BlockSpec((k_pad * d_pad, d_store), lambda i: (0, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k_pad * d_pad, d_store), jnp.float32),
            jax.ShapeDtypeStruct((SUBLANES, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((k_pad * d_pad, d_store), jnp.float32),   # a tile's sums
            pltpu.VMEM((k_pad, chunk), jnp.float32),             # Mahalanobis
            pltpu.VMEM((k_pad, chunk), jnp.float32),             # r
            pltpu.VMEM((GROUP, chunk), jnp.float32),             # a group's rows
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=NAME,
    )(x, w_pass, const.astype(jnp.float32))
    return stats, jnp.sum(ll)


# -- the twin ------------------------------------------------------------------ #

def twin_block(rows: int, k_pad: int, d_pad: int) -> int:
    """Rows of the twin's blocks: its ``(block, K_pad·D_pad)`` float32
    temporaries within :data:`_TWIN_BYTES`, no more than ``rows``, and a
    divisor of ``rows`` where one is at least an eighth of that (else the
    rows are padded: a copy of the points)."""
    most = max(1, min(rows, _TWIN_BYTES // (4 * 3 * k_pad * d_pad)))
    best = max(b for b in range(1, most + 1) if rows % b == 0)
    return best if 8 * best >= most else most


def estep_xla(x: jax.Array, w: jax.Array, const: jax.Array, k_pad: int,
              d_pad: int, block: int) -> Tuple[jax.Array, jax.Array]:
    """The contract of :func:`estep_pallas` in ``jax.numpy``, by blocks of
    ``block`` rows under ``lax.scan`` (the rows padded to whole blocks with
    zeros, which add nothing to the statistics and are left out of the
    log-likelihood): never more than ``(block, K_pad·D_pad)`` at once.
    Every product at ``Precision.HIGHEST``."""
    rows, d_store = x.shape
    nblocks = -(-rows // block)
    xb = jnp.pad(x, ((0, nblocks * block - rows), (0, 0))).reshape(
        nblocks, block, d_store)
    c = const.reshape(1, k_pad)

    def visit(carry, args):
        stats, ll = carry
        b, xk = args
        y = jnp.dot(xk, w.T, precision=_HIGHEST)           # (block, Kp·Dp)
        maha = jnp.sum((y * y).reshape(block, k_pad, d_pad), axis=2)
        logp = c - 0.5 * maha
        logz = jax.scipy.special.logsumexp(logp, axis=1, keepdims=True)
        valid = (b * block + jnp.arange(block))[:, None] < rows
        r = jnp.where(valid, jnp.exp(logp - logz), 0.0)        # (block, Kp)
        z = (r[:, :, None] * xk[:, None, :d_pad]).reshape(block, k_pad * d_pad)
        stats = stats + jnp.dot(z.T, xk, precision=_HIGHEST)
        return (stats, ll + jnp.sum(jnp.where(valid, logz, 0.0))), None

    zero = (jnp.zeros((k_pad * d_pad, d_store), jnp.float32),
            jnp.zeros((), jnp.float32))
    (stats, ll), _ = jax.lax.scan(visit, zero, (jnp.arange(nblocks), xb))
    return stats, ll
