"""The two N x N passes of a WDA-SMACOF iteration, fused.

One iteration of weighted SMACOF (``models/mds.py``) reads the target
distances ``delta`` and the weights ``w`` (this worker's rows of both, as
they lie: nothing is padded or copied) in two kinds of pass.

**B(X)X and the stress** (:func:`bc_pallas`, kernel ``mds_bc_stress``). With
``d_ij = |x_i - x_j|`` and the annealed targets ``dhat = max(delta - shift,
0)`` (``shift = T sqrt(2L)``), ``B_ij = -w_ij dhat_ij / d_ij`` off the
diagonal (0 where ``d_ij = 0``, SMACOF's convention) and ``B_ii = -sum_j
B_ij``, so a row of the Guttman right-hand side is::

    (B(X) X)_i = sum_j  w_ij dhat_ij / d_ij  (x_i - x_j)

and the same pass gives the row's part of the raw stress, ``sum_j w_ij
(delta_ij - d_ij)^2``. Distances are never stored: the coordinates of every
point stay RESIDENT in VMEM (transposed, ``(8, store)`` float32: 1 MB at
32,768 points) beside the tile, and a cell costs ~25 operations on the VPU
and one reciprocal square root. Left to XLA the pass writes and reads
float32 planes of the tile's rows; here nothing N x N is written.

**The weighted-Laplacian matvec** (:func:`matvec_pallas`, kernel
``mds_laplacian_matvec``): ``(w p)_i = sum_j w_ij p_j`` for the embedding's
few columns at once (the caller adds the diagonal, ``V p = V_ii p - w p``).
The weights ride the MXU as they are stored. Weights stored in bfloat16
(every one exact there: ``models/mds.py`` decides by the data) meet ``p``
split into three bfloat16 terms, ``p = hi + mid + lo`` exactly (masks on the
bits, so no compiler can fold a rounding away): each product is exact in
float32 and the sum is accumulated in float32, one MXU pass in place of the
six of ``Precision.HIGHEST``. Weights stored in float32 run ``HIGHEST``.
CG cannot take less: at one bfloat16 term the chip returned a stress that
was not a number at iteration 1 (``models/mds.py``).

Layout: a point is a LANE. Coordinates and CG vectors are handed over and
returned transposed, ``(8, points)`` (:data:`DIM_PAD` sublanes, zeros past
the embedding's dimension), at ``store`` columns with zeros past ``cols``
where an operand is resident. A partial block's overhang holds unspecified
cells: where ``cols`` is no whole number of chunks the kernels mask by
column index (one compare a cell, only then); rows of an overhang give sums
nobody reads.

``use_mds_pallas`` decides between the kernels and their ``jax.numpy`` twins
(:func:`bc_xla`, :func:`matvec_xla`: the same passes in row blocks whose
float32 temporaries fit :data:`SCRATCH_BYTES`) by backend and shape alone.
"""

from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from harp_tpu.ops.lane_pack import LANES, round_up

BC_NAME = "mds_bc_stress"
MATVEC_NAME = "mds_laplacian_matvec"
# what a kernel may ask of VMEM (v5e: 128 MiB physical)
VMEM_LIMIT = 100 * 1024 * 1024
DIM_PAD = 8                 # sublanes a point's coordinates are stored at
ROW_TILES = (4 * LANES, 2 * LANES, LANES)
# columns of a tile the B(X)X pass works on at once, and its float32
# temporaries (on the chip at 32,768 points: 10.4 ms a pass at 512, 9.4 at
# 1024, 17.7 at 2048; pieces of 16 to 64 rows that could stay in vector
# registers 17 to 58: PERF.md, Findings, PR 34)
CHUNK = 8 * LANES
BC_MAX_COL_TILE = 8192      # 512 x 8192: 16 MiB of delta, 8 of bf16 weights
MATVEC_CHUNK = 4 * LANES    # columns a product of the matvec
MATVEC_MAX_COL_TILE = 16384
# float32 temporaries of a row block in the jax.numpy twins
SCRATCH_BYTES = 256 * 1024 ** 2
_HIGHEST = jax.lax.Precision.HIGHEST


# -- shapes ------------------------------------------------------------------ #

def _col_tile(cols: int, widest: int, chunk: int) -> int:
    """The fewest equal column tiles no wider than ``widest``, each a whole
    number of chunks."""
    return round_up(-(-cols // -(-cols // widest)), chunk)


def _vmem_bytes(row_tile: int, col_tile: int, cell_bytes: int,
                resident: int) -> int:
    """VMEM a kernel needs, from above: the matrices' blocks and the
    resident operand double buffered, accumulators and outputs of a row
    tile (eight lane tiles in float32, twice), 4 MiB."""
    return (2 * cell_bytes * row_tile * col_tile + 2 * resident
            + 2 * 8 * 4 * LANES * row_tile) + (4 << 20)


def tiles(rows: int, cols: int, w_bytes: int
          ) -> Tuple[int, int, int]:
    """``(row_tile, bc col_tile, matvec col_tile)`` over this worker's
    ``(rows, cols)`` of both matrices with weights of ``w_bytes`` bytes: the
    tallest row tile at which both kernels fit :data:`VMEM_LIMIT`, ``(0, 0,
    0)`` where the block is smaller than one tile or nothing fits."""
    if cols < max(CHUNK, MATVEC_CHUNK):
        return 0, 0, 0
    bc_ct = _col_tile(cols, BC_MAX_COL_TILE, CHUNK)
    mv_ct = _col_tile(cols, MATVEC_MAX_COL_TILE * 2 // w_bytes, MATVEC_CHUNK)
    for row_tile in ROW_TILES:
        if rows < row_tile:
            continue
        bc = _vmem_bytes(row_tile, bc_ct, 4 + w_bytes,
                         4 * DIM_PAD * store(cols, bc_ct))
        mv = _vmem_bytes(row_tile, mv_ct, w_bytes,
                         w_bytes * LANES * store(cols, mv_ct))
        if max(bc, mv) <= VMEM_LIMIT:
            return row_tile, bc_ct, mv_ct
    return 0, 0, 0


def store(cols: int, col_tile: int) -> int:
    """Columns a resident operand is stored at: whole column tiles (the
    kernels slice it by chunk), or ``cols`` where no kernel runs."""
    return round_up(cols, col_tile) if col_tile else cols


def use_mds_pallas(rows: int, cols: int, dim: int, w_bytes: int) -> bool:
    """Dispatch predicate: ON for TPU where the embedding's rows and the
    stress fit the eight output sublanes and a tile fits (:func:`tiles`)."""
    if jax.default_backend() != "tpu" or dim >= DIM_PAD:
        return False
    return tiles(rows, cols, w_bytes)[0] > 0


def row_blocks(rows: int, cols: int) -> Tuple[int, int]:
    """``(rows a block, blocks)`` of the twins: the fewest equal blocks, a
    multiple of 8 rows, of which ten float32 temporaries fit
    :data:`SCRATCH_BYTES`. One block is the side as it stands."""
    most = max(8, SCRATCH_BYTES // (10 * 4 * cols) // 8 * 8)
    blocks = -(-rows // most)
    if blocks == 1:
        return rows, 1
    return round_up(-(-rows // blocks), 8), blocks


# -- B(X)X and the stress ----------------------------------------------------- #

def _bc_terms(delta, w, xi: List, xj: List, shift, valid=None) -> List:
    """The summands of one block: ``delta`` and ``w`` (R, C) float32,
    ``xi[l]`` (R, 1) and ``xj[l]`` (1, C) coordinate l of the block's rows
    and columns, ``shift`` the annealing's ``T sqrt(2L)``. One array a
    coordinate, ``w dhat / d (x_i - x_j)``, and the stress's ``w (delta -
    d)^2`` last. ``valid`` (1, C) masks the columns of an overhang."""
    diffs = [a - b for a, b in zip(xi, xj)]
    d2 = functools.reduce(jnp.add, [x * x for x in diffs])
    apart = d2 > 0.0                         # coincident points: B_ij = 0
    if valid is not None:
        apart = apart & valid
    inv = jax.lax.rsqrt(jnp.where(apart, d2, 1.0))
    ratio = jnp.where(apart, w * jnp.maximum(delta - shift, 0.0) * inv, 0.0)
    err = delta - d2 * inv
    stress = w * err * err
    if valid is not None:
        stress = jnp.where(valid, stress, 0.0)
    return [ratio * x for x in diffs] + [stress]


def _fold(x):
    """Lane tile onto lane tile: plain vector adds, no cross-lane work."""
    return functools.reduce(jnp.add, [
        x[:, l:l + LANES] for l in range(0, x.shape[1], LANES)])


def _bc_kernel(shift_ref, delta_ref, w_ref, xi_ref, xt_ref, out_ref, acc_ref,
               *, dim: int, cols: int, col_tile: int, n_ct: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _row_tile_start():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    shift = shift_ref[0]
    ragged = cols % CHUNK != 0

    xi = [xi_ref[:, l:l + 1] for l in range(dim)]

    def chunk(c, carry):
        here = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        first = pl.multiple_of(j * col_tile + c * CHUNK, CHUNK)
        valid = None
        if ragged:
            valid = first + jax.lax.broadcasted_iota(
                jnp.int32, (1, CHUNK), 1) < cols
        terms = _bc_terms(
            delta_ref[:, here], w_ref[:, here].astype(jnp.float32), xi,
            [xt_ref[l:l + 1, pl.ds(first, CHUNK)] for l in range(dim)],
            shift, valid)
        for l, t in enumerate(terms):
            acc_ref[l] += _fold(t)
        return carry

    jax.lax.fori_loop(0, col_tile // CHUNK, chunk, 0)

    @pl.when(j == n_ct - 1)
    def _row_tile_end():
        # the 128 partial sums of a row, turned so that rows ride the lanes
        out_ref[...] = jnp.zeros_like(out_ref)
        for l in range(dim + 1):
            out_ref[l:l + 1, :] = jnp.sum(acc_ref[l].T, axis=0, keepdims=True)


def bc_pallas(delta: jax.Array, w: jax.Array, xi: jax.Array, xt: jax.Array,
              shift: jax.Array, dim: int, row_tile: int, col_tile: int,
              interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """One fused pass. ``delta`` (rows, cols) float32 and ``w`` (rows, cols)
    bfloat16 or float32, this worker's rows; ``xi`` (rows, DIM_PAD) float32
    their coordinates; ``xt`` (DIM_PAD, store) float32 every point's,
    transposed, ``store = store(cols, col_tile)``, zeros past ``cols``;
    ``shift`` a float32 scalar. Returns ``(B(X)X transposed, (DIM_PAD, rows)
    with zero rows past dim, the rows' raw stress (rows,))``."""
    rows, cols = delta.shape
    if (w.shape != delta.shape or xi.shape != (rows, DIM_PAD)
            or xt.shape != (DIM_PAD, store(cols, col_tile))):
        raise ValueError("mds bc: inconsistent shapes")
    if (dim >= DIM_PAD or row_tile % LANES or col_tile % CHUNK
            or rows < row_tile):
        raise ValueError("mds bc: tiling constraints violated")
    n_rb, n_ct = -(-rows // row_tile), xt.shape[1] // col_tile
    kernel = functools.partial(_bc_kernel, dim=dim, cols=cols,
                               col_tile=col_tile, n_ct=n_ct)
    out = pl.pallas_call(
        kernel,
        grid=(n_rb, n_ct),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                     # shift
            pl.BlockSpec((row_tile, col_tile), lambda i, j: (i, j)),   # delta
            pl.BlockSpec((row_tile, col_tile), lambda i, j: (i, j)),   # w
            pl.BlockSpec((row_tile, DIM_PAD), lambda i, j: (i, 0)),    # xi
            pl.BlockSpec(xt.shape, lambda i, j: (0, 0)),               # xt
        ],
        out_specs=pl.BlockSpec((DIM_PAD, row_tile), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((DIM_PAD, n_rb * row_tile),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((dim + 1, row_tile, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=BC_NAME,
    )(jnp.reshape(shift, (1,)).astype(jnp.float32), delta, w, xi, xt)
    keep = jnp.arange(DIM_PAD)[:, None] < dim
    return jnp.where(keep, out[:, :rows], 0.0), out[dim, :rows]


def _blocked(rows: int, block: Tuple[int, int], one, out):
    """``one(first row, out)`` over the row blocks. The last block is taken
    flush with the end; what it shares with the one before is computed twice
    and written once."""
    rb, n_rb = block
    if n_rb == 1:
        return one(0, out)
    return jax.lax.fori_loop(
        0, n_rb, lambda i, o: one(jnp.minimum(i * rb, rows - rb), o), out)


def bc_xla(delta: jax.Array, w: jax.Array, xi: jax.Array, xt: jax.Array,
           shift: jax.Array, dim: int, block: Tuple[int, int]
           ) -> Tuple[jax.Array, jax.Array]:
    """The same pass in plain ``jax.numpy`` in row blocks. Operands and
    results as :func:`bc_pallas` (``store >= cols``)."""
    rows, cols = delta.shape
    xj = [xt[l][None, :cols] for l in range(dim)]

    def one(r0, out):
        mine = jax.lax.dynamic_slice_in_dim(xi, r0, block[0], 0)
        terms = _bc_terms(
            jax.lax.dynamic_slice_in_dim(delta, r0, block[0], 0),
            jax.lax.dynamic_slice_in_dim(w, r0, block[0], 0
                                         ).astype(jnp.float32),
            [mine[:, l:l + 1] for l in range(dim)], xj, shift)
        sums = jnp.stack([jnp.sum(t, axis=1) for t in terms])
        return jax.lax.dynamic_update_slice(out, sums, (0, r0))

    out = _blocked(rows, block, one,
                   jnp.zeros((dim + 1, rows), jnp.float32))
    return jnp.pad(out[:dim], ((0, DIM_PAD - dim), (0, 0))), out[dim]


# -- the matvec --------------------------------------------------------------- #

def _terms_of(w_dtype) -> int:
    return 3 if w_dtype == jnp.bfloat16 else 1


def matvec_operand(pt: jax.Array, w_dtype) -> jax.Array:
    """``pt`` (DIM_PAD, store) float32, zero rows past the embedding's
    dimension, as the matvec's right-hand operand,
    ``(store, 128)`` in the weights' type: beside float32 weights the
    columns themselves; beside bfloat16 weights three terms of each,
    ``hi + mid + lo = p`` exactly (the upper 8 bits of the mantissa, the
    next 8, the rest: cut by masks, so each conversion is exact and a
    compiler that keeps excess precision changes nothing), term t of
    column l at lane ``8 t + l``."""
    if _terms_of(w_dtype) == 1:
        parts = [pt]
    else:
        def upper(x):
            bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
            return jax.lax.bitcast_convert_type(
                bits & jnp.uint32(0xFFFF0000), jnp.float32)

        hi = upper(pt)
        mid = upper(pt - hi)
        parts = [hi, mid, pt - hi - mid]
    lanes = jnp.concatenate([x.astype(w_dtype) for x in parts])
    return jnp.pad(lanes, ((0, LANES - lanes.shape[0]), (0, 0))).T


def _product(w, p):
    """``w`` (R, C) by ``p`` (C, 128) in float32: bfloat16 operands are one
    exact MXU pass, float32 ones ``HIGHEST``."""
    return jax.lax.dot_general(
        w, p, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=None if w.dtype == jnp.bfloat16 else _HIGHEST)


def _gather_terms(acc_t, terms: int):
    """(128, R) sums by lane -> (DIM_PAD, R): the terms of a column added."""
    return functools.reduce(jnp.add, [
        acc_t[DIM_PAD * t:DIM_PAD * (t + 1)] for t in range(terms)])


def _matvec_kernel(w_ref, p_ref, out_ref, acc_ref, *, cols: int,
                   col_tile: int, n_ct: int, terms: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _row_tile_start():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ragged = cols % MATVEC_CHUNK != 0

    def chunk(c, carry):
        here = pl.ds(pl.multiple_of(c * MATVEC_CHUNK, MATVEC_CHUNK),
                     MATVEC_CHUNK)
        first = pl.multiple_of(j * col_tile + c * MATVEC_CHUNK, MATVEC_CHUNK)
        w = w_ref[:, here]
        if ragged:
            # compare and select in float32: mosaic has no bf16 vector select
            valid = first + jax.lax.broadcasted_iota(
                jnp.int32, (1, MATVEC_CHUNK), 1) < cols
            w = jnp.where(valid, w.astype(jnp.float32), 0.0).astype(w.dtype)
        acc_ref[...] += _product(w, p_ref[pl.ds(first, MATVEC_CHUNK), :])
        return carry

    jax.lax.fori_loop(0, col_tile // MATVEC_CHUNK, chunk, 0)

    @pl.when(j == n_ct - 1)
    def _row_tile_end():
        out_ref[...] = _gather_terms(acc_ref[...].T, terms)


def matvec_pallas(w: jax.Array, p: jax.Array, row_tile: int, col_tile: int,
                  interpret: bool = False) -> jax.Array:
    """``w`` (rows, cols) times the columns ``p`` holds
    (:func:`matvec_operand`, ``(store(cols, col_tile), 128)`` in ``w``'s
    type), transposed: ``(DIM_PAD, rows)`` float32."""
    rows, cols = w.shape
    if p.shape != (store(cols, col_tile), LANES) or p.dtype != w.dtype:
        raise ValueError("mds matvec: inconsistent shapes")
    if row_tile % LANES or col_tile % MATVEC_CHUNK or rows < row_tile:
        raise ValueError("mds matvec: tiling constraints violated")
    n_rb, n_ct = -(-rows // row_tile), p.shape[0] // col_tile
    kernel = functools.partial(_matvec_kernel, cols=cols, col_tile=col_tile,
                               n_ct=n_ct, terms=_terms_of(w.dtype))
    out = pl.pallas_call(
        kernel,
        grid=(n_rb, n_ct),
        in_specs=[
            pl.BlockSpec((row_tile, col_tile), lambda i, j: (i, j)),   # w
            pl.BlockSpec(p.shape, lambda i, j: (0, 0)),                # p
        ],
        out_specs=pl.BlockSpec((DIM_PAD, row_tile), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((DIM_PAD, n_rb * row_tile),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((row_tile, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=MATVEC_NAME,
    )(w, p)
    return out[:, :rows]


def matvec_xla(w: jax.Array, p: jax.Array, block: Tuple[int, int]
               ) -> jax.Array:
    """The same products in plain ``jax.numpy`` in row blocks (a backend
    that widens bfloat16 operands then widens a block, not the matrix).
    Operands and result as :func:`matvec_pallas` (``p`` at ``>= cols``
    rows)."""
    rows, cols = w.shape
    p = p[:cols]

    def one(r0, out):
        acc = _product(jax.lax.dynamic_slice_in_dim(w, r0, block[0], 0), p)
        return jax.lax.dynamic_update_slice(
            out, _gather_terms(acc.T, _terms_of(w.dtype)), (0, r0))

    return _blocked(rows, block, one,
                    jnp.zeros((DIM_PAD, rows), jnp.float32))
