"""The CCD++ rank-one sweep: one pass over a dense rating plane, fused.

One half-step of CCD++ (``models/ccd.py``) for feature t on one side needs,
for every row i of that side's NaN-encoded plane ``A`` (rows, cols)::

    s_i = sum_j m_ij (a_ij - p_ij) v_jt        p = U V' (all features)
    d_i = sum_j m_ij v_jt^2                    m_ij = 1 where a_ij is rated

from which the caller takes ``u_it <- (s_i + u_it d_i) / (lam + d_i)``: the
residual without feature t is ``a - p + u_t v_t'``, and the part that adds
feature t back leaves the sum as ``u_it d_i``, so no pass needs t itself.

Left to XLA the float32 prediction plane is written to HBM and read again
(3 GB at MovieLens-10M's shape, 400 times an epoch). Here the plane is read
once: a grid step holds a ``(row_tile, col_tile)`` block of it and walks it
in chunks of :data:`CHUNK` columns, each a bfloat16 product on the MXU with
float32 accumulation, the masked residual and both row sums in float32 on
the VPU, the sums kept in VMEM across the row block's column tiles. The
other side's factors (transposed, bfloat16) and its column t (float32) stay
RESIDENT in VMEM for the whole kernel, sliced by chunk: they are read from
HBM once a pass, not once a row block.

Layout: factors are handed over TRANSPOSED, ``(K, entities)``, K a multiple
of 16 (bfloat16 packs two rows a sublane), so a side's rows ride the lanes
of its own block and of both outputs. The resident operands are stored at
``sweep_store(cols, col_tile)`` columns, zeros past the plane's own: a cell
of a block's overhang (the plane is not padded; what a partial block reads
there is unspecified) then multiplies a zero, and a product that is not a
number counts as unrated. Rows of an overhang give sums nobody reads.

``use_ccd_sweep_pallas`` decides between this kernel and ``sweep_xla``, the
same pass in plain ``jax.numpy`` in row blocks, by backend and shape alone.
``squared_error_xla`` is the per-epoch monitor, once in 400 passes.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from harp_tpu.ops.lane_pack import LANES, round_up

NAME = "ccd_rank1_sweep"
# what the kernel may ask of VMEM (v5e: 128 MiB physical)
VMEM_LIMIT = 100 * 1024 * 1024
CHUNK = 4 * LANES           # columns a product, and its float32 temporaries
ROW_TILES = (4 * LANES, 2 * LANES, LANES)
_MAX_COL_TILE = 16384       # widest column tile: 512 x 16384 bf16 = 16 MiB
RANK_MULTIPLE = 16


def sweep_vmem_bytes(k: int, store: int, row_tile: int, col_tile: int) -> int:
    """VMEM the sweep needs at these shapes, from above: the plane's block
    and every operand double buffered (the resident ones too: the pipeline
    allocates two of each), the two accumulators, eight float32 temporaries
    of a chunk, 4 MiB."""
    return (2 * 2 * row_tile * col_tile            # the plane's block, bf16
            + 2 * (2 * k + 4 * 8) * store           # resident factors + column
            + 2 * 2 * k * row_tile                  # this side's factors
            + (2 + 2 * 2) * 4 * 8 * row_tile        # outputs, double buffered
            + 2 * 4 * LANES * row_tile              # accumulators
            + 8 * 4 * row_tile * CHUNK) + (4 << 20)


def sweep_tiles(rows: int, cols: int, k: int) -> Tuple[int, int]:
    """``(row_tile, col_tile)`` of the sweep over a ``(rows, cols)`` plane at
    stored rank ``k``: the fewest equal column tiles no wider than
    :data:`_MAX_COL_TILE`, each a whole number of chunks, and the tallest row
    tile whose VMEM estimate fits :data:`VMEM_LIMIT`. ``(0, 0)`` where the
    rank is not stored at a multiple of 16, the plane is smaller than one
    tile, or nothing fits."""
    if k % RANK_MULTIPLE or cols < CHUNK:
        return 0, 0
    n_ct = -(-cols // _MAX_COL_TILE)
    col_tile = round_up(-(-cols // n_ct), CHUNK)
    store = sweep_store(cols, col_tile)
    for row_tile in ROW_TILES:
        if rows >= row_tile and sweep_vmem_bytes(
                k, store, row_tile, col_tile) <= VMEM_LIMIT:
            return row_tile, col_tile
    return 0, 0


def sweep_store(cols: int, col_tile: int) -> int:
    """Columns the resident operands are stored at: whole column tiles (the
    kernel slices them by chunk), or the plane's own where no kernel runs."""
    return round_up(cols, col_tile) if col_tile else cols


def use_ccd_sweep_pallas(rows: int, cols: int, k: int) -> bool:
    """Dispatch predicate: ON for TPU where a tile fits
    (:func:`sweep_tiles`)."""
    if jax.default_backend() != "tpu":
        return False
    return sweep_tiles(rows, cols, k)[0] > 0


def _sums(a, pred, v):
    """Both row sums' terms of a block: ``a`` the ratings (NaN = unrated),
    ``pred`` the prediction, ``v`` (1, cols) the other side's column t."""
    rv = (a - pred) * v
    bad = jnp.isnan(rv)
    return jnp.where(bad, 0.0, rv), jnp.where(bad, 0.0, v * v)


def _sweep_kernel(a_ref, mine_ref, other_ref, col_ref, s_ref, d_ref,
                  s_acc, d_acc, *, col_tile: int, n_ct: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _row_block_start():
        s_acc[...] = jnp.zeros_like(s_acc)
        d_acc[...] = jnp.zeros_like(d_acc)

    mine = mine_ref[...]                          # (K, row_tile) bf16

    def chunk(c, carry):
        here = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        there = pl.ds(pl.multiple_of(j * col_tile + c * CHUNK, CHUNK), CHUNK)
        pred = jax.lax.dot_general(
            mine, other_ref[:, there], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)   # (row_tile, CHUNK)
        # NaN test in f32: mosaic has no bf16 vector compare
        s, d = _sums(a_ref[:, here].astype(jnp.float32), pred,
                     col_ref[0:1, there])
        # lane tile onto lane tile: plain vector adds, no cross-lane work
        s_acc[...] += functools.reduce(jnp.add, [
            s[:, l:l + LANES] for l in range(0, CHUNK, LANES)])
        d_acc[...] += functools.reduce(jnp.add, [
            d[:, l:l + LANES] for l in range(0, CHUNK, LANES)])
        return carry

    jax.lax.fori_loop(0, col_tile // CHUNK, chunk, 0)

    @pl.when(j == n_ct - 1)
    def _row_block_end():
        # the 128 partial sums of a row, turned so that rows ride the lanes
        for acc, out in ((s_acc, s_ref), (d_acc, d_ref)):
            row = jnp.sum(acc[...].T, axis=0, keepdims=True)
            out[...] = jnp.broadcast_to(row, out.shape)


def sweep_pallas(plane: jax.Array, mine_t: jax.Array, other_t: jax.Array,
                 col: jax.Array, row_tile: int, col_tile: int,
                 interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """One fused pass. ``plane`` (rows, cols) bf16, NaN = unrated;
    ``mine_t`` (K, >= rows) bf16, this side's factors; ``other_t`` (K, store)
    bf16 and ``col`` (store,) float32, the other side's factors and their
    column t, ``store = sweep_store(cols, col_tile)``, zeros past ``cols``.
    Returns ``(s, d)``, float32 ``(rows,)`` each (module docstring)."""
    rows, cols = plane.shape
    k, store = other_t.shape
    if (mine_t.shape[0] != k or mine_t.shape[1] < rows or col.shape != (store,)
            or store != sweep_store(cols, col_tile)):
        raise ValueError("ccd sweep: inconsistent shapes")
    if (k % RANK_MULTIPLE or row_tile % LANES or col_tile % CHUNK
            or rows < row_tile):
        raise ValueError("ccd sweep: tiling constraints violated")
    n_rb, n_ct = -(-rows // row_tile), store // col_tile
    kernel = functools.partial(_sweep_kernel, col_tile=col_tile, n_ct=n_ct)
    # column t rides an 8-sublane-replicated block (mosaic loads whole
    # sublane tiles); the kernel reads its first row
    col8 = jnp.broadcast_to(col[None, :], (8, store))
    out = jax.ShapeDtypeStruct((8, n_rb * row_tile), jnp.float32)
    s, d = pl.pallas_call(
        kernel,
        grid=(n_rb, n_ct),
        in_specs=[
            pl.BlockSpec((row_tile, col_tile), lambda i, j: (i, j)),  # plane
            pl.BlockSpec((k, row_tile), lambda i, j: (0, i)),         # mine
            pl.BlockSpec((k, store), lambda i, j: (0, 0)),            # other
            pl.BlockSpec((8, store), lambda i, j: (0, 0)),            # col8
        ],
        out_specs=[pl.BlockSpec((8, row_tile), lambda i, j: (0, i))] * 2,
        out_shape=[out, out],
        scratch_shapes=[pltpu.VMEM((row_tile, LANES), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=NAME,
    )(plane, mine_t, other_t, col8)
    return s[0, :rows], d[0, :rows]


def _rows_xla(plane, mine_t, other_t, block, terms):
    """Row sums of ``terms(ratings, prediction)`` (two float32 arrays a
    block) in row blocks, ``block = (rows a block, blocks)``: the float32
    prediction of all rows at once is another plane. The last block is
    taken flush with the end; what it shares with the one before is computed
    twice and written once."""
    rows, cols = plane.shape
    rb, n_rb = block
    other = other_t[:, :cols]

    def one(i, out):
        r0 = jnp.minimum(i * rb, rows - rb)
        mine = jax.lax.dynamic_slice_in_dim(mine_t, r0, rb, 1)
        pred = jax.lax.dot_general(mine, other, (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        a = jax.lax.dynamic_slice_in_dim(plane, r0, rb, 0)
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(o, jnp.sum(x, axis=1), r0, 0)
            for o, x in zip(out, terms(a.astype(jnp.float32), pred)))

    zero = jnp.zeros((rows,), jnp.float32)
    if n_rb == 1:
        return one(0, (zero, zero))
    return jax.lax.fori_loop(0, n_rb, one, (zero, zero))


def sweep_xla(plane: jax.Array, mine_t: jax.Array, other_t: jax.Array,
              col: jax.Array, block: Tuple[int, int]
              ) -> Tuple[jax.Array, jax.Array]:
    """The same pass in plain ``jax.numpy`` in row blocks
    (:func:`_rows_xla`). Operands as :func:`sweep_pallas` takes them
    (``store >= cols``)."""
    v = col[None, :plane.shape[1]]
    return _rows_xla(plane, mine_t, other_t, block,
                     lambda a, pred: _sums(a, pred, v))


def squared_error_xla(plane: jax.Array, mine_t: jax.Array,
                      other_t: jax.Array, block: Tuple[int, int]
                      ) -> Tuple[jax.Array, jax.Array]:
    """``(sum of squared residuals, rated cells)`` of a plane under the
    bfloat16 factors, in the same row blocks: the monitor."""
    def terms(a, pred):
        rated = ~jnp.isnan(a)
        return jnp.where(rated, (a - pred) ** 2, 0.0), rated.astype(jnp.float32)

    sse, cnt = _rows_xla(plane, mine_t, other_t, block, terms)
    return jnp.sum(sse), jnp.sum(cnt)
