"""CCD++ matrix factorization — cyclic coordinate descent, one rank at a time.

Reference parity: ml/java ccd/ (CCDMPCollectiveMapper.java:51 — CCD++ MF using
the same dymoro model-rotation machinery as SGD-MF; BASELINE's "CCD MF vs CCD++"
comparison rows). Yu, Hsieh, Si, Dhillon, ICDM 2012.

TPU-native: CCD++ sweeps features t = 1..K; for each it alternates closed-form
rank-one updates of u_t (this worker's rows of the user plane) and v_t (its
rows of the item plane), each re-replicated by one allgather::

    u_it <- sum_j (a_ij - p_ij + u_it v_jt) v_jt / (lam + sum_j v_jt^2)

over the row's rated cells, ``p = U V'``. The residual is never kept: every
half-step recomputes it in ONE pass over the side's dense plane — ALS's
NaN-encoded bfloat16 planes, user-major and item-major
(``models/dense_planes.py``) — by the fused kernel of ``ops/ccd_sweep.py``
on TPU (prediction on the MXU from bfloat16 operands with float32
accumulation; residual and both row sums in float32), the same pass in plain
``jax.numpy`` elsewhere. That trades FLOPs (cheap on the MXU) for the
reference's maintained residual matrix (cheap on a CPU, racy to parallelize),
and is stateless and static-shape. A row with no rating keeps its value.

One layout, the dense planes: bfloat16 keeps 8 bits of a rating (half stars
are exact); ``prepare`` raises where a worker's two plane shards pass
:data:`DENSE_PLANE_BYTES`. Duplicate (row, col) pairs are dropped keep-first
(the ``sgd_mf.dedupe_coo`` contract; ``last_layout_stats``). Host phases:
``ccd.prepare``; ``ccd.call`` with ``step.dispatch`` and ``step.fetch``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu import telemetry
from harp_tpu.collectives import lax_ops
from harp_tpu.models import als
from harp_tpu.models.dense_planes import dense_plane, place_dense_planes
from harp_tpu.ops import ccd_sweep
from harp_tpu.ops.lane_pack import round_up
from harp_tpu.parallel.mesh import WORKERS
from harp_tpu.session import HarpSession
from harp_tpu.utils import metrics

# what a worker's two plane shards may take of its memory (16 GB a v5e chip;
# MovieLens-10M's are 3.06 GB on one)
DENSE_PLANE_BYTES = 8 * 1024 ** 3


@dataclasses.dataclass(frozen=True)
class CCDConfig:
    rank: int = 8
    lam: float = 0.05
    outer_iterations: int = 10   # full sweeps over all ranks
    inner_iterations: int = 2    # u/v alternations per rank


@dataclasses.dataclass(frozen=True)
class _Side:
    """One side's half-step: this worker's plane shard is ``(rows, cols)``;
    ``store`` the columns the OTHER side's factors are kept at."""
    rows: int
    cols: int
    row_tile: int               # 0: the jax.numpy pass, in `block`s
    col_tile: int
    block: Tuple[int, int]
    interpret: bool = False     # the kernel off the TPU (tests only)

    @property
    def store(self) -> int:
        return ccd_sweep.sweep_store(self.cols, self.col_tile)


def _geometry(u_rpw: int, i_rpw: int, w: int, kp: int):
    """``(users' side, items' side)``: the kernel's tiles where both planes
    take it (one predicate beside the kernel decides), else row blocks whose
    float32 temporaries fit ``als.DENSE_SCRATCH_BYTES``."""
    shapes = ((u_rpw, w * i_rpw), (i_rpw, w * u_rpw))
    fused = all(ccd_sweep.use_ccd_sweep_pallas(r, c, kp) for r, c in shapes)
    return tuple(
        _Side(r, c, *(ccd_sweep.sweep_tiles(r, c, kp) if fused else (0, 0)),
              block=als._row_block(r, 16 * c),
              interpret=fused and jax.default_backend() != "tpu")
        for r, c in shapes)


def _half_step(plane, side: _Side, mine, other, t, lam: float,
               axis_name: str):
    """Feature t of one side from the current factors. ``mine`` / ``other``:
    ``(float32, bfloat16)`` transposed factors of this and the other side,
    ``(K, store)``, replicated. Returns ``mine`` with row t replaced."""
    mine_t, mine_b = mine
    other_t, other_b = other
    lo = lax_ops.worker_id(axis_name) * side.rows
    with jax.named_scope("ccd.column"):
        col = other_t[t]
        old = jax.lax.dynamic_slice_in_dim(mine_t[t], lo, side.rows)
        rows_b = jax.lax.dynamic_slice_in_dim(mine_b, lo, side.rows, 1)
    with jax.named_scope("ccd.sweep"):
        # runs when jax traces, only: which pass this program's half-steps run
        if side.row_tile:
            metrics.DEFAULT.count("ccd.sweeps.pallas")
            s, d = ccd_sweep.sweep_pallas(
                plane, rows_b, other_b, col, side.row_tile, side.col_tile,
                interpret=side.interpret)
        else:
            metrics.DEFAULT.count("ccd.sweeps.xla")
            s, d = ccd_sweep.sweep_xla(plane, rows_b, other_b, col,
                                       side.block)
    with jax.named_scope("ccd.column"):
        # a - p + u_t v_t' is the residual without feature t; d > 0 wherever
        # the row has a rating
        new = jnp.where(d > 0, (s + old * d) / (lam + d), old)
    new = lax_ops.allgather(new, axis_name)
    with jax.named_scope("ccd.column"):
        new = jnp.pad(new, (0, mine_t.shape[1] - new.shape[0]))[None, :]
        return (jax.lax.dynamic_update_slice_in_dim(mine_t, new, t, 0),
                jax.lax.dynamic_update_slice_in_dim(
                    mine_b, new.astype(jnp.bfloat16), t, 0))


def _train(u_plane, i_plane, u0, v0, sides, cfg: CCDConfig,
           axis_name: str = WORKERS):
    """``outer_iterations`` sweeps from the replicated factors ``u0`` (U, K)
    and ``v0`` (V, K); returns them and each sweep's RMSE over the rated
    cells. Inside, factors are carried transposed and padded, ``(Kp, store)``
    in float32 and bfloat16 (``ops/ccd_sweep.py``): a feature is a row."""
    telemetry.traced("ccd")                # runs when jax traces, only
    u_side, i_side = sides
    k = cfg.rank
    kp = round_up(k, ccd_sweep.RANK_MULTIPLE)
    metrics.DEFAULT.count("ccd.passes", 2 * k * cfg.inner_iterations)

    def carried(x, store):
        x_t = jnp.pad(x.T, ((0, kp - k), (0, store - x.shape[0])))
        return x_t, x_t.astype(jnp.bfloat16)

    def rank_one(carry, t):
        u, v = carry
        u = _half_step(u_plane, u_side, u, v, t, cfg.lam, axis_name)
        v = _half_step(i_plane, i_side, v, u, t, cfg.lam, axis_name)
        return (u, v), None

    def outer(carry, _):
        carry, _ = jax.lax.scan(
            rank_one, carry, jnp.repeat(jnp.arange(k), cfg.inner_iterations))
        (_, u_b), (_, v_b) = carry
        with jax.named_scope("ccd.monitor"):
            lo = lax_ops.worker_id(axis_name) * u_side.rows
            mine = jax.lax.dynamic_slice_in_dim(u_b, lo, u_side.rows, 1)
            sse, cnt = ccd_sweep.squared_error_xla(u_plane, mine, v_b,
                                                   u_side.block)
            sse = jax.lax.psum(sse, axis_name)
            cnt = jax.lax.psum(cnt, axis_name)
            rmse = jnp.sqrt(sse / jnp.maximum(cnt, 1.0))
        return carry, rmse

    first = (carried(u0, i_side.store), carried(v0, u_side.store))
    ((u_t, _), (v_t, _)), rmse = jax.lax.scan(
        outer, first, None, length=cfg.outer_iterations)
    return u_t[:k, :u0.shape[0]].T, v_t[:k, :v0.shape[0]].T, rmse


class CCD:
    """Distributed CCD++ over a HarpSession mesh (ml/java ccd parity)."""

    def __init__(self, session: HarpSession, config: CCDConfig):
        self.session = session
        self.config = config
        self._fns = {}
        self.last_layout_stats: dict = {}

    def prepare(self, rows, cols, vals, num_rows: int, num_cols: int,
                seed: int = 0):
        """Host layout + H2D ONCE; returns an opaque state for
        :meth:`train_prepared` / :meth:`fit_prepared` (the ALS/SGDMF prepare
        idiom). The last two entries of ``state[1]`` are the first factors:
        U = 0, as the paper starts it, and V uniform in [0, 1/sqrt(rank))
        from ``seed``, rows of the padding zero. (At random signs the first
        features find the ratings' mean only by a power iteration of
        ``inner_iterations`` rounds, and a job's whole curve then follows
        the draw: ``PERF.md``, Findings, PR 31, 5.)"""
        with telemetry.phase("ccd.prepare"):
            return self._prepare(rows, cols, vals, num_rows, num_cols, seed)

    def _prepare(self, rows, cols, vals, num_rows: int, num_cols: int,
                 seed: int):
        from harp_tpu.models.sgd_mf import _validate_coo, dedupe_coo

        sess, cfg = self.session, self.config
        w = sess.num_workers
        rows = np.asarray(rows)
        cols = np.asarray(cols)
        vals = np.asarray(vals, np.float32)
        _validate_coo(rows, cols, num_rows, num_cols, vals)     # incl. NaN
        u_rpw = -(-num_rows // w)
        i_rpw = -(-num_cols // w)
        u_pad, i_pad = w * u_rpw, w * i_rpw
        per_worker = 2 * 2 * u_rpw * i_rpw * w
        if per_worker > DENSE_PLANE_BYTES:
            raise ValueError(
                f"CCD++ keeps a {num_rows} x {num_cols} rating matrix as two "
                f"dense bfloat16 planes: {per_worker} bytes a worker on "
                f"{w}, over DENSE_PLANE_BYTES = {DENSE_PLANE_BYTES}")
        rows, cols, vals, dropped = dedupe_coo(rows, cols, vals, num_cols)
        key = self._program(u_rpw, i_rpw)
        sides = key[1]
        self.last_layout_stats = {
            "layout": "dense",
            "plane_bytes": 2 * u_pad * i_pad * 2,
            "duplicates_dropped": dropped,
            "row_tile": [s.row_tile for s in sides],
            "col_tile": [s.col_tile for s in sides],
            "sweep": "pallas" if sides[0].row_tile else "xla",
        }
        rng = np.random.default_rng(seed)
        u0 = np.zeros((u_pad, cfg.rank), np.float32)
        v0 = (rng.random((i_pad, cfg.rank)) / np.sqrt(cfg.rank)
              ).astype(np.float32)
        v0[num_cols:] = 0.0
        u_plane = dense_plane(rows, cols, vals, u_pad, i_pad)
        placed = (*place_dense_planes(sess, self._fns, u_plane),
                  sess.replicate_put(u0), sess.replicate_put(v0))
        return key, placed, num_rows, num_cols

    def _program(self, u_rpw: int, i_rpw: int):
        """Key of the SPMD program at these rows per worker (built on first
        use): planes sharded by rows, factors replicated. ``key[1]`` holds
        the two sides' shapes and tiles."""
        sess, cfg = self.session, self.config
        sides = _geometry(u_rpw, i_rpw, sess.num_workers,
                          round_up(cfg.rank, ccd_sweep.RANK_MULTIPLE))
        key = ("ccd", sides, sess.num_workers)
        if key not in self._fns:
            self._fns[key] = sess.spmd(
                lambda up, ip, u, v: _train(up, ip, u, v, sides, cfg),
                in_specs=(sess.shard(), sess.shard(),
                          sess.replicate(), sess.replicate()),
                out_specs=(sess.replicate(),) * 3)
        return key

    def train_prepared(self, state):
        """Run the compiled sweeps; factors stay ON DEVICE. Returns (u_dev,
        v_dev, rmse ndarray): the rmse fetch forces execution. The last two
        entries of ``state[1]`` are the factors the call starts from: a
        caller that trains in several calls hands back what the call before
        returned."""
        key, placed, _, _ = state
        with telemetry.phase("ccd.call") as call:
            step = self._fns[key]
            # (the dispatch keeps its line number: PERF.md section 7, row 11)
            with telemetry.phase("step.dispatch"):
                u, v, rmse = step(*placed)
            telemetry.record_program("ccd", step, placed)
            with telemetry.phase("step.fetch"):
                rmse = np.asarray(rmse)
            telemetry.record_chunk("ccd", start=0, losses=rmse.tolist(),
                                   wall_s=call.elapsed())
        return u, v, rmse

    def fit_prepared(self, state
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(U (num_rows, K), V (num_cols, K), rmse per outer iteration)."""
        u, v, rmse = self.train_prepared(state)
        _, _, num_rows, num_cols = state
        return np.asarray(u)[:num_rows], np.asarray(v)[:num_cols], rmse

    def fit(self, rows, cols, vals, num_rows: int, num_cols: int,
            seed: int = 0) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        return self.fit_prepared(self.prepare(rows, cols, vals, num_rows,
                                              num_cols, seed))
