"""K-means — the flagship workload, in every Harp communication pattern.

Reference parity: Harp implemented the SAME algorithm under five comm patterns as a
capability matrix (contrib kmeans/{allreduce,regroupallgather,pushpull,bcastreduce},
ml/java kmeans/{regroupallgather,rotation}); the flagship BASELINE config[0] is
``edu.iu.kmeans.regroupallgather.KMeansLauncher`` (KMeansCollectiveMapper.java:38,
hot loop :147-197: CenCalcTask distances → regroup → local average → allgather).

TPU-native: the entire iteration loop is ONE compiled XLA program — a ``lax.scan``
over iterations inside ``shard_map`` — rather than one JVM network op per phase.
Per iteration each worker computes partial sums/counts for its point block (two
MXU matmuls: one fused Pallas pass over the points on TPU at lane-padded
shapes, ops/kmeans_kernels.py; XLA's two products elsewhere, ops/distance.py),
then the chosen collective combines them:

  * ``regroupallgather`` — reduce_scatter the (K, D+1) stat table, each worker
    averages its centroid block, all_gather the new centroids. Bandwidth-optimal;
    identical math to Harp's flagship.
  * ``allreduce``    — one psum, every worker averages everything.
  * ``pushpull``     — stats pushed into a persistent SHARDED global table, pulled
    back (LocalGlobalSyncCollective push:209/pull:185 pattern).
  * ``bcastreduce``  — reduce to master, master averages, broadcast.
  * ``rotation``     — centroid blocks ring-rotate (ml/java kmeans/rotation): each
    worker accumulates stats for the resident block against ALL its points each hop.

All variants produce bit-identical centroid trajectories (they compute the same
sums in the same tree order per partition), which the tests assert — the reference
could only claim statistical equivalence across its variants. The bit-identity
guarantee holds for the default f32 path; ``compute_dtype="bfloat16"`` keeps all
accumulations f32 but near-tie assignments may differ across variants. Where
the fused E-step kernel runs (TPU), ``rotation`` keeps XLA's products (its
work is block-local over circulating centroids): it then agrees with the other
four to rounding, with the same near-tie caveat, and they with each other
bit for bit. Host phases: ``kmeans.prepare``; ``kmeans.call`` with
``step.dispatch`` (and ``step.fetch`` where the call fetches its cost).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu import combiner as cb
from harp_tpu import telemetry
from harp_tpu.collectives import lax_ops, quantize, rotation, table_ops
from harp_tpu.ops import distance, kmeans_kernels, lane_pack
from harp_tpu.session import HarpSession
from harp_tpu.table import Table
from harp_tpu.telemetry.scopes import scoped
from harp_tpu.utils import metrics

COMM_VARIANTS = ("regroupallgather", "allreduce", "pushpull", "bcastreduce",
                 "rotation")
# the collective-budget manifest's trace mesh width (tools/jaxlint/
# trace_targets.NUM_WORKERS) — comm telemetry pricing is exact only there
TRACE_WORKERS = 8


@dataclasses.dataclass(frozen=True)
class KMeansConfig:
    """Reference CLI parity (README.md:148-160: numCentroids, dim, ..., iterations)."""

    num_centroids: int = 10
    dim: int = 100
    iterations: int = 10
    comm: str = "regroupallgather"
    compute_dtype: str = "float32"   # "bfloat16": bf16 matmuls, f32 accumulate
    lane_pad: bool = True   # pad K to an lcm(128, W) multiple and D to a 128
    #   multiple (ops/lane_pack) so the E-step's distance/stats GEMMs and the
    #   (N, K) one-hot run on FULL 128-lane MXU tiles instead of e.g.
    #   100-wide ones (the flagship measured 28% MFU on 100-wide tiles, r5:
    #   ~1.3× left in lane padding) and operand reads stay lane-aligned.
    #   Phantom centroid rows are zero, masked out of every argmin (+inf
    #   score columns — no point can assign to padding) and average to zero;
    #   phantom feature columns are zero (exact no-ops in scores and sums).
    #   Numerics: the wider GEMM lets XLA re-tile the D-reduction, so scores
    #   shift by ulps vs lane_pad=False — a NEAR-TIE assignment can flip and
    #   fork the trajectory (measured: identical to 1.8e-7 for 3 iters, then
    #   one flip; converged cost equal to 7 digits). Same epsilon class as
    #   compute_dtype="bfloat16"'s documented flips. Cross-VARIANT bit
    #   identity is unaffected (every variant shares the padded formulation).
    #   Off: the pre-r6 worker-multiple-only padding, and always the XLA
    #   E-step (ops/distance.py): the fused kernel (ops/kmeans_kernels.py,
    #   chosen by use_kmeans_estep_pallas on TPU) needs the 128-lane store,
    #   whose spare lane also carries its counts.
    quant: Optional[str] = None   # None | "int8" | "bf16": quantize the
    #   stats-table collectives' WIRE format (collectives/quantize.py) with
    #   error-feedback residual carried in the fit scan. The math stays f32
    #   (dequantize-after-transport); trajectories are convergence-
    #   equivalent, NOT bit-identical — quant breaks the cross-variant
    #   bit-identity claim (each variant's wire format differs), and the
    #   tests pin a per-codec tolerance vs the f32 run instead. Unsupported
    #   for bcastreduce (rooted reduce/broadcast are masked psums whose
    #   mask trick defeats per-block scales).


def _fused_estep(points, k_pad: int, cdtype) -> Tuple[bool, bool]:
    """``(whether the E-step over this worker's stored block runs the fused
    kernel, whether interpreted)``: one predicate beside the kernel decides,
    by backend, stored shape and dtype; elsewhere the XLA twin."""
    fused = (cdtype in (None, jnp.bfloat16)
             and kmeans_kernels.use_kmeans_estep_pallas(
                 points.shape[0], points.shape[1], k_pad, points.dtype))
    return fused, fused and jax.default_backend() != "tpu"


class KMeans:
    """Distributed K-means over a HarpSession mesh."""

    def __init__(self, session: HarpSession, config: KMeansConfig):
        if config.comm not in COMM_VARIANTS:
            raise ValueError(f"comm must be one of {COMM_VARIANTS}")
        if config.quant is not None and config.comm == "bcastreduce":
            raise ValueError(
                "quant is not supported for comm='bcastreduce' (rooted "
                "reduce/broadcast lower to masked psums; the mask defeats "
                "per-block quantization scales) — use any other variant")
        self.session = session
        self.config = config
        self._mb_steps = {}   # (budget, cols) -> compiled minibatch step
        self._fit = self._build()

    def _build(self):
        sess, cfg = self.session, self.config
        w = sess.num_workers
        # stat-table partition count: always a worker multiple (Table
        # contract); with lane_pad additionally an MXU-lane multiple, and the
        # feature axis a 128 multiple, so the E-step's score GEMM, one-hot
        # and stats GEMM all run on full 128-lane tiles (ops/lane_pack —
        # phantom centroid rows are masked from every argmin and average to
        # zero, phantom feature columns are exact zero no-ops)
        if cfg.lane_pad:
            k_pad = lane_pack.lane_target(cfg.num_centroids, divisor=w)
            d_pad = lane_pack.round_up(cfg.dim, lane_pack.LANES)
        else:
            k_pad = Table.local(jnp.zeros((cfg.num_centroids, 1)),
                                num_workers=w).num_partitions
            d_pad = cfg.dim
        self._k_pad, self._d_pad = k_pad, d_pad

        cdtype = None if cfg.compute_dtype == "float32" else jnp.dtype(
            cfg.compute_dtype)

        def estep(points, centroids, x_sq_sum=None):
            # centroids carry k_pad rows, valid_k masks the phantoms
            fused, interpret = _fused_estep(points, k_pad, cdtype)
            # runs when jax traces, only: which E-step this program's body runs
            if fused:
                metrics.DEFAULT.count("kmeans.estep.pallas")
                with jax.named_scope("kmeans.estep"):
                    # one pass over the points: Σ‖x‖² comes with it
                    sums, counts, sq = kmeans_kernels.estep_pallas(
                        points, centroids, cdtype, valid_k=cfg.num_centroids,
                        valid_d=cfg.dim, interpret=interpret)
            else:
                metrics.DEFAULT.count("kmeans.estep.xla")
                sums, counts, sq = distance.partial_sums_counts(
                    points, centroids, cdtype, x_sq_sum,
                    valid_k=cfg.num_centroids, valid_d=cfg.dim)
            with jax.named_scope("kmeans.stats"):
                stats = jnp.concatenate([sums, counts[:, None]], axis=1)  # (K, D+1)
            return stats, sq

        @scoped("kmeans.update")
        def average(stats):
            return stats[:, :-1] / jnp.maximum(stats[:, -1:], 1.0)

        @scoped("kmeans.update")
        def total_cost(sq):
            return jax.lax.psum(sq, lax_ops.WORKERS)

        comm = (quantize.CommConfig(quant=cfg.quant) if cfg.quant is not None
                else None)

        def iter_body(centroids, points, x_sq_sum=None, qres=None):
            # centroids: (k_pad, d_pad) — phantom rows ride the collectives
            # (zero counts → average 0) and are trimmed once, at fit_fn exit.
            # qres: error-feedback residual for the quantized wire format,
            # shaped like the stats table (quant only — the f32 programs are
            # structurally untouched, the collective-budget manifest pins
            # them)
            if cfg.comm == "rotation":
                new_c, sq, qres = self._rotation_iter(
                    points, centroids, k_pad, w, x_sq_sum, cdtype, comm, qres)
                cost = total_cost(sq)
                return new_c, cost, qres
            stats, sq = estep(points, centroids, x_sq_sum)
            local = Table.local(stats, num_workers=w, name="cen")
            if cfg.comm == "regroupallgather":
                # KMeansCollectiveMapper :168-189: regroup → average own block → allgather
                if comm is None:
                    g = table_ops.regroup(local)
                else:
                    g, qres = table_ops.regroup(local, comm=comm,
                                                residual=qres)
                own = average(g.data)
                new_c = lax_ops.allgather(own, comm=comm)
            elif cfg.comm == "allreduce":
                if comm is None:
                    full = table_ops.allreduce(local)
                else:
                    full, qres = table_ops.allreduce(local, comm=comm,
                                                     residual=qres)
                new_c = average(full.data)
            elif cfg.comm == "pushpull":
                zero = Table.sharded(
                    jnp.zeros((k_pad // w,) + stats.shape[1:]), num_workers=w)
                if comm is None:
                    g = table_ops.push(local, zero)
                else:
                    g, qres = table_ops.push(local, zero, comm=comm,
                                             residual=qres)
                pulled = table_ops.pull(g, comm=comm)
                new_c = average(pulled.data)
            else:  # bcastreduce (quant rejected at __init__)
                red = table_ops.reduce(local, root=0)
                own = average(red.data)
                new_c = table_ops.broadcast(
                    Table.local(own, num_workers=w), root=0).data
            cost = total_cost(sq)
            return new_c, cost, qres

        def fit_fn(points, centroids0):
            telemetry.traced("kmeans.fit")   # runs when jax traces, only
            # points arrive feature-padded from prepare(); pad again here so
            # a raw fit_prepared(points, ·) call stays correct (no-op on
            # prepared arrays). Centroids pad to the full (k_pad, d_pad)
            # carry once per program.
            points = lane_pack.pad_cols(points, d_pad)
            cen = lane_pack.pad_rows(
                lane_pack.pad_cols(centroids0, d_pad), k_pad)
            # Σ‖x‖² is iteration-invariant: where the E-step is the XLA twin
            # (and in the rotation variant) hoist it, so the hot loop reads
            # the point block exactly twice per iteration (the two MXU
            # matmuls); the fused kernel reads a tile once for everything
            x_sq_sum = None
            if cfg.comm == "rotation" or not _fused_estep(
                    points, k_pad, cdtype)[0]:
                with jax.named_scope("kmeans.norms"):
                    pf = points.astype(jnp.float32)
                    x_sq_sum = jnp.sum(pf * pf)

            # the loop's own plumbing (the counter, the stacking of the
            # per-iteration cost) reads under the M-step's name; what the
            # body names itself keeps its deeper name
            loop_scope = jax.named_scope("kmeans.update")
            if comm is None:
                def scan_body(c, _):
                    new_c, cost, _ = iter_body(c, points, x_sq_sum)
                    return new_c, cost

                with loop_scope:
                    cen, costs = jax.lax.scan(scan_body, cen, None,
                                              length=cfg.iterations)
            else:
                # EF residual rides the fit carry: stats-table shaped f32
                qres0 = jnp.zeros((k_pad, d_pad + 1), jnp.float32)

                def scan_body_q(carry, _):
                    c, qres = carry
                    new_c, cost, qres = iter_body(c, points, x_sq_sum, qres)
                    return (new_c, qres), cost

                with loop_scope:
                    (cen, _), costs = jax.lax.scan(
                        scan_body_q, (cen, qres0), None,
                        length=cfg.iterations)
            return cen[: cfg.num_centroids, : cfg.dim], costs

        return sess.spmd(fit_fn, in_specs=(sess.shard(), sess.replicate()),
                         out_specs=(sess.replicate(), sess.replicate()))

    def _rotation_iter(self, points, cen_pad, k_pad, w, x_sq_sum, cdtype,
                       comm=None, qres=None):
        """ml/java kmeans/rotation: centroid blocks circulate the ring; each worker
        scores its points against the resident block, tracking the block-local best;
        after a full cycle the global argmin resolves and stats are aggregated.

        Uses the SAME score formulation (‖c‖² − 2x·c) as every other variant so
        argmin tie-breaking is formulation-identical — the module's cross-variant
        bit-identity claim depends on it. ``cen_pad`` arrives already padded
        to (k_pad, d_pad) (lane_pack padding is part of the carry); phantom
        rows (global id >= num_centroids) are zero-filled and masked with
        +inf AFTER the score matrix is computed."""
        cfg = self.config
        block = k_pad // w
        my = jax.lax.dynamic_slice_in_dim(
            cen_pad, lax_ops.worker_id() * block, block, axis=0)

        def body(carry, cen_block, t):
            best_d, best_id = carry
            d = distance.pairwise_scores(points, cen_block, cdtype)  # (N, block)
            # global centroid id of each column: owner shifts with rotation step
            src = (lax_ops.worker_id() - t) % w
            col_gid = src * block + jnp.arange(block)
            d = jnp.where(col_gid[None, :] < cfg.num_centroids, d, jnp.inf)
            dmin = jnp.min(d, axis=1)
            darg = jnp.argmin(d, axis=1)
            gid = src * block + darg
            # tie-break on global id so ties resolve like jnp.argmin's
            # lowest-index rule in the non-rotation variants (bit-identity)
            upd = (dmin < best_d) | ((dmin == best_d) & (gid < best_id))
            return (jnp.where(upd, dmin, best_d),
                    jnp.where(upd, gid, best_id)), cen_block

        init = (jnp.full((points.shape[0],), jnp.inf), jnp.zeros(points.shape[0], jnp.int32))
        (best_d, best_id), my = rotation.rotate_scan(
            scoped("kmeans.scores")(body), init, my, w)
        # the same stats product as every other variant's E-step (the
        # cross-variant bit identity depends on it), over the points as stored
        sums, counts = distance.onehot_stats(points, best_id, k_pad,
                                             valid_d=cfg.dim)
        with jax.named_scope("kmeans.stats"):
            stats = jnp.concatenate([sums, counts[:, None]], axis=1)
        if comm is None:
            full = table_ops.allreduce(Table.local(stats, num_workers=w))
        else:
            # quantized stats allreduce; the circulating centroid blocks stay
            # f32 (they feed every argmin — a lossy block would perturb
            # assignments each hop, where the stats error is one EF'd
            # correction per iteration)
            full, qres = table_ops.allreduce(
                Table.local(stats, num_workers=w), comm=comm, residual=qres)
        data = full.data
        # keep the full padded table in the carry (phantom rows average to
        # zero); fit_fn trims once at exit
        with jax.named_scope("kmeans.update"):
            new_c = data[:, :-1] / jnp.maximum(data[:, -1:], 1.0)
        # best_d holds scores; true sq-distance cost adds the Σ‖x‖² constant
        with jax.named_scope("kmeans.scores"):
            return new_c, jnp.sum(best_d) + x_sq_sum, qres

    def comm_scale(self) -> float:
        """Ratio of this model's padded stat-table elements to the budget
        manifest's traced tier-1 shape (k=8, d=16, w=8, lane_pad default):
        every K-means collective moves slices of the (k_pad, d_pad+1) f32
        table, so the manifest's ``bytes_per_step`` times this ratio prices
        the job's true wire volume (the few-byte scalar-cost psum rides
        unscaled — noise). EXACT only at ``num_workers == TRACE_WORKERS``:
        the sharded variants' operands (a 1/w table shard per
        reduce_scatter/all_gather) also depend on w, which this ratio does
        not capture — fit_checkpointed passes exact= accordingly. Consumed
        by telemetry.comm_ledger."""
        ref_k = lane_pack.lane_target(8, divisor=TRACE_WORKERS)
        ref_d = lane_pack.round_up(16, lane_pack.LANES)
        return (self._k_pad * (self._d_pad + 1)) / (ref_k * (ref_d + 1))

    def fit(self, points: np.ndarray, centroids0: np.ndarray
            ) -> Tuple[jax.Array, jax.Array]:
        """Run the full training; returns (final centroids, per-iteration cost).

        ``points`` rows are split across workers (pad to a multiple of num_workers
        with jnp.inf rows excluded by distance? — instead require divisibility, the
        loaders pad at ingest).
        """
        pts, cen = self.prepare(points, centroids0)
        return self.fit_prepared(pts, cen)

    def prepare(self, points, centroids0):
        """Place data on the mesh once; pair with :meth:`fit_prepared` to keep
        host→device transfer out of timed regions.

        With ``compute_dtype="bfloat16"`` the point block is STORED in bf16 —
        the E-step is HBM-bound on reading the points (twice per iteration), so
        halving the bytes is the dominant lever on v5e; norms and all
        accumulations stay f32.

        With ``lane_pad`` (default) the stored block is feature-padded to a
        128 multiple ONCE here, so every iteration's GEMM operands are
        lane-aligned with no per-read re-tiling (zero columns are exact
        no-ops in scores and sums)."""
        n = points.shape[0]
        if n % self.session.num_workers:
            raise ValueError(
                f"num points {n} must divide over {self.session.num_workers} workers"
                " (pad at ingest)")
        with telemetry.phase("kmeans.prepare"):
            dtype = (jnp.bfloat16 if self.config.compute_dtype == "bfloat16"
                     else jnp.float32)
            # cast and pad on the HOST and scatter from the host array: each
            # worker's rows go straight to its own device. Staging through
            # jnp.asarray first would land the whole block on device 0 — the
            # one chip that then has to hold W workers' data
            points = np.asarray(points, dtype)
            if self.config.lane_pad and points.shape[1] < self._d_pad:
                points = np.pad(
                    points, ((0, 0), (0, self._d_pad - points.shape[1])))
            pts = self.session.scatter(points)
            cen = self.session.replicate_put(
                np.asarray(centroids0, np.float32))
        return pts, cen

    def fit_prepared(self, pts: jax.Array, cen: jax.Array):
        """Run training on already-placed device arrays (no H2D in the hot
        path). Returns at the enqueue: the caller's fetch waits for the run."""
        with telemetry.phase("kmeans.call"):
            with telemetry.phase("step.dispatch"):
                out = self._fit(pts, cen)
            telemetry.record_program("kmeans.fit", self._fit, (pts, cen))
        return out

    def fit_from_stream(self, chunks, centroids0, total_rows: int,
                        *, metrics=None) -> Tuple[jax.Array, jax.Array]:
        """Stream-fed training (io/pipeline.StreamLoader): assemble the
        chunk stream into the SAME row-sharded, feature-padded device block
        :meth:`prepare` would place for the identical data, then run the
        unchanged compiled fit — BITWISE-equal to ``fit(points, centroids0)``
        when the stream carries the same rows in one pass order
        (``assemble_stream`` holds the placement contract; chunk N+1's
        parse + H2D overlaps chunk N's device scatter when the stream rides
        a ``DevicePrefetcher``).

        ``total_rows`` must divide the mesh — truncate at ingest, exactly
        like :func:`loaders.truncate_to_workers`; streamed rows past it are
        masked off on device.
        """
        from harp_tpu.io import pipeline as io_pipeline
        from harp_tpu.utils.metrics import Metrics

        metrics = metrics if metrics is not None else Metrics()
        pts = io_pipeline.assemble_stream(
            self.session, chunks, total_rows, self._d_pad,
            ("bfloat16" if self.config.compute_dtype == "bfloat16"
             else "float32"), metrics=metrics)
        cen = self.session.replicate_put(
            jnp.asarray(np.asarray(centroids0), jnp.float32))
        with metrics.timer("ingest.compute"):
            out = self._fit(pts, cen)
            jax.block_until_ready(out)
        return out

    def _minibatch_step(self, budget: int, cols: int):
        """Compile (and cache per chunk shape) the one-chunk minibatch
        E-step + online M-step program fit_stream_minibatch folds over."""
        key = (budget, cols)
        if key in self._mb_steps:
            return self._mb_steps[key]
        sess, cfg = self.session, self.config
        w = sess.num_workers
        if budget % w:
            raise ValueError(
                f"chunk budget {budget} must divide over {w} workers "
                f"(StreamLoader chunk_rows)")
        k_pad, d_pad = self._k_pad, self._d_pad
        cdtype = None if cfg.compute_dtype == "float32" else jnp.dtype(
            cfg.compute_dtype)

        def step_fn(pts, mask, cen, counts):
            x = lane_pack.pad_cols(pts, d_pad)
            scores = distance.pairwise_scores(x, cen, cdtype)   # (b, k_pad)
            scores = jnp.where(
                jnp.arange(k_pad)[None, :] < cfg.num_centroids,
                scores, jnp.inf)
            onehot = jax.nn.one_hot(jnp.argmin(scores, axis=1), k_pad,
                                    dtype=jnp.float32) * mask[:, None]
            sums = jax.lax.dot_general(
                onehot, x.astype(jnp.float32), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            cnt = jnp.sum(onehot, axis=0)
            sums = jax.lax.psum(sums, lax_ops.WORKERS)
            cnt = jax.lax.psum(cnt, lax_ops.WORKERS)
            new_counts = counts + cnt
            # MacQueen online mean: fold this chunk's sums into the running
            # per-centroid mean weighted by cumulative counts
            new_cen = jnp.where(
                new_counts[:, None] > 0,
                (counts[:, None] * cen + sums)
                / jnp.maximum(new_counts[:, None], 1.0),
                cen)
            xf = x.astype(jnp.float32)
            sq = (jnp.sum(jnp.min(scores, axis=1) * mask)
                  + jnp.sum((xf * xf) * mask[:, None]))
            cost = jax.lax.psum(sq, lax_ops.WORKERS)
            return new_cen, new_counts, cost

        fn = sess.spmd(
            step_fn,
            in_specs=(sess.shard(), sess.shard(), sess.replicate(),
                      sess.replicate()),
            out_specs=(sess.replicate(), sess.replicate(),
                       sess.replicate()))
        self._mb_steps[key] = fn
        return fn

    def fit_stream_minibatch(self, chunks, centroids0
                             ) -> Tuple[np.ndarray, np.ndarray]:
        """True streaming path for unbounded chunk streams (the DrJAX-style
        minibatch discipline, PAPERS.md arXiv:2403.07128): one E-step per
        chunk against the CURRENT centroids, folded into a running mean
        weighted by cumulative per-centroid counts.  Chunk order IS the
        algorithm here, so this is convergence-equivalent — NOT bitwise —
        to the batch fit; use :meth:`fit_from_stream` when the stream is a
        finite dataset and bitwise parity matters.  Returns
        (centroids (k, d), per-chunk cost trace).
        """
        sess, cfg = self.session, self.config
        cen = sess.replicate_put(lane_pack.pad_rows(lane_pack.pad_cols(
            jnp.asarray(np.asarray(centroids0), jnp.float32),
            self._d_pad), self._k_pad))
        counts = sess.replicate_put(jnp.zeros((self._k_pad,), jnp.float32))
        costs = []
        for ch in chunks:
            data = ch.data
            budget, cols = int(np.shape(data)[0]), int(np.shape(data)[1])
            step = self._minibatch_step(budget, cols)
            mask = (np.arange(budget) < ch.rows).astype(np.float32)
            pts = data if isinstance(data, jax.Array) else sess.scatter(
                np.ascontiguousarray(data, np.float32))
            cen, counts, cost = step(pts, sess.scatter(mask), cen, counts)
            costs.append(cost)
        cen_h = np.asarray(cen)[:cfg.num_centroids, :cfg.dim]
        cost_h = (np.asarray(jnp.stack(costs)) if costs
                  else np.zeros(0, np.float32))
        return cen_h, cost_h

    def fit_checkpointed(self, pts: jax.Array, cen: jax.Array, checkpointer,
                         save_every: int = 1,
                         iterations: Optional[int] = None):
        """Train with periodic centroid checkpointing and automatic resume
        (reference: KMUtil.storeCentroids saved only the FINAL model; resume
        is a capability upgrade, SURVEY §5).

        Runs ``save_every``-iteration compiled chunks; each chunk boundary
        saves the replicated centroids. If the checkpoint directory already
        holds state, training resumes from the newest iteration. Lloyd
        iterations are deterministic given (points, centroids), and the
        chunked program runs the identical per-iteration math as the full
        scan, so interrupted + resumed trajectories are bitwise identical to
        uninterrupted ones. Returns (centroids, costs-for-run-iterations,
        start_iteration).

        World-size-agnostic: the centroid table is REPLICATED, so a
        checkpoint written by a W-worker gang restores EXACTLY into a
        W' != W gang (the supervisor's shrink-relaunch path) — the
        resume-across-resize reshard (collectives.reshard) is the IDENTITY
        for replicated leaves (every worker already holds the full table;
        the new world replicates it at placement), so K-means pays zero
        redistribution rounds where SGD-MF/LDA pay their bounded
        all_to_all schedule. Only the point shards re-split, which
        prepare() does per run. The manifest meta records the writing
        world for the journal/debugging."""
        from harp_tpu.parallel import faults
        from harp_tpu.utils import checkpoint as ckpt_lib

        total = iterations if iterations is not None else \
            self.config.iterations
        start = 0
        # verified resume, single read: a corrupt/torn newest checkpoint is
        # skipped in favor of the previous step (manifest checksums) instead
        # of crashing the relaunch
        resume, saved = checkpointer.restore_latest_valid(
            like={"centroids": np.zeros(cen.shape, cen.dtype)})
        if resume is not None:
            start = resume
            if start > total:
                raise ValueError(
                    f"checkpoint at iteration {start} exceeds the requested "
                    f"{total} iterations (pass a fresh directory or a larger "
                    f"budget)")
            cen = self.session.replicate_put(
                jnp.asarray(saved["centroids"]))
        chunk_fits = {}
        costs = []
        # telemetry (harp_tpu.telemetry): step events + manifest-priced comm
        # volume at the chunk boundaries below — the ONLY host syncs are the
        # np.asarray(cost) fetches that were already here; None when off.
        # Pricing is exact only at the manifest's traced worker count: the
        # sharded variants' per-step operands (reduce_scatter/all_gather
        # shards) depend on w, not just on the table elements comm_scale
        # rescales (comm_ledger.ledger_for docstring)
        ledger = telemetry.ledger_for(
            "kmeans", comm=self.config.comm, quant=self.config.quant,
            scale=self.comm_scale(),
            exact=self.session.num_workers == TRACE_WORKERS)
        it = start
        while it < total:
            # iteration-boundary fault hook (parallel.faults): a scripted
            # crash/hang lands here, where a real preemption is survivable
            faults.fire(it + 1, checkpointer)
            chunk = min(save_every, total - it)
            if chunk not in chunk_fits:
                chunk_fits[chunk] = KMeans(
                    self.session,
                    dataclasses.replace(self.config, iterations=chunk))._fit
            with telemetry.phase("kmeans.call") as call:
                with telemetry.phase("step.dispatch"):
                    cen, cost = chunk_fits[chunk](pts, cen)
                with telemetry.phase("step.fetch"):
                    chunk_costs = np.asarray(cost).tolist()
            costs.extend(chunk_costs)
            telemetry.record_chunk("kmeans", start=it, losses=chunk_costs,
                                   wall_s=call.elapsed(), ledger=ledger,
                                   extra={"comm": self.config.comm})
            it += chunk
            with telemetry.phase("kmeans.checkpoint"):
                save_state = {"centroids": np.asarray(cen)}
                checkpointer.save(it, save_state, meta=ckpt_lib.state_meta(
                    save_state, model="kmeans",
                    world=self.session.num_workers))
        if hasattr(checkpointer, "wait"):
            checkpointer.wait()       # surface a failed async final write
        return cen, np.asarray(costs, np.float32), start


def numpy_reference(points, cen, iters):
    """Plain-numpy Lloyd iterations for convergence parity tests."""
    for _ in range(iters):
        d = ((points[:, None, :] - cen[None, :, :]) ** 2).sum(-1)
        a = d.argmin(1)
        new = np.zeros_like(cen)
        cnt = np.zeros(cen.shape[0])
        np.add.at(new, a, points)
        np.add.at(cnt, a, 1)
        cen = new / np.maximum(cnt[:, None], 1.0)
    return cen
