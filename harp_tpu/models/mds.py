"""WDA-MDS — weighted multidimensional scaling by SMACOF majorization under
deterministic annealing.

Reference parity: ml/java wdamds (WDAMDSMapper.java:35 — WDA-SMACOF:
iterative allgather+allreduce matrix ops over BC/stress calc tasks, and the
distributed conjugate-gradient solve of the weighted Guttman transform,
WDAMDSMapper.java:585 ``conjugateGradient``, cgIter config :86, iteration
accounting :326-355; 2,883 LoC of partitioned matrix arithmetic). Ruan and
Fox, IEEE eScience 2013 (WDA-SMACOF); the annealing: Bae, Qiu and Fox, IEEE
eScience 2010.

One iteration at temperature T, target dimension L, symmetric weights w
(w_ii = 0), over the target distances ``delta``::

    dhat_ij = max(delta_ij - T sqrt(2L), 0)       d_ij = |x_i - x_j|
    B_ij = -w_ij dhat_ij / d_ij  (0 where d_ij = 0)    B_ii = -sum_j B_ij
    V_ij = -w_ij                                  V_ii = sum_j w_ij
    solve  V X+ = B(X) X  by cg_iters steps of CG warm-started at X
    sigma = sum w (delta - d)^2 / sum w delta^2   (against the raw distances)

Schedule: ``T_0 = alpha max(delta) / sqrt(2L)``, the maximum over the pairs
that have a weight (a distance marked missing sets no temperature: above the
largest weighted distance every dhat is 0, B(X) = 0, and the embedding
collapses onto what CG's rounding leaves of it); iteration i, counted from
the job's start and carried with X, runs at ``T_0 alpha^floor(i /
level_iterations)``; once ``T sqrt(2L) < t_floor max(delta)``, T = 0 and
stays. (The sources lower T when the stress at that T stops moving by a
threshold; a fixed count a temperature is one scan with no data-dependent
branch, and two float32 implementations cannot part over a near tie.)

TPU-native: the rows of ``delta`` and ``w`` are sharded and stay as they
were placed; the embedding is carried replicated and TRANSPOSED, ``(8, N)``
(a point is a lane: ``ops/mds_kernels.py``). An iteration is one fused pass
over both matrices for B(X)X and the stress, then ``cg_iters + 1`` passes
over ``w`` alone, the matvecs of a distributed CG in which every inner
product is one psum and every direction one allgather — the reference's
allreduce-per-iteration CG. ``iterations`` of them are one compiled scan, a
call; a job is calls until the schedule has ended
(:func:`schedule_iterations`). Weights of which every one is exact in
bfloat16 (0/1 masks, small dyadic confidences) are stored so, others in
float32: ``prepare`` decides by the data it is handed.

V is PSD with nullspace span{1}; B(X)X is orthogonal to 1, so CG iterates
stay in the solvable subspace and the translation-invariant embedding is
unaffected by any residual nullspace component in the warm start (the
previous iteration's embedding, which makes uniform-weight problems converge
in one CG step — V acts as n·centering there). Host phases: ``mds.prepare``
(its one wait under ``session.fetch``); ``mds.call`` with dispatch and fetch.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu import telemetry
from harp_tpu.collectives import lax_ops
from harp_tpu.ops import mds_kernels as mk
from harp_tpu.parallel.mesh import WORKERS
from harp_tpu.session import HarpSession
from harp_tpu.utils import metrics

_HOST_BLOCK = 2048          # rows of a host pass over an N x N matrix


@dataclasses.dataclass(frozen=True)
class MDSConfig:
    dim: int = 2                # embedding dimensionality (reference: targetDim)
    iterations: int = 50        # SMACOF iterations of one call
    cg_iters: int = 10          # CG steps per Guttman solve (reference: cgIter)
    alpha: float = 0.95         # cooling factor between temperatures
    level_iterations: int = 4   # iterations at one temperature
    t_floor: float = 0.02       # T sqrt(2L) under this share of max(delta): 0

    def __post_init__(self):
        if not (0.0 < self.alpha < 1.0 and 0.0 < self.t_floor < 1.0):
            raise ValueError("MDSConfig: alpha and t_floor lie in (0, 1)")
        if min(self.dim, self.iterations, self.level_iterations) < 1:
            raise ValueError("MDSConfig: dim, iterations and "
                             "level_iterations are at least 1")
        if self.cg_iters < 0:
            raise ValueError("MDSConfig: cg_iters is at least 0")


def schedule(cfg: MDSConfig) -> np.ndarray:
    """``T sqrt(2L) / max(delta)`` by temperature level, float32: ``alpha^(k
    + 1)`` while that is at least ``t_floor``, then 0, the last level."""
    shares, share = [], cfg.alpha
    while share >= cfg.t_floor:
        shares.append(share)
        share *= cfg.alpha
    return np.asarray(shares + [0.0], np.float32)


def schedule_iterations(cfg: MDSConfig) -> int:
    """Iterations of one job: every temperature's, T = 0 included."""
    return len(schedule(cfg)) * cfg.level_iterations


def distance_matrix(points: np.ndarray) -> np.ndarray:
    """Euclidean distances between the rows of ``points``, float32, in row
    blocks on the host (what a distance file holds; diagonal 0). The block
    is part of the file: a BLAS product's last bit follows its shape (on the
    chip's host 1 % of the cells differ by an ulp between blocks of 256 and
    of 2,048 rows, and two embeddings from the two files lie 20 times
    further apart than two from one: PERF.md, Findings, PR 34, 5)."""
    pts = np.ascontiguousarray(points, np.float32)
    sq = np.einsum("ij,ij->i", pts, pts)
    out = np.empty((len(pts), len(pts)), np.float32)
    for lo in range(0, len(pts), _HOST_BLOCK):
        blk = out[lo:lo + _HOST_BLOCK]
        np.matmul(pts[lo:lo + _HOST_BLOCK], pts.T, out=blk)
        blk *= -2.0
        blk += sq[lo:lo + _HOST_BLOCK, None]
        blk += sq[None, :]
        np.sqrt(np.maximum(blk, 0.0, out=blk), out=blk)
    np.fill_diagonal(out, 0.0)
    return out


def _stored_weights(weights: np.ndarray) -> np.ndarray:
    """``weights`` as the device keeps them: bfloat16 where every one is
    exact there (handed over so, or the lower half of each float32 is 0),
    else float32 as handed over. Row blocks over the two halves of a float32
    as they lie: the one N x N array made is the bfloat16 one."""
    if weights.dtype == jnp.bfloat16:
        return np.ascontiguousarray(weights)
    w = np.ascontiguousarray(weights, np.float32)
    halves = w.view(np.uint16).reshape(*w.shape, 2)
    low = 0 if sys.byteorder == "little" else 1
    out = np.empty(w.shape, jnp.bfloat16)
    for lo in range(0, len(w), _HOST_BLOCK):
        blk = halves[lo:lo + _HOST_BLOCK]
        if blk[..., low].any():
            return w
        out[lo:lo + _HOST_BLOCK].view(np.uint16)[...] = blk[..., 1 - low]
    return out


@dataclasses.dataclass(frozen=True)
class _Geometry:
    """One worker's ``(rows, n)`` of both matrices: the kernels' tiles
    (``row_tile`` 0: the jax.numpy passes, in ``block``s)."""
    rows: int
    n: int
    weights_dtype: str
    row_tile: int
    bc_col_tile: int
    mv_col_tile: int
    block: Tuple[int, int]
    interpret: bool = False     # the kernels off the TPU (tests only)


def _geometry(rows: int, n: int, dim: int, weights_dtype) -> _Geometry:
    """The kernels' tiles where the block takes them (one predicate beside
    the kernels decides), else row blocks."""
    w_bytes = jnp.dtype(weights_dtype).itemsize
    fused = mk.use_mds_pallas(rows, n, dim, w_bytes)
    return _Geometry(
        rows, n, jnp.dtype(weights_dtype).name,
        *(mk.tiles(rows, n, w_bytes) if fused else (0, 0, 0)),
        block=mk.row_blocks(rows, n),
        interpret=fused and jax.default_backend() != "tpu")


def _stored(xt, store: int):
    """``xt`` (8, n) at ``store`` columns, zeros past its own."""
    return jnp.pad(xt, ((0, 0), (0, store - xt.shape[1])))


def _bc(d_block, w_block, mine_t, xt, shift, dim: int, g: _Geometry):
    """This worker's rows of B(X)X, transposed, and of the raw stress."""
    # runs when jax traces, only: which pass this program's iterations run
    if g.row_tile:
        metrics.DEFAULT.count("mds.bc.pallas")
        return mk.bc_pallas(
            d_block, w_block, mine_t.T, _stored(xt, mk.store(g.n, g.bc_col_tile)),
            shift, dim, g.row_tile, g.bc_col_tile, interpret=g.interpret)
    metrics.DEFAULT.count("mds.bc.xla")
    return mk.bc_xla(d_block, w_block, mine_t.T, xt, shift, dim, g.block)


def _weighted_sum(w_block, pt, g: _Geometry):
    """``sum_j w_ij p_j`` over this worker's rows, transposed: (8, rows)."""
    if g.row_tile:
        metrics.DEFAULT.count("mds.matvec.pallas")
        p = mk.matvec_operand(_stored(pt, mk.store(g.n, g.mv_col_tile)),
                              w_block.dtype)
        return mk.matvec_pallas(w_block, p, g.row_tile, g.mv_col_tile,
                                interpret=g.interpret)
    metrics.DEFAULT.count("mds.matvec.xla")
    return mk.matvec_xla(w_block, mk.matvec_operand(pt, w_block.dtype),
                         g.block)


def _all_columns(a_loc, axis_name: str):
    """Every worker's ``(8, rows)`` side by side: ``(8, n)``."""
    parts = lax_ops.allgather(a_loc, axis_name, tiled=False)
    return jnp.moveaxis(parts, 0, 1).reshape(a_loc.shape[0], -1)


def _cg_solve(t_loc, z_loc, z_all, v_diag, w_block, g: _Geometry,
              cg_iters: int, axis_name: str):
    """Distributed CG on V z = t, warm-started at ``z_loc`` (``z_all`` the
    same, every worker's), all columns advanced together (per-column
    alpha/beta). One allgather + two psums per step —
    WDAMDSMapper.conjugateGradient's collective count. Vectors are
    transposed, a column of the embedding a row."""

    def vmatvec(p_loc, p_all):
        # the products' precision is load-bearing: the TPU's default
        # float32 matmul truncates operands to one bfloat16 term, and CG is
        # exactly the algorithm that cannot take it — near convergence p'Vp
        # lives at noise scale, a truncation sign-flip sends alpha through
        # the 1e-20 guard and the iterate to overflow (measured on the real
        # chip: stress NaN at iteration 1; the CPU-mesh tests never see the
        # default-precision path). ops/mds_kernels.py: three exact bfloat16
        # terms beside bfloat16 weights, HIGHEST beside float32 ones.
        return v_diag[None, :] * p_loc - _weighted_sum(w_block, p_all, g)

    def dots(a, b):
        return jax.lax.psum(jnp.sum(a * b, axis=1, keepdims=True), axis_name)

    def centred(r):
        """``r`` less its mean over all points, and the squared norm of
        that, from ONE psum. V's nullspace is span{1}: the part of a
        residual along it is rounding (B(X)X and V p both sum to 0 over the
        points), p'Vp cannot see it, and a step length taken from a norm
        that holds it sends the iterate off along 1 — to overflow once a
        solve has converged early and CG runs on on its own noise."""
        sums = jax.lax.psum(jnp.concatenate(
            [jnp.sum(r * r, axis=1, keepdims=True),
             jnp.sum(r, axis=1, keepdims=True)], axis=1), axis_name)
        mean = sums[:, 1:] / g.n
        return r - mean, jnp.maximum(sums[:, :1] - g.n * mean * mean, 0.0)

    z = z_loc
    r, rs = centred(t_loc - vmatvec(z, z_all))       # rs: (8, 1)
    p = r
    # convergence floor anchored to the RHS scale (NOT the initial
    # residual — a near-exact warm start makes that itself noise-sized)
    ts = dots(t_loc, t_loc)

    def body(carry, _):
        z, r, p, rs = carry
        # freeze converged columns (residual at the f32 noise floor):
        # running CG past convergence makes beta ~ 1+noise and p grow
        # exponentially — the fixed-iteration analog of the reference
        # CG's tolerance test
        active = rs > 1e-10 * jnp.maximum(ts, 1e-20)
        vp = vmatvec(p, _all_columns(p, axis_name))
        pvp = dots(p, vp)
        alpha = jnp.where(active, rs / jnp.maximum(pvp, 1e-20), 0.0)
        z = z + alpha * p
        r, rs_new = centred(r - alpha * vp)
        beta = jnp.where(active, rs_new / jnp.maximum(rs, 1e-20), 0.0)
        p = r + beta * p
        return (z, r, p, rs_new), None

    (z, _, _, _), _ = jax.lax.scan(body, (z, r, p, rs), None, length=cg_iters)
    return z


def _train(d_block, w_block, v_diag, scales, xt, count, g: _Geometry,
           cfg: MDSConfig, axis_name: str = WORKERS):
    """``cfg.iterations`` iterations from the carried embedding ``xt`` (8, n)
    and iteration count. ``d_block`` / ``w_block``: this worker's rows of
    both matrices (w's diagonal zero), ``v_diag`` its rows of V's diagonal,
    ``scales`` = (sum w delta^2, max weighted delta). Returns the carry and each
    iteration's normalised stress, taken of the embedding it started from."""
    telemetry.traced("mds")                # runs when jax traces, only
    metrics.DEFAULT.count("mds.cg.steps", cfg.cg_iters)
    shares = jnp.asarray(schedule(cfg))
    lo = lax_ops.worker_id(axis_name) * g.rows

    def step(carry, _):
        xt, count = carry
        with jax.named_scope("mds.anneal"):
            level = jnp.minimum(count // cfg.level_iterations,
                                len(shares) - 1)
            shift = shares[level] * scales[1]        # T sqrt(2L)
            mine_t = jax.lax.dynamic_slice_in_dim(xt, lo, g.rows, 1)
        with jax.named_scope("mds.bc"):
            t_loc, stress = _bc(d_block, w_block, mine_t, xt, shift,
                                cfg.dim, g)
            sigma = jax.lax.psum(jnp.sum(stress), axis_name) / scales[0]
        with jax.named_scope("mds.cg"):
            # weighted Guttman transform: V X+ = B(X) X, warm-started at
            # the current embedding (WDAMDSMapper.java:585)
            new = _cg_solve(t_loc, mine_t, xt, v_diag, w_block, g,
                            cfg.cg_iters, axis_name)
            new = _all_columns(new, axis_name)
        return (new, count + 1), sigma

    # the schedule's loop: what the scan itself adds (the carry, the curve's
    # stacking) stands under mds.anneal, the passes under their own names
    with jax.named_scope("mds.anneal"):
        (xt, count), sigma = jax.lax.scan(step, (xt, count), None,
                                          length=cfg.iterations)
    return xt, count, sigma


def _normalise(d_block, w_block, axis_name: str = WORKERS):
    """Once a job, on the device: w with its diagonal zeroed (in place: the
    caller donates it), V's diagonal, and (sum w delta^2, max delta over the
    pairs that have a weight)."""
    rows, n = w_block.shape
    mine = lax_ops.worker_id(axis_name) * rows + jnp.arange(rows)
    w_block = jnp.where(mine[:, None] == jnp.arange(n)[None, :],
                        jnp.zeros((), w_block.dtype), w_block)
    w32 = w_block.astype(jnp.float32)
    v_diag = jnp.sum(w32, axis=1)
    norm = jax.lax.psum(jnp.sum(w32 * d_block * d_block), axis_name)
    widest = jax.lax.pmax(jnp.max(jnp.where(w32 > 0, d_block, 0.0)), axis_name)
    return w_block, v_diag, jnp.stack([norm, widest])


class WDAMDS:
    """Distributed WDA-SMACOF MDS (wdamds parity: the annealing schedule and
    the weighted V CG solve)."""

    def __init__(self, session: HarpSession, config: MDSConfig):
        self.session = session
        self.config = config
        self._fns = {}
        self.last_layout_stats: dict = {}
        self._shares = schedule(config)
        self._max_delta = 0.0

    def prepare(self, dist_matrix: np.ndarray, weights: np.ndarray = None,
                seed: int = 0):
        """Place the (N, N) matrices on the mesh ONCE, as a distance file and
        a weight file give them (symmetric; the diagonal is zeroed here);
        returns an opaque state for :meth:`train_prepared` /
        :meth:`fit_prepared`. The last two entries of ``state[1]`` are the
        carry: the embedding (transposed, ``(8, N)``; N(0, 1) from ``seed``,
        centred) and the iteration count, 0."""
        with telemetry.phase("mds.prepare"):
            return self._prepare(dist_matrix, weights, seed)

    def _prepare(self, dist_matrix, weights, seed: int):
        sess, cfg = self.session, self.config
        n = dist_matrix.shape[0]
        if dist_matrix.shape != (n, n) or (
                weights is not None and weights.shape != (n, n)):
            raise ValueError("WDA-MDS takes square (N, N) matrices")
        if n % sess.num_workers:
            raise ValueError(f"N={n} must divide over {sess.num_workers} workers")
        d_dev = sess.scatter(np.ascontiguousarray(dist_matrix, np.float32))
        if weights is None:
            w_dev = sess.run(
                lambda: jnp.ones((n // sess.num_workers, n), jnp.bfloat16),
                in_specs=(), out_specs=sess.shard())
        else:
            w_dev = sess.scatter(_stored_weights(weights))
        key = self._program(n, w_dev.dtype)
        geom = key[1]
        w_dev, v_diag, scales = sess.run(
            _normalise, d_dev, w_dev, in_specs=(sess.shard(), sess.shard()),
            out_specs=(sess.shard(), sess.shard(), sess.replicate()),
            donate_argnums=(1,))
        with telemetry.phase("session.fetch"):    # prepare's one wait
            self._max_delta = float(np.asarray(scales)[1])
        self.last_layout_stats = {
            "row_tile": geom.row_tile, "weights_dtype": geom.weights_dtype,
            "resident_bytes": n * n * (4 + w_dev.dtype.itemsize),
            "kernel": "pallas" if geom.row_tile else "xla",
        }
        rng = np.random.default_rng(seed)
        x0 = rng.standard_normal((n, cfg.dim)).astype(np.float32)
        x0 -= x0.mean(axis=0)        # start in V's solvable subspace
        xt0 = np.zeros((mk.DIM_PAD, n), np.float32)
        xt0[:cfg.dim] = x0.T
        return key, (d_dev, w_dev, v_diag, scales, sess.replicate_put(xt0),
                     sess.replicate_put(np.int32(0)))

    def _program(self, n: int, weights_dtype):
        """Key of the SPMD program at these sizes (built on first use):
        matrices sharded by rows, the carry replicated. ``key[1]`` holds the
        worker's shapes and tiles."""
        sess, cfg = self.session, self.config
        if cfg.dim > mk.DIM_PAD:
            raise ValueError(f"WDA-MDS embeds into at most {mk.DIM_PAD} "
                             f"dimensions, not {cfg.dim}")
        geom = _geometry(n // sess.num_workers, n, cfg.dim, weights_dtype)
        key = ("mds", geom, sess.num_workers)
        if key not in self._fns:
            self._fns[key] = sess.spmd(
                lambda d, w, v, s, xt, c: _train(d, w, v, s, xt, c, geom, cfg),
                in_specs=(sess.shard(),) * 3 + (sess.replicate(),) * 3,
                out_specs=(sess.replicate(),) * 3)
        return key

    def _level(self, count: int) -> int:
        return min(count // self.config.level_iterations,
                   len(self._shares) - 1)

    def temperature(self, iteration: int) -> float:
        """T of the job's 0-based ``iteration`` (after :meth:`prepare`)."""
        return float(self._shares[self._level(iteration)]
                     * self._max_delta / np.sqrt(2.0 * self.config.dim))

    def train_prepared(self, state):
        """Run one call of the compiled iterations; the embedding stays ON
        DEVICE. Returns ``(carry, sigma ndarray)``: the fetch forces
        execution. The last two entries of ``state[1]`` are the carry the
        call starts from: a caller that trains in several calls hands back
        what the call before returned."""
        key, placed = state
        with telemetry.phase("mds.call") as call:
            step = self._fns[key]
            # (the dispatch keeps its line number: PERF.md section 7, row 11)
            with telemetry.phase("step.dispatch"):
                xt, count, sigma = step(*placed)
            telemetry.record_program("mds", step, placed)
            with telemetry.phase("step.fetch"):
                sigma, done = jax.device_get((sigma, count))
            start = int(done) - len(sigma)
            metrics.DEFAULT.count("mds.anneal.levels",
                                  self._level(int(done)) - self._level(start))
            telemetry.record_chunk("mds", start=start, losses=sigma.tolist(),
                                   wall_s=call.elapsed())
        return (xt, count), sigma

    def embedding(self, carry) -> np.ndarray:
        """The carried embedding on the host, ``(N, dim)``, centred."""
        x = np.asarray(carry[0])[:self.config.dim].T
        return x - x.mean(axis=0)

    def fit_prepared(self, state, on_call=None
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Calls from the state's carry until the schedule has ended (T = 0
        reached and ``level_iterations`` run there): ``(embedding (N, dim),
        sigma per iteration)``. ``on_call(iterations done, the call's
        sigma)`` after every call, where given."""
        key, placed = state
        total = schedule_iterations(self.config)
        carry, curve = placed[-2:], []
        done = int(np.asarray(carry[1]))
        while done < total or not curve:
            carry, sigma = self.train_prepared((key, (*placed[:-2], *carry)))
            curve.append(sigma)
            done += len(sigma)
            if on_call is not None:
                on_call(done, sigma)
        return self.embedding(carry), np.concatenate(curve)

    def fit(self, dist_matrix: np.ndarray, weights: np.ndarray = None,
            seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
        """Embed N points given an (N, N) target distance matrix.

        Returns (embedding (N, dim), normalised stress per iteration).
        """
        return self.fit_prepared(self.prepare(dist_matrix, weights, seed))


def numpy_wda_smacof(dist_matrix: np.ndarray, weights: np.ndarray,
                     x0: np.ndarray, cfg: MDSConfig, iterations: int,
                     start: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Single-host oracle in float64: ``iterations`` iterations of the
    annealed SMACOF from iteration count ``start``, the weighted V solved by
    the SAME truncated CG (for parity tests against the distributed
    program). Returns (embedding, normalised stress per iteration)."""
    delta = np.asarray(dist_matrix, np.float64)
    n = delta.shape[0]
    w = np.asarray(weights, np.float64) * (1.0 - np.eye(n))
    v = np.diag(w.sum(1)) - w
    shares = schedule(cfg).astype(np.float64)
    norm, widest = (w * delta * delta).sum(), delta[w > 0].max()
    x = np.asarray(x0, np.float64).copy()
    sigmas = []
    for count in range(start, start + iterations):
        level = min(count // cfg.level_iterations, len(shares) - 1)
        dhat = np.maximum(delta - shares[level] * widest, 0.0)
        cur = np.sqrt(((x[:, None, :] - x[None, :, :]) ** 2).sum(-1))
        with np.errstate(divide="ignore", invalid="ignore"):
            ratio = np.where(cur > 0, w * dhat / cur, 0.0)
        t = ratio.sum(1)[:, None] * x - ratio @ x
        z = x.copy()
        r = t - v @ z
        r -= r.mean(0)              # V's nullspace: the program's CG
        p = r.copy()
        rs = (r * r).sum(0)
        ts = (t * t).sum(0)
        for _ in range(cfg.cg_iters):
            active = rs > 1e-10 * np.maximum(ts, 1e-20)
            vp = v @ p
            alpha = np.where(active,
                             rs / np.maximum((p * vp).sum(0), 1e-20), 0.0)
            z = z + alpha[None, :] * p
            r = r - alpha[None, :] * vp
            r -= r.mean(0)
            rs_new = (r * r).sum(0)
            beta = np.where(active, rs_new / np.maximum(rs, 1e-20), 0.0)
            p = r + beta[None, :] * p
            rs = rs_new
        sigmas.append(float((w * (delta - cur) ** 2).sum() / norm))
        x = z
    return x, np.asarray(sigmas)
