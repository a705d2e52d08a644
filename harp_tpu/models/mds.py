"""WDA-MDS — weighted multidimensional scaling by SMACOF majorization.

Reference parity: ml/java wdamds (WDAMDSMapper.java:35 — WDA-SMACOF:
iterative allgather+allreduce matrix ops over BC/stress calc tasks, and the
distributed conjugate-gradient solve of the weighted Guttman transform,
WDAMDSMapper.java:585 ``conjugateGradient``, cgIter config :86, iteration
accounting :326-355; 2,883 LoC of partitioned matrix arithmetic).

TPU-native: the target-distance and weight matrix rows are sharded; each
SMACOF iteration computes this worker's block of B(X)·X with two MXU matmuls
on the replicated embedding, then solves V·X_new = B(X)·X by a distributed
CG in which the weighted-Laplacian matvec is one local (rows, N) matmul and
every inner product is one psum — the same one-collective-per-CG-step shape
as the reference's allreduce-per-iteration CG. The whole (SMACOF × CG) loop
nest is a single compiled program.

V is the weighted Laplacian (V_ij = −w_ij off-diagonal, V_ii = Σ_{j≠i}
w_ij), PSD with nullspace span{1}; B(X)X is orthogonal to 1, so CG iterates
stay in the solvable subspace and the translation-invariant embedding is
unaffected by any residual nullspace component in the warm start (the
previous iteration's embedding, which makes uniform-weight problems converge
in one CG step — V acts as n·centering there).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu.collectives import lax_ops
from harp_tpu.ops import distance as dist_ops
from harp_tpu.parallel.mesh import WORKERS
from harp_tpu.session import HarpSession


@dataclasses.dataclass(frozen=True)
class MDSConfig:
    dim: int = 2                # embedding dimensionality (reference: targetDim)
    iterations: int = 50
    cg_iters: int = 10          # CG steps per Guttman solve (reference: cgIter)


def _smacof(d_block, w_block, x0, n: int, cfg: MDSConfig,
            axis_name: str = WORKERS):
    """d_block/w_block: this worker's rows of the (N, N) target distance and
    weight matrices (w diagonal already zeroed). x0: replicated (N, dim)."""
    wid = lax_ops.worker_id(axis_name)
    rows = d_block.shape[0]
    w_rowsum = jnp.sum(w_block, axis=1)              # (rows,) = diag of V

    def vmatvec(p_loc, p_full):
        """Local rows of V @ p: diag term minus the weighted neighbor sum.

        Precision HIGHEST is load-bearing: the TPU's default f32 matmul
        truncates operands to bf16, and CG is exactly the algorithm that
        cannot take it — near convergence pᵀVp lives at noise scale, a
        truncation sign-flip sends alpha through the 1e-20 guard and the
        iterate to overflow (measured on the real chip: stress NaN at
        iteration 1; the CPU-mesh tests never see the default-precision
        path)."""
        return w_rowsum[:, None] * p_loc - jax.lax.dot_general(
            w_block, p_full, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)

    def colsum(a):
        return jnp.sum(a, axis=0)                    # per-embedding-column

    def cg_solve(t_loc, z0_loc):
        """Distributed CG on V z = t, all dim columns advanced together
        (per-column alpha/beta). One allgather + two psums per step —
        WDAMDSMapper.conjugateGradient's collective count."""
        z = z0_loc
        r = t_loc - vmatvec(z, lax_ops.allgather(z, axis_name))
        p = r
        rs = jax.lax.psum(colsum(r * r), axis_name)  # (dim,)
        # convergence floor anchored to the RHS scale (NOT the initial
        # residual — a near-exact warm start makes that itself noise-sized)
        ts = jax.lax.psum(colsum(t_loc * t_loc), axis_name)

        def body(carry, _):
            z, r, p, rs = carry
            # freeze converged columns (residual at the f32 noise floor):
            # running CG past convergence makes beta ~ 1+noise and p grow
            # exponentially — the fixed-iteration analog of the reference
            # CG's tolerance test
            active = rs > 1e-10 * jnp.maximum(ts, 1e-20)
            p_full = lax_ops.allgather(p, axis_name)
            vp = vmatvec(p, p_full)
            pvp = jax.lax.psum(colsum(p * vp), axis_name)
            alpha = jnp.where(active, rs / jnp.maximum(pvp, 1e-20), 0.0)
            z = z + alpha[None, :] * p
            r = r - alpha[None, :] * vp
            rs_new = jax.lax.psum(colsum(r * r), axis_name)
            beta = jnp.where(active, rs_new / jnp.maximum(rs, 1e-20), 0.0)
            p = r + beta[None, :] * p
            return (z, r, p, rs_new), None

        (z, _, _, _), _ = jax.lax.scan(body, (z, r, p, rs), None,
                                       length=cfg.cg_iters)
        return z

    def step(x, _):
        my_x = jax.lax.dynamic_slice_in_dim(x, wid * rows, rows, 0)
        cur = jnp.sqrt(jnp.maximum(
            dist_ops.pairwise_sq_dist(my_x, x,
                                      precision=jax.lax.Precision.HIGHEST),
            1e-12))
        ratio = jnp.where(cur > 1e-9, d_block / cur, 0.0) * w_block
        # B(X) row block: off-diagonal −ratio, diagonal = row-sum of ratios
        row_sum = jnp.sum(ratio, axis=1)
        col_ids = jnp.arange(x.shape[0])[None, :]
        diag_mask = col_ids == (wid * rows + jnp.arange(rows))[:, None]
        bx = -ratio + diag_mask * row_sum[:, None]
        t_loc = jax.lax.dot_general(bx, x, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=jax.lax.Precision.HIGHEST)
        # weighted Guttman transform: V X_new = B(X) X, warm-started at the
        # current embedding block (WDAMDSMapper.java:585)
        new_block = cg_solve(t_loc, my_x)
        x_new = lax_ops.allgather(new_block, axis_name)
        stress = jax.lax.psum(jnp.sum(w_block * (d_block - cur) ** 2),
                              axis_name)
        return x_new, stress

    return jax.lax.scan(step, x0, None, length=cfg.iterations)


class WDAMDS:
    """Distributed WDA-SMACOF MDS (wdamds parity, including the weighted
    V CG solve)."""

    def __init__(self, session: HarpSession, config: MDSConfig):
        self.session = session
        self.config = config
        self._fns = {}

    def prepare(self, dist_matrix: np.ndarray, weights: np.ndarray = None,
                seed: int = 0):
        """Place the (N, N) matrices on the mesh ONCE; returns an opaque
        state for :meth:`fit_prepared` (keeps the ~2·N² H2D transfer out of
        timed regions — the KMeans.prepare idiom)."""
        sess, cfg = self.session, self.config
        n = dist_matrix.shape[0]
        if n % sess.num_workers:
            raise ValueError(f"N={n} must divide over {sess.num_workers} workers")
        if weights is None:
            weights = np.ones_like(dist_matrix)
        weights = weights * (1.0 - np.eye(n, dtype=weights.dtype))
        rng = np.random.default_rng(seed)
        x0 = rng.standard_normal((n, cfg.dim)).astype(np.float32)
        x0 -= x0.mean(axis=0)        # start in V's solvable subspace
        key = (n,)
        if key not in self._fns:
            self._fns[key] = sess.spmd(
                lambda a, b, c: _smacof(a, b, c, n, cfg),
                in_specs=(sess.shard(), sess.shard(), sess.replicate()),
                out_specs=(sess.replicate(), sess.replicate()))
        return (key,
                sess.scatter(jnp.asarray(dist_matrix, jnp.float32)),
                sess.scatter(jnp.asarray(weights, jnp.float32)),
                jnp.asarray(x0))

    def fit_prepared(self, state) -> Tuple[np.ndarray, np.ndarray]:
        """Run SMACOF on already-placed device data (no host prep/H2D)."""
        key, d_dev, w_dev, x0 = state
        x, stress = self._fns[key](d_dev, w_dev, x0)
        return np.asarray(x), np.asarray(stress)

    def fit(self, dist_matrix: np.ndarray, weights: np.ndarray = None,
            seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
        """Embed N points given an (N, N) target distance matrix.

        Returns (embedding (N, dim), stress per iteration).
        """
        return self.fit_prepared(self.prepare(dist_matrix, weights, seed))


def numpy_wda_smacof(dist_matrix: np.ndarray, weights: np.ndarray,
                     x0: np.ndarray, iterations: int, cg_iters: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Single-host oracle: SMACOF with the weighted V solved by the SAME
    truncated CG (for parity tests against the distributed program)."""
    n = dist_matrix.shape[0]
    w = weights * (1.0 - np.eye(n, dtype=weights.dtype))
    v = np.diag(w.sum(1)) - w
    x = x0.copy()
    stresses = []
    for _ in range(iterations):
        cur = np.sqrt(np.maximum(
            ((x[:, None, :] - x[None, :, :]) ** 2).sum(-1), 1e-12))
        ratio = np.where(cur > 1e-9, dist_matrix / cur, 0.0) * w
        b = -ratio + np.diag(ratio.sum(1))
        t = b @ x
        z = x.copy()
        r = t - v @ z
        p = r.copy()
        rs = (r * r).sum(0)
        ts = (t * t).sum(0)
        for _ in range(cg_iters):
            active = rs > 1e-10 * np.maximum(ts, 1e-20)
            vp = v @ p
            alpha = np.where(active,
                             rs / np.maximum((p * vp).sum(0), 1e-20), 0.0)
            z = z + alpha[None, :] * p
            r = r - alpha[None, :] * vp
            rs_new = (r * r).sum(0)
            beta = np.where(active, rs_new / np.maximum(rs, 1e-20), 0.0)
            p = r + beta[None, :] * p
            rs = rs_new
        stresses.append(float((w * (dist_matrix - cur) ** 2).sum()))
        x = z
    return x, np.asarray(stresses, np.float32)
