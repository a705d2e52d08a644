"""Plain reference for the ``wdamds-d3`` configuration: WDA-SMACOF (Ruan and
Fox, "A Robust and Scalable Solution for Interpolative Multidimensional
Scaling with Weighting", IEEE eScience 2013) under the deterministic
annealing of Bae, Qiu and Fox (IEEE eScience 2010).

One iteration at temperature T, target dimension L, weights w (w_ii = 0)::

    dhat_ij = max(delta_ij - T sqrt(2L), 0)       d_ij = |x_i - x_j|
    B_ij = -w_ij dhat_ij / d_ij  (0 where d_ij = 0)    B_ii = -sum_j B_ij
    V_ij = -w_ij                                  V_ii = sum_j w_ij
    solve  V X+ = B(X) X  by cg_iters steps of CG warm-started at X
    sigma = sum w (delta - d)^2 / sum w delta^2

``T sqrt(2L) = alpha^(k + 1) max(delta)`` at level ``k = floor(i /
level_iterations)`` of the job's iteration i (the maximum over the pairs
that have a weight), and 0 from the first level at which that falls under
``t_floor max(delta)``: the one departure from the
sources (which cool when the stress stops moving), stated in the
configuration's file under ``assumed``. The CG is the sources' in form:
per-column step lengths, a column frozen once its residual is at the
float32 floor of the right-hand side, every residual kept orthogonal to V's
nullspace (its mean over the points taken out).

Plain ``jax.numpy`` in float32, every product at ``highest``: B(X) is
formed as a matrix block and multiplied, the Laplacian likewise. The N x N
passes walk row blocks, so what one holds beside ``delta`` and ``w`` (both
float32 on the device, w made there from the cut) is a few blocks. No
kernel, no transposed carry, no split operands, and nothing of the program
is imported. The data: Euclidean distances between the cell's points, by
row blocks on the host in float32, as a distance file would hold them;
weight 1 where the distance is at most ``distance_cut``, else 0. The first
embedding is as the program's module states it: N(0, 1) from numpy
``default_rng(seed)``, centred.

``products`` rounds the operands of the two matrix products (B(X) X and
the Laplacian's matvec) to a narrower type: the lower-precision control,
never the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
_ROWS = 512                 # rows of a block of an N x N pass


def _distances(points: np.ndarray) -> np.ndarray:
    pts = np.ascontiguousarray(points, np.float32)
    sq = np.einsum("ij,ij->i", pts, pts)
    out = np.empty((len(pts), len(pts)), np.float32)
    for lo in range(0, len(pts), 2048):
        blk = out[lo:lo + 2048]
        np.matmul(pts[lo:lo + 2048], pts.T, out=blk)
        blk *= -2.0
        blk += sq[lo:lo + 2048, None]
        blk += sq[None, :]
        np.sqrt(np.maximum(blk, 0.0, out=blk), out=blk)
    np.fill_diagonal(out, 0.0)
    return out


def _shares(alpha: float, t_floor: float) -> np.ndarray:
    out, share = [], alpha
    while share >= t_floor:
        out.append(share)
        share *= alpha
    return np.asarray(out + [0.0], np.float32)


def _rounded(x, dtype):
    """``x`` rounded to ``dtype`` and back, the narrow value held behind a
    barrier (XLA may keep excess precision where one fusion holds both
    conversions: ``PERF.md``, Findings, PR 31)."""
    if dtype is None:
        return x
    return jax.lax.optimization_barrier(x.astype(dtype)).astype(jnp.float32)


def _by_rows(n: int, width: int, one):
    """``one(first row) -> (block, width)`` over the row blocks of an N x N
    pass, stacked to ``(n, width)``. The last block lies flush with the end."""
    rb = min(_ROWS, n)

    def body(i, out):
        r0 = jnp.minimum(i * rb, n - rb)
        return jax.lax.dynamic_update_slice(out, one(r0, rb), (r0, 0))

    return jax.lax.fori_loop(0, -(-n // rb), body,
                             jnp.zeros((n, width), jnp.float32))


@jax.jit
def _weights(delta, cut):
    n = delta.shape[0]
    w = jnp.where(delta <= cut, 1.0, 0.0).astype(jnp.float32)
    return w.at[jnp.arange(n), jnp.arange(n)].set(0.0)


@jax.jit
def _scales(delta, w):
    return (jnp.sum(w * delta * delta),
            jnp.max(jnp.where(w > 0.0, delta, 0.0)), jnp.sum(w, axis=1))


@functools.partial(jax.jit, static_argnames=("cg_iters", "products"))
def _iteration(delta, w, v_diag, norm, x, shift, *, cg_iters, products=None):
    """One SMACOF iteration from ``x`` (n, dim): the new embedding and the
    normalised stress of the old one."""
    n, dim = x.shape
    x_narrow = _rounded(x, products)

    def guttman_rows(r0, rb):
        dl = jax.lax.dynamic_slice_in_dim(delta, r0, rb, 0)
        wl = jax.lax.dynamic_slice_in_dim(w, r0, rb, 0)
        xi = jax.lax.dynamic_slice_in_dim(x, r0, rb, 0)
        d = jnp.sqrt(sum((xi[:, l, None] - x[None, :, l]) ** 2
                         for l in range(dim)))
        ratio = jnp.where(d > 0.0, wl * jnp.maximum(dl - shift, 0.0) / d, 0.0)
        # B's row block times X: the diagonal holds the block's row sums
        bx = jnp.sum(ratio, axis=1)[:, None] * xi - jnp.dot(
            _rounded(ratio, products), x_narrow, precision=_HIGHEST)
        stress = jnp.sum(wl * (dl - d) ** 2, axis=1)
        return jnp.concatenate([bx, stress[:, None]], axis=1)

    out = _by_rows(n, dim + 1, guttman_rows)
    t, sigma = out[:, :dim], jnp.sum(out[:, dim]) / norm

    def laplacian(p):
        p_narrow = _rounded(p, products)

        def rows(r0, rb):
            wl = jax.lax.dynamic_slice_in_dim(w, r0, rb, 0)
            return jnp.dot(_rounded(wl, products), p_narrow,
                           precision=_HIGHEST)

        return v_diag[:, None] * p - _by_rows(n, dim, rows)

    def centred(r):
        # V's nullspace is span{1}: what a residual holds along it is
        # rounding, and a step length must not be taken from it
        return r - jnp.mean(r, axis=0)

    z = x
    r = centred(t - laplacian(z))
    p = r
    rs = jnp.sum(r * r, axis=0)
    ts = jnp.sum(t * t, axis=0)

    def cg_step(_, carry):
        z, r, p, rs = carry
        active = rs > 1e-10 * jnp.maximum(ts, 1e-20)
        vp = laplacian(p)
        alpha = jnp.where(
            active, rs / jnp.maximum(jnp.sum(p * vp, axis=0), 1e-20), 0.0)
        z = z + alpha[None, :] * p
        r = centred(r - alpha[None, :] * vp)
        rs_new = jnp.sum(r * r, axis=0)
        beta = jnp.where(active, rs_new / jnp.maximum(rs, 1e-20), 0.0)
        return z, r, r + beta[None, :] * p, rs_new

    z, _, _, _ = jax.lax.fori_loop(0, cg_iters, cg_step, (z, r, p, rs))
    return z, sigma


class Reference:
    """Holds the cell's two matrices on the device; ``advance`` follows the
    program. A state is ``{"X": the centred embedding, "_count": iterations
    done}`` (the count is no leaf of the comparison)."""

    def __init__(self, config: dict, data: dict, num_workers: int = 1):
        self._dim = int(config["target_dim"])
        self._cg_iters = int(config["cg_iters"])
        self._level = int(config["level_iterations"])
        self._shares = _shares(float(config["alpha"]),
                               float(config["t_floor"]))
        self._seed = int(data["init_seed"])
        self._n = len(data["points"])
        self._delta = jnp.asarray(_distances(data["points"]))
        self._w = _weights(self._delta, np.float32(config["distance_cut"]))
        self._norm, widest, self._v_diag = _scales(self._delta, self._w)
        self._widest = np.float32(widest)

    def initial(self) -> dict:
        rng = np.random.default_rng(self._seed)
        x0 = rng.standard_normal((self._n, self._dim)).astype(np.float32)
        return {"X": x0 - x0.mean(axis=0), "_count": 0}

    def advance(self, state: dict, epochs: int, products=None):
        """``epochs`` iterations from ``state``: the new state and each
        iteration's normalised stress."""
        x, count = jnp.asarray(state["X"]), int(state["_count"])
        quality = []
        for i in range(count, count + epochs):
            level = min(i // self._level, len(self._shares) - 1)
            x, sigma = _iteration(
                self._delta, self._w, self._v_diag, self._norm, x,
                self._shares[level] * self._widest,
                cg_iters=self._cg_iters, products=products)
            quality.append(sigma)
        x = np.asarray(x)
        return ({"X": x - x.mean(axis=0), "_count": count + epochs},
                np.asarray(jnp.stack(quality), np.float64))

    def free(self) -> None:
        self._delta = self._w = self._v_diag = None
