"""Plain reference for the ``emgmm-k100d100`` configuration: full-covariance
EM for a Gaussian mixture.

Written from the equations (one iteration, N points x_n in R^D, K
components)::

    Σ_k + reg I = L_k L_k'    A_k = L_k⁻¹    logdet_k = 2 Σ_i log (L_k)_ii
    log p_nk = log π_k − ½ (D log 2π + logdet_k + ‖A_k (x_n − μ_k)‖²)
    log z_n = logsumexp_k log p_nk           r_nk = exp(log p_nk − log z_n)
    N_k = Σ_n r_nk    μ_k = Σ_n r_nk x_n / N_k    π_k = N_k / N
    Σ_k = Σ_n r_nk x_n x_n' / N_k − μ_k μ_k'

and the iteration's quality ``−(1/N) Σ_n log z_n``, at the parameters the
iteration started from. Straightforward ``jax.numpy`` in float32 with every
product at ``highest`` precision, over row blocks small enough that the
``(block, K, D)`` differences fit; no stacked operand, no lane padding, no
collectives. It imports nothing of the program and takes nothing the program
made: the points and the first model come from the traffic generator.

``products`` rounds the operands of the matrix products (the whitening
against the differences, the responsibilities and the weighted points
against the points) to a narrower type, the sums staying float32: that is
the lower-precision control of the comparison, never the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
_MAX_ROWS = 8192      # rows of a block: (block, K, D) float32 at most 328 MB


def _rounded(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("reg", "products"))
def _iteration(blocks, pi, mean, cov, reg, products=None):
    k, d = mean.shape
    n = blocks.shape[0] * blocks.shape[1]
    eye = jnp.eye(d, dtype=jnp.float32)
    chol = jnp.linalg.cholesky(cov + reg * eye[None])
    a = jax.scipy.linalg.solve_triangular(
        chol, jnp.broadcast_to(eye, chol.shape), lower=True)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol, axis1=1, axis2=2)),
                           axis=1)
    const = jnp.log(pi) - 0.5 * (d * jnp.log(2.0 * jnp.pi) + logdet)
    a_r = _rounded(a, products)

    def visit(acc, x):
        nk, sums, outer, ll = acc
        diff = x[:, None, :] - mean[None]                      # (B, K, D)
        y = jnp.einsum("kde,bke->bkd", a_r, _rounded(diff, products),
                       precision=_HIGHEST)
        logp = const[None] - 0.5 * jnp.sum(y * y, axis=2)       # (B, K)
        logz = jax.scipy.special.logsumexp(logp, axis=1, keepdims=True)
        r = jnp.exp(logp - logz)
        xr = _rounded(x, products)
        z = r[:, :, None] * x[:, None, :]                       # (B, K, D)
        return (nk + jnp.sum(r, axis=0),
                sums + jnp.einsum("bk,bd->kd", _rounded(r, products), xr,
                                  precision=_HIGHEST),
                outer + jnp.einsum("bkd,be->kde", _rounded(z, products), xr,
                                   precision=_HIGHEST),
                ll + jnp.sum(logz)), None

    zero = (jnp.zeros((k,), jnp.float32), jnp.zeros((k, d), jnp.float32),
            jnp.zeros((k, d, d), jnp.float32), jnp.zeros((), jnp.float32))
    (nk, sums, outer, ll), _ = jax.lax.scan(visit, zero, blocks)
    safe = jnp.maximum(nk, 1e-8)
    mean_new = sums / safe[:, None]
    cov_new = (outer / safe[:, None, None]
               - mean_new[:, :, None] * mean_new[:, None, :])
    return nk / n, mean_new, cov_new, -ll / n


class Reference:
    """Holds the cell's data once; ``advance`` follows the program's calls."""

    def __init__(self, config: dict, data: dict, num_workers: int = 1):
        del num_workers                  # EM does not depend on the mesh
        points = data["points"]
        n, d = points.shape
        blocks = next(b for b in range(-(-n // _MAX_ROWS), n + 1)
                      if n % b == 0)
        self._blocks = jnp.asarray(points).reshape(blocks, n // blocks, d)
        self._first = {"weights": np.asarray(data["weights0"], np.float32),
                       "means": np.asarray(data["means0"], np.float32),
                       "covs": np.asarray(data["covs0"], np.float32)}
        self._reg = float(config["reg"])

    def initial(self) -> dict:
        return {k: v.copy() for k, v in self._first.items()}

    def advance(self, state: dict, epochs: int, products=None):
        """``epochs`` EM iterations from ``state``: the new state (host
        arrays) and each iteration's quality."""
        pi, mean, cov = (jnp.asarray(state[k])
                         for k in ("weights", "means", "covs"))
        qualities = []
        for _ in range(epochs):
            pi, mean, cov, quality = _iteration(
                self._blocks, pi, mean, cov, self._reg, products=products)
            qualities.append(quality)
        return ({"weights": np.asarray(pi), "means": np.asarray(mean),
                 "covs": np.asarray(cov)},
                np.asarray(jnp.stack(qualities), np.float64))

    def free(self) -> None:
        self._blocks = None
