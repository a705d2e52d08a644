"""Plain reference for the ``ccd-k100`` configuration: CCD++ (Yu, Hsieh, Si,
Dhillon, "Scalable Coordinate Descent Approaches to Parallel Matrix
Factorization for Recommender Systems", ICDM 2012, Algorithm 2), with the
**maintained residual** of the paper.

One call holds ``R = A - U V'`` on the rated cells (0 elsewhere). One outer
iteration is, for every feature t in turn::

    R^ = R + u_t v_t'                             on the rated cells
    repeat inner_iterations times:
        u_it = sum_j R^_ij v_jt / (lam + sum_j v_jt^2)   over i's rated cells
        v_jt = sum_i R^_ij u_it / (lam + sum_i u_it^2)   over j's rated cells
    R = R^ - u_t v_t'                             on the rated cells

then the monitor: the root mean square of R over the rated cells. A row or a
column with no rating keeps its value.

One departure from the paper, the program's (``harp_tpu/models/ccd.py``)
and stated in the configuration's file under ``assumed``: ``lam`` is added
to the denominator as it stands, not weighted by the row's count. The first
model is the paper's in form: the side that is updated first starts from 0.

Plain ``jax.numpy`` in float32: R is a float32 plane with a 0/1 mask beside
it (the ratings are scattered into it once a call), every sum an elementwise
product and a reduction (exact float32 on any backend), the one matrix
product (``U V'``, once a call) at ``highest``. No
bfloat16 plane, no kernel, no recomputed prediction: the program recomputes
the residual from its planes in every half-step, this file never does. It
imports nothing of the program. The first factors are as the program's
module states them: U = 0; V from numpy ``default_rng(seed)``, ``random``
over ``sqrt(rank)`` (uniform in [0, 1/sqrt(rank))), at the size padded to
the worker count.

``products`` rounds the factors wherever they enter the residual (``U V'``
and every rank-one term) to a narrower type: the lower-precision control,
never the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _rounded(x, dtype):
    """``x`` rounded to ``dtype`` and back. The narrow value is held behind
    a barrier: XLA may keep excess precision where a fusion holds both
    conversions (on the chip the float8 control read within 3e-4 of the
    float32 reference without it: ``PERF.md``, Findings, PR 31)."""
    if dtype is None:
        return x
    return jax.lax.optimization_barrier(x.astype(dtype)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("products",))
def _residual(rows, cols, vals, mask, u, v, *, products=None):
    a = jnp.zeros(mask.shape, jnp.float32).at[rows, cols].set(vals)
    pred = jnp.dot(_rounded(u, products), _rounded(v, products).T,
                   precision=_HIGHEST)
    return jnp.where(mask, a - pred, 0.0)


@functools.partial(jax.jit, static_argnames=("lam", "inner", "products"),
                   donate_argnums=(0,))
def _outer_iteration(r, mask, u_t, v_t, *, lam, inner, products=None):
    """One sweep over the features. ``r`` (rows, cols) the residual, ``u_t``
    (k, rows) and ``v_t`` (k, cols) the factors, a feature a row."""
    rated_rows = jnp.any(mask, axis=1)
    rated_cols = jnp.any(mask, axis=0)

    def rank_one(x, y):
        outer = _rounded(x, products)[:, None] * _rounded(y, products)[None, :]
        return jnp.where(mask, outer, 0.0)

    def feature(t, carry):
        r, u_t, v_t = carry
        x, y = u_t[t], v_t[t]
        r_hat = r + rank_one(x, y)
        for _ in range(inner):
            num = jnp.sum(r_hat * y[None, :], axis=1)
            den = lam + jnp.sum(jnp.where(mask, (y * y)[None, :], 0.0), axis=1)
            x = jnp.where(rated_rows, num / den, x)
            num = jnp.sum(r_hat * x[:, None], axis=0)
            den = lam + jnp.sum(jnp.where(mask, (x * x)[:, None], 0.0), axis=0)
            y = jnp.where(rated_cols, num / den, y)
        return (r_hat - rank_one(x, y), u_t.at[t].set(x), v_t.at[t].set(y))

    r, u_t, v_t = jax.lax.fori_loop(0, u_t.shape[0], feature, (r, u_t, v_t))
    return r, u_t, v_t, jnp.sum(r * r)


class Reference:
    """Holds the cell's ratings as they come and the plane's 0/1 mask;
    ``advance`` follows the program."""

    def __init__(self, config: dict, data: dict, num_workers: int = 1):
        w = int(num_workers)
        self._rank = int(config["rank"])
        self._lam = float(config["lam"])
        self._inner = int(config["inner_iterations"])
        self._m, self._n = int(data["num_rows"]), int(data["num_cols"])
        self._n_pad = _ceil_div(self._n, w) * w
        self._seed = int(data["init_seed"])
        self._entries = (jnp.asarray(np.asarray(data["rows"], np.int32)),
                         jnp.asarray(np.asarray(data["cols"], np.int32)),
                         jnp.asarray(np.asarray(data["vals"], np.float32)))
        rows, cols, vals = self._entries
        self._nnz = int(vals.shape[0])
        self._mask = jnp.zeros((self._m, self._n), jnp.bool_
                               ).at[rows, cols].set(True)

    def initial(self) -> dict:
        rng = np.random.default_rng(self._seed)
        v0 = (rng.random((self._n_pad, self._rank)) / np.sqrt(self._rank)
              ).astype(np.float32)
        return {"U": np.zeros((self._m, self._rank), np.float32),
                "V": v0[: self._n]}

    def advance(self, state: dict, epochs: int, products=None):
        """``epochs`` outer iterations from ``state``: the new state and
        each iteration's monitor."""
        u_t, v_t = jnp.asarray(state["U"]).T, jnp.asarray(state["V"]).T
        r = _residual(*self._entries, self._mask, u_t.T, v_t.T,
                      products=products)
        quality = []
        for _ in range(epochs):
            r, u_t, v_t, sse = _outer_iteration(
                r, self._mask, u_t, v_t, lam=self._lam, inner=self._inner,
                products=products)
            quality.append(jnp.sqrt(sse / max(self._nnz, 1)))
        return ({"U": np.asarray(u_t.T), "V": np.asarray(v_t.T)},
                np.asarray(jnp.stack(quality), np.float64))

    def free(self) -> None:
        self._entries = self._mask = None
