"""Plain reference for the ``als-k100`` configuration: implicit-feedback
alternating least squares (Hu, Koren, Volinsky, ICDM 2008).

Every observed pair (u, i) with rating r has preference 1 and confidence
``1 + alpha r``; everything else preference 0 and confidence 1. One
iteration solves, for every user u with the items' factors V held fixed::

    A_u = V'V + alpha * sum_i r_ui v_i v_i' + lam I
    b_u = sum_i (1 + alpha r_ui) v_i            (sums over u's observed items)
    x_u = A_u^-1 b_u

then the same for every item with the new users' factors, then reads the
monitor: the root mean square of ``1 - x_u . y_i`` over the observed pairs,
with both new tables. ``lam I`` is not weighted by the row's count.

Plain ``jax.numpy`` in float32, products at ``highest`` precision,
``jnp.linalg.solve``. The sums run over the raw (row, col, value) list in
blocks of rows that fit: a block's weights are scattered into a
``(rows, others)`` sheet and multiplied with the others' row-wise outer
products, which is the sum above written as one product. No plane is kept, no
kernel, no transposed system. It imports nothing of the program. The first
factors are drawn as the program's module states them: numpy
``default_rng(seed)``, uniform on ``[0, 1/sqrt(rank))``, users then items, at
the sizes padded to the worker count.

``products`` rounds the operands of the sheet products (the outer products,
the factors and the weights) to a narrower type: the lower-precision control,
never the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
# rows of a block: its two weight sheets are 8 bytes a cell
_SHEET_BYTES = 512 * 1024 * 1024
_MONITOR_CHUNK = 1 << 20


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _rounded(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "rb", "alpha", "lam", "products"))
def _half_step(other, tiles, *, rb, alpha, lam, products=None):
    """New factors of one side, ``(blocks * rb, k)``, from the other side's
    ``other`` (E, k) and that side's ratings in row blocks (``tiles``: local
    row, column, value and 1/0 for a real entry, each ``(blocks, cap)``)."""
    e, k = other.shape
    f = _rounded(other, products)
    outer = _rounded((f[:, :, None] * f[:, None, :]).reshape(e, k * k),
                     products)
    fixed = (jnp.dot(other.T, other, precision=_HIGHEST)
             + lam * jnp.eye(k, dtype=jnp.float32))

    def block(tile):
        r, c, val, live = tile
        zero = jnp.zeros((rb, e), jnp.float32)
        extra = zero.at[r, c].add(alpha * val * live)       # confidence - 1
        conf = zero.at[r, c].add((1.0 + alpha * val) * live)
        a = jnp.dot(_rounded(extra, products), outer, precision=_HIGHEST)
        b = jnp.dot(_rounded(conf, products), f, precision=_HIGHEST)
        return jnp.linalg.solve(a.reshape(rb, k, k) + fixed, b[..., None])[..., 0]

    return jax.lax.map(block, tiles).reshape(-1, k)


@jax.jit
def _squared_error(u, v, r, c, live):
    pred = jnp.einsum("nk,nk->n", u[r], v[c], precision=_HIGHEST)
    return jnp.sum(live * (1.0 - pred) ** 2)


class Reference:
    """Holds the cell's ratings as row blocks of either side; ``advance``
    follows the program."""

    def __init__(self, config: dict, data: dict, num_workers: int = 1):
        w = int(num_workers)
        self._rank = int(config["rank"])
        self._lam, self._alpha = float(config["lam"]), float(config["alpha"])
        if not config["implicit"]:
            raise ValueError("this reference is the implicit model's")
        self._m, self._n = int(data["num_rows"]), int(data["num_cols"])
        self._m_pad = _ceil_div(self._m, w) * w
        self._n_pad = _ceil_div(self._n, w) * w
        self._seed = int(data["init_seed"])
        rows = np.asarray(data["rows"], np.int64)
        cols = np.asarray(data["cols"], np.int64)
        vals = np.asarray(data["vals"], np.float32)
        self._nnz = len(vals)
        self._by_user = self._blocks(rows, cols, vals, self._m, self._n)
        self._by_item = self._blocks(cols, rows, vals, self._n, self._m)
        pad = -self._nnz % _MONITOR_CHUNK
        shape = (-1, _MONITOR_CHUNK)
        self._entries = tuple(jnp.asarray(np.pad(a, (0, pad)).reshape(shape))
                              for a in (rows.astype(np.int32),
                                        cols.astype(np.int32),
                                        np.ones(self._nnz, np.float32)))

    @staticmethod
    def _blocks(rows, cols, vals, num_rows, num_others):
        rb = max(8, min(num_rows, _SHEET_BYTES // (8 * num_others)) // 8 * 8)
        blocks = _ceil_div(num_rows, rb)
        block = rows // rb
        order = np.argsort(block, kind="stable")
        counts = np.bincount(block, minlength=blocks)
        # the fullest block's count, rounded up to a coarse step so that
        # every seed gives one shape (the padding has ``live`` 0)
        cap = max(int(counts.max()), 1)
        step = 1 << max(10, cap.bit_length() - 5)
        cap = _ceil_div(cap, step) * step
        starts = np.concatenate([[0], np.cumsum(counts)])
        b_o = block[order]
        slot = np.arange(len(rows)) - starts[b_o]
        r = np.zeros((blocks, cap), np.int32)
        c = np.zeros((blocks, cap), np.int32)
        val = np.zeros((blocks, cap), np.float32)
        live = np.zeros((blocks, cap), np.float32)
        r[b_o, slot] = (rows % rb)[order]
        c[b_o, slot] = cols[order]
        val[b_o, slot] = vals[order]
        live[b_o, slot] = 1.0
        return rb, tuple(jnp.asarray(a) for a in (r, c, val, live))

    def initial(self) -> dict:
        rng = np.random.default_rng(self._seed)
        scale = 1.0 / np.sqrt(self._rank)
        u0 = (scale * rng.random((self._m_pad, self._rank))).astype(np.float32)
        v0 = (scale * rng.random((self._n_pad, self._rank))).astype(np.float32)
        return {"U": u0[: self._m], "V": v0[: self._n]}

    def advance(self, state: dict, epochs: int, products=None):
        """``epochs`` iterations from ``state``: the new state and each
        iteration's monitor."""
        u, v = jnp.asarray(state["U"]), jnp.asarray(state["V"])
        fixed = {"alpha": self._alpha, "lam": self._lam, "products": products}
        quality = []
        for _ in range(epochs):
            u = _half_step(v, self._by_user[1], rb=self._by_user[0],
                           **fixed)[: self._m]
            v = _half_step(u, self._by_item[1], rb=self._by_item[0],
                           **fixed)[: self._n]
            sse = sum(_squared_error(u, v, r, c, live)
                      for r, c, live in zip(*self._entries))
            quality.append(jnp.sqrt(sse / max(self._nnz, 1)))
        return ({"U": np.asarray(u), "V": np.asarray(v)},
                np.asarray(jnp.stack(quality), np.float64))

    def free(self) -> None:
        self._by_user = self._by_item = self._entries = None
