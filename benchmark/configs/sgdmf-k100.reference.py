"""Plain reference for the ``sgdmf-k100`` configuration: rotated stripe SGD.

Written from the update rule in ``harp_tpu/models/sgd_mf.py``'s docstring.
Users (rows) are split into ``W`` contiguous worker ranges and items
(columns) into ``W`` contiguous blocks of H. An epoch is ``W`` hops; at hop
``t`` worker ``w`` holds block ``(w - t) mod W`` (ring rotation), and runs
``minibatches_per_hop`` mini-batches over its ratings in that block, one per
contiguous stripe of its rows, in order. A mini-batch is one simultaneous
gradient step on every rating of the stripe x block tile::

    G  = R - W_s H_b^T            on the ratings that exist, 0 elsewhere
    W_s += lr (G H_b    - lam * n_row[:, None] * W_s)
    H_b += lr (G^T W_s  - lam * n_col[:, None] * H_b)      (old W_s)

with ``n_row``/``n_col`` the number of ratings each row/column has in the
tile (the sum of the per-rating L2 terms). The epoch's RMSE is taken over the
residuals ``G`` before each update. Workers of one hop touch disjoint rows
and disjoint blocks, so visiting them one after another is the same
arithmetic as the mesh's: this reference runs on one device, whatever ``W``.

Plain ``jax.numpy`` in float32, products at ``highest`` precision, ratings
scattered tile by tile from the raw (row, col, value) list; no slab, no
rotation primitive, no kernel. It imports nothing of the program. The first
factors are drawn as the configuration states them (``init``: numpy
``default_rng(seed)``, ``N(0, 1/rank)``, W then H, at the padded sizes).

``products`` rounds the operands of the three products to a narrower type:
the lower-precision control, never the reference.
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
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "s_rows", "cpb", "lr", "lam", "products"))
def _epoch(w_all, h_all, tiles, *, s_rows, cpb, lr, lam, products=None):
    def minibatch(carry, tile):
        w_all, h_all, sse = carry
        r, c, val, live, row0, col0 = tile
        rated = jnp.zeros((s_rows, cpb), jnp.float32).at[r, c].add(live)
        rating = jnp.zeros((s_rows, cpb), jnp.float32).at[r, c].add(val * live)
        w_s = jax.lax.dynamic_slice_in_dim(w_all, row0, s_rows)
        h_b = jax.lax.dynamic_slice_in_dim(h_all, col0, cpb)
        w_r, h_r = _rounded(w_s, products), _rounded(h_b, products)
        g = rated * (rating - jnp.dot(w_r, h_r.T, precision=_HIGHEST))
        g_r = _rounded(g, products)
        d_w = jnp.dot(g_r, h_r, precision=_HIGHEST)
        d_h = jnp.dot(g_r.T, w_r, precision=_HIGHEST)
        n_row = jnp.sum(rated, axis=1)[:, None]
        n_col = jnp.sum(rated, axis=0)[:, None]
        w_all = jax.lax.dynamic_update_slice_in_dim(
            w_all, w_s + lr * (d_w - lam * n_row * w_s), row0, 0)
        h_all = jax.lax.dynamic_update_slice_in_dim(
            h_all, h_b + lr * (d_h - lam * n_col * h_b), col0, 0)
        return (w_all, h_all, sse + jnp.sum(g_r * g_r)), None

    (w_all, h_all, sse), _ = jax.lax.scan(
        minibatch, (w_all, h_all, jnp.zeros((), jnp.float32)), tiles)
    return w_all, h_all, sse


class Reference:
    """Holds the cell's ratings as tiles; ``advance`` follows the program."""

    def __init__(self, config: dict, data: dict, num_workers: int = 1):
        w = int(num_workers)
        nmb = int(config["minibatches_per_hop"])
        self._rank = int(config["rank"])
        self._lr, self._lam = float(config["lr"]), float(config["lam"])
        self._m, self._n = int(data["num_rows"]), int(data["num_cols"])
        rpw = _ceil_div(_ceil_div(self._m, w), nmb) * nmb
        cpb = _ceil_div(self._n, w)
        self._s_rows, self._cpb, self._rpw, self._w = rpw // nmb, cpb, rpw, w
        self._nnz = len(data["vals"])
        self._seed = int(data["init_seed"])
        self._tiles = self._make_tiles(data, w, nmb)

    def _make_tiles(self, data, w, nmb):
        rows = np.asarray(data["rows"], np.int64)
        cols = np.asarray(data["cols"], np.int64)
        s_rows, cpb, rpw = self._s_rows, self._cpb, self._rpw
        worker, r_in = rows // rpw, rows % rpw
        stripe, block = r_in // s_rows, cols // cpb
        hop = (worker - block) % w
        # schedule order: hop, then worker, then stripe
        tile = (hop * w + worker) * nmb + stripe
        order = np.argsort(tile, kind="stable")
        counts = np.bincount(tile, minlength=w * w * nmb)
        # the fullest tile's count, rounded up to a coarse step so that every
        # seed gives one shape and the compiled epoch comes from the cache
        # (the padding has ``live`` 0 and adds nothing)
        cap = max(int(counts.max()), 1)
        step = 1 << max(10, cap.bit_length() - 5)
        cap = _ceil_div(cap, step) * step
        starts = np.concatenate([[0], np.cumsum(counts)])
        slot = np.arange(len(rows)) - starts[tile[order]]
        shape = (w * w * nmb, cap)
        r = np.zeros(shape, np.int32)
        c = np.zeros(shape, np.int32)
        val = np.zeros(shape, np.float32)
        live = np.zeros(shape, np.float32)
        t_o = tile[order]
        r[t_o, slot] = (r_in % s_rows)[order]
        c[t_o, slot] = (cols % cpb)[order]
        val[t_o, slot] = np.asarray(data["vals"], np.float32)[order]
        live[t_o, slot] = 1.0
        ids = np.arange(w * w * nmb)
        t_hop, t_worker, t_stripe = (ids // (w * nmb), (ids // nmb) % w,
                                     ids % nmb)
        row0 = (t_worker * rpw + t_stripe * s_rows).astype(np.int32)
        col0 = (((t_worker - t_hop) % w) * cpb).astype(np.int32)
        return tuple(jnp.asarray(a) for a in (r, c, val, live, row0, col0))

    def initial(self) -> dict:
        rng = np.random.default_rng(self._seed)
        scale = 1.0 / np.sqrt(self._rank)
        w0 = (scale * rng.standard_normal(
            (self._w * self._rpw, self._rank))).astype(np.float32)
        h0 = (scale * rng.standard_normal(
            (self._w * self._cpb, self._rank))).astype(np.float32)
        return {"W": w0[: self._m], "H": h0[: self._n],
                "_w_pad": w0, "_h_pad": h0}

    def advance(self, state: dict, epochs: int, products=None):
        """``epochs`` epochs from ``state``: the new state and each epoch's
        RMSE. Rows and columns past the matrix (padding of the ranges) have
        no rating and never move; they ride along under ``_w_pad``/``_h_pad``."""
        w_all, h_all = jnp.asarray(state["_w_pad"]), jnp.asarray(state["_h_pad"])
        rmse = []
        for _ in range(epochs):
            w_all, h_all, sse = _epoch(
                w_all, h_all, self._tiles, s_rows=self._s_rows, cpb=self._cpb,
                lr=self._lr, lam=self._lam, products=products)
            rmse.append(jnp.sqrt(sse / max(self._nnz, 1)))
        w_np, h_np = np.asarray(w_all), np.asarray(h_all)
        return ({"W": w_np[: self._m], "H": h_np[: self._n],
                 "_w_pad": w_np, "_h_pad": h_np},
                np.asarray(jnp.stack(rmse), np.float64))

    def free(self) -> None:
        self._tiles = None
