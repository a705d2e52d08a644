"""CCD++ matrix factorization — cyclic coordinate descent, one rank at a time.

Reference parity: ml/java ccd/ (CCDMPCollectiveMapper.java:51 — CCD++ MF using
the same dymoro model-rotation machinery as SGD-MF; BASELINE's "CCD MF vs CCD++"
comparison rows).

TPU-native: CCD++ sweeps ranks f = 1..K; for each rank it alternates closed-form
rank-1 updates of u_f (rows, sharded) and v_f (cols, re-replicated by allgather).
The residual against all OTHER ranks is recomputed on the fly from the padded
neighbor lists (O(nnz·K) per rank-sweep) — stateless and static-shape, trading
FLOPs (cheap on MXU) for the reference's carefully-maintained residual matrix
(cheap on CPU, racy to parallelize). Data layout reuses ALS's padded CSR lists.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu.collectives import lax_ops
from harp_tpu.models.als import pad_csr_lists
from harp_tpu.parallel.mesh import WORKERS
from harp_tpu.session import HarpSession


@dataclasses.dataclass(frozen=True)
class CCDConfig:
    rank: int = 8
    lam: float = 0.05
    outer_iterations: int = 10   # full sweeps over all ranks
    inner_iterations: int = 2    # u/v alternations per rank


def _rank1_update(factor_other, my_factor, idx, val, mask, f, lam):
    """Closed-form rank-1 coordinate update for one side.

    my_factor: (E_local, K); factor_other: replicated (E_other, K). Returns the
    new column f of my_factor. Residual excludes rank f:
      r_ij = val_ij − Σ_k u_ik v_jk + u_if v_jf.
    """
    vi = factor_other[idx] * mask[..., None]            # (E_local, M, K)
    pred = jnp.einsum("emk,ek->em", vi, my_factor)      # full prediction
    vf = vi[..., f]                                      # (E_local, M)
    uf = my_factor[:, f]
    resid = (val - pred) * mask + uf[:, None] * vf       # exclude rank f
    num = jnp.sum(resid * vf, axis=1)
    den = lam + jnp.sum(vf * vf, axis=1)
    return num / den


def _train(u_idx, u_val, u_mask, i_idx, i_val, i_mask, u0, v0,
           cfg: CCDConfig, axis_name: str = WORKERS):
    w = jax.lax.axis_size(axis_name)

    def rank_sweep(carry, f):
        u, v = carry          # u: (U, K) replicated; v: (V, K) replicated
        wid = lax_ops.worker_id(axis_name)
        u_rows = u.shape[0] // w
        v_rows = v.shape[0] // w

        def inner(carry, _):
            u, v = carry
            my_u = jax.lax.dynamic_slice_in_dim(u, wid * u_rows, u_rows, 0)
            uf = _rank1_update(v, my_u, u_idx, u_val, u_mask, f, cfg.lam)
            u = jax.lax.dynamic_update_index_in_dim(
                u, lax_ops.allgather(uf, axis_name), f, axis=1)
            my_v = jax.lax.dynamic_slice_in_dim(v, wid * v_rows, v_rows, 0)
            vf = _rank1_update(u, my_v, i_idx, i_val, i_mask, f, cfg.lam)
            v = jax.lax.dynamic_update_index_in_dim(
                v, lax_ops.allgather(vf, axis_name), f, axis=1)
            return (u, v), None

        (u, v), _ = jax.lax.scan(inner, (u, v), None,
                                 length=cfg.inner_iterations)
        return (u, v), None

    def outer(carry, _):
        carry, _ = jax.lax.scan(rank_sweep, carry, jnp.arange(cfg.rank))
        u, v = carry
        wid = lax_ops.worker_id(axis_name)
        u_rows = u.shape[0] // w
        my_u = jax.lax.dynamic_slice_in_dim(u, wid * u_rows, u_rows, 0)
        vi = v[u_idx] * u_mask[..., None]
        pred = jnp.einsum("emk,ek->em", vi, my_u)
        sse = jax.lax.psum(jnp.sum(u_mask * (u_val - pred) ** 2), axis_name)
        cnt = jax.lax.psum(jnp.sum(u_mask), axis_name)
        return carry, jnp.sqrt(sse / jnp.maximum(cnt, 1.0))

    (u, v), rmse = jax.lax.scan(outer, (u0, v0), None,
                                length=cfg.outer_iterations)
    return u, v, rmse


class CCD:
    """Distributed CCD++ over a HarpSession mesh (ml/java ccd parity)."""

    def __init__(self, session: HarpSession, config: CCDConfig):
        self.session = session
        self.config = config
        self._fns = {}

    def fit(self, rows, cols, vals, num_rows: int, num_cols: int,
            seed: int = 0) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        sess, cfg = self.session, self.config
        w = sess.num_workers
        u_idx, u_val, u_mask = pad_csr_lists(rows, cols, vals, num_rows, w)
        i_idx, i_val, i_mask = pad_csr_lists(cols, rows, vals, num_cols, w)
        rng = np.random.default_rng(seed)
        scale = 1.0 / np.sqrt(cfg.rank)
        u0 = (scale * rng.standard_normal(
            (u_idx.shape[0], cfg.rank))).astype(np.float32)
        v0 = (scale * rng.standard_normal(
            (i_idx.shape[0], cfg.rank))).astype(np.float32)

        key = (u_idx.shape, i_idx.shape)
        if key not in self._fns:
            self._fns[key] = sess.spmd(
                lambda a, b, c, d, e, f, g, h: _train(a, b, c, d, e, f, g, h,
                                                      cfg),
                in_specs=(sess.shard(),) * 6 + (sess.replicate(),) * 2,
                out_specs=(sess.replicate(),) * 3)
        u, v, rmse = self._fns[key](
            sess.scatter(u_idx), sess.scatter(u_val), sess.scatter(u_mask),
            sess.scatter(i_idx), sess.scatter(i_val), sess.scatter(i_mask),
            sess.replicate_put(u0), sess.replicate_put(v0))
        return (np.asarray(u)[:num_rows], np.asarray(v)[:num_cols],
                np.asarray(rmse))
