"""Statistics / decomposition model family — the ml/daal dense-analytics suite.

Reference parity (SURVEY §2.7): daal_cov/densedistri, daal_pca/cordensedistr +
svddensedistr, daal_mom, daal_normalization, daal_qr, daal_svd, daal_cholesky,
daal_quantile, daal_sorting, daal_outlier. Each reference family = a Launcher + a
CollectiveMapper gluing Harp collectives around DAAL Step1Local/Step2Master
kernels; here each is a thin session wrapper around ``harp_tpu.ops.linalg`` — one
compiled SPMD program, data row-sharded over the worker mesh.

All ``fit``/``transform`` methods accept host numpy arrays whose row count must be
divisible by the worker count (loaders pad at ingest).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu.ops import linalg
from harp_tpu.parallel.mesh import fetch
from harp_tpu.session import HarpSession


class _SPMDWrapper:
    def __init__(self, session: HarpSession):
        self.session = session
        self._fns = {}   # compiled-program cache: key -> jitted callable

    def _compile(self, key, fn, n_out_rep: int, extra_sharded_out: int = 0):
        if key in self._fns:
            return self._fns[key]
        sess = self.session
        out_specs = tuple([sess.shard()] * extra_sharded_out
                          + [sess.replicate()] * n_out_rep)
        if len(out_specs) == 1:
            out_specs = out_specs[0]
        compiled = sess.spmd(fn, in_specs=(sess.shard(),), out_specs=out_specs)
        self._fns[key] = compiled
        return compiled


class Covariance(_SPMDWrapper):
    """daal_cov: distributed covariance + mean."""

    def compute(self, x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        fn = self._compile("cov", lambda a: linalg.covariance(a), 2)
        cov, mean = fn(self.session.scatter(jnp.asarray(x)))
        return fetch(cov), fetch(mean)


class LowOrderMoments(_SPMDWrapper):
    """daal_mom: the full moments result set."""

    def compute(self, x: np.ndarray) -> linalg.Moments:
        fn = self._compile("mom", lambda a: tuple(linalg.moments(a)), 10)
        out = fn(self.session.scatter(jnp.asarray(x)))
        return linalg.Moments(*[fetch(o) for o in out])


class PCA(_SPMDWrapper):
    """daal_pca: ``method="cor"`` = cordensedistr (correlation eigh),
    ``method="svd"`` = svddensedistr (z-score + distributed TSQR-SVD; same
    eigenvalues, better conditioning at large D — linalg.pca_svd)."""

    def __init__(self, session: HarpSession, method: str = "cor"):
        super().__init__(session)
        if method not in ("cor", "svd"):
            raise ValueError(f"method must be cor|svd, got {method!r}")
        self.method = method

    def fit(self, x: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        impl = linalg.pca if self.method == "cor" else linalg.pca_svd
        fn = self._compile(("pca", self.method), lambda a: impl(a), 3)
        w, comps, mean = fn(self.session.scatter(jnp.asarray(x)))
        return fetch(w), fetch(comps), fetch(mean)

    def fit_repeated(self, x, repeats: int
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Run ``repeats`` full fits inside ONE compiled program; returns the
        last fit's (eigenvalues, components, mean).

        Benchmarks time this instead of looping :meth:`fit` on the host so
        the measurement is device work, not per-call dispatch. The scan
        body rescales the input by a
        carry the fit itself produces (exactly 1.0 at runtime, unknowable at
        compile time), so XLA cannot hoist the loop-invariant gram/eigh out
        of the scan and fold ``repeats`` fits into one."""
        key = ("pca_rep", self.method, repeats)
        if key not in self._fns:
            sess = self.session
            impl = linalg.pca if self.method == "cor" else linalg.pca_svd

            def fn(a):
                d = a.shape[-1]
                dt = a.dtype

                def body(carry, _):
                    s = carry[0]
                    w, comps, mean = impl(a * s)
                    # w[0] is the top eigenvalue (>= 0; >= 1e-30 on the cor
                    # path via linalg.correlation's clamp), so s stays
                    # exactly 1.0 while staying runtime-dependent
                    s_next = jnp.asarray(1.0, dt) + jnp.asarray(0.0, dt) * w[0]
                    return (s_next, w, comps, mean), None

                init = (jnp.asarray(1.0, dt), jnp.zeros((d,), dt),
                        jnp.zeros((d, d), dt), jnp.zeros((d,), dt))
                (s, w, comps, mean), _ = jax.lax.scan(
                    body, init, None, length=repeats)
                return w, comps, mean

            self._fns[key] = sess.spmd(fn, in_specs=(sess.shard(),),
                                       out_specs=(sess.replicate(),) * 3)
        out = self._fns[key](self.session.scatter(jnp.asarray(x)))
        return tuple(fetch(o) for o in out)


class ZScore(_SPMDWrapper):
    """daal_normalization (z-score): per-column standardization by global stats."""

    def transform(self, x: np.ndarray) -> np.ndarray:
        fn = self._compile("zscore", lambda a: linalg.zscore(a), 0, extra_sharded_out=1)
        return fetch(fn(self.session.scatter(jnp.asarray(x))))


class MinMax(_SPMDWrapper):
    """daal_normalization (min-max)."""

    def __init__(self, session: HarpSession, lo: float = 0.0, hi: float = 1.0):
        super().__init__(session)
        self.lo, self.hi = lo, hi

    def transform(self, x: np.ndarray) -> np.ndarray:
        fn = self._compile("minmax", lambda a: linalg.minmax(a, self.lo, self.hi),
                           0, extra_sharded_out=1)
        return fetch(fn(self.session.scatter(jnp.asarray(x))))


class QR(_SPMDWrapper):
    """daal_qr: distributed tall-skinny QR. Returns (Q (N, D), R (D, D))."""

    def compute(self, x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        sess = self.session
        if "qr" not in self._fns:
            self._fns["qr"] = sess.spmd(
                lambda a: linalg.tsqr(a), in_specs=(sess.shard(),),
                out_specs=(sess.shard(), sess.replicate()))
        q, r = self._fns["qr"](sess.scatter(jnp.asarray(x)))
        return fetch(q), fetch(r)


class PivotedQR(_SPMDWrapper):
    """daal_pivoted_qr: column-pivoted distributed QR.
    Returns (Q (N, D), R (D, D), pivots) with x[:, pivots] == Q @ R."""

    def compute(self, x: np.ndarray):
        fn = self._compile("pqr", lambda a: linalg.pivoted_qr(a), 2,
                           extra_sharded_out=1)
        q, r, piv = fn(self.session.scatter(jnp.asarray(x)))
        return fetch(q), fetch(r), fetch(piv)


class SVD(_SPMDWrapper):
    """daal_svd: distributed SVD of a tall matrix. Returns (U (N, D), s, V^T)."""

    def compute(self, x: np.ndarray):
        sess = self.session
        if "svd" not in self._fns:
            self._fns["svd"] = sess.spmd(
                lambda a: linalg.svd_tall(a), in_specs=(sess.shard(),),
                out_specs=(sess.shard(), sess.replicate(), sess.replicate()))
        u, s, vt = self._fns["svd"](sess.scatter(jnp.asarray(x)))
        return fetch(u), fetch(s), fetch(vt)


class Cholesky(_SPMDWrapper):
    """daal_cholesky on the distributed gram matrix X'X."""

    def compute(self, x: np.ndarray) -> np.ndarray:
        fn = self._compile("chol", lambda a: linalg.cholesky_gram(a), 1)
        return fetch(fn(self.session.scatter(jnp.asarray(x))))


class Quantiles(_SPMDWrapper):
    """daal_quantile: per-column quantiles of the full dataset."""

    def compute(self, x: np.ndarray, qs) -> np.ndarray:
        qs_arr = jnp.asarray(qs, jnp.float32)
        key = ("quantiles", tuple(np.asarray(qs).tolist()))
        fn = self._compile(key, lambda a: linalg.quantiles(a, qs_arr), 1)
        return fetch(fn(self.session.scatter(jnp.asarray(x))))


class Sorting(_SPMDWrapper):
    """daal_sorting: column-wise sort of all rows (distributed odd-even
    block sort — the device output is SHARDED in global sorted order;
    compute() assembles the full matrix on the host via fetch)."""

    def compute(self, x: np.ndarray) -> np.ndarray:
        fn = self._compile("sort", lambda a: linalg.distributed_sort(a), 0,
                           extra_sharded_out=1)
        return fetch(fn(self.session.scatter(jnp.asarray(x))))


class OutlierDetection(_SPMDWrapper):
    """daal_outlier: multivariate Mahalanobis outlier flags per row."""

    def __init__(self, session: HarpSession, threshold: float = 3.0):
        super().__init__(session)
        self.threshold = threshold

    def compute(self, x: np.ndarray) -> np.ndarray:
        fn = self._compile(
            "outlier", lambda a: linalg.mahalanobis_outliers(a, self.threshold),
            0, extra_sharded_out=1)
        return fetch(fn(self.session.scatter(jnp.asarray(x))))
