"""EM for Gaussian mixtures — distributed sufficient statistics.

Reference parity: daal_em (SURVEY §2.7 — DAAL's em_gmm batch kernel wrapped in a
1-mapper Harp job). The TPU-native version is genuinely distributed: the E-step
runs on each worker's row shard against replicated parameters; the M-step's
sufficient statistics (responsibility sums, weighted feature sums, weighted
outer products, stacked in one array) and the log-likelihood combine with one
psum each. Full-covariance components, regularized.

One iteration at ``(π_k, μ_k, Σ_k)`` over N points x_n in R^D::

    factor   Σ_k + reg I = L_k L_k'    A_k = L_k⁻¹    b_k = A_k μ_k
             logdet_k = 2 Σ_i log (L_k)_ii
    E-step   log p_nk = log π_k − ½ (D log 2π + logdet_k + ‖A_k x_n − b_k‖²)
             log z_n = logsumexp_k log p_nk    r_nk = exp(log p_nk − log z_n)
    M-step   N_k = Σ_n r_nk    μ_k = Σ_n r_nk x_n / N_k
             Σ_k = Σ_n r_nk x_n x_n' / N_k − μ_k μ_k'    π_k = N_k / N

The iteration's quality is ``−(1/N) Σ_n log z_n`` at the parameters it
started from (lower is better). ``reg`` is added at the factorization only:
the carried and returned covariances are the ML estimates.

:meth:`EMGMM.prepare` places the points ONCE (float32, lane-padded, a 1 in
the lane after the last coordinate: ``ops/em_kernels.stored_points``);
:meth:`EMGMM.train_prepared` runs a call of iterations as one compiled scan,
the E-step of each one fused pass over the points on TPU (kernel
``em_estep``), its ``jax.numpy`` twin by row blocks elsewhere: nothing of
size N·K·D is formed. The factorization and the M-step are ``jax.numpy`` at
``Precision.HIGHEST``. Scopes ``em.factor``, ``em.estep``, ``em.update``;
host phases ``em.prepare`` (``session.place`` under it) and ``em.call``
with ``step.dispatch`` and ``step.fetch``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu import telemetry
from harp_tpu.ops import em_kernels as ek
from harp_tpu.parallel.mesh import WORKERS
from harp_tpu.session import HarpSession
from harp_tpu.utils import metrics

_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class EMConfig:
    num_components: int = 3
    iterations: int = 30
    reg: float = 1e-4           # covariance ridge


@dataclasses.dataclass(frozen=True)
class _Geometry:
    """One worker's block of ``rows`` points and the kernel's tiles
    (``tile`` 0: the ``jax.numpy`` twin, in blocks of ``block`` rows)."""
    rows: int
    k: int
    d: int
    k_pad: int
    d_pad: int
    d_store: int
    tile: int
    chunk: int
    block: int
    interpret: bool = False     # the kernel off the TPU (tests only)


def _geometry(rows: int, k: int, d: int) -> _Geometry:
    """The kernel's tiles where the block takes them (one predicate beside
    the kernel decides), else the twin's row blocks."""
    k_pad, d_pad, d_store = ek.padded(k, d)
    fused = ek.use_em_estep_pallas(rows, k, d)
    return _Geometry(
        rows, k, d, k_pad, d_pad, d_store,
        *(ek.estep_tiles(rows, d_store) if fused else (0, 0)),
        block=ek.twin_block(rows, k_pad, d_pad),
        interpret=fused and jax.default_backend() != "tpu")


def _factor(pi, mean, cov, g: _Geometry, reg: float):
    """The stacked whitening operand and the per-component constants."""
    eye = jnp.eye(g.d, dtype=jnp.float32)
    chol = jnp.linalg.cholesky(cov + reg * eye[None])
    a = jax.scipy.linalg.solve_triangular(
        chol, jnp.broadcast_to(eye, chol.shape), lower=True)
    b = jnp.einsum("kde,ke->kd", a, mean, precision=_HIGHEST)
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol, axis1=1, axis2=2)),
                           axis=1)
    const = jnp.log(pi) - 0.5 * (g.d * jnp.log(2.0 * jnp.pi) + logdet)
    return (ek.stacked_operand(a, b, g.k_pad, g.d_pad, g.d_store),
            ek.padded_const(const, g.k_pad))


def _estep(x, w, const, g: _Geometry):
    """This worker's statistics and ``Σ log z``."""
    # runs when jax traces, only: which E-step this program's iterations run
    if g.tile:
        metrics.DEFAULT.count("em.estep.pallas")
        return ek.estep_pallas(x, w, const, g.k_pad, g.d_pad, g.tile,
                               g.chunk, interpret=g.interpret)
    metrics.DEFAULT.count("em.estep.xla")
    return ek.estep_xla(x, w, const, g.k_pad, g.d_pad, g.block)


def _train(x, pi, mean, cov, g: _Geometry, reg: float, iterations: int,
           axis_name: str = WORKERS):
    """``iterations`` EM iterations from ``(pi, mean, cov)``: the new
    parameters and each iteration's quality."""
    telemetry.traced("em")                 # runs when jax traces, only
    n_total = jax.lax.psum(jnp.asarray(x.shape[0], jnp.float32), axis_name)

    def step(carry, _):
        pi, mean, cov = carry
        with jax.named_scope("em.factor"):
            w, const = _factor(pi, mean, cov, g, reg)
        with jax.named_scope("em.estep"):
            stats, ll = _estep(x, w, const, g)
        with jax.named_scope("em.update"):
            stats = jax.lax.psum(stats, axis_name)
            ll = jax.lax.psum(ll, axis_name)
            s = stats.reshape(g.k_pad, g.d_pad, g.d_store)[:g.k]
            nk = s[:, g.d, g.d]
            mean_new = s[:, g.d, :g.d] / jnp.maximum(nk, 1e-8)[:, None]
            cov_new = (s[:, :g.d, :g.d] / jnp.maximum(nk, 1e-8)[:, None, None]
                       - mean_new[:, :, None] * mean_new[:, None, :])
        return (nk / n_total, mean_new, cov_new), -ll / n_total

    # what the scan itself adds (the carry, the curve's stacking) stands
    # under em.update, the iteration's parts under their own names
    with jax.named_scope("em.update"):
        return jax.lax.scan(step, (pi, mean, cov), None, length=iterations)


class EMGMM:
    """Distributed full-covariance Gaussian mixture EM (daal_em parity)."""

    def __init__(self, session: HarpSession, config: EMConfig):
        self.session = session
        self.config = config
        self._fns = {}
        self.last_layout_stats: dict = {}

    def prepare(self, points: np.ndarray, weights0, means0, covs0):
        """Place the (N, D) points on the mesh ONCE, rows over the workers,
        and the first model ``(weights (K,), means (K, D), covs (K, D, D))``
        replicated; returns the state :meth:`train_prepared` takes."""
        with telemetry.phase("em.prepare"):
            sess, k = self.session, self.config.num_components
            n, d = points.shape
            if n % sess.num_workers:
                raise ValueError(f"num points {n} must divide over "
                                 f"{sess.num_workers} workers (pad at ingest)")
            shapes = tuple(np.shape(a) for a in (weights0, means0, covs0))
            if shapes != ((k,), (k, d), (k, d, d)):
                raise ValueError(f"a first model of {k} components in {d} "
                                 f"dimensions, not {shapes}")
            g = _geometry(n // sess.num_workers, k, d)
            pts = sess.scatter(ek.stored_points(points, g.d_store))
            model = tuple(sess.replicate_put(np.asarray(a, np.float32))
                          for a in (weights0, means0, covs0))
            self.last_layout_stats = {
                "kernel": "pallas" if g.tile else "xla",
                "row_tile": g.tile, "col_group": ek.GROUP,
                "resident_bytes": n * g.d_store * 4,
            }
        return g, (pts, *model)

    def _program(self, g: _Geometry, iterations: int):
        """Key of the SPMD program of ``iterations`` iterations (built on
        first use): points sharded by rows, the model replicated."""
        sess, cfg = self.session, self.config
        key = ("em", g, iterations, sess.num_workers)
        if key not in self._fns:
            self._fns[key] = sess.spmd(
                lambda x, p, m, c: _train(x, p, m, c, g, cfg.reg, iterations),
                in_specs=(sess.shard(),) + (sess.replicate(),) * 3,
                out_specs=((sess.replicate(),) * 3, sess.replicate()))
        return key

    def train_prepared(self, state, iterations: int = None):
        """Run ``iterations`` (default ``config.iterations``) EM iterations
        as one compiled program from the state's model. Returns ``(state,
        quality)``: the state holds the new ``(weights, means, covs)``, still
        on the device; ``quality`` (host) is each iteration's ``−(1/N) Σ log
        z``, at the parameters it started from. The fetch forces execution."""
        g, placed = state
        key = self._program(g, iterations or self.config.iterations)
        with telemetry.phase("em.call"):
            step = self._fns[key]
            with telemetry.phase("step.dispatch"):
                model, quality = step(*placed)
            telemetry.record_program("em", step, placed)
            with telemetry.phase("step.fetch"):
                quality, weights = jax.device_get((quality, model[0]))
            # components left with no more points than dimensions: their
            # covariance is no longer an estimate
            metrics.DEFAULT.count("em.components.collapsed", int(np.sum(
                weights * g.rows * self.session.num_workers <= g.d)))
        return (g, (placed[0], *model)), quality

    @staticmethod
    def parameters(state) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The state's ``(weights, means, covs)`` on the host."""
        return tuple(np.asarray(a) for a in state[1][1:])

    def first_model(self, x: np.ndarray, seed: int = 0):
        """``(weights, means, covs)`` to start from: uniform weights, means
        at distinct points drawn by ``seed``, every covariance the sample's
        plus ``1e-3 I``."""
        k, d = self.config.num_components, x.shape[1]
        rng = np.random.default_rng(seed)
        mean0 = x[rng.choice(x.shape[0], k, replace=False)].astype(np.float32)
        pi0 = np.full(k, 1.0 / k, np.float32)
        cov0 = np.tile(np.cov(x, rowvar=False).astype(np.float32)[None],
                       (k, 1, 1)) + 1e-3 * np.eye(d, dtype=np.float32)
        return pi0, mean0, cov0

    def fit(self, x: np.ndarray, seed: int = 0
            ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Returns (weights (K,), means (K, D), covs (K, D, D), mean
        log-likelihood per iteration)."""
        state, quality = self.train_prepared(
            self.prepare(x, *self.first_model(x, seed)))
        return (*self.parameters(state), -quality)
