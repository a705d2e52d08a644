"""Full-covariance EM (``models/em.py``) and its fused E-step
(``ops/em_kernels.py``) on the CPU at small sizes, seeded: the program
against a plain float64 EM of its own, the kernel (interpret mode) against
its ``jax.numpy`` twin, eight workers against one, no ``N·K·D`` array, the
prepared path against ``fit``, the collapsed-component counter, and a run at
one bfloat16 pass that the comparison refuses."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harp_tpu.models import em
from harp_tpu.ops import em_kernels as ek
from harp_tpu.session import HarpSession
from harp_tpu.utils import metrics

REG = 1e-4
ITERATIONS = 5


def plain_em(x, pi, mu, cov, iterations, reg=REG):
    """The equations in float64: ``(weights, means, covs, quality per
    iteration)``."""
    x = x.astype(np.float64)
    pi, mu, cov = (np.asarray(a, np.float64) for a in (pi, mu, cov))
    n, d = x.shape
    quality = []
    for _ in range(iterations):
        chol = np.linalg.cholesky(cov + reg * np.eye(d))
        a = np.linalg.inv(chol)
        logdet = 2 * np.log(np.diagonal(chol, axis1=1, axis2=2)).sum(1)
        y = np.einsum("kde,nke->nkd", a, x[:, None, :] - mu[None])
        logp = np.log(pi) - 0.5 * (d * np.log(2 * np.pi) + logdet
                                   + (y * y).sum(-1))
        top = logp.max(1, keepdims=True)
        logz = top + np.log(np.exp(logp - top).sum(1, keepdims=True))
        r = np.exp(logp - logz)
        quality.append(-logz.mean())
        nk = r.sum(0)
        mu = r.T @ x / nk[:, None]
        cov = (np.einsum("nk,nd,ne->kde", r, x, x) / nk[:, None, None]
               - mu[:, :, None] * mu[:, None, :])
        pi = nk / n
    return pi, mu, cov, np.asarray(quality)


def mixture(n, k, d, seed=0):
    """Points from ``k`` separated Gaussians and a first model near them."""
    rng = np.random.default_rng(seed)
    centres = 2.0 * rng.standard_normal((k, d))
    x = (centres[rng.integers(0, k, n)]
         + rng.standard_normal((n, d))).astype(np.float32)
    means0 = (centres + 0.5 * rng.standard_normal((k, d))).astype(np.float32)
    covs0 = np.tile((np.cov(x, rowvar=False) + 1e-3 * np.eye(d)).astype(
        np.float32)[None], (k, 1, 1))
    return x, np.full(k, 1.0 / k, np.float32), means0, covs0


@pytest.fixture
def kernel(monkeypatch):
    """The fused E-step wherever the block takes it (interpret mode here)."""
    monkeypatch.setattr(ek, "use_em_estep_pallas", lambda *a: True)


def _train(x, first, k, workers=1, iterations=ITERATIONS):
    model = em.EMGMM(HarpSession(num_workers=workers),
                     em.EMConfig(num_components=k, iterations=iterations))
    state, quality = model.train_prepared(model.prepare(x, *first))
    return model, (*model.parameters(state), quality)


def _rel(got, want):
    return float(np.max(np.abs(np.asarray(got, np.float64) - want))
                 / np.max(np.abs(want)))


@pytest.mark.parametrize("path", ["twin", "kernel"])
@pytest.mark.parametrize("n, k, d", [(2048, 4, 8), (4096, 3, 100)])
def test_the_program_follows_a_plain_em_to_float32_rounding(
        n, k, d, path, monkeypatch):
    if path == "kernel":
        monkeypatch.setattr(ek, "use_em_estep_pallas", lambda *a: True)
    x, *first = mixture(n, k, d)
    model, got = _train(x, first, k)
    assert model.last_layout_stats["kernel"] == (
        "pallas" if path == "kernel" else "xla")
    want = plain_em(x, *first, ITERATIONS)
    for name, g, w in zip(("weights", "means", "covs", "quality"), got, want):
        assert _rel(g, w) < 1e-5, (name, _rel(g, w))
    assert np.all(np.diff(got[3]) < 0)          # EM never loses likelihood


def test_at_one_bfloat16_pass_the_comparison_fails(kernel, monkeypatch):
    """The kernel with its operands cut to one bfloat16 term: what the TPU's
    default precision makes of a float32 product."""
    monkeypatch.setattr(ek, "TERMS", 1)
    x, *first = mixture(4096, 3, 100)
    _, got = _train(x, first, 3)
    want = plain_em(x, *first, ITERATIONS)
    assert max(_rel(g, w) for g, w in zip(got, want)) > 100 * 1e-5


def _operands(rows, k, d, seed=1):
    rng = np.random.default_rng(seed)
    k_pad, d_pad, d_store = ek.padded(k, d)
    x = jnp.asarray(ek.stored_points(
        rng.standard_normal((rows, d)).astype(np.float32), d_store))
    a = jnp.asarray(np.tril(0.1 * rng.standard_normal((k, d, d)))
                    + np.eye(d), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)
    const = ek.padded_const(jnp.asarray(rng.standard_normal(k), jnp.float32),
                            k_pad)
    w = ek.stacked_operand(a, b, k_pad, d_pad, d_store)
    return x, w, const, k_pad, d_pad


@pytest.mark.parametrize("rows, k, d, tiles", [
    (2048, 8, 100, None),             # aligned: one tile of two chunks
    (4096, 16, 20, (1024, 256)),      # four tiles, two groups
    (3000, 12, 100, None),            # a ragged last tile, phantom components
    (1000, 3, 5, (512, 256))])        # ragged, the block under a tile of rows
def test_the_kernel_is_its_twin(rows, k, d, tiles):
    x, w, const, k_pad, d_pad = _operands(rows, k, d)
    tile, chunk = tiles or ek.estep_tiles(rows, x.shape[1])
    fused = jax.jit(lambda x, w, c: ek.estep_pallas(
        x, w, c, k_pad, d_pad, tile, chunk, interpret=True))(x, w, const)
    twin = jax.jit(lambda x, w, c: ek.estep_xla(
        x, w, c, k_pad, d_pad, 700))(x, w, const)
    for got, want in zip(fused, twin):
        assert _rel(got, np.asarray(want, np.float64)) < 2e-6
    # the constant lane counts the rows: N_k sums to the block's rows
    s = np.asarray(fused[0]).reshape(k_pad, d_pad, -1)
    assert np.sum(s[:, d, d]) == pytest.approx(rows, rel=1e-6)


@pytest.mark.parametrize("path", ["twin", "kernel"])
def test_eight_workers_agree_with_one(path, monkeypatch):
    if path == "kernel":
        monkeypatch.setattr(ek, "use_em_estep_pallas", lambda *a: True)
    x, *first = mixture(8 * 1024, 4, 8, seed=3)
    _, one = _train(x, first, 4, workers=1, iterations=3)
    _, eight = _train(x, first, 4, workers=8, iterations=3)
    for a, b in zip(one, eight):
        assert _rel(a, np.asarray(b, np.float64)) < 1e-5


def _shapes(jaxpr):
    """The shape of every value an equation makes, nested bodies included
    (a scan's, a shard_map's, a Pallas kernel's)."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple)) else [param]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _shapes(inner)


@pytest.mark.parametrize("path", ["twin", "kernel"])
def test_nothing_of_n_k_d_size_is_formed(path, monkeypatch):
    """At N = 65,536 and K = D = 100 the traced call's largest value, the
    kernel's VMEM temporaries included, is under a fiftieth of N·K·D (the old
    E-step's (N, K, D) differences), and no value but the stored points has
    a row a point: nothing per point and component."""
    if path == "kernel":
        monkeypatch.setattr(ek, "use_em_estep_pallas", lambda *a: True)
    n, k, d = 65_536, 100, 100
    sess = HarpSession(num_workers=1)
    model = em.EMGMM(sess, em.EMConfig(num_components=k, iterations=1))
    g = em._geometry(n, k, d)
    key = model._program(g, 1)
    shapes = (jax.ShapeDtypeStruct((n, g.d_store), jnp.float32),
              jax.ShapeDtypeStruct((k,), jnp.float32),
              jax.ShapeDtypeStruct((k, d), jnp.float32),
              jax.ShapeDtypeStruct((k, d, d), jnp.float32))
    made = set(_shapes(jax.make_jaxpr(model._fns[key])(*shapes).jaxpr))
    assert max(int(np.prod(s)) for s in made) < n * k * d // 50
    assert {s for s in made if n in s} <= {(n, g.d_store)}, made


def test_the_prepared_path_is_fit():
    x, *_ = mixture(1024, 3, 6, seed=5)
    model = em.EMGMM(HarpSession(num_workers=1),
                     em.EMConfig(num_components=3, iterations=4))
    weights, means, covs, ll = model.fit(x, seed=2)
    state = model.prepare(x, *model.first_model(x, seed=2))
    curve = []
    for _ in range(4):                       # one call an iteration
        state, quality = model.train_prepared(state, 1)
        curve.append(quality)
    for a, b in zip((weights, means, covs, ll),
                    (*model.parameters(state), -np.concatenate(curve))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_a_collapsed_component_is_counted():
    """A component planted far from every point keeps none of them: N_k = 0,
    counted once a call, and the job goes on without a NaN."""
    x, pi, means, covs = mixture(2048, 3, 4, seed=7)
    means[2] = 1e3
    before = metrics.DEFAULT.counters["em.components.collapsed"]
    model = em.EMGMM(HarpSession(num_workers=1),
                     em.EMConfig(num_components=3, iterations=2))
    state = model.prepare(x, pi, means, covs)
    for _ in range(2):
        state, quality = model.train_prepared(state)
    assert metrics.DEFAULT.counters["em.components.collapsed"] - before == 2
    weights, *_ = model.parameters(state)
    assert weights[2] == 0.0 and np.all(np.isfinite(quality))


def test_prepare_refuses_a_first_model_of_another_shape():
    x, pi, means, covs = mixture(256, 3, 4)
    model = em.EMGMM(HarpSession(num_workers=1), em.EMConfig(num_components=3))
    with pytest.raises(ValueError, match="first model"):
        model.prepare(x, pi, means[:, :3], covs)
    with pytest.raises(ValueError, match="divide"):
        em.EMGMM(HarpSession(num_workers=8), em.EMConfig(
            num_components=3)).prepare(x[:250], pi, means, covs)


def test_the_layout_is_reported():
    x, *first = mixture(2048, 3, 4)
    model = em.EMGMM(HarpSession(num_workers=1), em.EMConfig(num_components=3))
    model.prepare(x, *first)
    assert model.last_layout_stats == {
        "kernel": "xla", "row_tile": 0, "col_group": ek.GROUP,
        "resident_bytes": 2048 * 128 * 4}
    assert dataclasses.replace(em._geometry(2048, 3, 4)).d_store == 128
