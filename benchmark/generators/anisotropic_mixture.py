"""``points`` rows in ``dim`` columns from ``components`` Gaussians with full,
anisotropic covariances, and the first model of an EM job.

Structure, from ``structure_seed``: centres ``center_scale * N(0, I)``;
component k's covariance ``R_k diag(λ_k) R_k'``, R_k a random orthogonal
matrix (QR of a Gaussian one, signs fixed) and λ_k log-uniform over
[``eig_min``, ``eig_max``]; mixing weights Zipf, ``(rank + 1)^-weight_exponent``
normalised, over the components in a seeded order; the first model's means
the centres plus ``init_offset * N(0, I)``. The sample, from ``--seed``:
each row's component drawn by the weights, then ``μ_k + R_k diag(√λ_k) z``,
z ~ N(0, I), made on the host in a fixed partition of row blocks, each with
its own child seed, by threads (one seed gives one data set on any machine).

The first model is the one ``EMGMM.fit`` starts from: weights uniform, every
covariance the sample's (``n - 1`` in the denominator, float64 by blocks)
plus ``1e-3 I``, stored float32.
"""

from __future__ import annotations

import concurrent.futures

import numpy as np

_BLOCKS = 32
_THREADS = 8


def _structure(params: dict, k: int, d: int):
    rng = np.random.default_rng(
        np.random.SeedSequence([int(params["structure_seed"]), 0]))
    centres = params["center_scale"] * rng.standard_normal((k, d))
    q, r = np.linalg.qr(rng.standard_normal((k, d, d)))
    rot = q * np.sign(np.diagonal(r, axis1=1, axis2=2))[:, None, :]
    lo, hi = np.log(params["eig_min"]), np.log(params["eig_max"])
    eig = np.exp(rng.uniform(lo, hi, (k, d)))
    weights = (np.arange(k) + 1.0) ** -float(params["weight_exponent"])
    weights = (weights / weights.sum())[rng.permutation(k)]
    means0 = centres + params["init_offset"] * rng.standard_normal((k, d))
    # x = centre + factor z: the covariance's square root R diag(sqrt(eig))
    factors = (rot * np.sqrt(eig)[:, None, :]).astype(np.float32)
    return (centres.astype(np.float32), factors, weights,
            means0.astype(np.float32))


def generate(params: dict, config: dict, seed: int) -> dict:
    n, d = int(params["points"]), int(config["dim"])
    k = int(params["components"])
    centres, factors, weights, means0 = _structure(params, k, d)
    cdf = np.cumsum(weights)
    points = np.empty((n, d), np.float32)
    bounds = np.linspace(0, n, _BLOCKS + 1).astype(np.int64)
    children = np.random.SeedSequence([int(seed), 3]).spawn(_BLOCKS)

    def fill(b: int):
        lo, hi = int(bounds[b]), int(bounds[b + 1])
        rng = np.random.default_rng(children[b])
        lab = np.minimum(np.searchsorted(cdf, rng.random(hi - lo)), k - 1)
        z = rng.standard_normal((hi - lo, d), dtype=np.float32)
        out = points[lo:hi]
        for c in np.unique(lab):
            at = lab == c
            out[at] = z[at] @ factors[c].T + centres[c]
        x = out.astype(np.float64)
        return x.sum(axis=0), x.T @ x

    with concurrent.futures.ThreadPoolExecutor(_THREADS) as pool:
        parts = list(pool.map(fill, range(_BLOCKS)))
    total = sum(s for s, _ in parts)
    second = sum(m for _, m in parts)
    cov = (second - np.outer(total, total) / n) / (n - 1)
    covs0 = np.broadcast_to((cov + 1e-3 * np.eye(d)).astype(np.float32),
                            (int(config["num_components"]), d, d)).copy()
    k0 = int(config["num_components"])
    return {"points": points, "weights0": np.full(k0, 1.0 / k0, np.float32),
            "means0": means0[:k0].copy(), "covs0": covs0,
            "samples_per_epoch": n}
