"""Plain reference for the ``kmeans-d100`` configuration: Lloyd's algorithm.

Written from the rule in ``harp_tpu/models/kmeans.py``'s docstring (distances
to every centroid, each point to its nearest, every centroid to the mean of
its points; the reported cost of an epoch is the sum of squared distances to
the centroids the epoch *started* from). Straightforward ``jax.numpy`` in
float32 with every product at ``highest`` precision; no lane padding, no
hoisted norms, no collectives. It imports nothing of the program and takes
nothing the program made: the points and the first centroids come from the
traffic generator.

Departures from a textbook Lloyd, both the program's documented rule:
a centroid that no point chose becomes the zero vector, and ties go to the
lowest centroid index.

``products`` rounds the operands of the two matrix products to a narrower
type (the sums stay float32): that is the lower-precision control of the
comparison, never the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HIGHEST = jax.lax.Precision.HIGHEST
_ROW_BLOCKS = 64      # rows are visited in this many blocks so that the
#                       (block, k) distance matrix stays small


def _rounded(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("products",))
def _epoch(blocks, centroids, products=None):
    k = centroids.shape[0]
    c = _rounded(centroids, products)
    c_sq = jnp.sum(centroids * centroids, axis=1)

    def visit(acc, x):
        sums, counts, cost = acc
        xr = _rounded(x, products)
        d2 = (jnp.sum(x * x, axis=1, keepdims=True) + c_sq[None, :]
              - 2.0 * jnp.dot(xr, c.T, precision=_HIGHEST))
        onehot = jax.nn.one_hot(jnp.argmin(d2, axis=1), k, dtype=jnp.float32)
        return (sums + jnp.dot(onehot.T, xr, precision=_HIGHEST),
                counts + jnp.sum(onehot, axis=0),
                cost + jnp.sum(jnp.min(d2, axis=1))), None

    zero = (jnp.zeros_like(centroids), jnp.zeros((k,), jnp.float32),
            jnp.zeros((), jnp.float32))
    (sums, counts, cost), _ = jax.lax.scan(visit, zero, blocks)
    return sums / jnp.maximum(counts, 1.0)[:, None], cost


class Reference:
    """Holds the cell's data once; ``advance`` follows the program's calls."""

    def __init__(self, config: dict, data: dict, num_workers: int = 1):
        del num_workers                  # Lloyd does not depend on the mesh
        points = data["points"]
        n, d = points.shape
        blocks = next(b for b in range(_ROW_BLOCKS, 0, -1) if n % b == 0)
        self._blocks = jnp.asarray(points).reshape(blocks, n // blocks, d)
        self._first = np.asarray(data["centroids0"], np.float32)
        self._k = int(config["num_centroids"])

    def initial(self) -> dict:
        return {"centroids": self._first[: self._k].copy()}

    def advance(self, state: dict, epochs: int, products=None):
        """``epochs`` Lloyd iterations from ``state``: the new state (host
        arrays) and each epoch's cost."""
        cen = jnp.asarray(state["centroids"])
        costs = []
        for _ in range(epochs):
            cen, cost = _epoch(self._blocks, cen, products=products)
            costs.append(cost)
        return ({"centroids": np.asarray(cen)},
                np.asarray(jnp.stack(costs), np.float64))

    def free(self) -> None:
        self._blocks = None
