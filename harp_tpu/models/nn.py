"""Mini-batch neural network training — data-parallel allreduce.

Reference parity: daal_nn (NNDaalCollectiveMapper.java:47 — mini-batch MLP
training on DAAL NN layers; gather of partial results:218, bcast of weights:250 —
BASELINE's "daal_nn mini-batch allreduce" workload).

TPU-native: an MLP expressed in pure jnp (matmuls + relu on the MXU); per
mini-batch each worker computes the gradient of its shard via ``jax.grad`` and
one psum averages it — the gather+bcast round-trip of the reference is a single
fused allreduce. The whole epoch loop (minibatch scan inside epoch scan) is one
compiled program.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harp_tpu import telemetry
from harp_tpu.parallel.mesh import WORKERS
from harp_tpu.session import HarpSession


@dataclasses.dataclass(frozen=True)
class NNConfig:
    layers: Tuple[int, ...] = (64, 32)   # hidden sizes
    num_classes: int = 2
    lr: float = 0.1
    momentum: float = 0.9
    batch_size: int = 32                 # per worker
    epochs: int = 10


def init_params(dims: Sequence[int], seed: int = 0) -> List:
    rng = np.random.default_rng(seed)
    params = []
    for d_in, d_out in zip(dims[:-1], dims[1:]):
        w = (rng.standard_normal((d_in, d_out)) *
             np.sqrt(2.0 / d_in)).astype(np.float32)
        params.append((jnp.asarray(w), jnp.zeros((d_out,), jnp.float32)))
    return params


def forward(params, x):
    h = x
    for w, b in params[:-1]:
        h = jax.nn.relu(h @ w + b)
    w, b = params[-1]
    return h @ w + b


def _loss(params, x, y, num_classes):
    logits = forward(params, x)
    onehot = jax.nn.one_hot(y, num_classes, dtype=logits.dtype)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


def _train(x, y, params0, cfg: NNConfig, axis_name: str = WORKERS):
    n_local = x.shape[0]
    bs = min(cfg.batch_size, n_local)
    nb = -(-n_local // bs)
    # wrap-around padding: the final partial batch is filled from the front so
    # every sample trains each epoch (no silent tail drop)
    sel = jnp.arange(nb * bs) % n_local
    xb = x[sel].reshape(nb, bs, -1)
    yb = y[sel].reshape(nb, bs)
    grad_fn = jax.value_and_grad(
        lambda p, a, t: _loss(p, a, t, cfg.num_classes))

    def mb_step(carry, xs):
        params, vel = carry
        bx, by = xs
        loss, g = grad_fn(params, bx, by)
        loss = jax.lax.pmean(loss, axis_name)
        g = jax.lax.pmean(g, axis_name)             # the allreduce
        vel = jax.tree.map(lambda v, gi: cfg.momentum * v - cfg.lr * gi, vel, g)
        params = jax.tree.map(lambda p, v: p + v, params, vel)
        return (params, vel), loss

    def epoch(carry, _):
        carry, losses = jax.lax.scan(mb_step, carry, (xb, yb))
        return carry, jnp.mean(losses)

    vel0 = jax.tree.map(jnp.zeros_like, params0)
    (params, _), losses = jax.lax.scan(epoch, (params0, vel0), None,
                                       length=cfg.epochs)
    return params, losses


class MLPClassifier:
    """daal_nn parity: distributed mini-batch MLP with momentum SGD."""

    def __init__(self, session: HarpSession, config: NNConfig):
        self.session = session
        self.config = config
        self.params = None
        self._fn = None

    def fit(self, x: np.ndarray, y: np.ndarray, seed: int = 0) -> np.ndarray:
        sess, cfg = self.session, self.config
        dims = (x.shape[1],) + tuple(cfg.layers) + (cfg.num_classes,)
        params0 = init_params(dims, seed)
        if self._fn is None:
            self._fn = sess.spmd(
                lambda a, t, p: _train(a, t, p, cfg),
                in_specs=(sess.shard(), sess.shard(), sess.replicate()),
                out_specs=(sess.replicate(), sess.replicate()))
        import time as _time

        t0 = _time.perf_counter()
        params, losses = self._fn(
            sess.scatter(jnp.asarray(x, jnp.float32)),
            sess.scatter(jnp.asarray(y, jnp.int32)), params0)
        self.params = jax.tree.map(np.asarray, params)
        losses = np.asarray(losses)
        # telemetry at the loss fetch that was already here (per-epoch
        # events, wall amortized over the scanned program)
        telemetry.record_chunk("nn", start=0, losses=losses.tolist(),
                               wall_s=_time.perf_counter() - t0,
                               ledger=telemetry.ledger_for("nn"))
        return losses

    def predict(self, x: np.ndarray) -> np.ndarray:
        logits = forward([(jnp.asarray(w), jnp.asarray(b))
                          for w, b in self.params], jnp.asarray(x, jnp.float32))
        return np.asarray(jnp.argmax(logits, axis=-1)).astype(np.int32)
