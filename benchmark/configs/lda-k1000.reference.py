"""Plain reference for the ``lda-k1000`` configuration: collapsed Gibbs
sampling for LDA, in the blocked schedule the configuration states.

Written from the equations (a corpus of D documents over W words, K topics,
counts n_dk, n_wk, n_k that leave out the token being drawn)::

    p_k = (n_dk + α) · (n_wk + β) · r_k        r_k = 1 / (n_k + Wβ)
    z = #{k < K : c_k < u · c}                 (float32)

``z`` clipped to K − 1. The running sum ``c_k`` is added in the order the
configuration states: the topics in eight chains of 128 (topic 128σ + m the
m-th of chain σ; p = 0 past K), each chain summed topic by topic from zero
(a_σm), the chain totals t_σ = a_σ,127 into offsets topic by topic (o_0 = 0,
o_σ = o_σ-1 + t_σ-1), c_k = o_σ + a_σm and c = o_7 + t_7. A sweep is G
groups of whole documents, group g
starting at the first document whose token prefix reaches g·N/G; every token
of a group draws against the counts at the group's start, and the group's
changes are applied before the next. ``u`` is the configuration's counter
hash of (seed, sweep, the token's corpus index); the first model is the
hash's topics at sweep 0. A sweep's quality is ``−log p(w, z) / N`` after
it: MALLET's word part plus the document part.

Straightforward ``jax.numpy``: tokens in corpus order, in chunks small enough
that a (chunk, K) float32 array fits, rows gathered from (W, K) and (D, K)
int32 tables, a ``lax.scan`` over the 128 places of a chain for the running
sum, scatter-adds for the counts.
It imports nothing of the program and takes nothing the program made.

``products`` rounds the conditional's three factors (n_dk + α), (n_wk + β)
and r_k to a narrower type before the products, which stay float32: that
is the lower-precision control of the comparison, never the reference.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_CHUNK = 32768         # tokens a step: (chunk, K) float32 is 131 MB at K 1000
_GOLDEN = 0x9E3779B9
_CHAINS, _PER_CHAIN = 8, 128  # the running sum's chains, and topics a chain


def _fmix(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _key(seed: int, sweep: int):
    seed = int(seed) % (1 << 64)
    lo, hi = jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32(seed >> 32)
    g = jnp.uint32(_GOLDEN)
    return _fmix(_fmix(lo ^ _fmix(hi ^ g)) ^ _fmix(jnp.uint32(sweep) + g))


def _uniform(key, index):
    bits = _fmix(key ^ _fmix(index.astype(jnp.uint32)))
    return (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def _rounded(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def _draw(p, u, k):
    """z of each row of ``p`` (chunk, K) at ``u``: the running sum in its
    stated order."""
    n = p.shape[0]
    p = jnp.pad(p, ((0, 0), (0, _CHAINS * _PER_CHAIN - k)))
    places = p.reshape(n, _CHAINS, _PER_CHAIN).transpose(2, 1, 0)  # (m, σ, token)

    def step(a, x):
        return a + x, a + x

    totals, a = jax.lax.scan(step, jnp.zeros((_CHAINS, n), jnp.float32),
                             places)
    offsets, c = [jnp.zeros((n,), jnp.float32)], totals[0]
    for sigma in range(1, _CHAINS):
        offsets.append(c)
        c = c + totals[sigma]
    below = (jnp.stack(offsets)[None] + a) < (u * c)[None, None]
    real = (jnp.arange(_CHAINS)[None, :, None] * _PER_CHAIN
            + jnp.arange(_PER_CHAIN)[:, None, None]) < k
    return jnp.minimum(jnp.sum(below & real, axis=(0, 1), dtype=jnp.int32),
                       k - 1)


@functools.partial(jax.jit, static_argnames=("k", "vocab", "alpha", "beta",
                                             "span", "products"))
def _group(words, docs, z, wt, dt, nk, key, lo, hi, *, k, vocab, alpha,
           beta, span, products=None):
    """The tokens ``lo .. hi`` (at most ``span``) of the padded corpus: new
    topics, counts."""
    topics = jnp.arange(k)
    r_all = 1.0 / (nk.astype(jnp.float32) + jnp.float32(vocab * beta))
    r_own = 1.0 / ((nk - 1).astype(jnp.float32) + jnp.float32(vocab * beta))

    def draw(i, z_new):
        at = lo + i * _CHUNK
        w = jax.lax.dynamic_slice(words, (at,), (_CHUNK,))
        d = jax.lax.dynamic_slice(docs, (at,), (_CHUNK,))
        zo = jax.lax.dynamic_slice(z, (at,), (_CHUNK,))
        own = topics[None] == zo[:, None]
        nd = (dt[d] - own).astype(jnp.float32) + alpha
        nw = (wt[w] - own).astype(jnp.float32) + beta
        r = jnp.where(own, r_own[None], r_all[None])
        p = (_rounded(nd, products) * _rounded(nw, products)) * _rounded(
            r, products)
        zn = _draw(p, _uniform(key, at + jnp.arange(_CHUNK)), k)
        inside = at + jnp.arange(_CHUNK) < hi
        return jax.lax.dynamic_update_slice(z_new, jnp.where(inside, zn, zo),
                                            (at,))

    z_new = jax.lax.fori_loop(0, (hi - lo + _CHUNK - 1) // _CHUNK, draw, z)
    # the group's changes in one scatter-add a table and sign: a call of
    # XLA's scatter costs the TPU a fixed time, and flat indices (one a
    # count) are its fast form
    w, d, old, new = (jax.lax.dynamic_slice(a, (lo,), (span,)) * m
                      for a, m in ((words, k), (docs, k), (z, 1), (z_new, 1)))
    one = (jnp.arange(span) < hi - lo).astype(jnp.int32)
    wt = (wt.reshape(-1).at[w + new].add(one).at[w + old].add(-one)
          .reshape(-1, k))
    dt = (dt.reshape(-1).at[d + new].add(one).at[d + old].add(-one)
          .reshape(-1, k))
    nk = nk.at[new].add(one).at[old].add(-one)
    return z_new, wt, dt, nk


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "vocab",
                                             "tokens", "doc_const"))
def _quality(wt, dt, nk, *, alpha, beta, vocab, tokens, doc_const):
    lg = jax.scipy.special.gammaln

    def cells(n, prior):
        return jnp.sum(jnp.sum(jnp.where(
            n > 0, lg(n.astype(jnp.float32) + prior) - lg(jnp.float32(prior)),
            0.0), axis=1))

    wbeta = vocab * beta
    topic = jnp.sum(jnp.where(nk > 0, lg(nk.astype(jnp.float32) + wbeta)
                              - lg(jnp.float32(wbeta)), 0.0))
    ll = (cells(wt, beta) - topic + cells(dt, alpha)
          - jnp.float32(doc_const))
    return -ll / tokens


class Reference:
    """Holds the cell's corpus once; ``advance`` follows the program's
    calls."""

    def __init__(self, config: dict, data: dict, num_workers: int = 1):
        del num_workers         # the schedule is stated for one worker
        doc_ptr = np.asarray(data["doc_ptr"], np.int64)
        n, d = int(doc_ptr[-1]), len(doc_ptr) - 1
        self._k, self._w = int(config["num_topics"]), int(config["vocab"])
        self._alpha, self._beta = float(config["alpha"]), float(config["beta"])
        groups = int(config["groups_per_sweep"])
        first = np.searchsorted(doc_ptr[:-1] * groups,
                                np.arange(groups, dtype=np.int64) * n, "left")
        self._bounds = [int(doc_ptr[b]) for b in first] + [n]
        self._span = max(b - a for a, b in zip(self._bounds[:-1],
                                               self._bounds[1:]))
        pad = (-n) % _CHUNK + max(_CHUNK, self._span)
        lens = np.diff(doc_ptr)
        self._words = jnp.asarray(np.pad(np.asarray(data["words"], np.int32),
                                         (0, pad)))
        self._docs = jnp.asarray(np.pad(
            np.repeat(np.arange(d, dtype=np.int32), lens), (0, pad)))
        self._n, self._d = n, d
        self._seed = int(data["init_seed"])
        ka = self._k * self._alpha
        lgamma = np.vectorize(math.lgamma)
        uniq, counts = np.unique(lens, return_counts=True)
        self._doc_const = float(np.sum(counts * (lgamma(uniq + ka)
                                                 - lgamma(ka))))

    def initial(self) -> dict:
        u = _uniform(_key(self._seed, 0), jnp.arange(self._words.shape[0]))
        z = jnp.minimum((u * jnp.float32(self._k)).astype(jnp.int32),
                        self._k - 1)
        z = jnp.where(jnp.arange(z.shape[0]) < self._n, z, 0)
        one = (jnp.arange(z.shape[0]) < self._n).astype(jnp.int32)
        wt = jnp.zeros((self._w * self._k,), jnp.int32).at[
            self._words * self._k + z].add(one).reshape(self._w, self._k)
        dt = jnp.zeros((self._d * self._k,), jnp.int32).at[
            self._docs * self._k + z].add(one).reshape(self._d, self._k)
        nk = jnp.zeros((self._k,), jnp.int32).at[z].add(one)
        return {"word_topic": np.asarray(wt), "doc_topic": np.asarray(dt),
                "_z": np.asarray(z), "_nk": np.asarray(nk), "_sweep": 0}

    def advance(self, state: dict, epochs: int, products=None):
        """``epochs`` sweeps from ``state``: the new state (host arrays) and
        each sweep's quality."""
        z, wt, dt, nk = (jnp.asarray(state[k]) for k in
                         ("_z", "word_topic", "doc_topic", "_nk"))
        sweep = int(state["_sweep"])
        qualities = []
        for _ in range(epochs):
            sweep += 1
            key = _key(self._seed, sweep)
            for lo, hi in zip(self._bounds[:-1], self._bounds[1:]):
                z, wt, dt, nk = _group(
                    self._words, self._docs, z, wt, dt, nk, key, lo, hi,
                    k=self._k, vocab=self._w, alpha=self._alpha,
                    beta=self._beta, span=self._span, products=products)
            qualities.append(_quality(
                wt, dt, nk, alpha=self._alpha, beta=self._beta,
                vocab=self._w, tokens=self._n, doc_const=self._doc_const))
        return ({"word_topic": np.asarray(wt), "doc_topic": np.asarray(dt),
                 "_z": np.asarray(z), "_nk": np.asarray(nk),
                 "_sweep": sweep},
                np.asarray(jnp.stack(qualities), np.float64))

    def free(self) -> None:
        self._words = self._docs = None
