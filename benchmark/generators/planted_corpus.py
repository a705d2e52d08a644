"""A bag-of-words corpus planted by LDA's own generative story, at the shape
of a public collection, as CSR: ``doc_ptr`` (D + 1,) int64 and ``words``
(N,) int32, the documents' tokens back to back.

Structure, from ``structure_seed``: the Zipf(``word_zipf``) law over the
``vocab`` words, in a seeded order of the ids; ``topics`` topic-word
distributions, each Dirichlet(``topic_concentration`` · that law). The
sample, from ``--seed``: each of the ``docs`` documents' length, log-normal
with mean ``mean_doc_len`` and ``len_sigma`` in log space, rounded and
clipped to [8, 10,000]; its topic mixture,
Dirichlet(``doc_alpha``); each token's topic from the mixture, then its word
from the topic, both by inverse CDF (``searchsorted`` on cumulative tables).
The documents are made on the host in a fixed partition of blocks, each with
its own child seed, by threads: one seed gives one corpus on any machine.
N is whatever the lengths come to.
"""

from __future__ import annotations

import concurrent.futures

import numpy as np

_BLOCKS = 64
_THREADS = 8
_LENGTHS = (8, 10_000)       # a document's tokens, at least and at most


def _topic_words(params: dict) -> np.ndarray:
    """``(topics, vocab)`` cumulative word tables, float64."""
    v, k = int(params["vocab"]), int(params["topics"])
    rng = np.random.default_rng(
        np.random.SeedSequence([int(params["structure_seed"]), 0]))
    children = np.random.SeedSequence(
        [int(params["structure_seed"]), 1]).spawn(k)
    law = (np.arange(v, dtype=np.float64) + 1.0) ** -float(params["word_zipf"])
    law = (law / law.sum())[rng.permutation(v)]
    shape = float(params["topic_concentration"]) * law
    cdf = np.empty((k, v), np.float64)

    def fill(t: int) -> None:
        row = np.random.default_rng(children[t]).standard_gamma(shape)
        np.cumsum(row, out=cdf[t])
        cdf[t] /= cdf[t, -1]

    with concurrent.futures.ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill, range(k)))
    return cdf


def generate(params: dict, config: dict, seed: int) -> dict:
    d, k = int(params["docs"]), int(params["topics"])
    cdf = _topic_words(params)
    mu = np.log(float(params["mean_doc_len"])) - 0.5 * float(
        params["len_sigma"]) ** 2
    bounds = np.linspace(0, d, _BLOCKS + 1).astype(np.int64)
    children = np.random.SeedSequence([int(seed), 4]).spawn(_BLOCKS)
    alpha = float(params["doc_alpha"])

    def block(b: int):
        lo, hi = int(bounds[b]), int(bounds[b + 1])
        rng = np.random.default_rng(children[b])
        lens = np.clip(np.rint(rng.lognormal(mu, float(params["len_sigma"]),
                                             hi - lo)),
                       *_LENGTHS).astype(np.int64)
        theta = rng.standard_gamma(alpha, (hi - lo, k))
        # each document's cumulative mixture, shifted by its row so that one
        # searchsorted draws every token's topic
        theta = np.cumsum(theta, axis=1)
        theta /= theta[:, -1:]
        theta += np.arange(hi - lo)[:, None]
        doc = np.repeat(np.arange(hi - lo), lens)
        topic = np.searchsorted(theta.reshape(-1), doc + rng.random(len(doc)))
        topic = np.minimum(topic - doc * k, k - 1)
        u = rng.random(len(doc))
        words = np.empty(len(doc), np.int32)
        order = np.argsort(topic, kind="stable")
        starts = np.searchsorted(topic[order], np.arange(k + 1))
        for t in range(k):
            at = order[starts[t]:starts[t + 1]]
            words[at] = np.searchsorted(cdf[t], u[at])
        np.minimum(words, cdf.shape[1] - 1, out=words)
        return lens, words

    with concurrent.futures.ThreadPoolExecutor(_THREADS) as pool:
        parts = list(pool.map(block, range(_BLOCKS)))
    lens = np.concatenate([p[0] for p in parts])
    doc_ptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    words = np.concatenate([p[1] for p in parts])
    return {"doc_ptr": doc_ptr, "words": words,
            "samples_per_epoch": int(doc_ptr[-1])}
