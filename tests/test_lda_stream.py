"""The stream layout of CGS-LDA (``layout="stream"``): the program against a
plain float32 CGS of this file's own with the same hash and groups, the
``lda_sample`` kernel (interpret mode) against its ``jax.numpy`` twin,
calls that continue the chain, the CSR and matrix ways in, the 8-worker
mesh, int32 totals past 2^24, and no tokens x K value in the program."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harp_tpu.models import lda
from harp_tpu.ops import lda_kernels
from harp_tpu.session import HarpSession

D, W, K = 64, 500, 16
SEED = 2 ** 31 + 41


def _corpus(seed=0, docs=D, vocab=W, longest=200):
    rng = np.random.default_rng(seed)
    lens = rng.integers(1, longest + 1, docs)
    doc_ptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    # a few popular words, so that word rows hold real counts
    p = (np.arange(vocab) + 1.0) ** -1.1
    words = rng.choice(vocab, doc_ptr[-1], p=p / p.sum()).astype(np.int32)
    return doc_ptr, words


def _cfg(**kw):
    base = dict(num_topics=K, vocab=W, alpha=0.1, beta=0.01, epochs=1,
                layout="stream", groups_per_sweep=4)
    return lda.LDAConfig(**{**base, **kw})


@pytest.fixture(scope="module")
def one():
    return HarpSession(num_workers=1)


# -- a plain CGS of this file's own ----------------------------------------- #

def _fmix(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _u(seed, sweep, index):
    lo, hi = jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32(seed >> 32)
    g = jnp.uint32(0x9E3779B9)
    key = _fmix(_fmix(lo ^ _fmix(hi ^ g)) ^ _fmix(jnp.uint32(sweep) + g))
    bits = _fmix(key ^ _fmix(jnp.asarray(index, jnp.uint32)))
    return (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def _plain_cgs(doc_ptr, words, cfg, seed, sweeps, products=None):
    """Blocked CGS from the equations, whole groups at a time: returns each
    sweep's z (corpus order) and quality."""
    n, d = len(words), len(doc_ptr) - 1
    docs = np.repeat(np.arange(d), np.diff(doc_ptr))
    g = cfg.groups_per_sweep
    first = np.searchsorted(doc_ptr[:-1] * g, np.arange(g) * n, "left")
    bounds = [int(doc_ptr[b]) for b in first] + [n]
    z = np.asarray(jnp.minimum((_u(seed, 0, np.arange(n)) * jnp.float32(
        cfg.num_topics)).astype(jnp.int32), cfg.num_topics - 1))
    k = cfg.num_topics
    wt = np.zeros((cfg.vocab, k), np.int64)
    dt = np.zeros((d, k), np.int64)
    np.add.at(wt, (words, z), 1)
    np.add.at(dt, (docs, z), 1)
    rnd = (lambda x: x) if products is None else (
        lambda x: x.astype(products).astype(jnp.float32))
    zs, quality = [], []
    for sweep in range(1, sweeps + 1):
        u = _u(seed, sweep, np.arange(n))
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            nk = wt.sum(axis=0)
            own = np.arange(k)[None] == z[lo:hi, None]
            nd = jnp.asarray(dt[docs[lo:hi]] - own).astype(jnp.float32)
            nw = jnp.asarray(wt[words[lo:hi]] - own).astype(jnp.float32)
            r = jnp.asarray(np.where(own, 1.0 / ((nk - 1).astype(np.float32)
                                                 + np.float32(W * cfg.beta)),
                                     1.0 / (nk.astype(np.float32)
                                            + np.float32(W * cfg.beta))),
                            jnp.float32)
            p = (rnd(nd + cfg.alpha) * rnd(nw + cfg.beta)) * rnd(r)
            new = _stated_draw(np.asarray(p), np.asarray(u[lo:hi]), k)
            np.add.at(wt, (words[lo:hi], new), 1)
            np.add.at(wt, (words[lo:hi], z[lo:hi]), -1)
            np.add.at(dt, (docs[lo:hi], new), 1)
            np.add.at(dt, (docs[lo:hi], z[lo:hi]), -1)
            z = z.copy()
            z[lo:hi] = new
        zs.append(z.copy())
        quality.append(_neg_loglik(wt, dt, cfg, np.diff(doc_ptr)))
    return zs, np.asarray(quality)


def _stated_draw(p, u, k):
    """The inverse-CDF draw with the running sum added in the stated order:
    eight chains of 128 topics, each from zero topic by topic, then the
    chain totals into offsets one by one (float32 throughout)."""
    p = np.pad(p, ((0, 0), (0, 1024 - k))).reshape(len(p), 8, 128)
    a = np.zeros((len(p), 8, 128), np.float32)
    run = np.zeros((len(p), 8), np.float32)
    for m in range(128):
        run = run + p[:, :, m]
        a[:, :, m] = run
    offsets = np.zeros((len(p), 8), np.float32)
    whole = run[:, 0]
    for c in range(1, 8):
        offsets[:, c] = whole
        whole = whole + run[:, c]
    below = (offsets[:, :, None] + a) < (u * whole)[:, None, None]
    return np.minimum(below.reshape(len(p), -1)[:, :k].sum(axis=1), k - 1)


def _neg_loglik(wt, dt, cfg, lens):
    from scipy.special import gammaln as lg

    k = cfg.num_topics
    nk = wt.sum(axis=0)
    ll = (np.sum(lg(wt + cfg.beta) - lg(cfg.beta))
          - np.sum(lg(nk + cfg.vocab * cfg.beta) - lg(cfg.vocab * cfg.beta))
          + np.sum(lg(dt + cfg.alpha) - lg(cfg.alpha))
          - np.sum(lg(lens + k * cfg.alpha) - lg(k * cfg.alpha)))
    return -ll / wt.sum()


def _recount(doc_ptr, words, z, cfg):
    docs = np.repeat(np.arange(len(doc_ptr) - 1), np.diff(doc_ptr))
    wt = np.zeros((cfg.vocab, cfg.num_topics), np.int64)
    dt = np.zeros((len(doc_ptr) - 1, cfg.num_topics), np.int64)
    np.add.at(wt, (words, z), 1)
    np.add.at(dt, (docs, z), 1)
    return wt, dt


@pytest.fixture(scope="module")
def plain():
    doc_ptr, words = _corpus()
    return doc_ptr, words, _plain_cgs(doc_ptr, words, _cfg(), SEED, 5)


def test_one_sweep_draws_as_the_plain_cgs_and_counts_follow_z(one, plain):
    doc_ptr, words, (zs, _) = plain
    model = lda.LDA(one, _cfg())
    state, _ = model.train_prepared(model.prepare((doc_ptr, words), SEED), 1)
    wt, dt, z = model.parameters(state)
    # the running sum's order is stated: every draw is the plain CGS's
    assert np.array_equal(z, zs[0])
    want_wt, want_dt = _recount(doc_ptr, words, z, model.config)
    assert wt.dtype == np.int32 and np.array_equal(wt, want_wt)
    assert np.array_equal(dt, want_dt)


def test_the_quality_follows_the_plain_cgs_over_five_sweeps(one, plain):
    doc_ptr, words, (_, quality) = plain
    model = lda.LDA(one, _cfg())
    _, got = model.train_prepared(model.prepare((doc_ptr, words), SEED), 5)
    assert got.shape == (5,) and got[-1] < got[0]
    np.testing.assert_allclose(got, quality, rtol=1e-5)


def test_bfloat16_factors_fail_the_draw_agreement(plain):
    doc_ptr, words, (zs, _) = plain
    zb, _ = _plain_cgs(doc_ptr, words, _cfg(), SEED, 1,
                       products=jnp.bfloat16)
    assert np.mean(zb[0] == zs[0]) < 0.999


def test_two_calls_of_two_and_three_sweeps_are_one_of_five(one):
    doc_ptr, words = _corpus(3)
    model = lda.LDA(one, _cfg())
    first = model.prepare((doc_ptr, words), SEED)
    a, qa = model.train_prepared(first, 5)
    b, qb2 = model.train_prepared(first, 2)
    b, qb3 = model.train_prepared(b, 3)
    for x, y in zip(model.parameters(a), model.parameters(b)):
        assert np.array_equal(x, y)
    assert np.array_equal(qa, np.concatenate([qb2, qb3]))
    # the first state is still the first model: a job restarts from it
    again, _ = model.train_prepared(first, 2)
    assert np.array_equal(model.parameters(again)[2],
                          model.parameters(model.train_prepared(first, 2)[0])[2])


def test_fit_on_a_matrix_is_fit_on_the_same_corpus_as_csr(one):
    rng = np.random.default_rng(5)
    docs = rng.integers(0, W, (40, 30)).astype(np.int32)
    model = lda.LDA(one, _cfg(epochs=3))
    dt_m, wt_m, ll_m = model.fit(docs, seed=SEED)
    doc_ptr = np.arange(41, dtype=np.int64) * 30
    dt_c, wt_c, ll_c = model.fit((doc_ptr, docs.reshape(-1)), seed=SEED)
    assert np.array_equal(dt_m, dt_c) and np.array_equal(wt_m, wt_c)
    assert np.array_equal(ll_m, ll_c) and ll_m.shape == (3,)
    # the bucketed layout's value: the reference formula's word part
    assert ll_m[-1] > ll_m[0]
    np.testing.assert_allclose(ll_m[-1], lda.reference_log_likelihood(
        wt_m, model.config.beta, W), rtol=1e-5)


def test_auto_lays_a_csr_corpus_out_as_the_stream_and_a_matrix_in_buckets(
        one):
    doc_ptr, words = _corpus(9, docs=16)
    cfg = _cfg(layout="auto")
    model = lda.LDA(one, cfg)
    assert lda._is_stream(model.prepare((doc_ptr, words), SEED))
    assert model.last_layout_stats["layout"] == "stream"
    docs = np.random.default_rng(9).integers(0, W, (16, 8)).astype(np.int32)
    assert not lda._is_stream(model.prepare(docs, 3))
    for kw in ({"method": "cvb0"}, {"num_model_slices": 2}):
        with pytest.raises(ValueError, match="CSR corpus runs on the stream"):
            lda.LDA(one, _cfg(layout="auto", **kw)).prepare(
                (doc_ptr, words), SEED)
    with pytest.raises(ValueError, match="layout"):
        lda.LDA(one, _cfg(layout="bucketed"))


@pytest.mark.parametrize("corpus", ["whole", "ragged", "one_document",
                                    "runs"],
                         ids=["False", "True", "one_document", "runs"])
def test_the_kernel_in_interpret_mode_is_its_twin(one, monkeypatch, corpus):
    """Equal-length documents of 128 tokens (tiles that are whole parts),
    ragged ones, groups of one long document over six words of one word
    block (every token of a part adds to the same document row, so each
    count must see the previous token's; each group's last part is partial,
    with pad lanes), and groups of three word-block runs (three tiles, one
    partial tile, then one tile that ends the group: the word block's change
    is folded at a block change and at the group's last tile): one sweep on
    the kernel draws every token as the twin, and both tables come out the
    same."""
    rng = np.random.default_rng(7)
    if corpus == "ragged":
        doc_ptr, words = _corpus(7, docs=24, longest=300)
    elif corpus == "one_document":
        doc_ptr = np.cumsum([0, 1000, 700, 1100, 300]).astype(np.int64)
        words = rng.integers(0, 6, doc_ptr[-1]).astype(np.int32)
    elif corpus == "runs":
        # two documents a group: 2,060 tokens of block 0 (tiles of 1,024,
        # 1,024 and 12), 40 of block 1, 700 of block 3 (one tile, 6 parts);
        # the last group moves 60 of block 3's to block 2, a fourth run, so
        # the others' windows end in steps past their tiles
        doc_ptr = np.arange(9, dtype=np.int64) * 1400
        words = np.concatenate([np.concatenate([
            rng.integers(0, 128, 1030), rng.integers(128, 256, 20),
            rng.integers(256, 384, 30 * (d >= 6)),
            rng.integers(384, W, 350 - 30 * (d >= 6))])
            for d in range(8)]).astype(np.int32)
    else:
        doc_ptr = np.arange(17, dtype=np.int64) * 128
        words = rng.integers(0, W, 16 * 128).astype(np.int32)
    out = {}
    for kernel in (False, True):
        monkeypatch.setattr(lda_kernels, "use_lda_sample_pallas",
                            lambda *a, k=kernel: k)
        model = lda.LDA(one, _cfg())
        state = model.prepare((doc_ptr, words), SEED)
        assert state[0].kernel is kernel
        assert model.last_layout_stats["count_write"] == (
            "gemm_scatter" if kernel else "segment")
        if corpus == "runs":
            assert model.last_layout_stats["word_block_runs"] == 3 * 3 + 4
            assert model.last_layout_stats["parts_per_run"] == 4 * 24 / 13
        state, quality = model.train_prepared(state, 1)
        out[kernel] = (model.parameters(state), quality)
    for x, y in zip(out[False][0], out[True][0]):
        assert np.array_equal(x, y)
    np.testing.assert_allclose(out[True][1], out[False][1], rtol=1e-6)


def test_topic_slot_is_the_tables_slot_of_every_topic():
    topics = np.arange(lda_kernels.KP, dtype=np.int32)
    for z in (topics, jnp.asarray(topics)):
        assert np.array_equal(np.asarray(lda_kernels.topic_slot(z)),
                              lda_kernels.TOPIC_SLOT)
    assert np.array_equal(
        lda_kernels.SLOT_TOPIC[lda_kernels.topic_slot(topics)], topics)


def test_eight_workers_keep_the_counts_and_reach_one_workers_likelihood(
        session, one):
    doc_ptr, words = _corpus(11, docs=96)
    cfg = _cfg(epochs=20)
    got = {}
    for sess in (session, one):
        model = lda.LDA(sess, cfg)
        state, quality = model.train_prepared(
            model.prepare((doc_ptr, words), SEED), 20)
        wt, dt, z = model.parameters(state)
        want_wt, want_dt = _recount(doc_ptr, words, z, cfg)
        assert np.array_equal(wt, want_wt) and np.array_equal(dt, want_dt)
        assert wt.sum() == len(words) and np.all(dt.sum(axis=1)
                                                 == np.diff(doc_ptr))
        got[sess.num_workers] = quality
    assert got[8][-1] < got[8][0]
    # the blocking differs (groups per worker), the chain is statistical:
    # the same quality within 2 %
    assert abs(got[8][-1] - got[1][-1]) < 0.02 * got[1][-1]


def test_int32_totals_stay_exact_past_two_to_the_24(one):
    """A head word planted at 2^24 + 5 tokens of topic 3 (in the counts the
    state carries): a sweep moves the real tokens and leaves the planted
    mass exact, where float32 would round it."""
    doc_ptr, words = _corpus(13)
    model = lda.LDA(one, _cfg())
    g, host, static, (z, wt, dt, nk, sweep) = model.prepare(
        (doc_ptr, words), SEED)
    head = 2 ** 24 + 5
    assert float(np.float32(head)) != head
    slot = lda_kernels.TOPIC_SLOT[3]
    plant = np.zeros((g.words_store, lda_kernels.KP), np.int32)
    plant[W + 7, slot] = head          # a padding word row: no real token
    wt = wt + jnp.asarray(plant.reshape(wt.shape))
    nk = nk + jnp.asarray(plant.sum(axis=0).reshape(nk.shape))
    state, _ = model.train_prepared((g, host, static, (z, wt, dt, nk, sweep)),
                                    1)
    out_wt = np.asarray(state[3][1]).reshape(g.words_store, -1)
    out_nk = np.asarray(state[3][3]).reshape(-1)
    assert out_wt[W + 7, slot] == head
    real = out_wt[:W][:, lda_kernels.TOPIC_SLOT[:K]]
    z_new = model.parameters(state)[2]
    assert np.array_equal(real, _recount(doc_ptr, words, z_new,
                                         model.config)[0])
    assert out_nk[slot] == head + real[:, 3].sum()


@pytest.mark.parametrize("kernel", [False, True])
def test_no_value_of_the_program_is_tokens_by_topics(one, monkeypatch,
                                                     kernel):
    n, k = 65_536, 256
    rng = np.random.default_rng(17)
    doc_ptr = np.arange(257, dtype=np.int64) * 256
    words = rng.integers(0, 2000, n).astype(np.int32)
    monkeypatch.setattr(lda_kernels, "use_lda_sample_pallas",
                        lambda *a: kernel)
    model = lda.LDA(one, _cfg(num_topics=k, vocab=2000))
    g, _, static, chain = model.prepare((doc_ptr, words), SEED)
    jaxpr = jax.make_jaxpr(
        lambda *a: lda._stream_sweeps(*a, g=g, sweeps=1))(*static, *chain)

    def sizes(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                yield int(np.prod(v.aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sizes(sub)

    assert max(sizes(jaxpr.jaxpr)) < n * k // 4


def test_the_stream_refuses_the_bucketed_variants(one):
    for kw in ({"method": "cvb0"}, {"num_model_slices": 2},
               {"quant": "int8"}, {"fused_dma": True},
               {"vocab_sub_block": 128}):
        with pytest.raises(ValueError, match="bucketed layout"):
            lda.LDA(one, _cfg(**kw))
    with pytest.raises(ValueError, match="layout"):
        lda.LDA(one, dataclasses.replace(_cfg(), layout="rows"))


def test_a_uci_docword_file_reads_as_csr(tmp_path):
    from harp_tpu.io import loaders

    path = tmp_path / "docword.tiny.txt"
    # documents 1 and 3 in file order, 2 after them: read by document
    path.write_text("3\n30\n6\n1 1 2\n1 30 1\n3 7 1\n2 3 4\n3 1 2\n"
                    "2 9 1\n")
    assert loaders.is_docword(str(path))
    doc_ptr, words, vocab = loaders.load_docword(str(path))
    assert vocab == 30 and doc_ptr.tolist() == [0, 3, 8, 11]
    assert words.tolist() == [0, 0, 29, 2, 2, 2, 2, 8, 6, 0, 0]
    bad = tmp_path / "docword.bad.txt"
    bad.write_text("2\n5\n1\n3 1 1\n")
    with pytest.raises(ValueError, match="outside"):
        loaders.load_docword(str(bad))
    matrix = tmp_path / "tokens.txt"
    matrix.write_text("1 2 3\n4 5 6\n")
    assert not loaders.is_docword(str(matrix))
