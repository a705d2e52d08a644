"""On-device live resharding (collectives.reshard) — ISSUE 11.

The device engine must be BITWISE the numpy oracle
(collectives.repartition) on the same maps, its traced program must never
carry more than ``chunk_bytes`` of row payload per collective (the
arXiv:2112.01075 memory-efficient bound, pinned by the jaxlint
``reshard_factor_*`` manifest rows), and the resume paths that ride it
(SGD-MF W/H incl. the previously-rejected 2-slice resize, the LDA chain,
serving KV shard restore/rebalance) must complete with NO host gather of a
sharded leaf.
"""

import json
import os

import jax
import numpy as np
import pytest

from harp_tpu.collectives import repartition as rep
from harp_tpu.collectives import reshard as rs
from harp_tpu.io import datagen
from harp_tpu.models.sgd_mf import identity_assign, serpentine_assign
from harp_tpu.session import HarpSession
from harp_tpu.utils.checkpoint import Checkpointer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCHEDULES = ("alltoall", "ring")


@pytest.fixture(scope="module")
def sess8():
    return HarpSession(num_workers=8)


@pytest.fixture(scope="module")
def sess4():
    return HarpSession(num_workers=4)


def _collectives(fn, args):
    """(name, operand bytes) of every cross-worker collective in the traced
    program (the walker mirrors tools/jaxlint/checkers_jaxpr)."""
    out = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name in ("all_to_all", "ppermute", "psum",
                                      "all_gather", "psum_scatter",
                                      "reduce_scatter"):
                out.append((eqn.primitive.name, sum(
                    int(np.prod(v.aval.shape, initial=1))
                    * v.aval.dtype.itemsize for v in eqn.invars)))
            for v in eqn.params.values():
                items = v if isinstance(v, (list, tuple)) else [v]
                for it in items:
                    if hasattr(it, "eqns"):
                        walk(it)
                    elif hasattr(it, "jaxpr") and hasattr(it.jaxpr, "eqns"):
                        walk(it.jaxpr)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


# --------------------------------------------------------------------------- #
# engine: bitwise vs the numpy oracle, bounded rounds
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("schedule", SCHEDULES)
@pytest.mark.parametrize("old_world,new_world,n", [
    (4, 8, 97),      # grow, prime valid rows
    (8, 8, 64),      # same world, different maps
    (2, 8, 61),      # steep grow
])
def test_engine_bitwise_vs_oracle(sess8, rng, schedule, old_world,
                                  new_world, n):
    assert new_world == 8    # the module mesh
    old_rpb = -(-n // old_world) + 3      # padded slots on the old side too
    new_rpb = -(-n // new_world) + 2
    old_assign = serpentine_assign(rng.integers(1, 9, n), old_world)
    new_assign = identity_assign(n, new_world)
    saved = rng.standard_normal((old_world * old_rpb, 5)).astype(np.float32)
    fill_host = rng.standard_normal(
        (new_world * new_rpb, 5)).astype(np.float32)
    oracle = rep.repartition_factor(saved, old_assign, old_rpb, new_assign,
                                    new_rpb, n, fill_host.copy())
    old = rs.block_layout(old_assign, old_rpb, old_world)
    new = rs.block_layout(new_assign, new_rpb, new_world)
    out = rs.reshard_factor(sess8, saved, old, old_world, new, n,
                            sess8.scatter(fill_host), chunk_bytes=256,
                            schedule=schedule)
    np.testing.assert_array_equal(np.asarray(out), oracle)


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_engine_shrink_on_4worker_mesh(sess4, rng, schedule):
    # W8 -> W4: the supervisor's shrink-relaunch direction
    n = 53
    old_assign = serpentine_assign(rng.integers(1, 9, n), 8)
    new_assign = serpentine_assign(rng.integers(1, 9, n), 4)
    old_rpb, new_rpb = 7, 14
    saved = rep.permute_rows(
        rng.standard_normal((n, 3)).astype(np.float32), old_assign[0],
        old_assign[1], old_rpb, np.zeros((8 * old_rpb, 3), np.float32))
    fill = rng.standard_normal((4 * new_rpb, 3)).astype(np.float32)
    oracle = rep.repartition_factor(saved, old_assign, old_rpb, new_assign,
                                    new_rpb, n, fill.copy())
    out = rs.reshard_factor(
        sess4, saved, rs.block_layout(old_assign, old_rpb, 8), 8,
        rs.block_layout(new_assign, new_rpb, 4), n, sess4.scatter(fill),
        chunk_bytes=128, schedule=schedule)
    np.testing.assert_array_equal(np.asarray(out), oracle)


def test_padded_slots_keep_fill_bitwise(sess8, rng):
    # rows no id maps to are the FILL's (fresh-init semantics)
    n = 10
    old_assign = identity_assign(n, 4)
    new_assign = identity_assign(n, 8)
    fill = rng.standard_normal((8 * 4, 2)).astype(np.float32)
    saved = rng.standard_normal((4 * 3, 2)).astype(np.float32)
    out = np.asarray(rs.reshard_factor(
        sess8, saved, rs.block_layout(old_assign, 3, 4), 4,
        rs.block_layout(new_assign, 4, 8), n, sess8.scatter(fill)))
    new_pos = rs.block_layout(new_assign, 4, 8).device_positions(n)
    untouched = np.setdiff1d(np.arange(32), new_pos)
    np.testing.assert_array_equal(out[untouched], fill[untouched])


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_traced_rounds_respect_chunk_bytes(sess8, rng, schedule):
    # the acceptance bound: per-collective payload <= chunk_bytes in the
    # TRACED program (what jaxlint pins via the reshard_factor_* rows)
    n, r, chunk = 97, 8, 512
    old = rs.block_layout(serpentine_assign(rng.integers(1, 9, n), 4),
                          28, 4)
    new = rs.block_layout(identity_assign(n, 8), 16, 8)
    saved = rng.standard_normal((4 * 28, r)).astype(np.float32)
    plan = rs.plan_factor_reshard(old, 4, new, 8, n, r * 4,
                                  chunk_bytes=chunk, schedule=schedule)
    assert plan.rounds > 1, "shape must force multiple rounds"
    fn, args = rs.prepare_reshard(
        sess8, saved, plan, sess8.scatter(np.zeros((8 * 16, r),
                                                   np.float32)))
    colls = _collectives(fn, args)
    assert colls, "program must move rows through collectives"
    assert all(b <= chunk for _, b in colls), colls
    # and the manifest pins exactly these per-round bytes
    with open(os.path.join(REPO, "tools", "collective_budget.json")) as f:
        budget = json.load(f)["targets"]
    key = ("reshard_factor_a2a" if schedule == "alltoall"
           else "reshard_factor_ring")
    assert key in budget, "reshard step program must be jaxlint-pinned"
    assert budget[key]["bytes_per_step"] <= 512 * (
        1 if schedule == "alltoall" else 7)


def test_plan_validation_is_loud(rng):
    with pytest.raises(ValueError, match="alltoall|ring"):
        rs.plan_moves(np.arange(4), np.arange(4), 8, 8, 4, 4,
                      schedule="gather")
    with pytest.raises(ValueError, match="collide"):
        rs.plan_moves(np.arange(4), np.zeros(4, np.int64), 8, 8, 4, 4)
    with pytest.raises(ValueError, match="outside the new layout"):
        rs.plan_moves(np.arange(4), np.array([0, 1, 2, 99]), 8, 8, 4, 4)
    with pytest.raises(ValueError, match="outside the flat leaf"):
        rs.plan_moves(np.array([99]), np.array([0]), 8, 8, 4, 4)


def test_bytes_moved_accounting(rng):
    # moved_rows counts only cross-worker rows; the host path's cost is the
    # full table on every worker — the asymmetry the bench rows report
    n = 32
    old = rs.block_layout(identity_assign(n, 4), 8, 4)
    new = rs.block_layout(identity_assign(n, 8), 4, 8)
    plan = rs.plan_factor_reshard(old, 4, new, 8, n, 16)
    assert plan.moved_rows + plan.local_rows_moved == n
    assert plan.bytes_moved == plan.moved_rows * 16


# --------------------------------------------------------------------------- #
# sgd_mf: device resume bitwise, incl. the 2-slice resize, NO host gather
# --------------------------------------------------------------------------- #

def _ratings():
    return datagen.sparse_ratings(64, 64, rank=4, density=0.25, seed=3)


def _mf_cfg(**kw):
    from harp_tpu.models import sgd_mf

    base = dict(rank=4, epochs=2, layout="sparse", minibatches_per_hop=2)
    base.update(kw)
    return sgd_mf.SGDMFConfig(**base)


@pytest.mark.parametrize("direction", ["shrink", "grow"])
def test_sgd_mf_device_resume_bitwise(tmp_path, sess8, sess4, direction):
    from harp_tpu.models import sgd_mf

    rows, cols, vals = _ratings()
    a, b = (sess8, sess4) if direction == "shrink" else (sess4, sess8)
    m_a = sgd_mf.SGDMF(a, _mf_cfg())
    ck = Checkpointer(str(tmp_path / "ck"))
    w_a, h_a, _, _ = m_a.fit_checkpointed(
        m_a.prepare(rows, cols, vals, 64, 64, seed=0), ck, save_every=1)

    m_dev = sgd_mf.SGDMF(b, _mf_cfg(reshard="device"))
    w_b, h_b, rmse_b, start = m_dev.fit_checkpointed(
        m_dev.prepare(rows, cols, vals, 64, 64, seed=0),
        Checkpointer(str(tmp_path / "ck")), save_every=1)
    assert start == 2 and len(rmse_b) == 0
    np.testing.assert_array_equal(w_b, w_a)
    np.testing.assert_array_equal(h_b, h_a)

    # device path leaf-for-leaf vs the host oracle path
    m_host = sgd_mf.SGDMF(b, _mf_cfg(reshard="host"))
    w_c, h_c, _, _ = m_host.fit_checkpointed(
        m_host.prepare(rows, cols, vals, 64, 64, seed=0),
        Checkpointer(str(tmp_path / "ck")), save_every=1)
    np.testing.assert_array_equal(w_c, w_b)
    np.testing.assert_array_equal(h_c, h_b)


def test_sgd_mf_2slice_resize_now_supported(tmp_path, sess8, sess4):
    # the PR 8 loud rejection, turned into a tested supported case: a
    # 2-slice W8 checkpoint resumes into a 2-slice W4 gang (and the
    # finalized factors are bitwise), through the worker-major half-slice
    # layout on BOTH sides
    from harp_tpu.models import sgd_mf

    rows, cols, vals = _ratings()
    m8 = sgd_mf.SGDMF(sess8, _mf_cfg(num_slices=2))
    ck = Checkpointer(str(tmp_path / "ck"))
    w_a, h_a, _, _ = m8.fit_checkpointed(
        m8.prepare(rows, cols, vals, 64, 64, seed=0), ck, save_every=1)

    m4 = sgd_mf.SGDMF(sess4, _mf_cfg(num_slices=2, reshard="device"))
    w_b, h_b, _, start = m4.fit_checkpointed(
        m4.prepare(rows, cols, vals, 64, 64, seed=0),
        Checkpointer(str(tmp_path / "ck")), save_every=1)
    assert start == 2
    np.testing.assert_array_equal(w_b, w_a)
    np.testing.assert_array_equal(h_b, h_a)


def test_sgd_mf_slice_count_change_resume(tmp_path, sess8, sess4):
    # 2-slice checkpoint into a 1-slice config across a resize: the layouts
    # differ in bin placement AND bin count — the maps route it exactly
    from harp_tpu.models import sgd_mf

    rows, cols, vals = _ratings()
    m8 = sgd_mf.SGDMF(sess8, _mf_cfg(num_slices=2))
    ck = Checkpointer(str(tmp_path / "ck"))
    w_a, h_a, _, _ = m8.fit_checkpointed(
        m8.prepare(rows, cols, vals, 64, 64, seed=0), ck, save_every=1)
    m4 = sgd_mf.SGDMF(sess4, _mf_cfg(num_slices=1))
    w_b, h_b, _, start = m4.fit_checkpointed(
        m4.prepare(rows, cols, vals, 64, 64, seed=0),
        Checkpointer(str(tmp_path / "ck")), save_every=1)
    assert start == 2
    np.testing.assert_array_equal(w_b, w_a)
    np.testing.assert_array_equal(h_b, h_a)


@pytest.mark.parametrize("mode, num_slices", [
    ("device", 1), ("ring", 1), ("host", 1), ("device", 2)])
def test_sgd_mf_dense_unaligned_resume_under_another_layout(
        tmp_path, sess4, mode, num_slices):
    """The dense layout at a shape off every tile (rank 12, 13-row stripes,
    27- and 53-column blocks): a W=4 run's checkpoints hold the LOGICAL
    tables (no stored pad row, column or rank column), and a W=2 resume of
    them finalizes bitwise what the uninterrupted W=4 run returned, through
    every reshard path."""
    from harp_tpu.models import sgd_mf
    from harp_tpu.utils import checkpoint as ckpt_lib

    m, n = 103, 105
    rows, cols, vals = datagen.sparse_ratings(m, n, rank=4, density=0.3,
                                              seed=5)
    cfg = dict(rank=12, layout="dense", minibatches_per_hop=2, epochs=3,
               lr=0.02)
    m4 = sgd_mf.SGDMF(sess4, sgd_mf.SGDMFConfig(**cfg))
    st4 = m4.prepare(rows, cols, vals, m, n, seed=0)
    g4 = st4[4][6]
    assert (g4.s_rows, g4.cpb, g4.rank) == (13, 27, 12)
    assert (g4.s_store, g4.cpb_store, g4.rank_store) == (128, 256, 16)
    w_a, h_a, rmse_a, _ = m4.fit_checkpointed(
        st4, Checkpointer(str(tmp_path / "ck")), save_every=1)
    assert w_a.shape == (m, 12) and h_a.shape == (n, 12)
    assert rmse_a[-1] < rmse_a[0]

    _, saved, meta = Checkpointer(str(tmp_path / "ck")).restore_latest_valid(
        like_from_meta=lambda mt: ckpt_lib.meta_like(mt), return_meta=True)
    assert np.shape(saved["w"]) == (4 * g4.rpw, 12)
    assert np.shape(saved["h"]) == (4 * g4.cpb, 12)

    sess2 = HarpSession(num_workers=2)
    m2 = sgd_mf.SGDMF(sess2, sgd_mf.SGDMFConfig(
        **cfg, reshard=mode, num_slices=num_slices))
    st2 = m2.prepare(rows, cols, vals, m, n, seed=0)
    assert st2[4][6].cpb == -(-n // (2 * num_slices))
    w_b, h_b, rmse_b, start = m2.fit_checkpointed(
        st2, Checkpointer(str(tmp_path / "ck")), save_every=1)
    assert start == 3 and len(rmse_b) == 0
    np.testing.assert_array_equal(w_b, w_a)
    np.testing.assert_array_equal(h_b, h_a)


def test_sgd_mf_dense_unaligned_same_world_resume_is_bitwise(tmp_path,
                                                             sess4):
    """Interrupted after 2 of 4 epochs and resumed in the same world: the
    logical tables go back into the stored arrays, and the run ends bitwise
    where the uninterrupted one does."""
    from harp_tpu.models import sgd_mf

    m, n = 103, 105
    rows, cols, vals = datagen.sparse_ratings(m, n, rank=4, density=0.3,
                                              seed=5)
    cfg = sgd_mf.SGDMFConfig(rank=12, layout="dense", minibatches_per_hop=2,
                             epochs=4, lr=0.02)
    model = sgd_mf.SGDMF(sess4, cfg)
    state = model.prepare(rows, cols, vals, m, n, seed=0)
    w_a, h_a, rmse_a, _ = model.fit_checkpointed(
        state, Checkpointer(str(tmp_path / "whole")), save_every=2)
    model.fit_checkpointed(state, Checkpointer(str(tmp_path / "cut")),
                           epochs=2, save_every=2)
    w_b, h_b, rmse_b, start = model.fit_checkpointed(
        state, Checkpointer(str(tmp_path / "cut")), save_every=2)
    assert start == 2
    np.testing.assert_array_equal(rmse_b, rmse_a[2:])
    np.testing.assert_array_equal(w_b, w_a)
    np.testing.assert_array_equal(h_b, h_a)


def test_sgd_mf_device_resume_never_gathers_factors(tmp_path, sess8, sess4,
                                                    monkeypatch):
    # the acceptance assert: the device reshard path never fetches a
    # factor-table device array to host — mesh.fetch (the only
    # sharded-leaf gather seam) is poisoned during the resume restore
    from harp_tpu.models import sgd_mf

    rows, cols, vals = _ratings()
    m8 = sgd_mf.SGDMF(sess8, _mf_cfg())
    ck = Checkpointer(str(tmp_path / "ck"))
    m8.fit_checkpointed(m8.prepare(rows, cols, vals, 64, 64, seed=0), ck,
                        save_every=1)

    from harp_tpu.utils import checkpoint as ckpt_lib

    m4 = sgd_mf.SGDMF(sess4, _mf_cfg(reshard="device"))
    st4 = m4.prepare(rows, cols, vals, 64, 64, seed=0)

    def poisoned_fetch(x):
        raise AssertionError(
            "device reshard path gathered a sharded leaf to host")

    _, saved, meta = Checkpointer(str(tmp_path / "ck")).restore_latest_valid(
        like_from_meta=lambda m: ckpt_lib.meta_like(m), return_meta=True)
    monkeypatch.setattr(sgd_mf, "fetch", poisoned_fetch)
    out = m4._repartition_saved(saved, meta, st4)
    assert isinstance(out["w"], jax.Array)
    assert isinstance(out["h"], jax.Array)
    # while the host oracle path DOES fetch (the behavior being replaced)
    m4h = sgd_mf.SGDMF(sess4, _mf_cfg(reshard="host"))
    st4h = m4h.prepare(rows, cols, vals, 64, 64, seed=0)
    with pytest.raises(AssertionError, match="gathered a sharded leaf"):
        m4h._repartition_saved(saved, meta, st4h)


def test_reshard_mode_validation(sess8):
    from harp_tpu.models import sgd_mf

    m = sgd_mf.SGDMF(sess8, _mf_cfg(reshard="teleport"))
    with pytest.raises(ValueError, match="auto\\|device\\|ring\\|host"):
        m._reshard_mode()


# --------------------------------------------------------------------------- #
# lda + kmeans parity
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("direction", ["shrink", "grow"])
def test_lda_device_resume_exact(tmp_path, sess8, sess4, direction):
    from harp_tpu.models import lda

    docs = datagen.lda_corpus(16, 32, 4, 12, seed=5)
    a, b = (sess8, sess4) if direction == "shrink" else (sess4, sess8)
    cfg = lda.LDAConfig(num_topics=4, vocab=32, epochs=2)
    m_a = lda.LDA(a, cfg)
    ck = Checkpointer(str(tmp_path / "ck"))
    dt_a, wt_a, _, _ = m_a.fit_checkpointed(m_a.prepare(docs, seed=0), ck,
                                            save_every=1)
    m_b = lda.LDA(b, lda.LDAConfig(num_topics=4, vocab=32, epochs=2,
                                   reshard="device"))
    dt_b, wt_b, ll_b, start = m_b.fit_checkpointed(
        m_b.prepare(docs, seed=0), Checkpointer(str(tmp_path / "ck")),
        save_every=1)
    assert start == 2 and len(ll_b) == 0
    np.testing.assert_array_equal(np.asarray(dt_b), np.asarray(dt_a))
    np.testing.assert_array_equal(np.asarray(wt_b), np.asarray(wt_a))
    # and leaf-for-leaf vs the host rematch/rebuild oracle
    m_c = lda.LDA(b, lda.LDAConfig(num_topics=4, vocab=32, epochs=2,
                                   reshard="host"))
    dt_c, wt_c, _, _ = m_c.fit_checkpointed(
        m_c.prepare(docs, seed=0), Checkpointer(str(tmp_path / "ck")),
        save_every=1)
    np.testing.assert_array_equal(np.asarray(dt_c), np.asarray(dt_b))
    np.testing.assert_array_equal(np.asarray(wt_c), np.asarray(wt_b))


def test_lda_2slice_resize_now_supported(tmp_path, sess8, sess4):
    from harp_tpu.models import lda

    docs = datagen.lda_corpus(16, 32, 4, 12, seed=5)
    cfg = lda.LDAConfig(num_topics=4, vocab=32, epochs=2,
                        num_model_slices=2)
    m8 = lda.LDA(sess8, cfg)
    ck = Checkpointer(str(tmp_path / "ck"))
    dt_a, wt_a, _, _ = m8.fit_checkpointed(m8.prepare(docs, seed=0), ck,
                                           save_every=1)
    m4 = lda.LDA(sess4, lda.LDAConfig(num_topics=4, vocab=32, epochs=2,
                                      num_model_slices=2))
    dt_b, wt_b, _, start = m4.fit_checkpointed(
        m4.prepare(docs, seed=0), Checkpointer(str(tmp_path / "ck")),
        save_every=1)
    assert start == 2
    np.testing.assert_array_equal(np.asarray(dt_b), np.asarray(dt_a))
    np.testing.assert_array_equal(np.asarray(wt_b), np.asarray(wt_a))


def test_kmeans_resize_is_replicated_identity(tmp_path, sess8, sess4):
    # the kmeans leg of the parity matrix: replicated leaves re-shard as
    # the identity — a W8 checkpoint's centroids land bitwise in a W4 gang
    from harp_tpu.models import kmeans as km

    pts = datagen.dense_points(256, 8, seed=0, num_clusters=4)
    cen0 = datagen.initial_centroids(pts, 4, seed=1)
    cfg = km.KMeansConfig(4, 8, iterations=2)
    m8 = km.KMeans(sess8, cfg)
    ck = Checkpointer(str(tmp_path / "ck"))
    cen_a, _, _ = m8.fit_checkpointed(*m8.prepare(pts, cen0), ck,
                                      save_every=1)
    _, saved = Checkpointer(str(tmp_path / "ck")).restore_latest_valid(
        like={"centroids": np.zeros_like(np.asarray(cen_a))})
    m4 = km.KMeans(sess4, cfg)
    cen_b, costs_b, start = m4.fit_checkpointed(*m4.prepare(pts, cen0),
                                                Checkpointer(
                                                    str(tmp_path / "ck")),
                                                save_every=1)
    assert start == 2 and len(costs_b) == 0
    np.testing.assert_array_equal(np.asarray(cen_b),
                                  np.asarray(saved["centroids"]))


# --------------------------------------------------------------------------- #
# serving: shard restore + rebalance
# --------------------------------------------------------------------------- #

def _endpoint(sess, rng, name="mf"):
    from harp_tpu.serve import endpoints as serve_ep

    uf = rng.normal(size=(64, 8)).astype(np.float32)
    items = rng.normal(size=(32, 8)).astype(np.float32)
    return serve_ep.TopKEndpoint(sess, name, uf, items, k=4), uf


def test_topk_restore_shard_only_touches_lost_rank(sess8, rng):
    ep, uf = _endpoint(sess8, rng)
    ids = np.arange(0, 64, 3)
    baseline = ep.dispatch(ids[:8])
    keys_d, vals_d, counts_d, items_d = ep._state[:4]
    vals_h = np.asarray(vals_d)
    wiped = vals_h.copy()
    wiped[2] = 0.0                       # rank 2's shard is lost
    ep._state = (keys_d, ep.session.scatter(wiped), counts_d, items_d)
    assert ep.dispatch(ids[:8]) != baseline
    n = ep.restore_shard(2, uf)
    assert n == int(np.sum(np.arange(64) % 8 == 2))
    assert ep.dispatch(ids[:8]) == baseline
    after = np.asarray(ep._state[1])
    others = [r for r in range(8) if r != 2]
    np.testing.assert_array_equal(after[others], vals_h[others])


def test_topk_rebalance_moves_shards_and_keeps_answers(sess8, rng):
    ep, _ = _endpoint(sess8, rng)
    ids = np.arange(0, 64, 3)
    baseline = ep.dispatch(ids[:8])
    info = ep.rebalance(1)
    assert info["owners"][1] == 0, "straggler must own nothing after"
    assert info["moved"] >= int(np.sum(np.arange(64) % 8 == 1))
    assert ep.dispatch(ids[:8]) == baseline
    unk = ep.dispatch(np.array([999]))
    assert unk[0]["found"] is False
    # the owner-routed dispatch keeps the pinned collective shape: exactly
    # the 3 all_to_alls (+ 4 B overflow psum) of serve_topk_mf
    fn, args, _, _ = ep.prepared(np.arange(8))
    kinds = {}
    for name, b in _collectives(fn, args):
        kinds[name] = kinds.get(name, 0) + 1
    assert kinds == {"all_to_all": 3, "psum": 1}, kinds


def test_topk_rebalance_validation(sess8, rng):
    ep, _ = _endpoint(sess8, rng)
    with pytest.raises(ValueError, match="at least one rank"):
        ep.rebalance(list(range(8)))
    with pytest.raises(ValueError, match="outside the"):
        ep.rebalance(9)
    with pytest.raises(ValueError, match="outside the"):
        ep.restore_shard(8, np.zeros((64, 8), np.float32))
    with pytest.raises(ValueError, match="canonical factors"):
        ep.restore_shard(0, np.zeros((3, 8), np.float32))


def test_rebalance_from_report(sess8, rng, tmp_path):
    import time

    from harp_tpu.serve import endpoints as serve_ep

    ep, _ = _endpoint(sess8, rng, name="mf-report")
    ids = np.arange(0, 64, 3)
    baseline = ep.dispatch(ids[:8])
    # no report -> no-op
    assert serve_ep.rebalance_from_report(ep, str(tmp_path)) == []
    report_path = os.path.join(str(tmp_path), "straggler_report.json")
    # a STALE report (dead gang's leftover) earns no shard migration
    with open(report_path, "w") as f:
        json.dump({"suspects": [3], "bsp_suspects": [5], "num_ranks": 8,
                   "ts": 1}, f)
    assert serve_ep.rebalance_from_report(ep, str(tmp_path)) == []
    assert not ep._owner_routed
    # a fresh report drives the move
    with open(report_path, "w") as f:
        json.dump({"suspects": [3], "bsp_suspects": [5], "num_ranks": 8,
                   "ts": time.time()}, f)
    moved = serve_ep.rebalance_from_report(ep, str(tmp_path))
    assert moved == [3, 5]
    assert ep.dispatch(ids[:8]) == baseline
    assert ep._counts[3] == 0 and ep._counts[5] == 0


def test_rebalance_is_safe_under_live_dispatch(sess8, rng):
    # the "nothing restarts" contract under traffic: dispatch threads keep
    # answering (correctly) while rebalance swaps the (state, program)
    # pair — the resident lock makes the snapshot atomic
    import threading

    ep, _ = _endpoint(sess8, rng, name="mf-live")
    ids = np.arange(0, 64, 3)
    baseline = ep.dispatch(ids[:8])
    errors = []
    stop = threading.Event()

    def loop():
        while not stop.is_set():
            try:
                if ep.dispatch(ids[:8]) != baseline:
                    errors.append("wrong answer")
                    return
            except Exception as e:      # noqa: BLE001 — the test's assert
                errors.append(repr(e))
                return

    threads = [threading.Thread(target=loop) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        ep.rebalance(2)
        ep.restore_shard(0, _endpoint_uf(ep))
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert not errors, errors


def _endpoint_uf(ep):
    # reconstruct the canonical factors from the endpoint's live store (the
    # test built ids 0..63 dense, so owner/slot invert exactly)
    vals = np.asarray(ep._state[1])
    return vals[ep._owner, ep._slot]


def test_supervisor_straggler_ranks(tmp_path):
    import time

    from harp_tpu.parallel.supervisor import straggler_ranks

    assert straggler_ranks(None) == []
    assert straggler_ranks(str(tmp_path)) == []
    with open(os.path.join(str(tmp_path), "straggler_report.json"),
              "w") as f:
        json.dump({"suspects": [1, 9], "bsp_suspects": [2], "ts": 1}, f)
    assert straggler_ranks(str(tmp_path)) == [1, 2, 9]
    assert straggler_ranks(str(tmp_path), world=8) == [1, 2]
    # freshness gate: a 1970 report is stale for any sane bound, a fresh
    # one passes, a missing ts never passes a bounded read
    assert straggler_ranks(str(tmp_path), max_age_s=600.0) == []
    with open(os.path.join(str(tmp_path), "straggler_report.json"),
              "w") as f:
        json.dump({"suspects": [1], "bsp_suspects": [],
                   "ts": time.time()}, f)
    assert straggler_ranks(str(tmp_path), max_age_s=600.0) == [1]
    with open(os.path.join(str(tmp_path), "straggler_report.json"),
              "w") as f:
        json.dump({"suspects": [1], "bsp_suspects": []}, f)
    assert straggler_ranks(str(tmp_path), max_age_s=600.0) == []


# --------------------------------------------------------------------------- #
# manifest schema
# --------------------------------------------------------------------------- #

def test_manifest_pins_reshard_targets():
    with open(os.path.join(REPO, "tools", "collective_budget.json")) as f:
        targets = json.load(f)["targets"]
    a2a = targets["reshard_factor_a2a"]
    assert a2a["collectives"] == {"all_to_all": 1}
    assert a2a["bytes_per_step"] == 512        # == the traced chunk budget
    ring = targets["reshard_factor_ring"]
    assert set(ring["collectives"]) == {"ppermute"}
    reb = targets["serve_topk_mf_rebalanced"]
    assert reb["collectives"] == targets["serve_topk_mf"]["collectives"], \
        "rebalancing must not change the dispatch's collective shape"
