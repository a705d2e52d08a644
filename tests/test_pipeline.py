"""Streaming ingestion engine (io/pipeline, ISSUE 18): bounded-queue
backpressure, chunk-order determinism, stream-fed K-means bitwise parity,
object-store part-files, and the distributed COO→CSR regroup against the
host-shuffle oracle."""

import os
import time

import numpy as np
import pytest


def _write_parts(tmp_path, sizes, d=6, seed=7):
    rng = np.random.default_rng(seed)
    blocks, paths = [], []
    for i, n in enumerate(sizes):
        block = rng.standard_normal((n, d)).astype(np.float32)
        path = tmp_path / f"part-{i:03d}"
        np.savetxt(path, block, fmt="%.6f", delimiter=",")
        # reparse so expectations carry the exact %.6f round-trip values
        blocks.append(np.loadtxt(path, delimiter=",",
                                 dtype=np.float32, ndmin=2))
        paths.append(str(path))
    return paths, np.concatenate(blocks)


# --------------------------------------------------------------------------- #
# Reader-pool backpressure (DynamicScheduler out_capacity)
# --------------------------------------------------------------------------- #


def test_scheduler_bounded_output_backpressures_and_delivers():
    from harp_tpu.sched.dynamic import DynamicScheduler, Task

    class _Echo(Task):
        def run(self, item):
            return item

    sched = DynamicScheduler([_Echo() for _ in range(4)], out_capacity=2)
    sched.start()
    try:
        sched.submit_all(range(32))
        time.sleep(0.3)
        # producers are instant: without the bound all 32 results would be
        # resident by now; the bounded queue holds the pool at <= capacity
        assert sched._out.maxsize == 2
        assert sched._out.qsize() <= 2
        got = sorted(sched.wait_for_output() for _ in range(32))
        assert got == list(range(32))
    finally:
        sched.stop()


def test_scheduler_stop_with_full_output_queue_does_not_deadlock():
    from harp_tpu.sched.dynamic import DynamicScheduler, Task

    class _Echo(Task):
        def run(self, item):
            return item

    sched = DynamicScheduler([_Echo() for _ in range(2)], out_capacity=1)
    sched.start()
    sched.submit_all(range(16))
    time.sleep(0.2)        # workers now blocked publishing into the bound
    t0 = time.perf_counter()
    sched.stop()           # must drain-and-join, not hang on the full queue
    assert time.perf_counter() - t0 < 10.0


def test_stream_loader_backpressure_bound(tmp_path):
    from harp_tpu.io import pipeline as pl

    paths, _ = _write_parts(tmp_path, [40] * 8)
    loader = pl.StreamLoader(paths, chunk_rows=16, num_threads=4,
                             queue_depth=2)
    it = iter(loader)
    next(it)
    time.sleep(0.3)        # consumer stalls; the pool may NOT run ahead
    assert loader._sched._out.qsize() <= 2
    for _ in it:           # drain: every row still arrives, in order
        pass


# --------------------------------------------------------------------------- #
# Chunk determinism + counting pass
# --------------------------------------------------------------------------- #


def test_chunk_stream_deterministic_across_thread_counts(tmp_path):
    from harp_tpu.io import pipeline as pl

    sizes = [37, 5, 64, 1, 23]          # ragged on purpose
    paths, whole = _write_parts(tmp_path, sizes)

    def snapshot(**kw):
        chunks = list(pl.StreamLoader(paths, chunk_rows=32, **kw))
        return [(c.index, c.offset, c.rows, c.data.copy()) for c in chunks]

    ref = snapshot(serial=True)
    for kw in ({"num_threads": 1}, {"num_threads": 4},
               {"num_threads": 4, "queue_depth": 1}):
        got = snapshot(**kw)
        assert [(g[0], g[1], g[2]) for g in got] == \
            [(r[0], r[1], r[2]) for r in ref]
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(g[3], r[3])
    # fixed budget shape everywhere, zero-padded tail, exact coverage
    total = sum(sizes)
    assert all(r[3].shape == (32, whole.shape[1]) for r in ref)
    assert sum(r[2] for r in ref) == total
    flat = np.concatenate([r[3][:r[2]] for r in ref])
    np.testing.assert_array_equal(flat, whole)
    tail = ref[-1]
    assert not tail[3][tail[2]:].any()          # tail padding is zeros


def test_count_pass_totals(tmp_path):
    from harp_tpu.io import native_bridge, pipeline as pl

    if not native_bridge.available():
        pytest.skip("native parser not built")
    paths, whole = _write_parts(tmp_path, [10, 3, 9])
    loader = pl.StreamLoader(paths, chunk_rows=8)
    assert loader.total_rows == len(whole)
    assert loader.num_cols == whole.shape[1]
    assert loader.metrics.timing("ingest.count")["count"] == 1


def test_stream_over_memory_urls():
    """Object-store part-files ride the same pool (fsspec read timed as
    ingest.read, no native fast path, no counting pass)."""
    import fsspec

    from harp_tpu.io import loaders, pipeline as pl

    fs = fsspec.filesystem("memory")
    rng = np.random.default_rng(11)
    blocks = []
    try:
        for i in range(3):
            block = rng.standard_normal((12, 4)).astype(np.float32)
            blocks.append(block)
            with fsspec.open(f"memory://harp_pl_test/part-{i:02d}", "w") as f:
                for row in block:
                    f.write(",".join(f"{v:.6f}" for v in row) + "\n")
        paths = loaders.list_files("memory://harp_pl_test/")
        loader = pl.StreamLoader(paths, chunk_rows=10, num_threads=2)
        assert loader.total_rows is None        # no native count over URLs
        chunks = list(loader)
        flat = np.concatenate([c.data[:c.rows] for c in chunks])
        np.testing.assert_allclose(flat, np.concatenate(blocks), atol=1e-5)
        assert loader.metrics.timing("ingest.read")["count"] == 3
    finally:
        fs.rm("/harp_pl_test", recursive=True)


# --------------------------------------------------------------------------- #
# Stream-fed K-means: bitwise parity with the in-memory fit
# --------------------------------------------------------------------------- #


def test_fit_from_stream_bitwise_equals_in_memory(session, tmp_path):
    from harp_tpu.io import loaders, pipeline as pl
    from harp_tpu.models import kmeans as km

    paths, whole = _write_parts(tmp_path, [50, 17, 30], d=5)
    pts = loaders.truncate_to_workers(whole, session.num_workers)
    cen0 = whole[:4].copy()
    model = km.KMeans(session, km.KMeansConfig(
        num_centroids=4, dim=5, iterations=3))
    ref_cen, ref_costs = model.fit(pts, cen0)

    for wrap in (lambda ld: ld,
                 lambda ld: pl.DevicePrefetcher(ld, session.replicate_put)):
        loader = pl.StreamLoader(paths, chunk_rows=24, num_threads=3)
        cen, costs = model.fit_from_stream(wrap(loader), cen0, len(pts))
        np.testing.assert_array_equal(np.asarray(cen), np.asarray(ref_cen))
        np.testing.assert_array_equal(np.asarray(costs),
                                      np.asarray(ref_costs))


def test_fit_stream_minibatch_converges(session, tmp_path):
    from harp_tpu.io import pipeline as pl
    from harp_tpu.models import kmeans as km

    paths, whole = _write_parts(tmp_path, [64, 64], d=4, seed=3)
    model = km.KMeans(session, km.KMeansConfig(
        num_centroids=3, dim=4, iterations=1))
    cen, costs = model.fit_stream_minibatch(
        pl.StreamLoader(paths, chunk_rows=32), whole[:3])
    assert cen.shape == (3, 4) and np.isfinite(cen).all()
    assert costs.shape == (4,) and np.isfinite(costs).all()


def test_prefetcher_propagates_producer_error(session):
    from harp_tpu.io import pipeline as pl

    def boom():
        yield pl.Chunk(0, 0, 4, np.zeros((4, 2), np.float32), 32)
        raise RuntimeError("parse exploded")

    pre = pl.DevicePrefetcher(boom(), session.replicate_put)
    next(pre)
    with pytest.raises(RuntimeError, match="parse exploded"):
        for _ in pre:
            pass


def test_assemble_stream_validates_shape(session):
    from harp_tpu.io import pipeline as pl

    with pytest.raises(ValueError, match="multiple"):
        pl.assemble_stream(session, [], session.num_workers + 1, 8)


# --------------------------------------------------------------------------- #
# Distributed COO -> CSR
# --------------------------------------------------------------------------- #


def test_pack_unpack_coo_roundtrip(rng):
    from harp_tpu.io import pipeline as pl

    rows = rng.integers(0, 2 ** 40, 100)
    cols = rng.integers(0, 2 ** 40, 100)
    vals = rng.standard_normal(100).astype(np.float32)
    r, c, v = pl.unpack_coo(pl.pack_coo(rows, cols, vals))
    np.testing.assert_array_equal(r, rows)
    np.testing.assert_array_equal(c, cols)
    np.testing.assert_array_equal(v, vals)


def test_regroup_coo_device_matches_host_oracle(session, rng):
    from harp_tpu.io import pipeline as pl

    w = session.num_workers
    num_rows, nnz = 101, 4000           # ragged last block on purpose
    rows = rng.integers(0, num_rows, nnz).astype(np.int64)
    cols = rng.integers(0, 57, nnz).astype(np.int64)
    vals = rng.standard_normal(nnz).astype(np.float32)
    got = pl.regroup_coo_device(session, rows, cols, vals,
                                num_rows=num_rows)
    block = -(-num_rows // w)
    owner = np.minimum(rows // block, w - 1)
    assert len(got) == w
    for wi in range(w):
        m = owner == wi                 # host oracle: same order, nnz for nnz
        np.testing.assert_array_equal(got[wi][0], rows[m])
        np.testing.assert_array_equal(got[wi][1], cols[m])
        np.testing.assert_array_equal(got[wi][2], vals[m])


def test_regroup_coo_device_empty(session):
    from harp_tpu.io import pipeline as pl

    got = pl.regroup_coo_device(
        session, np.empty(0, np.int64), np.empty(0, np.int64),
        np.empty(0, np.float32))
    assert len(got) == session.num_workers
    assert all(len(r) == 0 for r, _, _ in got)


def test_coo_to_csr_distributed_matches_per_block_oracle(session, rng):
    from harp_tpu.io import loaders, pipeline as pl

    w = session.num_workers
    num_rows, nnz = 96, 3000
    rows = rng.integers(0, num_rows, nnz).astype(np.int64)
    cols = rng.integers(0, 33, nnz).astype(np.int64)
    vals = rng.standard_normal(nnz).astype(np.float32)
    got = pl.coo_to_csr_distributed(session, rows, cols, vals,
                                    num_rows=num_rows)
    block = num_rows // w
    for wi in range(w):
        m = (rows >= wi * block) & (rows < (wi + 1) * block)
        ip, ix, v = loaders.coo_to_csr(rows[m] - wi * block, cols[m],
                                       vals[m], num_rows=block)
        np.testing.assert_array_equal(got[wi][0], ip)
        np.testing.assert_array_equal(got[wi][1], ix)
        np.testing.assert_array_equal(got[wi][2], v)


def test_coo_to_csr_numpy_fallback_uses_bincount(monkeypatch):
    from harp_tpu.io import loaders, native_bridge

    rows = np.array([3, 0, 3, 1, 0], np.int64)
    cols = np.array([1, 2, 0, 4, 3], np.int64)
    vals = np.array([1, 2, 3, 4, 5], np.float32)
    expect = loaders.coo_to_csr(rows, cols, vals, num_rows=5)
    monkeypatch.setattr(native_bridge, "coo_to_csr",
                        lambda *a, **k: None)
    ip, ix, v = loaders.coo_to_csr(rows, cols, vals, num_rows=5)
    np.testing.assert_array_equal(ip, expect[0])
    np.testing.assert_array_equal(ix, expect[1])
    np.testing.assert_array_equal(v, expect[2])
    assert ip.tolist() == [0, 2, 3, 3, 5, 5]
    assert ix.tolist() == [2, 3, 4, 1, 0]      # stable within each row


# --------------------------------------------------------------------------- #
# Budget manifest: the pinned regroup schedule must stay bounded
# --------------------------------------------------------------------------- #


def test_ingest_regroup_budget_drift_is_loud():
    """JL203 teeth for the new target: the regroup silently degrading to a
    full-gather-sized transfer (same collective counts, 4x the bytes) must
    fail the budget check even though JL201 sees no count drift."""
    import json

    from tools.jaxlint import checkers_jaxpr

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, checkers_jaxpr.BUDGET_FILE)) as f:
        manifest = json.load(f)
    row = manifest["targets"]["ingest_coo_regroup"]
    assert row["bytes_per_step"] == 480     # 8 peers x 3 records x 20 B
    counts = dict(row["collectives"])
    widened = {k: 4 * v for k, v in row["bytes_by_kind"].items()}
    findings = checkers_jaxpr.check_budget(
        repo, {"ingest_coo_regroup": (counts, [], widened)})
    mine = [f for f in findings if f.func == "ingest_coo_regroup"]
    assert not any(f.code == "JL201" for f in mine)
    hits = [f for f in mine if f.code == "JL203"]
    assert hits and "byte-budget drift" in hits[0].message
    clean = {"ingest_coo_regroup": (counts, [], dict(row["bytes_by_kind"]))}
    assert not any(f.func == "ingest_coo_regroup"
                   for f in checkers_jaxpr.check_budget(repo, clean))
