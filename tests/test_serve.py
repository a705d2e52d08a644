"""Online serving tests (harp_tpu/serve/ — ISSUE 10).

Covers the endpoint dispatches (parity vs the models' own predict), the
one-compile-per-(model, batch-bucket) retrace contract, the 2-worker local
gang end-to-end under concurrent mixed traffic (the acceptance test), the
graceful-shutdown drain/reject contract, the micro-batcher's deadline/size
bounds, the jaxlint serve trace-target pins (a collective sneaking into
the classify dispatch fails the budget gate), and the load-generator row
schema.
"""

import os
import threading
import time

import numpy as np
import pytest

from harp_tpu.serve import (OP_CLASSIFY, OP_TOPK, MicroBatcher, ServeError,
                            TopKEndpoint, classify_from_forest,
                            classify_from_linear_svm,
                            classify_from_multiclass_svm, classify_from_nn,
                            local_gang)
from harp_tpu.serve import protocol, router

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _nn_model(session, dim=12, classes=3, seed=0):
    from harp_tpu.models import nn

    model = nn.MLPClassifier(session, nn.NNConfig(layers=(8,),
                                                  num_classes=classes))
    model.params = nn.init_params((dim, 8, classes), seed=seed)
    return model


# --------------------------------------------------------------------------- #
# Endpoint parity vs the models' own predict
# --------------------------------------------------------------------------- #

def test_classify_endpoint_parity_nn_linear_svm_forest(session, rng):
    from harp_tpu.models import forest, svm

    x = rng.normal(size=(11, 12)).astype(np.float32)

    nn_model = _nn_model(session)
    ep = classify_from_nn(session, nn_model)
    assert ep.dispatch(x) == nn_model.predict(x).tolist()

    lsvm = svm.LinearSVM(session)
    lsvm.w = rng.normal(size=12).astype(np.float32)
    lsvm.b = 0.25
    ep_svm = classify_from_linear_svm(session, lsvm)
    assert ep_svm.dispatch(x) == lsvm.predict(x).tolist()

    fx, fy = rng.normal(size=(64, 5)).astype(np.float32), \
        rng.integers(0, 2, size=64).astype(np.int32)
    rf = forest.RandomForest(session, forest.TreeConfig(
        depth=3, num_bins=8, num_classes=2, num_trees=2)).fit(fx, fy)
    ep_rf = classify_from_forest(session, rf)
    # device binning + walk must reproduce the host-numpy predict exactly
    assert ep_rf.dispatch(fx[:9]) == rf.predict(fx[:9]).tolist()


def test_classify_endpoint_parity_multiclass_svm(session, rng):
    from harp_tpu.io import datagen
    from harp_tpu.models import svm

    x, y = datagen.classification_data(64, 4, 3, seed=5)
    mc = svm.MultiClassSVM(session, svm.KernelSVMConfig(
        kernel="rbf", iterations=5, power_iters=2)).fit(x, y)
    ep = classify_from_multiclass_svm(session, mc)
    got = ep.dispatch(x[:10])
    assert got == mc.predict(x[:10]).tolist()


def test_topk_matches_numpy_and_unknown_ids(session, rng):
    uf = rng.normal(size=(48, 4)).astype(np.float32)
    items = rng.normal(size=(16, 4)).astype(np.float32)
    ep = TopKEndpoint(session, "mf", uf, items, k=3)
    rows = ep.dispatch(np.asarray([7, 11, 46, 10_000]))
    for qi, row in zip((7, 11, 46), rows):
        ref = np.argsort(-(uf[qi] @ items.T), kind="stable")[:3]
        assert row["found"] and row["items"] == ref.tolist(), (qi, row)
        np.testing.assert_allclose(row["scores"],
                                   (uf[qi] @ items.T)[ref], rtol=1e-5)
    # an id nobody owns comes back found=False, never a crash
    assert rows[3] == {"found": False, "items": [], "scores": []}


def test_topk_custom_user_ids_and_validation(session, rng):
    uf = rng.normal(size=(6, 4)).astype(np.float32)
    items = rng.normal(size=(8, 4)).astype(np.float32)
    ids = np.asarray([3, 100, 205, 1007, 40009, 123456])
    ep = TopKEndpoint(session, "mf", uf, items, k=2, user_ids=ids)
    row = ep.dispatch(np.asarray([40009]))[0]
    ref = np.argsort(-(uf[4] @ items.T), kind="stable")[:2]
    assert row["items"] == ref.tolist()
    with pytest.raises(ValueError):
        TopKEndpoint(session, "mf", uf, items, user_ids=ids[:3])
    with pytest.raises(ValueError):
        TopKEndpoint(session, "mf", uf[:, :2], items)


# --------------------------------------------------------------------------- #
# Retrace contract: one compile per (model, batch-bucket)
# --------------------------------------------------------------------------- #

def test_one_compile_per_model_bucket(session, rng):
    model = _nn_model(session)
    ep = classify_from_nn(session, model, bucket_sizes=(8, 32))
    for n in (1, 3, 8, 5, 2):            # all land in bucket 8
        ep.dispatch(rng.normal(size=(n, 12)).astype(np.float32))
    assert ep.trace_counts == {8: 1}, ep.trace_counts
    for n in (20, 32, 9):                # all land in bucket 32
        ep.dispatch(rng.normal(size=(n, 12)).astype(np.float32))
    assert ep.trace_counts == {8: 1, 32: 1}, ep.trace_counts
    with pytest.raises(ValueError):
        ep.dispatch(rng.normal(size=(33, 12)).astype(np.float32))


def test_bucket_sizes_must_split_over_mesh(session):
    model = _nn_model(session)
    with pytest.raises(ValueError):
        classify_from_nn(session, model, bucket_sizes=(7,))
    ep = classify_from_nn(session, model, bucket_sizes=(16,))
    assert ep.bucket_sizes == (16,) and ep.max_batch == 16


# --------------------------------------------------------------------------- #
# 2-worker local gang, concurrent mixed traffic (acceptance)
# --------------------------------------------------------------------------- #

def test_local_gang_concurrent_topk_classify_e2e(session, rng):
    """ISSUE 10 acceptance: a 2-worker local gang serves concurrent top-k +
    classify end-to-end with exactly one compile per (model, batch-bucket),
    including the forwarding leg (a request landing on a non-owning worker
    reaches the owner and the reply still travels owner -> client)."""
    nn_model = _nn_model(session)
    ep_c = classify_from_nn(session, nn_model, name="nn")
    uf = rng.normal(size=(48, 4)).astype(np.float32)
    items = rng.normal(size=(16, 4)).astype(np.float32)
    ep_t = TopKEndpoint(session, "mf", uf, items, k=3)
    x_pool = rng.normal(size=(32, 12)).astype(np.float32)
    ref_labels = nn_model.predict(x_pool)
    ref_top = {u: np.argsort(-(uf[u] @ items.T), kind="stable")[:3].tolist()
               for u in range(48)}

    workers, make_client = local_gang(session, [{"nn": ep_c}, {"mf": ep_t}])
    clients = [make_client() for _ in range(3)]
    failures = []

    def drive(ci, client):
        local_rng = np.random.default_rng(100 + ci)
        for i in range(30):
            try:
                if i % 2 == 0:
                    u = int(local_rng.integers(0, 48))
                    # client 0 misroutes every top-k to worker 0 — the
                    # forwarding leg carries it to the owner (worker 1)
                    dest = 0 if ci == 0 else None
                    res = client.request(OP_TOPK, "mf", u, dest=dest,
                                         timeout=60.0)
                    if res["items"] != ref_top[u]:
                        failures.append((ci, i, "topk", u, res))
                else:
                    j = int(local_rng.integers(0, len(x_pool)))
                    lab = client.request(OP_CLASSIFY, "nn", x_pool[j],
                                         timeout=60.0)
                    if lab != int(ref_labels[j]):
                        failures.append((ci, i, "classify", j, lab))
            except Exception as e:       # collected, asserted below
                failures.append((ci, i, type(e).__name__, str(e)))
    try:
        threads = [threading.Thread(target=drive, args=(ci, c))
                   for ci, c in enumerate(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
        assert failures == [], failures[:5]
        # exactly one compile per (model, bucket): 3 closed-loop clients
        # coalesce into batches <= 3, i.e. only the smallest bucket
        assert ep_c.trace_counts == {ep_c.bucket_sizes[0]: 1}
        assert ep_t.trace_counts == {ep_t.bucket_sizes[0]: 1}
        # the forwarding leg really ran (client 0 sent all top-k to rank 0)
        assert workers[0].metrics.counters.get("serve.forwarded", 0) >= 1
    finally:
        for c in clients:
            c.close()
        for w in workers:
            w.close()


# --------------------------------------------------------------------------- #
# Graceful shutdown: drain in-flight, reject new, no orphan threads
# --------------------------------------------------------------------------- #

def test_graceful_shutdown_drains_and_rejects(session, rng):
    nn_model = _nn_model(session)
    ep = classify_from_nn(session, nn_model, name="nn")
    # a long coalescing window keeps submissions in-flight deterministically
    workers, make_client = local_gang(session, [{"nn": ep}],
                                      max_wait_s=5.0)
    worker = workers[0]
    client = make_client()
    x = rng.normal(size=(12,)).astype(np.float32)
    try:
        pending = [client.submit(OP_CLASSIFY, "nn", x) for _ in range(3)]
        deadline = time.time() + 10.0
        while worker.batchers["nn"].pending() < 3:
            assert time.time() < deadline, "requests never reached batcher"
            time.sleep(0.005)
        worker.begin_drain()
        # new requests get the clean shutting-down reply
        with pytest.raises(ServeError, match=protocol.ERR_SHUTTING_DOWN):
            client.request(OP_CLASSIFY, "nn", x, timeout=30.0)
        # close() drains: the 3 in-flight requests are SERVED, not dropped
        worker.close()
        expect = int(nn_model.predict(x[None])[0])
        assert [p.result(30.0) for p in pending] == [expect] * 3
    finally:
        client.close()
        worker.close()                  # idempotent
    leftovers = [t.name for t in threading.enumerate()
                 if t.name.startswith(("harp-serve-worker",
                                       "harp-serve-batcher",
                                       "harp-serve-client"))]
    assert leftovers == [], leftovers


def test_atexit_close_contract(session, rng):
    """The PR 7 atexit-close contract extended to serve hooks: live
    workers/clients register and the exit hook closes them all."""
    nn_model = _nn_model(session)
    ep = classify_from_nn(session, nn_model, name="nn")
    workers, make_client = local_gang(session, [{"nn": ep}])
    client = make_client()
    assert workers[0] in router._LIVE and client in router._LIVE
    router._close_at_exit()
    assert workers[0]._closed and client._closed
    assert workers[0] not in router._LIVE and client not in router._LIVE
    router._close_at_exit()             # idempotent on an empty set


def test_unknown_model_is_a_clean_error(session, rng):
    nn_model = _nn_model(session)
    ep = classify_from_nn(session, nn_model, name="nn")
    workers, make_client = local_gang(session, [{"nn": ep}])
    client = make_client()
    try:
        with pytest.raises(ServeError, match=protocol.ERR_UNKNOWN_MODEL):
            client.request(OP_CLASSIFY, "no-such-model",
                           rng.normal(size=(12,)).astype(np.float32),
                           timeout=30.0)
    finally:
        client.close()
        workers[0].close()


# --------------------------------------------------------------------------- #
# Micro-batcher bounds (deterministic, fake endpoint — no mesh involved)
# --------------------------------------------------------------------------- #

class _FakeEndpoint:
    name = "fake"
    op = "classify"
    bucket_sizes = (4, 8)
    max_batch = 8

    def __init__(self):
        self.batches = []

    def bucket_for(self, n):
        for b in self.bucket_sizes:
            if n <= b:
                return b
        raise ValueError(n)

    def validate_query(self, op, data):
        return None if op == self.op else f"op {op!r} mismatch"

    def dispatch(self, batch):
        self.batches.append(len(batch))
        return list(range(len(batch)))


def _collecting_reply():
    replies = []
    lock = threading.Lock()

    def reply(msg, ok, result=None, error=None, batch=None, bucket=None):
        with lock:
            replies.append({"id": msg["id"], "ok": ok, "result": result,
                            "error": error, "batch": batch,
                            "bucket": bucket})
    return replies, reply


def _msg(i, deadline_ts=None):
    return {"kind": protocol.REQUEST, "id": f"t-{i}", "op": "classify",
            "model": "fake", "data": float(i),
            "reply_to": (9, "127.0.0.1", 1), "ts": time.time(),
            "deadline_ts": deadline_ts}


def test_batcher_size_bound_closes_full_batch_immediately():
    ep = _FakeEndpoint()
    replies, reply = _collecting_reply()
    b = MicroBatcher(ep, reply, max_wait_s=10.0)     # window >> test budget
    try:
        t0 = time.perf_counter()
        for i in range(8):
            assert b.submit(_msg(i))
        deadline = time.time() + 5.0
        while len(replies) < 8 and time.time() < deadline:
            time.sleep(0.005)
        # a full bucket dispatches on SIZE, long before the 10 s window
        assert time.perf_counter() - t0 < 5.0
        assert len(replies) == 8 and all(r["ok"] for r in replies)
        assert ep.batches == [8]
        assert {r["bucket"] for r in replies} == {8}
    finally:
        b.drain_and_stop()


def test_batcher_deadline_bound_serves_single_request():
    ep = _FakeEndpoint()
    replies, reply = _collecting_reply()
    b = MicroBatcher(ep, reply, max_wait_s=0.02)
    try:
        b.submit(_msg(0))
        deadline = time.time() + 5.0
        while not replies and time.time() < deadline:
            time.sleep(0.005)
        # an underfull batch closes max_wait_s after its oldest request
        assert replies and replies[0]["ok"] and replies[0]["batch"] == 1
        assert ep.batches == [1]
    finally:
        b.drain_and_stop()


def test_batcher_rejects_mismatched_request_not_its_batchmates():
    """One stale-placement/malformed request in a coalesced batch costs
    exactly that request a clean error — the batch-mates still dispatch."""
    ep = _FakeEndpoint()
    replies, reply = _collecting_reply()
    b = MicroBatcher(ep, reply, max_wait_s=10.0)
    bad = _msg(0)
    bad["op"] = "topk"                   # wrong op for this endpoint
    b.submit(bad)
    for i in range(1, 4):
        b.submit(_msg(i))
    b.drain_and_stop()
    by_id = {r["id"]: r for r in replies}
    assert by_id["t-0"]["ok"] is False
    assert "mismatch" in by_id["t-0"]["error"]
    assert all(by_id[f"t-{i}"]["ok"] for i in range(1, 4))
    assert ep.batches == [3]             # mates dispatched without the bad one


def test_reply_rank_collision_is_dropped_and_waiting_map_bounded(session,
                                                                 rng):
    """A client claiming a serving worker's rank must not hijack the gang's
    forwarding routes: the reply is dropped (counted), the client times
    out, and the timed-out entry leaves the client's waiting map."""
    from harp_tpu.serve.router import RouterClient
    from harp_tpu.utils.metrics import Metrics

    nn_model = _nn_model(session)
    ep = classify_from_nn(session, nn_model, name="nn")
    uf = rng.normal(size=(16, 4)).astype(np.float32)
    items = rng.normal(size=(8, 4)).astype(np.float32)
    ep_t = TopKEndpoint(session, "mf", uf, items, k=2)
    m = Metrics()
    workers, make_client = local_gang(session, [{"nn": ep}, {"mf": ep_t}],
                                      metrics=m)
    # the client claims WORKER 1's rank and talks to worker 0: worker 0
    # must not let the reply_to overwrite its forwarding route to worker 1
    bad_client = RouterClient(1, {0: workers[0].address}, {"nn": 0},
                              secret=b"harp-serve-local", metrics=m)
    try:
        pending = bad_client.submit(
            OP_CLASSIFY, "nn", rng.normal(size=(12,)).astype(np.float32))
        with pytest.raises(TimeoutError):
            pending.result(2.0)
        # the dispatch (first compile of this endpoint's bucket) may outlive
        # the client-side timeout — the dropped-reply counter ticks when
        # the batch is served, so poll for it
        deadline = time.time() + 30.0
        while (m.counters.get("serve.reply_rank_collisions", 0) < 1
               and time.time() < deadline):
            time.sleep(0.02)
        assert m.counters.get("serve.reply_rank_collisions", 0) >= 1
        # the timed-out entry was discarded — a resident client cannot
        # grow its waiting map through lost replies
        assert bad_client._waiting == {}
        # worker 0's route to worker 1 survived: a well-behaved client's
        # top-k request STILL forwards 0 -> 1 and comes back correct
        good = make_client()
        try:
            res = good.request(OP_TOPK, "mf", 5, dest=0, timeout=30.0)
            ref = np.argsort(-(uf[5] @ items.T), kind="stable")[:2]
            assert res["items"] == ref.tolist(), res
            x = rng.normal(size=(12,)).astype(np.float32)
            assert good.request(OP_CLASSIFY, "nn", x, timeout=30.0) == \
                int(nn_model.predict(x[None])[0])
        finally:
            good.close()
    finally:
        bad_client.close()
        for w in workers:
            w.close()


def test_batcher_expired_deadline_and_drain():
    ep = _FakeEndpoint()
    replies, reply = _collecting_reply()
    b = MicroBatcher(ep, reply, max_wait_s=10.0)
    b.submit(_msg(0, deadline_ts=time.time() - 1.0))   # already expired
    b.submit(_msg(1))
    b.drain_and_stop()                   # in-flight batch drains on stop
    assert not b.submit(_msg(2))         # refused once stopping
    by_id = {r["id"]: r for r in replies}
    assert by_id["t-0"]["ok"] is False
    assert protocol.ERR_DEADLINE in by_id["t-0"]["error"]
    assert by_id["t-1"]["ok"] is True
    assert ep.batches == [1]             # only the live request dispatched


# --------------------------------------------------------------------------- #
# jaxlint serve trace targets: zero-collective dispatch is a pinned contract
# --------------------------------------------------------------------------- #

def test_serve_trace_targets_pinned(session):
    import json

    from tools.jaxlint import checkers_jaxpr

    with open(os.path.join(REPO, checkers_jaxpr.BUDGET_FILE)) as f:
        manifest = json.load(f)["targets"]
    # the classify dispatch is pinned at ZERO collectives, zero bytes
    assert manifest["serve_classify_nn"]["collectives"] == {}
    assert manifest["serve_classify_nn"]["bytes_per_step"] == 0
    # the top-k dispatch is pinned at exactly the keyval lookup's routing:
    # bucket_route payload + mask all_to_alls, route_back all_to_all, and
    # the 4-byte route-overflow psum
    assert manifest["serve_topk_mf"]["collectives"] == {
        "all_to_all": 3, "psum": 1}
    assert manifest["serve_topk_mf"]["bytes_by_kind"]["psum"] == 4
    # live traces match the pins (the real JL201/JL203 gate re-checks this
    # over all targets in test_jaxlint; here we pin the serve rows' KINDS)
    counts, dtype_bad, nbytes = checkers_jaxpr.trace_target(
        "serve_classify_nn")
    assert counts == {} and dtype_bad == [] and nbytes == {}
    counts_t, _, nbytes_t = checkers_jaxpr.trace_target("serve_topk_mf")
    assert counts_t == manifest["serve_topk_mf"]["collectives"]
    assert sum(nbytes_t.values()) == \
        manifest["serve_topk_mf"]["bytes_per_step"]


def test_collective_in_classify_dispatch_fails_budget_gate():
    """ISSUE 10 acceptance: an in-dispatch collective fails jaxlint — a
    psum appearing in the (pinned-zero) classify dispatch is JL201 drift."""
    from tools.jaxlint import checkers_jaxpr

    doctored = {"serve_classify_nn": ({"psum": 1}, [], {"psum": 128})}
    findings = checkers_jaxpr.check_budget(REPO, doctored)
    hits = [f for f in findings if f.code == "JL201"
            and f.func == "serve_classify_nn" and "drift" in f.message]
    assert hits, findings
    assert "psum: traced 1 vs pinned 0" in hits[0].message


# --------------------------------------------------------------------------- #
# Load generator row schema
# --------------------------------------------------------------------------- #

def test_serving_load_row_schema(session):
    from harp_tpu.benchmark import serving_load

    row = serving_load.measure(session, requests_per_mix=24, num_clients=2)
    assert set(row["mixes"]) == {"topk_heavy", "classify_heavy", "mixed"}
    for mix, r in row["mixes"].items():
        assert r["errors"] == 0, (mix, r)
        assert r["requests"] > 0 and r["qps"] > 0
        assert 0 < r["p50_ms"] <= r["p99_ms"], (mix, r)
    # the batching stats prove the retrace contract held under load:
    # every bucket that was touched (warmup reaches each bucket a
    # num_clients closed loop can fill) compiled exactly once
    for name, occ in row["batching"].items():
        assert occ["trace_counts"] and all(
            v == 1 for v in occ["trace_counts"].values()), (name, occ)
    assert row["device"] in ("cpu", "tpu")
    if row["device"] != "tpu":
        assert "not a TPU's" in row["note"]
