"""AOT dispatch artifacts (ISSUE 15): store round-trip, the invalidation
matrix, endpoint load parity + the never-recompile contract, manifest
drift detection, the per-model coalescing deadline satellite, and the
persistent compile-cache wiring."""

import json
import os
import threading
import time

import numpy as np
import pytest

from harp_tpu.aot import serve_artifacts
from harp_tpu.aot.store import (FMT_EXPORT, ArtifactKey, ArtifactStore,
                                canonical_program_text, layout_of)
from harp_tpu.serve.endpoints import TopKEndpoint, classify_from_nn
from harp_tpu.utils.metrics import Metrics


def _metrics_store(tmp_path, sub="store"):
    m = Metrics()
    return m, ArtifactStore(str(tmp_path / sub), metrics=m)


def _topk(session, _rng=None, name="mf", buckets=(8,), k=3, seed=0):
    # self-seeded so a donor/twin pair built back to back holds the SAME
    # factor tables (parity asserts compare their dispatches)
    rng = np.random.default_rng(seed)
    uf = rng.normal(size=(48, 6)).astype(np.float32)
    items = rng.normal(size=(24, 6)).astype(np.float32)
    return TopKEndpoint(session, name, uf, items, k=k,
                        bucket_sizes=buckets), uf, items


def _nn_endpoint(session, name="nn", buckets=(8,)):
    from harp_tpu.models import nn

    model = nn.MLPClassifier(session, nn.NNConfig(layers=(8,),
                                                  num_classes=3))
    model.params = nn.init_params((12, 8, 3), seed=0)
    return classify_from_nn(session, model, name=name,
                            bucket_sizes=buckets)


# --------------------------------------------------------------------------- #
# Store round-trip
# --------------------------------------------------------------------------- #

def test_store_roundtrip_parity_and_hit_metric(session, rng, tmp_path):
    import jax.numpy as jnp

    m, store = _metrics_store(tmp_path)
    fn = session.spmd(lambda x: jnp.tanh(x) * 2.0,
                      in_specs=(session.shard(),),
                      out_specs=session.shard())
    x = session.scatter(rng.normal(size=(16, 4)).astype(np.float32))
    key = ArtifactKey(name="t/roundtrip", world=session.num_workers,
                      layout=layout_of((x,)), model_hash="h")
    meta = store.export_and_put(key, fn, (x,))
    assert meta["format"] == FMT_EXPORT and meta["content_hash"]
    hit = store.load(key)
    assert hit is not None
    loaded, meta2 = hit
    assert meta2["content_hash"] == meta["content_hash"]
    np.testing.assert_array_equal(np.asarray(loaded(x)),
                                  np.asarray(fn(x)))
    counters = m.snapshot()["counters"]
    assert counters["aot.store.hit"] == 1
    assert counters["aot.store.put"] == 1


def test_canonical_text_strips_locations():
    text = ('#loc1 = loc("/tmp/x.py":3:0)\n'
            'module @jit_f {\n'
            '  %0 = stablehlo.add %a, %b : tensor<f32> loc(#loc1)\n'
            '  %1 = stablehlo.abs %0 : tensor<f32> loc(unknown)\n'
            '  func.func private @g(%arg0: tensor<9xi32> '
            'loc(callsite(#loc1 at #loc2))) -> tensor<9xi32>\n'
            '}\n')
    canon = canonical_program_text(text)
    assert "loc(" not in canon
    assert "stablehlo.add" in canon and "stablehlo.abs" in canon


# --------------------------------------------------------------------------- #
# Invalidation matrix: every stale axis rejects LOUDLY and falls back
# --------------------------------------------------------------------------- #

def _doctor_meta(store, name, **fields):
    path = store._paths(name)[0]
    with open(path) as f:
        meta = json.load(f)
    meta.update(fields)
    with open(path, "w") as f:
        json.dump(meta, f)


@pytest.mark.parametrize("axis,doctor", [
    ("jax_version", {"jax_version": "0.0.1"}),
    ("device_kind", {"device_kind": "TPU v99"}),
    ("world", {"world": 4096}),
    ("layout", {"layout": "doctored-layout"}),
])
def test_invalidation_matrix_meta_axes(session, rng, tmp_path, axis,
                                       doctor):
    m, store = _metrics_store(tmp_path, sub=axis)
    ep, _uf, _items = _topk(session, rng)
    serve_artifacts.export_endpoint(store, ep, model_hash="h")
    name = serve_artifacts.dispatch_name("mf", 8)
    _doctor_meta(store, name, **doctor)
    twin, _, _ = _topk(session, rng)
    loaded = serve_artifacts.load_endpoint(store, twin, model_hash="h",
                                           warm=False)
    assert loaded == []              # rejected, not served
    counters = m.snapshot()["counters"]
    assert counters[f"aot.store.miss_{axis}"] == 1, counters
    # ...and the fallback COMPILES, correctly (the loud path never
    # degrades service)
    ids = np.array([1, 7, 40])
    assert twin.dispatch(ids) == ep.dispatch(ids)
    assert twin.trace_counts == {8: 1}
    assert twin.aot_loaded == set()


def test_invalidation_model_hash_absent_and_corrupt(session, rng,
                                                    tmp_path):
    m, store = _metrics_store(tmp_path)
    ep, _, _ = _topk(session, rng)
    name = serve_artifacts.dispatch_name("mf", 8)
    # absent: empty store
    twin, _, _ = _topk(session, rng)
    assert serve_artifacts.load_endpoint(store, twin, warm=False) == []
    assert m.snapshot()["counters"]["aot.store.miss_absent"] == 1
    # model hash: exported under one model identity, loaded under another
    serve_artifacts.export_endpoint(store, ep, model_hash="model-A")
    assert serve_artifacts.load_endpoint(store, twin, model_hash="model-B",
                                         warm=False) == []
    assert m.snapshot()["counters"]["aot.store.miss_model_hash"] == 1
    # corrupt payload: bytes no longer match the meta's sha
    with open(store._paths(name)[1], "r+b") as f:
        f.write(b"garbage")
    assert serve_artifacts.load_endpoint(store, twin, model_hash="model-A",
                                         warm=False) == []
    assert m.snapshot()["counters"]["aot.store.miss_corrupt"] == 1


# --------------------------------------------------------------------------- #
# Endpoint load: parity, zero traces, loud displacement, rebalance reset
# --------------------------------------------------------------------------- #

def test_endpoint_load_zero_trace_and_parity(session, rng, tmp_path):
    m, store = _metrics_store(tmp_path)
    donor, _, _ = _topk(session, rng, buckets=(8, 16))
    serve_artifacts.export_endpoint(store, donor, model_hash="h")
    twin, _, _ = _topk(session, rng, buckets=(8, 16))
    loaded = serve_artifacts.load_endpoint(store, twin, model_hash="h")
    assert loaded == [8, 16]
    assert twin.aot_loaded == {8, 16}
    for n in (3, 12):                # both buckets, real traffic
        ids = rng.integers(0, 48, size=n)
        assert twin.dispatch(ids) == donor.dispatch(ids)
    # THE contract: artifact-loaded buckets never traced in this process
    assert twin.trace_counts == {}
    assert m.snapshot()["counters"]["aot.store.hit"] == 2


def test_classify_endpoint_load_parity(session, rng, tmp_path):
    _m, store = _metrics_store(tmp_path)
    donor = _nn_endpoint(session)
    serve_artifacts.export_endpoint(store, donor, model_hash="h")
    twin = _nn_endpoint(session)
    assert serve_artifacts.load_endpoint(store, twin,
                                         model_hash="h") == [8]
    x = rng.normal(size=(5, 12)).astype(np.float32)
    assert twin.dispatch(x) == donor.dispatch(x)
    assert twin.trace_counts == {}


def test_displaced_artifact_install_fails_loud(session, rng, tmp_path):
    _m, store = _metrics_store(tmp_path)
    donor, _, _ = _topk(session, rng)
    serve_artifacts.export_endpoint(store, donor, model_hash="h")
    twin, _, _ = _topk(session, rng)
    serve_artifacts.load_endpoint(store, twin, model_hash="h", warm=False)
    # simulate a displacement bug: the installed fn vanishes while the
    # loaded mark stays — the rebuild must NOT silently recompile
    twin._fns.pop(8)
    with pytest.raises(RuntimeError, match="never recompile"):
        twin.dispatch(np.array([1]))


def test_rebalance_clears_loaded_marks_and_recompiles(session, rng,
                                                      tmp_path):
    _m, store = _metrics_store(tmp_path)
    donor, uf, _items = _topk(session, rng)
    serve_artifacts.export_endpoint(store, donor, model_hash="h")
    twin, _, _ = _topk(session, rng)
    serve_artifacts.load_endpoint(store, twin, model_hash="h", warm=False)
    assert twin.aot_loaded == {8}
    twin.rebalance(1)                # owner-routed layout: NEW program
    assert twin.aot_loaded == set()
    ids = np.array([2, 9, 33])
    assert twin.dispatch(ids) == donor.dispatch(ids)
    assert twin.trace_counts == {8: 1}    # the lazy rebuild may trace


# --------------------------------------------------------------------------- #
# Manifest: clean against the committed pin, drift is a finding
# --------------------------------------------------------------------------- #

def test_manifest_diff_logic(tmp_path, monkeypatch):
    from harp_tpu.aot import manifest

    rows = {"serve/x/b8": {"content_hash": "a" * 64,
                           "format": "jax_export", "payload_bytes": 10}}
    monkeypatch.setattr(manifest, "build_rows", lambda workdir: dict(rows))
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "tools"), exist_ok=True)
    manifest.write(root, dict(rows))
    assert manifest.check(root, str(tmp_path / "w")) == []
    # hash drift = a finding naming the target
    doctored = {"serve/x/b8": dict(rows["serve/x/b8"],
                                   content_hash="b" * 64)}
    manifest.write(root, doctored)
    findings = manifest.check(root, str(tmp_path / "w"))
    assert len(findings) == 1 and "serve/x/b8" in findings[0] \
        and "drifted" in findings[0]
    # stale pinned row + unpinned fresh target
    manifest.write(root, {"gone/row": rows["serve/x/b8"]})
    findings = manifest.check(root, str(tmp_path / "w"))
    assert any("not pinned" in f for f in findings)
    assert any("stale" in f for f in findings)
    # environment mismatch: ONE re-pin finding, no bogus per-row noise
    manifest.write(root, dict(rows))
    path = manifest.manifest_path(root)
    with open(path) as f:
        doc = json.load(f)
    doc["jax_version"] = "9.9.9"
    with open(path, "w") as f:
        json.dump(doc, f)
    findings = manifest.check(root, str(tmp_path / "w"))
    assert len(findings) == 1 and "re-pin" in findings[0]


@pytest.mark.large
def test_committed_manifest_matches_fresh_export(tmp_path):
    """The real gate: the committed tools/artifact_manifest.json must
    match a fresh in-process export of the registry (the jaxlint
    --artifacts-only stage, run as a test so tier-1 catches drift even
    when CI stages are skipped)."""
    from harp_tpu.aot import manifest

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    findings = manifest.check(root, str(tmp_path / "w"))
    assert findings == [], "\n".join(findings)


# --------------------------------------------------------------------------- #
# Satellites: per-model max_wait_s + suggestion, compile cache
# --------------------------------------------------------------------------- #

def test_suggest_max_wait_from_span_table():
    from harp_tpu.serve.batcher import suggest_max_wait_s
    from harp_tpu.telemetry import spans

    m = Metrics()
    assert suggest_max_wait_s(m, "mf") is None      # no samples: keep cfg
    for wait in (0.001, 0.002, 0.004):
        bd = {"total_s": wait + 0.001, "submit_hop_s": 0.0005,
              "route_s": 0.0, "coalesce_s": wait, "dispatch_s": 0.0004,
              "reply_build_s": 0.0, "reply_hop_s": 0.0001,
              "forwarded": False, "model": "mf"}
        spans.observe_span(bd, m)
    got = suggest_max_wait_s(m, "mf", headroom=1.0)
    assert got == pytest.approx(0.004)              # p90 of the coalesce
    # clamped at both ends
    assert suggest_max_wait_s(m, "mf", headroom=100.0) == 0.05
    assert suggest_max_wait_s(m, "mf", headroom=1e-6) == 0.0002


def test_two_models_one_worker_honor_different_deadlines(session, rng):
    """ISSUE 15 satellite acceptance: two models on ONE worker with
    per-model max_wait_s overrides — a lone request to the slow-coalesce
    model waits ~its deadline, the fast model replies well before it."""
    from harp_tpu.serve import OP_CLASSIFY, local_gang

    slow, fast = 0.25, 0.002
    eps = {"a": _nn_endpoint(session, name="a"),
           "b": _nn_endpoint(session, name="b")}
    workers, make_client = local_gang(
        session, [eps], max_wait_s=fast,
        max_wait_overrides={"a": slow})
    client = make_client()
    try:
        x = rng.normal(size=(12,)).astype(np.float32)
        for model in ("a", "b"):     # compile both buckets first
            client.request(OP_CLASSIFY, model, x, timeout=60.0)
        t0 = time.perf_counter()
        client.request(OP_CLASSIFY, "b", x, timeout=30.0)
        dt_fast = time.perf_counter() - t0
        t0 = time.perf_counter()
        client.request(OP_CLASSIFY, "a", x, timeout=30.0)
        dt_slow = time.perf_counter() - t0
    finally:
        client.close()
        for w in workers:
            w.close()
    assert workers[0].batchers["a"].max_wait_s == slow
    assert workers[0].batchers["b"].max_wait_s == fast
    # the slow model's lone request waits out its own window; the fast
    # one must not inherit it (generous margins — CI boxes wobble)
    assert dt_slow >= slow * 0.8, dt_slow
    assert dt_fast < slow * 0.5, dt_fast


def test_compile_cache_resolver(monkeypatch, tmp_path, caplog):
    """aot.cache.resolve_cache_dir is the ONE place the cache directory is
    chosen: the environment's when set (a disagreeing explicit directory
    does not win, and is logged once), else the explicit one, else one
    fixed path inside the checkout — never a temp name."""
    import tempfile

    from harp_tpu.aot import cache

    env_dir, explicit = str(tmp_path / "from_env"), str(tmp_path / "flag")
    monkeypatch.setenv(cache.ENV_VAR, env_dir)
    assert cache.resolve_cache_dir() == env_dir
    with caplog.at_level("WARNING", logger="harp_tpu.aot"):
        assert cache.resolve_cache_dir(explicit) == env_dir
        assert cache.resolve_cache_dir(explicit) == env_dir
    ignored = [r for r in caplog.records if "ignoring" in r.getMessage()]
    assert len(ignored) == 1 and explicit in ignored[0].getMessage()
    assert cache.resolve_cache_dir(env_dir) == env_dir   # agreeing: silent
    monkeypatch.delenv(cache.ENV_VAR)
    assert cache.resolve_cache_dir(explicit) == explicit
    default = cache.resolve_cache_dir()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert default == cache.DEFAULT_DIR == os.path.join(
        root, ".jax_compile_cache")
    assert not default.startswith(tempfile.gettempdir())
    assert cache.resolve_cache_dir() == default          # fixed, not minted
    # the CPU backend does not take the default directory (XLA:CPU logs an
    # error per executable it loads back); a named one it does take
    assert cache.enable_compile_cache() is None


def test_compile_cache_dir_populates(session, rng, tmp_path, monkeypatch):
    """ServeWorker(compile_cache_dir=) wires jax's persistent cache through
    the resolver: a dispatch writes cache entries into the directory."""
    import jax

    from harp_tpu.aot import cache
    from harp_tpu.serve import OP_CLASSIFY, local_gang

    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    cache_dir = str(tmp_path / "cc")
    prev = jax.config.jax_compilation_cache_dir
    workers, make_client = local_gang(
        session, [{"cc": _nn_endpoint(session, name="cc")}],
        compile_cache_dir=cache_dir)
    client = make_client()
    try:
        x = rng.normal(size=(12,)).astype(np.float32)
        client.request(OP_CLASSIFY, "cc", x, timeout=60.0)
        assert jax.config.jax_compilation_cache_dir == cache_dir
        assert os.listdir(cache_dir), "no persistent-cache entries written"
    finally:
        client.close()
        for w in workers:
            w.close()
        # the cache config is process-global: restore AND re-latch so the
        # rest of the suite compiles exactly as before this test
        jax.config.update("jax_compilation_cache_dir", prev)
        from jax._src import compilation_cache as _cc

        _cc.reset_cache()


def test_worker_aot_store_loads_before_serving(session, rng, tmp_path):
    """local_gang(aot_dir=): the worker ctor installs store hits — the
    endpoint serves loaded programs from its very first request
    (trace_counts stays empty) and reports what it loaded."""
    from harp_tpu.serve import OP_TOPK, local_gang

    _m, store = _metrics_store(tmp_path)
    donor, _, _ = _topk(session, rng, buckets=(8,))
    serve_artifacts.export_endpoint(store, donor)
    twin, _, _ = _topk(session, rng, buckets=(8,))
    workers, make_client = local_gang(session, [{"mf": twin}],
                                      aot_dir=store.root)
    client = make_client()
    try:
        assert workers[0].aot_loaded == {"mf": [8]}
        res = client.request(OP_TOPK, "mf", 7, timeout=60.0)
        assert res["items"] == donor.dispatch(np.array([7]))[0]["items"]
        assert twin.trace_counts == {}
    finally:
        client.close()
        for w in workers:
            w.close()


# --------------------------------------------------------------------------- #
# Static memory rows in artifact meta (ISSUE 19): metadata, never a key axis
# --------------------------------------------------------------------------- #

def test_export_records_static_memory_row_in_meta(session, rng, tmp_path):
    from harp_tpu.aot.store import KEY_AXES

    _m, store = _metrics_store(tmp_path)
    ep, _uf, _items = _topk(session, rng)
    metas = serve_artifacts.export_endpoint(store, ep, model_hash="h")
    assert metas, metas
    for meta in metas.values():
        mem = meta["memory"]
        assert mem["resident_arg_bytes"] > 0
        assert mem["peak_live_bytes"] >= mem["resident_arg_bytes"]
        assert mem["transient_peak_ratio"] > 1.0
    # the row is placement METADATA: the key matrix is unchanged, so a
    # memory field can never turn a load into a (or mask a real) miss
    assert KEY_AXES == ("jax_version", "device_kind", "world", "quant",
                        "layout", "model_hash")
    assert not any(axis in ("memory", "resident_arg_bytes",
                            "peak_live_bytes", "transient_peak_ratio")
                   for axis in KEY_AXES)


def test_memory_row_mismatch_or_absence_never_misses(session, rng,
                                                     tmp_path):
    # a doctored (or stripped — pre-r20 store) memory row must NOT reject
    # the artifact: only KEY_AXES decide hit vs miss
    m, store = _metrics_store(tmp_path)
    ep, _uf, _items = _topk(session, rng)
    serve_artifacts.export_endpoint(store, ep, model_hash="h")
    name = serve_artifacts.dispatch_name("mf", 8)
    _doctor_meta(store, name,
                 memory={"resident_arg_bytes": 1, "peak_live_bytes": 2,
                         "transient_peak_ratio": 2.0})
    twin, _, _ = _topk(session, rng)
    loaded = serve_artifacts.load_endpoint(store, twin, model_hash="h",
                                           warm=False)
    assert loaded == [8], loaded
    # strip the row entirely: still a hit
    path = store._paths(name)[0]
    with open(path) as f:
        meta = json.load(f)
    del meta["memory"]
    with open(path, "w") as f:
        json.dump(meta, f)
    twin2, _, _ = _topk(session, rng)
    loaded = serve_artifacts.load_endpoint(store, twin2, model_hash="h",
                                           warm=False)
    assert loaded == [8], loaded
    assert m.snapshot()["counters"]["aot.store.hit"] == 2


def test_aot_ls_prints_resident_and_peak_bytes(session, rng, tmp_path,
                                               capsys):
    from harp_tpu.run import run_aot

    _m, store = _metrics_store(tmp_path)
    ep, _uf, _items = _topk(session, rng)
    metas = serve_artifacts.export_endpoint(store, ep, model_hash="h")
    # one artifact with a pre-r20 (row-less) meta: the listing degrades
    # to placeholders instead of crashing
    name = serve_artifacts.dispatch_name("mf", 8)
    path = store._paths(name)[0]
    with open(path) as f:
        meta = json.load(f)
    stripped = dict(meta)
    del stripped["memory"]
    alt = str(tmp_path / "store2")
    store2 = ArtifactStore(alt)
    os.makedirs(os.path.dirname(store2._paths(name)[0]), exist_ok=True)
    with open(store2._paths(name)[0], "w") as f:
        json.dump(stripped, f)
    with open(store._paths(name)[1], "rb") as f:
        payload = f.read()
    with open(store2._paths(name)[1], "wb") as f:
        f.write(payload)

    assert run_aot(["ls", "--aot-dir", str(tmp_path / "store")]) == 0
    out = capsys.readouterr().out
    mem = metas[8]["memory"]
    assert f"res={mem['resident_arg_bytes']:>8d} B" in out
    assert f"peak={mem['peak_live_bytes']:>8d} B" in out

    assert run_aot(["ls", "--aot-dir", alt]) == 0
    out = capsys.readouterr().out
    assert "res=       ? B peak=       ? B" in out


# --------------------------------------------------------------------------- #
# Compiled-HLO rows in artifact meta (ISSUE 20): metadata, never a key axis
# --------------------------------------------------------------------------- #

def test_export_records_hlo_row_in_meta(session, rng, tmp_path):
    from harp_tpu.aot.store import KEY_AXES

    _m, store = _metrics_store(tmp_path)
    ep, _uf, _items = _topk(session, rng)
    metas = serve_artifacts.export_endpoint(store, ep, model_hash="h")
    assert metas, metas
    for meta in metas.values():
        hlo = meta["hlo"]
        assert hlo["instruction_count"] > 0
        assert set(hlo["collectives"]) == set(hlo["collective_bytes"])
        assert hlo["collective_bytes_total"] == sum(
            hlo["collective_bytes"].values())
        assert hlo["while_count"] >= 0
        # the top-k dispatch routes through the keyval all_to_alls — the
        # compiled row must show the partitioner kept them collective
        assert hlo["collectives"].get("all-to-all", 0) >= 1, hlo
    # the row is fleet-tooling METADATA: the key matrix is unchanged, so
    # an hlo field can never turn a load into a (or mask a real) miss
    assert KEY_AXES == ("jax_version", "device_kind", "world", "quant",
                        "layout", "model_hash")
    assert "hlo" not in KEY_AXES


def test_hlo_row_mismatch_or_absence_never_misses(session, rng, tmp_path):
    # a doctored (or stripped — pre-r21 store) hlo row must NOT reject
    # the artifact: only KEY_AXES decide hit vs miss
    m, store = _metrics_store(tmp_path)
    ep, _uf, _items = _topk(session, rng)
    serve_artifacts.export_endpoint(store, ep, model_hash="h")
    name = serve_artifacts.dispatch_name("mf", 8)
    _doctor_meta(store, name,
                 hlo={"collectives": {"all-gather": 99},
                      "collective_bytes": {"all-gather": 1},
                      "collective_bytes_total": 1,
                      "instruction_count": 1, "while_count": 0})
    twin, _, _ = _topk(session, rng)
    loaded = serve_artifacts.load_endpoint(store, twin, model_hash="h",
                                           warm=False)
    assert loaded == [8], loaded
    # strip the row entirely: still a hit
    path = store._paths(name)[0]
    with open(path) as f:
        meta = json.load(f)
    del meta["hlo"]
    with open(path, "w") as f:
        json.dump(meta, f)
    twin2, _, _ = _topk(session, rng)
    loaded = serve_artifacts.load_endpoint(store, twin2, model_hash="h",
                                           warm=False)
    assert loaded == [8], loaded
    assert m.snapshot()["counters"]["aot.store.hit"] == 2


def test_aot_ls_json_rows_are_machine_readable(session, rng, tmp_path,
                                               capsys):
    # `aot ls --json`: one JSON object per artifact with the key axes,
    # the r20 res/peak columns, and the r21 hlo row — and a pre-r20/r21
    # meta serializes those fields as null instead of crashing or
    # dropping the key
    from harp_tpu.run import run_aot

    _m, store = _metrics_store(tmp_path)
    ep, _uf, _items = _topk(session, rng)
    metas = serve_artifacts.export_endpoint(store, ep, model_hash="h")
    name = serve_artifacts.dispatch_name("mf", 8)
    path = store._paths(name)[0]
    with open(path) as f:
        meta = json.load(f)
    stripped = {k: v for k, v in meta.items()
                if k not in ("memory", "hlo")}
    alt = str(tmp_path / "store2")
    store2 = ArtifactStore(alt)
    os.makedirs(os.path.dirname(store2._paths(name)[0]), exist_ok=True)
    with open(store2._paths(name)[0], "w") as f:
        json.dump(stripped, f)
    with open(store._paths(name)[1], "rb") as f:
        payload = f.read()
    with open(store2._paths(name)[1], "wb") as f:
        f.write(payload)

    assert run_aot(["ls", "--aot-dir", str(tmp_path / "store"),
                    "--json"]) == 0
    rows = [json.loads(line)
            for line in capsys.readouterr().out.strip().splitlines()]
    assert len(rows) == len(metas)
    row = next(r for r in rows if r["name"] == name)
    assert row["resident_arg_bytes"] == \
        metas[8]["memory"]["resident_arg_bytes"]
    assert row["peak_live_bytes"] == metas[8]["memory"]["peak_live_bytes"]
    assert row["hlo"] == metas[8]["hlo"]
    assert row["world"] == session.num_workers
    assert row["content_hash"] == metas[8]["content_hash"]

    assert run_aot(["ls", "--aot-dir", alt, "--json"]) == 0
    rows = [json.loads(line)
            for line in capsys.readouterr().out.strip().splitlines()]
    assert rows[0]["hlo"] is None
    assert rows[0]["resident_arg_bytes"] is None
    assert rows[0]["peak_live_bytes"] is None
