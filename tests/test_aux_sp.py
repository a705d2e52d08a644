"""Aux subsystems (checkpoint/metrics/events/failure), collective micro-bench,
pallas kernel (interpret mode), and sequence parallelism tests."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harp_tpu.benchmark import collectives as bench
from harp_tpu.ops import pallas_kernels
from harp_tpu.parallel import events, failure, ring_attention
from harp_tpu.utils import checkpoint, metrics


def test_checkpointer_roundtrip(tmp_path):
    ck = checkpoint.Checkpointer(str(tmp_path), keep=2)
    state = {"w": np.arange(6.0).reshape(2, 3), "step": np.asarray(3)}
    for s in (1, 2, 3):
        ck.save(s, state)
    assert ck.steps() == [2, 3]            # keep=2 pruned step 1
    out = ck.restore_latest(like=state)
    np.testing.assert_allclose(np.asarray(out["w"]), state["w"])
    assert ck.latest_step() == 3


def test_checkpointer_async_save_roundtrip(tmp_path):
    """async_save overlaps the disk write; wait()/restore join it and the
    result is identical to a synchronous save. Resume via fit_checkpointed
    works across sync and async writers."""
    from harp_tpu.utils.checkpoint import Checkpointer

    state = {"w": np.arange(12.0).reshape(3, 4), "step": np.int32(7)}
    sync = Checkpointer(str(tmp_path / "sync"))
    sync.save(3, state)
    asy = Checkpointer(str(tmp_path / "async"), async_save=True)
    asy.save(3, state)
    asy.wait()
    got_s = sync.restore(3, like=state)
    got_a = asy.restore(3, like=state)
    np.testing.assert_array_equal(got_s["w"], got_a["w"])
    assert got_a["step"] == 7
    # steps() joins the in-flight write, so a save followed immediately by
    # steps() always sees the new checkpoint
    asy.save(4, state)
    assert asy.steps()[-1] == 4


def test_prune_spares_live_foreign_tmp_dir(tmp_path):
    """_prune must not delete a concurrently LIVE writer's tmp dir (ADVICE
    r5): a fresh foreign-pid ``*.tmp-*`` dir survives every prune; only one
    past the staleness threshold (a fail-stop orphan) is reaped."""
    ck = checkpoint.Checkpointer(str(tmp_path), keep=1, use_orbax=False)
    fresh = tmp_path / "step_000000000099.tmp-99999"   # foreign pid, live
    fresh.mkdir()
    (fresh / "payload.npz").write_bytes(b"in-flight")
    stale = tmp_path / "step_000000000098.tmp-88888"   # fail-stop orphan
    stale.mkdir()
    old = time.time() - 2 * checkpoint.STALE_TMP_SECONDS
    os.utime(stale, (old, old))
    state = {"a": np.ones(2)}
    ck.save(1, state)
    ck.save(2, state)                                  # both prune
    assert fresh.exists(), "live writer's tmp dir was deleted by prune"
    assert not stale.exists(), "stale orphan tmp dir survived prune"
    assert ck.steps() == [2]


def test_checkpointer_numpy_fallback(tmp_path):
    ck = checkpoint.Checkpointer(str(tmp_path), use_orbax=False)
    state = {"a": np.ones(4), "b": np.zeros((2, 2))}
    ck.save(7, state)
    out = ck.restore(7, like=state)
    np.testing.assert_allclose(out["a"], state["a"])
    assert ck.restore_latest(like=state) is not None


def test_metrics_registry():
    m = metrics.Metrics()
    m.count("iters", 3)
    m.gauge("loss", 0.5)
    with m.timer("phase"):
        time.sleep(0.01)
    snap = m.snapshot()
    assert snap["counters"]["iters"] == 3
    assert snap["gauges"]["loss"] == 0.5
    assert snap["timers"]["phase"]["count"] == 1
    assert snap["timers"]["phase"]["total_s"] >= 0.01
    m.log_summary()   # must not raise


def test_event_queue():
    q = events.EventQueue()
    client = events.EventClient(q, worker_id=0)
    client.send_local({"x": 1})
    client.send_collective("sync-point")
    client.send_message(0, "to-self")
    client.send_message(3, "dropped")     # single-process, not for us
    got = [q.get(), q.get(), q.get()]
    assert got[0].type is events.EventType.LOCAL
    assert got[1].type is events.EventType.COLLECTIVE
    assert got[2].payload == "to-self"
    assert q.get() is None
    assert q.wait(timeout=0.05) is None


def test_failure_watchdog():
    assert failure.probe_devices(timeout_s=30.0)
    with failure.Watchdog(interval_s=0.05, timeout_s=30.0) as wd:
        time.sleep(0.15)
        wd.ok()                            # healthy devices: no raise
    wd2 = failure.Watchdog()
    wd2.failed = True
    with pytest.raises(failure.WorkerFailure):
        wd2.ok()


def test_bench_collectives_smoke(session):
    results = bench.bench_collectives(session, sizes_kb=[4], loops=3,
                                      ops=["allreduce", "rotate"])
    assert len(results) == 2
    for r in results:
        assert r.seconds > 0 and r.us_per_op > 0
    table = bench.format_table(results)
    assert "allreduce" in table and "busbw GB/s" in table
    # renamed fields say what they mean (ADVICE r5): the PER-WORKER payload
    # (total array bytes / W) and NCCL-busbw bandwidth, with the convention
    # note available to ship inside emitted records
    w2 = session.num_workers ** 2
    rows = max(w2, (4 * 1024 // 4) // 128 // w2 * w2)
    assert results[0].payload_bytes_per_worker == \
        rows * 128 * 4 // session.num_workers
    assert results[0].busbw_gbps > 0
    assert "busbw" in bench.CONVENTION_NOTE


def test_pallas_spd_solve_interpret_matches_scipy():
    """The lane-vectorized batched Cholesky solve (interpret mode) matches
    jax.scipy's exact SPD solve, including K/N shapes that need padding and
    the K = 8 whose packed operand is the whole matrix."""
    rng = np.random.default_rng(7)
    # (aligned, needs K+N padding, one sublane group)
    for n, k in [(256, 16), (300, 10), (200, 8)]:
        g = rng.standard_normal((n, k, k)).astype(np.float32)
        a = g @ np.transpose(g, (0, 2, 1)) + 0.1 * np.eye(k, dtype=np.float32)
        b = rng.standard_normal((n, k)).astype(np.float32)
        want = jax.scipy.linalg.solve(jnp.asarray(a), jnp.asarray(b)[..., None],
                                      assume_a="pos")[..., 0]
        got = pallas_kernels.spd_solve_pallas(jnp.asarray(a), jnp.asarray(b),
                                              tile_b=128, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


def test_als_pallas_solver_matches_cholesky():
    """ALS solver='pallas' through the REAL _spd_solve dispatch (off-TPU the
    explicit request runs the kernel in interpret mode) agrees with the
    exact cholesky path on the regularized ALS normal equations."""
    from harp_tpu.models.als import ALSConfig, _spd_solve

    rng = np.random.default_rng(11)
    k = 8
    v = rng.standard_normal((64, k)).astype(np.float32)
    a = np.einsum("ek,el->kl", v, v) + 0.5 * np.eye(k, dtype=np.float32)
    a = np.broadcast_to(a, (32, k, k)).copy()
    b = rng.standard_normal((32, k)).astype(np.float32)
    exact = _spd_solve(jnp.asarray(a), jnp.asarray(b),
                       ALSConfig(rank=k, solver="cholesky"))
    fast = _spd_solve(jnp.asarray(a), jnp.asarray(b),
                      ALSConfig(rank=k, solver="pallas"))
    np.testing.assert_allclose(np.asarray(fast), np.asarray(exact),
                               rtol=2e-3, atol=2e-3)


def test_ring_attention_matches_reference(session):
    rng = np.random.default_rng(5)
    l, d, dv = 64, 16, 16
    q = rng.standard_normal((l, d)).astype(np.float32)
    k = rng.standard_normal((l, d)).astype(np.float32)
    v = rng.standard_normal((l, dv)).astype(np.float32)

    for causal in (False, True):
        out = session.run(
            lambda a, b, c: ring_attention.ring_attention(a, b, c, causal),
            session.scatter(jnp.asarray(q)), session.scatter(jnp.asarray(k)),
            session.scatter(jnp.asarray(v)),
            in_specs=(session.shard(),) * 3, out_specs=session.shard())
        ref = ring_attention.reference_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)


def test_ring_attention_mha_matches_ulysses_and_reference(session):
    """The two SP layouts compute the SAME attention: multi-head ring vs
    Ulysses vs the replicated per-head reference."""
    rng = np.random.default_rng(13)
    l, h, dh = 64, 8, 8
    q = rng.standard_normal((l, h, dh)).astype(np.float32)
    k = rng.standard_normal((l, h, dh)).astype(np.float32)
    v = rng.standard_normal((l, h, dh)).astype(np.float32)
    ring = session.run(
        lambda a, b, c: ring_attention.ring_attention_mha(a, b, c, True),
        session.scatter(jnp.asarray(q)), session.scatter(jnp.asarray(k)),
        session.scatter(jnp.asarray(v)),
        in_specs=(session.shard(),) * 3, out_specs=session.shard())
    uly = session.run(
        lambda a, b, c: ring_attention.ulysses_attention(a, b, c, h, True),
        session.scatter(jnp.asarray(q)), session.scatter(jnp.asarray(k)),
        session.scatter(jnp.asarray(v)),
        in_specs=(session.shard(),) * 3, out_specs=session.shard())
    np.testing.assert_allclose(np.asarray(ring), np.asarray(uly),
                               rtol=2e-3, atol=2e-3)
    ref = np.stack([
        np.asarray(ring_attention.reference_attention(
            jnp.asarray(q[:, i]), jnp.asarray(k[:, i]), jnp.asarray(v[:, i]),
            True)) for i in range(h)], axis=1)
    np.testing.assert_allclose(np.asarray(ring), ref, rtol=2e-3, atol=2e-3)


def test_blocked_attention_matches_reference_all_block_sizes():
    """The streamed-KV inner attention (what ulysses now runs) is exact for
    every block size, causal and not — including blocks that split the
    causal boundary."""
    rng = np.random.default_rng(13)
    for l in (48, 47):           # 47: prime length exercises the KV padding
        h, d = 2, 8
        q = jnp.asarray(rng.standard_normal((l, h, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((l, h, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((l, h, d)), jnp.float32)
        for causal in (False, True):
            ref = jax.vmap(
                lambda qh, kh, vh: ring_attention.reference_attention(
                    qh, kh, vh, causal), in_axes=1, out_axes=1)(q, k, v)
            for blk in (5, 16, 48, 512):
                got = ring_attention.blocked_attention(q, k, v, causal,
                                                       kv_block=blk)
                np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                           rtol=2e-4, atol=2e-5)


def test_ulysses_attention_matches_reference(session):
    rng = np.random.default_rng(9)
    l, h, dh = 64, 8, 8
    q = rng.standard_normal((l, h, dh)).astype(np.float32)
    k = rng.standard_normal((l, h, dh)).astype(np.float32)
    v = rng.standard_normal((l, h, dh)).astype(np.float32)
    out = session.run(
        lambda a, b, c: ring_attention.ulysses_attention(a, b, c, h, True),
        session.scatter(jnp.asarray(q)), session.scatter(jnp.asarray(k)),
        session.scatter(jnp.asarray(v)),
        in_specs=(session.shard(),) * 3, out_specs=session.shard())
    # per-head reference
    ref = np.stack([
        np.asarray(ring_attention.reference_attention(
            jnp.asarray(q[:, i]), jnp.asarray(k[:, i]), jnp.asarray(v[:, i]),
            True)) for i in range(h)], axis=1)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-3, atol=2e-3)


def test_session_event_api_single_process(session):
    """CollectiveMapper getEvent/waitEvent/sendEvent parity on HarpSession
    (single-process: local delivery, no transport)."""
    from harp_tpu.parallel.events import EventType

    assert session.get_event() is None
    session.send_event({"k": 1})                 # collective → local queue
    ev = session.get_event()
    assert ev is not None and ev.type is EventType.COLLECTIVE
    assert ev.payload == {"k": 1}
    session.send_event("mine", dest=0)           # dest == self
    ev = session.wait_event(timeout=5.0)
    assert ev is not None and ev.payload == "mine"
    import pytest as _pt

    with _pt.raises(ValueError, match="process rank"):
        session.send_event("not-mine", dest=3)   # rank out of range: loud
    session.close_events()
    assert session.get_event() is None           # closed plane: pure peek


def test_flash_attention_interpret_matches_reference():
    """The pallas flash kernel (interpret mode) is exact vs the replicated
    reference, causal and not, across tilings including multi-block grids,
    RAGGED lengths (prime L — padded keys masked inside the kernel,
    VERDICT r4 #10) and Dv != Dh value heads. r7: every pack-eligible shape
    (even H, Dh/Dv <= 64) also runs the two-heads-per-128-lane packed
    layout, which must be bit-for-par with the unpacked one."""
    rng = np.random.default_rng(21)
    for l, h, dh, dv, causal in [(64, 2, 16, 16, False),
                                 (64, 2, 16, 16, True),
                                 (96, 1, 8, 8, True),
                                 (61, 2, 16, 16, False),   # prime L
                                 (97, 1, 8, 8, True),      # prime L, causal
                                 (64, 2, 16, 24, True),    # Dv != Dh
                                 (127, 4, 64, 64, True)]:  # prime L, Dh=64
        q = jnp.asarray(rng.standard_normal((l, h, dh)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((l, h, dh)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((l, h, dv)), jnp.float32)
        ref = jax.vmap(lambda a, b, c: ring_attention.reference_attention(
            a, b, c, causal), in_axes=1, out_axes=1)(q, k, v)
        packs = [False]
        if h % 2 == 0 and dh <= 64 and dv <= 64:
            packs.append(True)
        for hp in packs:
            got = pallas_kernels.flash_attention_pallas(
                q, k, v, causal, bq=32, bk=32, interpret=True, head_pack=hp)
            assert got.shape == (l, h, dv)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-4, atol=1e-5)


def test_flash_attention_interpret_bf16():
    """bf16 q/k/v through the kernel (both layouts) tracks the f32
    reference within bf16 mantissa tolerance — the second dtype of the
    existing kernel test matrix (the K-means kernel tests bf16 the same
    way), at an aligned AND a prime (ragged-padding) length."""
    rng = np.random.default_rng(23)
    for l in (64, 61):
        h, dh = 2, 32
        q = jnp.asarray(rng.standard_normal((l, h, dh)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((l, h, dh)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((l, h, dh)), jnp.bfloat16)
        ref = jax.vmap(lambda a, b, c: ring_attention.reference_attention(
            a.astype(jnp.float32), b.astype(jnp.float32),
            c.astype(jnp.float32), True), in_axes=1, out_axes=1)(q, k, v)
        for hp in (False, True):
            got = pallas_kernels.flash_attention_pallas(
                q, k, v, True, bq=32, bk=32, interpret=True, head_pack=hp)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=3e-2, atol=3e-2)


def test_flash_causal_grid_is_blocksparse():
    """The causal grid NEVER fetches a fully-masked KV block: the
    scalar-prefetch layout arrays ARE the kernel's index map, so asserting
    on them is asserting what the DMA engine is steered to. The trapezoid
    visits ~L(L+bk)/2 worth of KV positions, not L²."""
    layout = pallas_kernels._flash_grid_layout
    # bench shape: L=16384, bq=256, bk=512 — 64 q tiles x 32 kv blocks
    n_q, n_kv, bq, bk = 64, 32, 256, 512
    iq_of, j_of = layout(n_q, n_kv, bq, bk, causal=True)
    # 1) no dead blocks: every visited pair has its smallest key position
    #    <= its largest query position
    assert np.all(j_of * bk <= (iq_of + 1) * bq - 1)
    # 2) no live block is missed and none visits twice: per q tile exactly
    #    ceil(((iq+1)*bq)/bk) blocks, each once
    for iq in range(n_q):
        js = np.sort(j_of[iq_of == iq])
        m = min(n_kv, -(-((iq + 1) * bq) // bk))
        assert js.tolist() == list(range(m))
    # 3) the r5 grid visited n_q*n_kv = 2048 blocks; the trapezoid visits
    #    1056 — the DMA traffic the pl.when predication could not remove
    assert len(iq_of) == 1056 < 0.55 * n_q * n_kv
    # 4) with bq == bk the visited KV positions are EXACTLY L(L+bk)/2
    l = 4096
    b = 256
    iq_sq, j_sq = layout(l // b, l // b, b, b, causal=True)
    assert len(iq_sq) * b * b == l * (l + b) // 2
    # non-causal stays the full rectangle
    iq_r, j_r = layout(4, 3, 32, 32, causal=False)
    assert len(iq_r) == 12 and j_r.max() == 2


def test_flash_stats_compose_ring_hops():
    """return_stats exposes the streaming-softmax pieces so ring hops can
    merge flash-kernel partial results: a diagonal-causal hop over the own
    block merged with a full hop over an earlier block equals the causal
    reference — the exact composition ring_attention_mha runs."""
    rng = np.random.default_rng(29)
    l, h, dh = 64, 4, 16
    lq = l // 2
    q = jnp.asarray(rng.standard_normal((l, h, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((l, h, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((l, h, dh)), jnp.float32)
    ref = jax.vmap(lambda a, b, c: ring_attention.reference_attention(
        a, b, c, True), in_axes=1, out_axes=1)(q, k, v)
    q1 = q[lq:]                         # "worker 1"'s query rows
    o0, m0, d0 = pallas_kernels.flash_attention_pallas(
        q1, k[lq:], v[lq:], causal=True, bq=16, bk=16, interpret=True,
        return_stats=True)              # hop 0: own (diagonal) block
    o1, m1, d1 = pallas_kernels.flash_attention_pallas(
        q1, k[:lq], v[:lq], causal=False, bq=16, bk=16, interpret=True,
        return_stats=True)              # hop 1: fully-live earlier block
    valid = jnp.ones(m0.shape, bool)
    _, num, den = ring_attention._softmax_merge(
        m0, o0 * d0[..., None], d0, m1, o1 * d1[..., None], d1, valid)
    out = num / jnp.maximum(den, 1e-30)[..., None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref[lq:]),
                               rtol=1e-4, atol=1e-5)


def test_ring_attention_mha_flash_hops_match_reference(session):
    """The full ring schedule with flash-kernel hops (interpret mode inside
    shard_map) matches the replicated reference — the TPU dispatch path,
    exercised end to end on the 8-worker CPU mesh."""
    rng = np.random.default_rng(31)
    l, h, dh = 64, 4, 16
    q = rng.standard_normal((l, h, dh)).astype(np.float32)
    k = rng.standard_normal((l, h, dh)).astype(np.float32)
    v = rng.standard_normal((l, h, dh)).astype(np.float32)
    for causal in (True, False):
        ref = np.stack([
            np.asarray(ring_attention.reference_attention(
                jnp.asarray(q[:, i]), jnp.asarray(k[:, i]),
                jnp.asarray(v[:, i]), causal)) for i in range(h)], axis=1)
        out = session.run(
            lambda a, b, c: ring_attention.ring_attention_mha(
                a, b, c, causal, use_flash=True, interpret=True),
            session.scatter(jnp.asarray(q)), session.scatter(jnp.asarray(k)),
            session.scatter(jnp.asarray(v)),
            in_specs=(session.shard(),) * 3, out_specs=session.shard())
        np.testing.assert_allclose(np.asarray(out), ref,
                                   rtol=2e-3, atol=2e-3)
