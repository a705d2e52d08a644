"""Multi-process gang smoke routine — the runnable proof of the L3 bootstrap.

Reference parity: Harp's de-facto integration harness was one JVM per worker
launched over ssh by ``collective/Driver.java:93`` + ``depl/Depl.java:36``, with
every collective class shipping a standalone ``main()`` (e.g.
AllreduceCollective.java:53). This module is that harness TPU-native: run

    python -m harp_tpu.parallel.mp_smoke <process_id> <num_processes> <port> \
        [devices_per_process]

once per process (the pytest parent and ``__graft_entry__.dryrun_multichip`` do
the spawning). Each process joins the gang through
``parallel.distributed.initialize`` (the YARN-AM/HDFS-rendezvous replacement),
builds a HarpSession over the GLOBAL mesh, and exercises:

* collective property checks vs numpy (allreduce, allgather, rotate) across the
  process boundary,
* one K-means iteration (the flagship workload) with replicated outputs compared
  across processes,
* the host event control plane's multi-process branches
  (``EventClient.send_collective`` / ``send_message`` over
  ``multihost_utils.broadcast_one_to_all``) AND the true P2P transport
  (``parallel.p2p.P2PTransport``: KV-store rendezvous, async TCP delivery,
  ring-neighbor messaging with no gang-wide call),
* ``HarpSession.barrier()``'s multihost branch and a clean
  ``distributed.shutdown`` (CollectiveMapper teardown :783-788).

Prints ``MP_SMOKE OK p<i>/<n>`` on success; any failure raises.
"""

from __future__ import annotations

import os
import sys


def run(process_id: int, num_processes: int, port: int,
        devices_per_process: int = 4) -> None:
    # Virtual CPU devices must be requested before the backend initializes
    # (see tests/conftest.py). An inherited device-count flag (e.g. the test
    # parent's 8) is REPLACED — this process must own exactly its
    # devices_per_process share of the gang.
    import re

    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags +
        f" --xla_force_host_platform_device_count={devices_per_process}"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from harp_tpu.parallel import distributed

    distributed.initialize(f"localhost:{port}", num_processes, process_id)
    assert jax.process_count() == num_processes, jax.process_count()
    world = num_processes * devices_per_process
    assert len(jax.devices()) == world, (len(jax.devices()), world)
    assert len(jax.local_devices()) == devices_per_process

    from harp_tpu.collectives import lax_ops, table_ops
    from harp_tpu.parallel.events import EventClient, EventQueue, EventType
    from harp_tpu.session import HarpSession
    from harp_tpu.table import Table

    sess = HarpSession(num_workers=world)

    # --- collective properties vs numpy across the process boundary --------- #
    w = world
    data = np.arange(w * 3, dtype=np.float32).reshape(w, 3) + 1.0

    def allreduce_fn(x):
        t = Table.local(x[0], num_workers=w)
        return table_ops.allreduce(t).data

    out = sess.run(allreduce_fn, sess.scatter(data[:, None, :]),
                   in_specs=(sess.shard(),), out_specs=sess.replicate())
    np.testing.assert_allclose(np.asarray(out)[0], data.sum(0), rtol=1e-6)

    out = sess.run(lambda x: lax_ops.allgather(x[0])[None],
                   sess.scatter(data[:, None, :]),
                   in_specs=(sess.shard(),), out_specs=sess.replicate())
    np.testing.assert_allclose(np.asarray(out)[0], data, rtol=1e-6)

    # rotate: sharded output — check only this process's addressable shards
    rot = sess.run(lambda x: lax_ops.rotate(x, 1),
                   sess.scatter(data), in_specs=(sess.shard(),),
                   out_specs=sess.shard())
    for shard in rot.addressable_shards:
        wid = shard.index[0].start
        np.testing.assert_allclose(
            np.asarray(shard.data)[0], data[(wid - 1) % w], rtol=1e-6)

    # --- one K-means iteration (flagship) ------------------------------------ #
    from harp_tpu.io import datagen
    from harp_tpu.models import kmeans as km

    pts = datagen.dense_points(world * 16, 8, seed=0, num_clusters=4)
    cen0 = datagen.initial_centroids(pts, 4, seed=1)
    model = km.KMeans(sess, km.KMeansConfig(4, 8, iterations=1))
    cen, cost = model.fit(pts, cen0)
    cen = np.asarray(cen)
    assert np.all(np.isfinite(cen))
    # replicated outputs must agree bit-for-bit across processes
    from jax.experimental import multihost_utils

    cen0_proc = multihost_utils.broadcast_one_to_all(
        cen, is_source=jax.process_index() == 0)
    np.testing.assert_array_equal(cen, cen0_proc)

    # --- sharded-output fits across the gang: SGD-MF and LDA out_specs are
    # SHARDED, so their final gathers ride mesh.fetch's process_allgather
    # branch (advisor r4 medium: these crashed with "array spans
    # non-addressable devices" under the gang CLI through round 3) --------- #
    from harp_tpu.models import lda as plda
    from harp_tpu.models import sgd_mf as smf

    nr = world * 4
    rng = np.random.default_rng(7)
    flat = rng.choice(nr * nr, size=nr * 6, replace=False)
    rr, cc = np.divmod(flat, nr)
    vv = (rng.random(len(rr)) + 0.5).astype(np.float32)
    mf = smf.SGDMF(sess, smf.SGDMFConfig(rank=4, epochs=2))
    w_f, h_f, _ = mf.fit(rr.astype(np.int64), cc.astype(np.int64), vv, nr, nr)
    assert w_f.shape == (nr, 4) and np.all(np.isfinite(w_f))
    w_f0 = multihost_utils.broadcast_one_to_all(
        w_f, is_source=jax.process_index() == 0)
    np.testing.assert_array_equal(w_f, w_f0)

    docs = rng.integers(0, 24, size=(world * 2, 8))
    model_lda = plda.LDA(sess, plda.LDAConfig(num_topics=4, vocab=24,
                                              epochs=2))
    dt, wt, _ = model_lda.fit(docs)
    assert dt.shape[0] == world * 2 and wt.shape == (24, 4)
    dt0 = multihost_utils.broadcast_one_to_all(
        dt, is_source=jax.process_index() == 0)
    np.testing.assert_array_equal(dt, dt0)

    # stats family: QR's Q is SHARDED output — the third fetch consumer
    from harp_tpu.models import stats as pstats

    # TSQR needs local rows >= D: world*8 rows over `world` workers, D=6
    xq = rng.standard_normal((world * 8, 6)).astype(np.float32)
    q_mat, r_mat = pstats.QR(sess).compute(xq)
    np.testing.assert_allclose(q_mat @ r_mat, xq, rtol=1e-3, atol=1e-3)
    q0_mat = multihost_utils.broadcast_one_to_all(
        q_mat, is_source=jax.process_index() == 0)
    np.testing.assert_array_equal(q_mat, q0_mat)

    # --- host event control plane (multi-process branches) ------------------- #
    q = EventQueue()
    client = EventClient(q, worker_id=process_id)
    client.send_collective({"msg": "hello-gang", "from": 0}, source=0)
    ev = q.get()
    assert ev is not None and ev.type is EventType.COLLECTIVE
    assert ev.payload["msg"] == "hello-gang"

    client.send_message(dest=1, payload="direct", source=0)
    ev = q.get()
    if process_id == 1:
        assert ev is not None and ev.type is EventType.MESSAGE
        assert ev.payload == "direct"
    else:
        assert ev is None

    # --- true P2P transport (SyncClient/Server residual): rendezvous through
    # the gang coordinator's KV store, async delivery, only 2 processes touch
    # each message -------------------------------------------------------- #
    from harp_tpu.parallel.p2p import P2PTransport

    p2p_q = EventQueue()
    with P2PTransport(p2p_q, rank=process_id) as transport:
        p2p_client = EventClient(p2p_q, worker_id=process_id,
                                 transport=transport)
        # ring: each process messages ONLY its successor (no gang-wide call)
        nxt = (process_id + 1) % num_processes
        p2p_client.send_message(nxt, {"hop": process_id, "blob": b"x" * 4096})
        ev = p2p_q.wait(timeout=60.0)
        assert ev is not None and ev.type is EventType.MESSAGE, ev
        assert ev.source == (process_id - 1) % num_processes
        assert ev.payload["hop"] == ev.source
        assert len(ev.payload["blob"]) == 4096
        # barrier before close so no send races a closed server
        multihost_utils.sync_global_devices("p2p-smoke-done")

    # --- session-level event API (CollectiveMapper getEvent/waitEvent/
    # sendEvent parity): collective fan-out + transport-backed P2P. One
    # shared queue, and P2P delivery is ASYNCHRONOUS — the predecessor's
    # message may land before our own collective enqueue, so consume
    # order-agnostically (the reference's EventQueue made the same
    # non-promise about arrival order) ------------------------------------ #
    sess.send_event({"note": "gang-wide"}, source=0)
    sess.send_event("session-p2p", dest=(process_id + 1) % num_processes)
    got = []
    for _ in range(2):
        ev = sess.wait_event(timeout=60.0)
        assert ev is not None, got
        got.append(ev)
    assert {e.type for e in got} == {EventType.COLLECTIVE,
                                     EventType.MESSAGE}, got
    coll = next(e for e in got if e.type is EventType.COLLECTIVE)
    msg = next(e for e in got if e.type is EventType.MESSAGE)
    assert coll.payload["note"] == "gang-wide"
    assert msg.payload == "session-p2p"
    assert msg.source == (process_id - 1) % num_processes
    multihost_utils.sync_global_devices("session-events-done")
    sess.close_events()
    # second generation: reopening after close must rendezvous under a FRESH
    # KV namespace (coordinator keys are write-once — a fixed namespace
    # would crash here or resolve the closed port)
    sess.send_event("gen2", dest=(process_id + 1) % num_processes)
    ev = sess.wait_event(timeout=60.0)
    assert ev is not None and ev.payload == "gen2", ev
    multihost_utils.sync_global_devices("session-events-gen2-done")
    sess.close_events()

    # --- gang telemetry (ISSUE 7 acceptance): a scripted slow rank is flagged
    # by the straggler report gathered over THIS control plane, and an
    # events-triggered xprof window writes per-rank trace directories ------- #
    import tempfile
    import time as _time

    from harp_tpu import telemetry
    from harp_tpu.parallel import faults as pfaults
    from harp_tpu.telemetry.gang import publish_straggler_report
    from harp_tpu.telemetry.xprof import XprofController, request_xprof

    # rank identity for the fault layer + per-rank telemetry files (the gang
    # launcher exports this; mp_smoke processes are spawned bare)
    os.environ["HARP_PROCESS_ID"] = str(process_id)
    tele_dir = tempfile.mkdtemp(prefix=f"harp-tele-p{process_id}-")
    telemetry.configure(tele_dir, interval=4)
    # sustained straggler on rank 1: 60 ms at every boundary (faults grammar)
    os.environ["HARP_FAULT"] = "slow@epoch=1:rank=1:ms=60"
    for step in range(6):
        t0 = _time.perf_counter()
        pfaults.fire(step + 1)
        telemetry.record_chunk("smoke", start=step, losses=[float(step)],
                               wall_s=_time.perf_counter() - t0)
    os.environ.pop("HARP_FAULT", None)
    # k=1.5: a 2-member gang's median is the mean of both p50s, so the
    # default k=2 can never flag (slow > 2*median iff slow > slow + fast)
    report = publish_straggler_report(sess, tele_dir, k=1.5)
    assert report["suspects"] == [1], report
    assert report["num_ranks"] == num_processes, report
    # every rank computed the same report; rank 0 also persisted it
    if process_id == 0:
        from harp_tpu.telemetry.gang import read_straggler_report

        on_disk = read_straggler_report(tele_dir)
        assert on_disk is not None and on_disk["suspects"] == [1], on_disk
    # the per-rank JSONL exists and carries the smoke steps (beside them,
    # as ``kind: "phase"`` events, whatever the report's collectives
    # compiled meanwhile: telemetry.host_spans)
    telemetry.active().flush()
    with open(os.path.join(tele_dir, f"rank{process_id}",
                           "steps.jsonl")) as f:
        lines = [line for line in f.read().strip().splitlines()
                 if '"kind"' not in line]
    assert len(lines) == 6, len(lines)

    # --- SLO watchdog (ISSUE 12 acceptance): on the LIVE gang, the slow
    # rank's own watchdog burns on its dragged boundary walls and fires the
    # PR 7 machinery exactly once — xprof trigger file armed, snapshot
    # dumped, the straggler report (which names this rank) attached to the
    # journaled incident — while every healthy rank's watchdog stays quiet.
    # Purely local per rank (no collective), so unaligned firing is safe. #
    import json as _json

    from harp_tpu.telemetry.gang import write_straggler_report
    from harp_tpu.telemetry.watchdog import SLOWatchdog

    write_straggler_report(tele_dir, report)   # each rank's own telemetry
    #                                            dir gets the gang's verdict
    wd = SLOWatchdog(0.020, window_s=60.0, min_samples=3, sustain=2,
                     telemetry_dir=tele_dir, rank=process_id)
    hook = wd.boundary_hook()
    os.environ["HARP_FAULT"] = "slow@epoch=1:rank=1:ms=60"
    for step in range(6):
        pfaults.fire(step + 1)
        hook(step, telemetry.active())
    os.environ.pop("HARP_FAULT", None)
    if process_id == 1:
        assert wd.incidents == 1, f"slow rank fired {wd.incidents}x"
        with open(os.path.join(tele_dir, "slo_incidents.jsonl")) as f:
            rec = _json.loads(f.read().strip().splitlines()[0])
        assert rec["straggler_report"]["suspects"] == [1], rec
        assert "xprof_request" in rec["triggered"], rec
        assert os.path.exists(os.path.join(tele_dir, "xprof_request.json"))
    else:
        assert wd.incidents == 0, \
            f"healthy rank {process_id} fired {wd.incidents}x"
    multihost_utils.sync_global_devices("slo-watchdog-smoke-done")

    # xprof window: COLLECTIVE request (rank 0's payload wins — every rank
    # traces into a per-rank dir under rank 0's telemetry root), opened at
    # the next boundary, closed after 2 boundaries
    ctrl = XprofController(sess, rank=process_id)
    request_xprof(sess, steps=2, directory=os.path.join(tele_dir, "xprof"))
    ctrl(1)
    assert ctrl.tracing, "xprof request not picked up at the boundary"
    ctrl(2)
    ctrl(3)
    assert not ctrl.tracing
    found = [os.path.join(r, fn) for r, _, fns in os.walk(ctrl.trace_dir)
             for fn in fns]
    assert found, f"no trace files under {ctrl.trace_dir}"
    multihost_utils.sync_global_devices("telemetry-smoke-done")
    telemetry.disable()
    sess.close_events()

    # --- barrier + teardown --------------------------------------------------- #
    sess.barrier()          # multihost branch: sync_global_devices
    distributed.shutdown()
    print(f"MP_SMOKE OK p{process_id}/{num_processes}", flush=True)


def spawn_gang(num_processes: int = 2, devices_per_process: int = 4,
               timeout: float = 240.0, repo_root: str | None = None
               ) -> list:
    """Spawn the gang from a parent process and reap it, killing every child on
    any failure (the one shared implementation of the Driver.java-style
    launcher; used by tests/test_multiprocess.py and __graft_entry__).

    Returns each child's combined output; raises AssertionError/RuntimeError on
    failure."""
    import socket
    import subprocess

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS":
           f"--xla_force_host_platform_device_count={devices_per_process}",
           # N members share ONE host core here (see test_three_process_gang:
           # member skew is minutes) — a device probe parked behind a
           # concurrent compile or a blocking Gloo collective is starvation,
           # not a dead device, so the gang watchdog gets a deadline sized
           # to the topology instead of the 60 s production default
           "HARP_WATCHDOG_TIMEOUT": os.environ.get(
               "HARP_WATCHDOG_TIMEOUT", "300")}
    root = repo_root or os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    procs = [subprocess.Popen(
        [sys.executable, "-m", "harp_tpu.parallel.mp_smoke",
         str(i), str(num_processes), str(port), str(devices_per_process)],
        cwd=root, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for i in range(num_processes)]
    outs = []
    try:
        for i, p in enumerate(procs):
            try:
                out, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                raise RuntimeError(
                    f"mp_smoke process {i} timed out after {timeout}s")
            outs.append(out)
            assert p.returncode == 0, f"mp_smoke process {i} failed:\n{out}"
            assert f"MP_SMOKE OK p{i}/{num_processes}" in out, out
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return outs


def main(argv=None) -> None:
    argv = argv if argv is not None else sys.argv[1:]
    if len(argv) < 3:
        raise SystemExit(__doc__)
    run(int(argv[0]), int(argv[1]), int(argv[2]),
        int(argv[3]) if len(argv) > 3 else 4)


if __name__ == "__main__":
    main()
