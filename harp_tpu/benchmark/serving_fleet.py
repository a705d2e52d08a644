"""Fleet-operations bench — recovery blip, refresh-under-load, hot keys.

Three scripted scenarios (ISSUE 14 acceptance), each returning a bench row
committed next to ``--only serving``'s latency rows:

* :func:`measure_recovery` — a SEPARATE-PROCESS serving gang under
  closed-loop load absorbs a scripted worker kill
  (``HARP_FAULT=kill@request=N:rank=R`` through the serving fault
  grammar): the fleet controller classifies the death, brings a spare up
  through the on-device reshard restore, and re-routes the placement;
  clients ride ``request_retry``. The row reports ZERO failed requests
  and the recovery-window p99 blip vs the steady-state p99 — the ROADMAP
  fleet item's "survives a killed worker under load with bounded p99
  blip", measured, not promised. Every answered reply is also checked
  against the canonical top-k reference — a recovery that serves wrong
  factors is a failure, not a success with an asterisk.
* :func:`measure_refresh` — an in-process gang serves concurrent clients
  while a "training" thread pushes new factor epochs through
  ``TopKEndpoint.push_epoch``. Every reply names the factor epoch that
  answered it (the versioned snapshot swap), and the row asserts every
  reply's top-k matches ITS version's reference exactly — zero torn
  reads, zero failed requests, mid-traffic.
* :func:`measure_hotkey` — Zipfian traffic against the top-k endpoint,
  measured WITHOUT and WITH the router reply cache
  (:class:`~harp_tpu.serve.cache.TopKReplyCache`): per-pass p50/p99/QPS,
  the endpoint's ``lookup_skew`` histogram (the PR 12 measurement the
  hot-key work is built against), and the cache hit rate.
* :func:`measure_autoscale` — ISSUE 16: a QPS ramp against a one-worker
  in-process fleet with the demand-driven autoscaler closing the loop:
  the row carries the worker-count trajectory (UP under pressure, back
  DOWN when the ramp subsides), every decision with the signals that
  drove it, the scale-up's journaled placement version + zero trace
  counts + AOT-store loads, and the served/shed/wrong tallies (zero
  failed, zero wrong asserted by tier-1's twin and the stage-8 smoke).

All rows carry ``device`` — CPU-mesh numbers price the router/recovery
machinery with CPU dispatches; the driver's on-chip run re-measures.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np


def _percentiles(lat_s: List[float]) -> dict:
    if not lat_s:
        return {"p50_ms": None, "p99_ms": None, "max_ms": None}
    arr = np.sort(np.asarray(lat_s))
    return {
        "p50_ms": round(float(arr[len(arr) // 2]) * 1e3, 3),
        "p99_ms": round(float(arr[min(len(arr) - 1,
                                      int(0.99 * len(arr)))]) * 1e3, 3),
        "max_ms": round(float(arr[-1]) * 1e3, 3),
    }


def _device() -> str:
    import jax

    return ("tpu" if any(d.platform == "tpu" for d in jax.devices())
            else jax.devices()[0].platform)


def _warm_subprocess(models: dict, aot_dir: str,
                     mesh_workers: int = 2) -> float:
    """Run ``harp_tpu.run aot warm`` in a subprocess (the real offline
    prebuild path — it forces its own virtual CPU mesh at the fleet's
    width, which the bench controller's already-initialized backend may
    not offer). Returns the wall seconds of the whole prebuild step."""
    import json
    import os
    import subprocess
    import sys

    import harp_tpu

    cwd = os.path.dirname(os.path.dirname(os.path.abspath(
        harp_tpu.__file__)))
    t0 = time.perf_counter()
    out = subprocess.run(
        [sys.executable, "-m", "harp_tpu.run", "aot", "warm",
         "--aot-dir", aot_dir, "--models-json", json.dumps(models),
         "--mesh-workers", str(mesh_workers)],
        cwd=cwd, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    if out.returncode != 0:
        raise RuntimeError(f"aot warm failed rc={out.returncode}:\n"
                           f"{out.stderr[-800:]}")
    return time.perf_counter() - t0


# --------------------------------------------------------------------------- #
# Recovery blip (separate-process gang, scripted kill)
# --------------------------------------------------------------------------- #

def measure_recovery(*, num_users: int = 64, num_items: int = 32,
                     rank: int = 8, k: int = 3, num_clients: int = 3,
                     requests_per_client: int = 120,
                     warmup_per_client: int = 12,
                     kill_at_request: int = 60,
                     request_timeout: float = 15.0,
                     attempts: int = 12, seed: int = 7,
                     aot_dir: Optional[str] = None,
                     prebuild_artifacts: bool = False) -> dict:
    """Kill serving rank 1 of a 2-process gang under load (module
    docstring). A concurrent warmup phase first compiles every bucket the
    measured loop can reach in both workers (compile time must not read
    as steady-state latency); ``kill_at_request`` counts rank 1's
    RECEIVED requests, so it is set past the warmup's share.
    ``prebuild_artifacts`` runs the ISSUE 15 leg: ``aot warm`` into
    ``aot_dir`` (a temp store by default) before the gang starts, so the
    spare REPLACEMENT loads every dispatch instead of compiling — the
    row gains the replacement's post-mortem ``trace_counts`` (asserted 0
    for loaded buckets by the tier-1 twin of this scenario). Returns the
    committed row."""
    import tempfile

    from harp_tpu.serve import OP_CLASSIFY, OP_TOPK
    from harp_tpu.serve import fleet as fleet_mod

    models = {"mf": {"kind": "topk", "num_users": num_users,
                     "num_items": num_items, "rank": rank, "k": k,
                     "seed": seed},
              "nn": {"kind": "classify_nn", "dim": 12, "classes": 3,
                     "layers": [8], "seed": 1}}
    placement = {"mf": 1, "nn": 0}
    prebuild_s = None
    # TemporaryDirectory, not mkdtemp: its finalizer removes the populated
    # store even when the run raises mid-scenario (a failing bench must
    # not accumulate /tmp stores), while the explicit cleanup() below
    # keeps the success path deterministic
    own_tmp = None
    if prebuild_artifacts and aot_dir is None:
        own_tmp = tempfile.TemporaryDirectory(prefix="harp-bench-aot-")
        aot_dir = own_tmp.name
    if prebuild_artifacts:
        prebuild_s = round(_warm_subprocess(models, aot_dir), 3)
    gang = fleet_mod.ProcessServeGang(
        models, placement, aot_dir=aot_dir,
        env_extra={"HARP_FAULT":
                   f"kill@request={kill_at_request}:rank=1"})
    ref = fleet_mod.topk_reference(*fleet_mod.topk_factors(models["mf"],
                                                           0), k)
    samples: List[tuple] = []        # (t_done, latency_s) per request
    errors: List[str] = []
    wrong: List[tuple] = []
    lock = threading.Lock()
    t_start = [0.0]
    barrier = threading.Barrier(num_clients + 1)

    def client_loop(ci: int) -> None:
        client = gang.make_client()
        rng = np.random.default_rng(seed + 100 + ci)
        try:
            # concurrent warmup: coalesced batches reach the same buckets
            # the measured loop will, in both workers
            for i in range(warmup_per_client):
                op, model, data = ((OP_TOPK, "mf",
                                    int(rng.integers(0, num_users)))
                                   if i % 2 == 0 else
                                   (OP_CLASSIFY, "nn",
                                    rng.normal(size=(12,)).astype(
                                        np.float32)))
                try:
                    client.request_retry(op, model, data,
                                         timeout=60.0, attempts=3)
                except Exception as e:
                    with lock:
                        errors.append(f"warmup {type(e).__name__}: {e}")
            barrier.wait()           # measurement starts together
            for _ in range(requests_per_client):
                u = int(rng.integers(0, num_users))
                t0 = time.perf_counter()
                try:
                    res = client.request_retry(
                        OP_TOPK, "mf", u, timeout=request_timeout,
                        attempts=attempts, backoff_s=0.05,
                        backoff_max_s=1.0, sync_timeout=3.0)
                except Exception as e:  # tallied: the row asserts zero
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}")
                    continue
                dt = time.perf_counter() - t0
                with lock:
                    samples.append((time.perf_counter() - t_start[0], dt))
                    if res["items"] != ref[u]:
                        wrong.append((u, res["items"]))
        finally:
            client.close()

    gang.start()
    try:
        threads = [threading.Thread(target=client_loop, args=(ci,),
                                    name=f"harp-fleet-bench-{ci}")
                   for ci in range(num_clients)]
        for t in threads:
            t.start()
        # anchor BEFORE releasing the barrier: a fast client's first
        # sample must never read t_start while it is still 0.0
        t_start[0] = time.perf_counter()
        barrier.wait()
        for t in threads:
            t.join(600.0)
        # the journal timestamps bound the controller-side recovery
        death = next((r for r in gang.journal.records
                      if r.get("event") == "worker-death"), None)
        replaced = next((r for r in gang.journal.records
                         if r.get("event") == "replaced"), None)
        # the replacement's own start-up stage timings (published with its
        # rendezvous record): where the recovery window actually went —
        # jax init vs restore vs compile-or-load (ISSUE 15's target
        # share). Guarded on the journal AND the record's generation: a
        # wedged recovery must not commit the DEAD gen-0 worker's stages
        # under the replacement's name
        rec1 = fleet_mod.read_worker_records(gang.rdv_dir).get(1, {})
        replacement_stages = (
            rec1.get("stages") if replaced is not None
            and rec1.get("generation") == replaced["generation"] else None)
    finally:
        gang.stop()
    replacement_status = (fleet_mod.read_status(
        gang.rdv_dir, 1, int(replaced["generation"]))
        if replaced else None)
    recovery_s = (round(replaced["ts"] - death["ts"], 3)
                  if death and replaced else None)
    # the OBSERVED recovery window: from the death to the completion of
    # the last retry-elevated request (> blip threshold) — this covers
    # what the controller's journal cannot see, e.g. the replacement's
    # first-dispatch compiles (the AOT-artifact ROADMAP item's target)
    lat_all = [dt for _t, dt in samples]
    in_window, steady = [], lat_all
    observed_recovery_s = None
    if death and samples:
        t0_wall = time.time() - time.perf_counter()  # perf->wall anchor
        w0 = death["ts"] - t0_wall - t_start[0]
        pre = [dt for t, dt in samples if t < w0]
        thresh = max(4.0 * (np.median(pre) if pre else 0.05), 0.25)
        elevated = [t for t, dt in samples if t >= w0 and dt > thresh]
        w1 = max(elevated) if elevated else w0
        in_window = [dt for t, dt in samples if w0 <= t <= w1]
        steady = [dt for t, dt in samples if t < w0 or t > w1]
        observed_recovery_s = round(w1 - w0, 3)
    n = len(samples)
    wall = max(t for t, _dt in samples) if samples else 0.0
    row = {
        "gang": f"2 worker processes + {num_clients} retrying clients, "
                f"scripted kill@request={kill_at_request}:rank=1, spare "
                f"restore via reshard engine",
        "device": _device(),
        "requests": n, "errors": len(errors),
        "error_sample": errors[:3],
        "wrong_results": len(wrong),
        "qps": round(n / wall, 1) if wall else None,
        "steady": _percentiles(steady),
        "recovery_window": _percentiles(in_window),
        "recovery_window_requests": len(in_window),
        "recovery_s": recovery_s,
        "observed_recovery_s": observed_recovery_s,
        "death_cause": death.get("cause") if death else None,
        "restored_version": (replaced or {}).get("restored_version"),
        "journal_events": [r.get("event") for r in gang.journal.records],
        "aot": bool(aot_dir),
        "prebuild_s": prebuild_s,
        "replacement_stages": replacement_stages,
        "replacement_trace_counts": (replacement_status or {}).get(
            "trace_counts"),
        "replacement_aot_loaded": (replacement_status or {}).get(
            "aot_loaded"),
    }
    if row["device"] != "tpu":
        row["note"] = ("cpu-mesh: recovery window prices subprocess jax "
                       "start + reshard restore + first-dispatch compile "
                       "with CPU dispatches; the driver's on-chip run "
                       "re-measures (AOT artifacts are the ROADMAP's next "
                       "rung for the compile share)")
    if own_tmp is not None:
        own_tmp.cleanup()
    return row


# --------------------------------------------------------------------------- #
# Restart to first reply (rolling-restart cold start, artifacts off vs on)
# --------------------------------------------------------------------------- #

def measure_restart(*, num_users: int = 64, num_items: int = 32,
                    rank: int = 8, k: int = 3, repeats: int = 3,
                    seed: int = 7) -> dict:
    """``restart_to_first_reply`` (ISSUE 15 acceptance): spawn a fresh
    1-rank serving gang and time spawn → first successful top-k reply,
    once with a cold store (every bucket compiles) and once against a
    pre-warmed artifact store (every bucket loads; all warm-up lands
    BEFORE rendezvous), plus the composed leg (``aot_cache``): artifacts
    + the persistent compilation cache, primed by one unmeasured start —
    export kills the trace, the cache kills the XLA compile of the
    shipped module. The two legs that measure WITHOUT the cache switch it
    off through jax's own ``JAX_ENABLE_COMPILATION_CACHE`` in the workers'
    environment (an outer ``JAX_COMPILATION_CACHE_DIR`` would otherwise
    turn it on); the composed leg names one fixed directory under the
    checkout's cache (the fleet's workers are CPU-pinned, and the CPU
    backend takes no cache unless one is named — ``aot.cache``). Per-leg medians over ``repeats`` runs, plus the
    replacement-side stage breakdown (spawn→main / jax init / build /
    compile-or-load) from the worker's published rendezvous record — the
    PERF.md recovery-window stage table is THIS data."""
    import os
    import tempfile

    from harp_tpu.serve import OP_TOPK
    from harp_tpu.serve import fleet as fleet_mod

    models = {"mf": {"kind": "topk", "num_users": num_users,
                     "num_items": num_items, "rank": rank, "k": k,
                     "seed": seed}}
    ref = fleet_mod.topk_reference(*fleet_mod.topk_factors(models["mf"],
                                                           0), k)

    def one_leg(aot_dir, compile_cache: bool = False, prime: bool = False
                ) -> dict:
        totals, stage_rows, first_reply_waits = [], [], []
        from harp_tpu.aot import cache

        env = ({} if compile_cache
               else {"JAX_ENABLE_COMPILATION_CACHE": "false"})
        cache_dir = (os.path.join(cache.DEFAULT_DIR, "serving_fleet_bench")
                     if compile_cache else None)
        for i in range(repeats + int(prime)):
            gang = fleet_mod.ProcessServeGang(
                models, {"mf": 0}, mesh_workers=2, aot_dir=aot_dir,
                compile_cache_dir=cache_dir, env_extra=env)
            t0 = time.perf_counter()
            t0_wall = time.time()
            try:
                gang.start()
                t_ready = time.perf_counter()
                client = gang.make_client()
                try:
                    res = client.request_retry(OP_TOPK, "mf", 7,
                                               timeout=30.0, attempts=5)
                finally:
                    client.close()
                t_reply = time.perf_counter()
                if res["items"] != ref[7]:
                    raise RuntimeError(f"cold-start reply wrong: "
                                       f"{res['items']} != {ref[7]}")
                stages = (fleet_mod.read_worker_records(gang.rdv_dir)
                          .get(0, {}).get("stages") or {})
            finally:
                gang.stop()
            if prime and i == 0:
                continue     # the unmeasured cache-priming start
            totals.append(t_reply - t0)
            first_reply_waits.append(t_reply - t_ready)
            if stages:
                stages = dict(stages)
                if stages.get("main_unix_ts"):
                    stages["spawn_to_main_s"] = round(
                        stages.pop("main_unix_ts") - t0_wall, 4)
                stage_rows.append(stages)
        import statistics

        out = {
            "restart_to_first_reply_s": round(statistics.median(totals),
                                              3),
            "runs_s": [round(t, 3) for t in sorted(totals)],
            "rendezvous_to_first_reply_s": round(
                statistics.median(first_reply_waits), 3),
        }
        if stage_rows:
            keys = sorted({k_ for s in stage_rows for k_ in s})
            out["stages_median_s"] = {
                k_: round(statistics.median(
                    s.get(k_, 0.0) for s in stage_rows), 4)
                for k_ in keys}
        return out

    import shutil

    aot_dir = tempfile.mkdtemp(prefix="harp-bench-aot-")
    try:
        prebuild_s = round(_warm_subprocess(models, aot_dir), 3)
        cold = one_leg(None)
        warm = one_leg(aot_dir)
        composed = one_leg(aot_dir, compile_cache=True, prime=True)
    finally:
        # bench runs must not accumulate populated stores in /tmp
        shutil.rmtree(aot_dir, ignore_errors=True)

    def speed(leg):
        return (round(cold["restart_to_first_reply_s"]
                      / leg["restart_to_first_reply_s"], 2)
                if leg["restart_to_first_reply_s"] else None)

    row = {
        "gang": f"fresh 1-rank gang (mesh width 2), spawn -> first "
                f"correct top-k reply, median of {repeats}",
        "device": _device(),
        "no_aot": cold,
        "aot": warm,
        "aot_cache": composed,
        "aot_prebuild_s": prebuild_s,
        "speedup": speed(warm),
        "speedup_aot_cache": speed(composed),
        # the traffic-visible cold-start blip: how long a client waits
        # AFTER the worker announced itself — the artifacts leg serves
        # warm from its first request (this is the number the recovery
        # window inherits; total start shifts warm-up earlier by design)
        "serving_window_speedup": (round(
            cold["rendezvous_to_first_reply_s"]
            / warm["rendezvous_to_first_reply_s"], 2)
            if warm["rendezvous_to_first_reply_s"] else None),
    }
    if row["device"] != "tpu":
        row["note"] = ("cpu-mesh: every leg pays ~1.1s subprocess "
                       "python+jax import; tier-1-shape CPU compiles are "
                       "milliseconds, so the artifact win shows in the "
                       "SERVING WINDOW (rendezvous->first reply: all "
                       "buckets pre-warmed vs compiled under traffic) "
                       "rather than total start; on-chip the compile "
                       "share — and the artifact win — grows, the "
                       "driver's on-chip run re-measures")
    return row


# --------------------------------------------------------------------------- #
# Live refresh under load (in-process gang, versioned swap)
# --------------------------------------------------------------------------- #

def measure_refresh(session=None, *, num_users: int = 64,
                    num_items: int = 32, rank: int = 8, k: int = 3,
                    num_clients: int = 3, refreshes: int = 4,
                    requests_per_client: int = 200,
                    refresh_interval_s: float = 0.25,
                    seed: int = 11) -> dict:
    """Push ``refreshes`` factor epochs into a LIVE in-process gang while
    clients hammer it; assert zero failed requests and zero torn reads
    (every reply consistent with the epoch it names)."""
    from harp_tpu.serve import OP_TOPK, TopKEndpoint, local_gang
    from harp_tpu.serve import fleet as fleet_mod

    if session is None:
        from harp_tpu.session import HarpSession

        session = HarpSession()
    # the SAME deterministic epoch builders the fleet workers/spares use
    # (one seeding recipe — a drift here would diverge the bench from
    # what a spare actually restores)
    mspec = {"num_users": num_users, "num_items": num_items,
             "rank": rank, "seed": seed}

    def factors(version: int):
        return fleet_mod.topk_factors(mspec, version)

    refs: Dict[int, dict] = {
        v: fleet_mod.topk_reference(*factors(v), k)
        for v in range(refreshes + 1)}
    uf0, items0 = factors(0)
    ep = TopKEndpoint(session, "mf", uf0, items0, k=k)
    workers, make_client = local_gang(session, [{"mf": ep}])
    clients = [make_client() for _ in range(num_clients)]
    errors: List[str] = []
    torn: List[tuple] = []
    lat: List[float] = []
    versions_seen = set()
    lock = threading.Lock()
    stop_training = threading.Event()

    def trainer() -> None:
        # the concurrently-training gang: one epoch push per interval,
        # through the same scatter path the parameter-server push ops use
        for v in range(1, refreshes + 1):
            if stop_training.wait(refresh_interval_s):
                return
            uf_v, it_v = factors(v)
            ep.push_epoch(uf_v, it_v, version=v)

    def client_loop(ci: int, client) -> None:
        rng = np.random.default_rng(seed + 200 + ci)
        for _ in range(requests_per_client):
            u = int(rng.integers(0, num_users))
            t0 = time.perf_counter()
            try:
                pending = client.submit(OP_TOPK, "mf", u)
                res = pending.result(30.0)
            except Exception as e:
                with lock:
                    errors.append(f"{type(e).__name__}: {e}")
                continue
            dt = time.perf_counter() - t0
            version = pending.reply.get("version")
            with lock:
                lat.append(dt)
                versions_seen.add(version)
                # THE torn-read assertion: the reply must match the
                # reference of the version it CLAIMS answered it
                if version not in refs or res["items"] != refs[version][u]:
                    torn.append((u, version, res["items"]))

    try:
        clients[0].request(OP_TOPK, "mf", 0, timeout=60.0)   # warm compile
        train_thread = threading.Thread(target=trainer, daemon=True,
                                        name="harp-refresh-trainer")
        threads = [threading.Thread(target=client_loop, args=(ci, c),
                                    name=f"harp-refresh-client-{ci}")
                   for ci, c in enumerate(clients)]
        t0 = time.perf_counter()
        train_thread.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(300.0)
        wall = time.perf_counter() - t0
        stop_training.set()
        train_thread.join(30.0)
    finally:
        stop_training.set()
        for c in clients:
            c.close()
        for w in workers:
            w.close()
    n = len(lat)
    row = {
        "gang": f"1 worker + {num_clients} clients, {refreshes} epoch "
                f"pushes at {refresh_interval_s}s cadence, versioned "
                f"snapshot swap",
        "device": _device(),
        "requests": n, "errors": len(errors),
        "error_sample": errors[:3],
        "torn_reads": len(torn),
        "versions_seen": sorted(v for v in versions_seen
                                if v is not None),
        "refreshes_applied": int(ep.version),
        "qps": round(n / wall, 1) if wall else None,
        **_percentiles(lat),
    }
    if row["device"] != "tpu":
        row["note"] = ("cpu-mesh: the swap itself is a lock-guarded "
                       "pointer flip; epoch build+transfer runs off-lock "
                       "(old epoch serves throughout)")
    return row


# --------------------------------------------------------------------------- #
# Hot keys: Zipfian traffic, cache off vs on
# --------------------------------------------------------------------------- #

def _zipf_ids(rng, num_users: int, n: int, alpha: float) -> np.ndarray:
    w = 1.0 / np.arange(1, num_users + 1) ** alpha
    return rng.choice(num_users, size=n, p=w / w.sum())


def measure_hotkey(session=None, *, num_users: int = 512,
                   num_items: int = 64, rank: int = 8, k: int = 5,
                   num_clients: int = 3, requests_per_client: int = 300,
                   zipf_alpha: float = 1.1, cache_ttl_s: float = 30.0,
                   send_interval_s: float = 0.006,
                   seed: int = 13) -> dict:
    """Zipfian load, one pass without and one with the router reply
    cache; reports tail latency, lookup skew, and the hit rate.

    Both passes offer the SAME paced arrival pattern (each client sends
    every ``send_interval_s``, slipping when a reply is late) — a bare
    closed loop would let the cache pass offer itself more load and
    poison the comparison. Latencies are split by key temperature: the
    HOT subset (the smallest id set carrying half the Zipf mass — the
    keys that melt ``owner = id mod W``) vs the cold tail. The mitigation
    targets exactly the hot subset, and that is where its tail-latency
    improvement is measured; the overall p50/QPS/hit-rate ride along. On
    a real mesh the unmitigated hot-owner route adds per-owner queueing
    the single-host CPU mesh cannot express — the skew histogram names
    the owner, the driver's on-chip run prices it."""
    from harp_tpu.serve import (OP_TOPK, TopKEndpoint, TopKReplyCache,
                                local_gang)

    if session is None:
        from harp_tpu.session import HarpSession

        session = HarpSession()
    rng = np.random.default_rng(seed)
    uf = rng.normal(size=(num_users, rank)).astype(np.float32)
    items = rng.normal(size=(num_items, rank)).astype(np.float32)
    # the HOT subset: smallest id set carrying half the Zipf mass (ids
    # are drawn rank-ordered, so it is a prefix)
    w = 1.0 / np.arange(1, num_users + 1) ** zipf_alpha
    cum = np.cumsum(w / w.sum())
    hot_ids = frozenset(range(int(np.searchsorted(cum, 0.5)) + 1))

    def one_pass(cache) -> dict:
        ep = TopKEndpoint(session, "mf", uf, items, k=k)
        workers, make_client = local_gang(session, [{"mf": ep}],
                                          cache=cache)
        clients = [make_client() for _ in range(num_clients)]
        lat: List[float] = []
        errors: List[str] = []
        lock = threading.Lock()

        def loop(ci: int, client) -> None:
            ids = _zipf_ids(np.random.default_rng(seed + ci), num_users,
                            requests_per_client, zipf_alpha)
            next_t = time.perf_counter() + ci * send_interval_s / \
                max(num_clients, 1)
            for u in ids:
                now = time.perf_counter()
                if now < next_t:
                    time.sleep(next_t - now)
                next_t += send_interval_s
                t0 = time.perf_counter()
                try:
                    client.request(OP_TOPK, "mf", int(u), timeout=30.0)
                except Exception as e:
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}")
                    continue
                with lock:
                    lat.append((int(u), time.perf_counter() - t0))

        try:
            clients[0].request(OP_TOPK, "mf", 0, timeout=60.0)  # warm
            ep.reset_lookup_skew()
            threads = [threading.Thread(target=loop, args=(ci, c))
                       for ci, c in enumerate(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(300.0)
            wall = time.perf_counter() - t0
            skew = ep.lookup_skew()
        finally:
            for c in clients:
                c.close()
            for w in workers:
                w.close()
        hot_lat = [dt for u, dt in lat if u in hot_ids]
        cold_lat = [dt for u, dt in lat if u not in hot_ids]
        out = {"requests": len(lat), "errors": len(errors),
               "qps": round(len(lat) / wall, 1) if wall else None,
               **_percentiles([dt for _u, dt in lat]),
               "hot_keys": _percentiles(hot_lat),
               "hot_requests": len(hot_lat),
               "cold_keys": _percentiles(cold_lat),
               "lookup_skew": {"skew": round(skew["skew"], 3),
                               "hottest": skew["hottest"],
                               "total": skew["total"],
                               "workers": session.num_workers}}
        if session.num_workers == 1:
            out["lookup_skew"]["note"] = (
                "owner = id mod 1 on a single-device session — the "
                "per-owner melt needs a multi-worker mesh (tier-1 "
                "measures it on the 8-worker virtual mesh; the driver's "
                "on-chip run prices the hot owner's route)")
        if cache is not None:
            out["cache"] = {k_: (round(v, 4) if isinstance(v, float)
                                 else v)
                            for k_, v in cache.stats().items()}
        return out

    baseline = one_pass(None)
    cache = TopKReplyCache(ttl_s=cache_ttl_s)
    cached = one_pass(cache)

    def ratio(a, b, key):
        return (round(a[key] / b[key], 2)
                if a.get(key) and b.get(key) else None)

    row = {
        "gang": f"1 worker + {num_clients} clients paced at "
                f"{send_interval_s * 1e3:g}ms, zipf(alpha={zipf_alpha}) "
                f"over {num_users} users, reply cache ttl={cache_ttl_s}s",
        "device": _device(),
        "hot_set_size": len(hot_ids),
        "unmitigated": baseline,
        "cached": cached,
        # the mitigation's target metric: the hot subset's tail
        "hot_p99_speedup": ratio(baseline["hot_keys"], cached["hot_keys"],
                                 "p99_ms"),
        "hot_p50_speedup": ratio(baseline["hot_keys"], cached["hot_keys"],
                                 "p50_ms"),
        "p50_speedup": ratio(baseline, cached, "p50_ms"),
        "p99_speedup": ratio(baseline, cached, "p99_ms"),
    }
    if row["device"] != "tpu":
        row["note"] = ("cpu-mesh: cache hits skip the route+coalesce+"
                       "dispatch stack; on-chip the dispatch share grows, "
                       "the driver's run re-measures the split")
    return row


# --------------------------------------------------------------------------- #
# Autoscale ramp (in-process fleet, demand-driven controller)
# --------------------------------------------------------------------------- #

def measure_autoscale(session=None, *, n_models: int = 3,
                      num_users: int = 32, num_items: int = 16,
                      rank: int = 4, k: int = 3, num_clients: int = 10,
                      max_queue: int = 48, ramp_hold_s: float = 8.0,
                      ramp_timeout_s: float = 30.0, max_workers: int = 3,
                      seed: int = 17,
                      prebuild_artifacts: bool = True) -> dict:
    """QPS ramp against a one-worker in-process gang with the
    demand-driven :class:`~harp_tpu.serve.autoscaler.Autoscaler` closing
    the loop (ISSUE 16 acceptance): the worker count must follow the ramp
    UP (queue-depth/shed pressure → ``scale_up`` through the versioned
    placement push, the fresh worker warming from the AOT store with
    ``trace_counts`` 0) and back DOWN once the clients stop (LIFO retire
    through the same builder path). Every answered reply is checked
    against the canonical top-k reference; a retry-exhausted ``overloaded``
    reply is a CLEAN shed (that is the admission-control contract), any
    other failure fails the row. The scenario runs on its own
    :class:`~harp_tpu.utils.metrics.Metrics` registry so the controller's
    shed/served deltas cannot be polluted by earlier bench rows."""
    import tempfile

    from harp_tpu.serve import OP_TOPK, local_gang, protocol
    from harp_tpu.serve import fleet as fleet_mod
    from harp_tpu.serve.autoscaler import Autoscaler
    from harp_tpu.utils.metrics import Metrics

    if session is None:
        from harp_tpu.session import HarpSession

        session = HarpSession()
    metrics = Metrics()
    specs = {f"m{i}": {"kind": "topk", "num_users": num_users,
                       "num_items": num_items, "rank": rank, "k": k,
                       "seed": seed + i} for i in range(n_models)}
    refs = {name: fleet_mod.topk_reference(
        *fleet_mod.topk_factors(sp, 0), k) for name, sp in specs.items()}
    own_tmp = None
    aot_dir = None
    prebuild_s = None
    hashes = None
    if prebuild_artifacts:
        from harp_tpu.aot import serve_artifacts

        own_tmp = tempfile.TemporaryDirectory(prefix="harp-bench-asc-aot-")
        aot_dir = own_tmp.name
        t0 = time.perf_counter()
        fleet_mod.warm_artifacts(specs, aot_dir, session=session,
                                 metrics=metrics)
        prebuild_s = round(time.perf_counter() - t0, 3)
        # the store is keyed by spec hash (warm_artifacts' convention):
        # the fleet must look up under the same axis or nothing loads
        hashes = {name: serve_artifacts.model_hash_from_spec(sp)
                  for name, sp in specs.items()}
    eps = {name: fleet_mod.build_endpoint(session, name, sp)
           for name, sp in specs.items()}
    workers, make_client = local_gang(
        session, [eps], max_wait_s=0.005, max_queue=max_queue,
        metrics=metrics, client_rank_base=1000)

    def builder(name, version):
        return fleet_mod.build_endpoint(session, name, specs[name],
                                        version=version, restore=True)

    fleet = fleet_mod.LocalFleet(workers, make_client,
                                 endpoint_builder=builder,
                                 metrics=metrics, aot_dir=aot_dir,
                                 aot_model_hashes=hashes)
    served: List[float] = []          # latencies of correct replies
    errors: List[str] = []
    wrong: List[tuple] = []
    shed = [0]
    lock = threading.Lock()
    stop = threading.Event()
    scenario_over = threading.Event()
    t_start = time.perf_counter()
    worker_traj: List[dict] = []      # change points of the worker count

    def sampler() -> None:
        last = None
        while not scenario_over.is_set():
            n = fleet.worker_count()
            if n != last:
                worker_traj.append(
                    {"t_s": round(time.perf_counter() - t_start, 2),
                     "workers": n})
                last = n
            time.sleep(0.02)

    def load(ci: int) -> None:
        client = fleet.make_client()
        rng = np.random.default_rng(seed + 300 + ci)
        try:
            while not stop.is_set():
                name = f"m{rng.integers(0, n_models)}"
                u = int(rng.integers(0, num_users))
                t0 = time.perf_counter()
                try:
                    res = client.request_retry(
                        OP_TOPK, name, u, timeout=10.0, attempts=10,
                        backoff_max_s=0.5, sync_timeout=2.0)
                except protocol.ServeError as e:
                    if str(e).startswith(protocol.ERR_OVERLOADED):
                        with lock:      # clean shed: retry budget spent
                            shed[0] += 1
                    else:
                        with lock:
                            errors.append(f"{type(e).__name__}: {e}")
                    continue
                except Exception as e:  # noqa: BLE001 — tallied, asserted
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}")
                    continue
                dt = time.perf_counter() - t0
                with lock:
                    served.append(dt)
                    if res["items"] != refs[name][u]:
                        wrong.append((name, u, res["items"]))
        finally:
            client.close()

    warm = fleet.make_client()
    try:
        for name in specs:
            warm.request_retry(OP_TOPK, name, 0, timeout=60.0)
    finally:
        warm.close()
    asc = Autoscaler(fleet, metrics=metrics, poll_interval_s=0.05,
                     up_depth=6.0, down_depth=0.5, up_streak=2,
                     down_streak=10, cooldown_s=0.5,
                     max_workers=max_workers, models_per_move=1)
    sampler_t = threading.Thread(target=sampler, daemon=True,
                                 name="harp-asc-bench-sampler")
    threads = [threading.Thread(target=load, args=(ci,),
                                name=f"harp-asc-bench-{ci}")
               for ci in range(num_clients)]
    peak = 1
    try:
        sampler_t.start()
        for t in threads:
            t.start()
        # hold the ramp until the controller has grown the fleet (and at
        # least ramp_hold_s so the grown shape actually serves traffic)
        t0 = time.monotonic()
        while time.monotonic() - t0 < ramp_timeout_s:
            peak = max(peak, fleet.worker_count())
            if peak >= 2 and time.monotonic() - t0 >= ramp_hold_s:
                break
            time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join(30.0)
        ramp_wall = time.monotonic() - t0
        # ramp over: the controller must unwind the shape it built
        t1 = time.monotonic()
        while time.monotonic() - t1 < 30.0 and fleet.worker_count() > 1:
            time.sleep(0.1)
        t2 = time.monotonic()
        while (time.monotonic() - t2 < 10.0
               and not any(r["action"] == "scale-down"
                           for r in asc.trajectory())):
            time.sleep(0.05)
    finally:
        stop.set()
        asc.close()
        scenario_over.set()
        sampler_t.join(5.0)
    # the ramp loop stops sampling once it has seen growth; the sampler
    # thread saw every change point, so the trajectory is the peak's truth
    peak = max([peak] + [p["workers"] for p in worker_traj])
    up_rec = next((r for r in fleet.journal.records
                   if r["event"] == "scale-up"), None)
    down_rec = next((r for r in fleet.journal.records
                     if r["event"] == "scale-down"), None)
    decisions = [{"t_s": r["t_s"], "action": r["action"],
                  "workers": r.get("workers"),
                  "total_depth": r.get("total_depth")}
                 for r in asc.trajectory()]
    final = fleet.worker_count()
    fleet.close()
    n = len(served)
    snap = metrics.snapshot()["counters"]
    row = {
        "gang": f"1 worker + {num_clients} closed-loop clients over "
                f"{n_models} models, max_queue={max_queue}, autoscaler "
                f"up_depth=6/down_depth=0.5 cooldown=0.5s, "
                f"max_workers={max_workers}",
        "device": _device(),
        "requests": n, "errors": len(errors),
        "error_sample": errors[:3],
        "wrong_results": len(wrong),
        "shed_after_retries": shed[0],
        "sheds_total": int(sum(v for k_, v in snap.items()
                               if k_.startswith("serve.shed."))),
        "qps": round(n / ramp_wall, 1) if ramp_wall else None,
        **_percentiles(served),
        "peak_workers": peak, "final_workers": final,
        "worker_trajectory": worker_traj,
        "decisions": decisions,
        "scale_up": (None if up_rec is None else {
            "rank": up_rec["rank"], "models": up_rec["models"],
            "placement_version": up_rec["placement_version"],
            "trace_counts": up_rec["trace_counts"],
            "aot_loaded": up_rec["aot_loaded"]}),
        "scale_down": (None if down_rec is None else {
            "rank": down_rec["rank"], "moved": down_rec["moved"],
            "placement_version": down_rec["placement_version"]}),
        "aot": bool(aot_dir),
        "prebuild_s": prebuild_s,
    }
    if row["device"] != "tpu":
        row["note"] = ("cpu-mesh: the ramp prices router+batcher+dispatch "
                       "with CPU dispatches; the controller reads the same "
                       "gauges either way, the driver's on-chip run "
                       "re-measures the latency split")
    if own_tmp is not None:
        own_tmp.cleanup()
    return row


def measure(session=None, *, recovery_kw: Optional[dict] = None,
            refresh_kw: Optional[dict] = None,
            hotkey_kw: Optional[dict] = None,
            restart_kw: Optional[dict] = None,
            autoscale_kw: Optional[dict] = None) -> dict:
    """All fleet rows; per-scenario kwargs forward to their measure_*
    functions. The ISSUE
    15 comparison rides as ``restart`` (cold start off/on artifacts) and
    ``recovery_aot`` (the scripted-kill recovery re-run with a pre-warmed
    store — the elastic replacement loads instead of compiling); the
    ISSUE 16 ramp rides as ``autoscale``."""
    base_kw = dict(recovery_kw or {})
    # the baseline leg must stay artifact-free for the comparison to mean
    # anything, and the aot leg's override must not collide with a
    # caller-supplied key
    base_kw.pop("prebuild_artifacts", None)
    base_kw.pop("aot_dir", None)
    return {
        "recovery": measure_recovery(**base_kw),
        "recovery_aot": measure_recovery(
            **{**dict(recovery_kw or {}), "prebuild_artifacts": True}),
        "refresh": measure_refresh(session, **(refresh_kw or {})),
        "hotkey": measure_hotkey(session, **(hotkey_kw or {})),
        "restart": measure_restart(**(restart_kw or {})),
        "autoscale": measure_autoscale(session, **(autoscale_kw or {})),
    }


def main(argv=None) -> None:
    """Subprocess entry for the autoscale ramp: ``python -m
    harp_tpu.benchmark.serving_fleet [--ramp_hold_s=N] [--mesh_workers=N]``
    prints the :func:`measure_autoscale` row as the last stdout line.
    Run it on the 8-device virtual CPU mesh — the fleet topology where the
    reshard-restore builder path and the AOT store's traced layouts agree
    (in a process that exposes a single device a restore-built table
    commits a replicated layout and every artifact load would miss into a
    warm-compile)."""
    import json
    import sys

    from harp_tpu.session import HarpSession

    argv = sys.argv[1:] if argv is None else argv
    kw = {}
    for a in argv:
        k, _, v = a.lstrip("-").partition("=")
        kw[k] = float(v) if "." in v else int(v)
    mesh_workers = int(kw.pop("mesh_workers", 8))
    session = HarpSession(num_workers=mesh_workers)
    print(json.dumps(measure_autoscale(session, **kw)))


if __name__ == "__main__":
    main()
