"""Serving-worker subprocess entry — one fleet member per OS process.

``python -m harp_tpu.serve.worker --spec <spec.json> --rank R`` is what the
:class:`~harp_tpu.serve.fleet.ProcessServeGang` controller launches through
the ``parallel/launch`` member-spawn path (one process per serving rank,
localhost Popen or ssh — the same split the training gang launcher uses).
The process:

1. forces the CPU platform at the spec's mesh width (a serving worker must
   never steal the accelerator a training gang holds unless the spec says
   so), builds a :class:`~harp_tpu.session.HarpSession`, and constructs the
   endpoints for every model the placement assigns to this rank from the
   spec's DETERMINISTIC model builders (``fleet.build_endpoint`` — seeded
   factor generators, so any process can regenerate any epoch's canonical
   table bit-identically);
2. ``--restore`` (the SPARE path): top-k endpoints are constructed with
   ZEROED user factors and re-materialized through the on-device reshard
   engine — :meth:`TopKEndpoint.restore_full` moves the canonical rows
   onto the mesh in chunk-bounded rounds and stamps ``--version`` so the
   spare rejoins announcing the factor epoch it restored;
3. starts a :class:`~harp_tpu.serve.router.ServeWorker` with
   ``fault_exit=True`` — the serving chaos grammar
   (``HARP_FAULT=kill|vanish@request=N:rank=R``) exits with the
   classification code the fleet supervisor maps to CRASH/VANISH — and an
   ``on_control`` hook that serves live-refresh pushes
   (``{"op": "refresh", "version": V}`` regenerates epoch V's factors and
   ``push_epoch``\\ s them on a side thread while traffic keeps flowing).
   With an ``aot_dir`` (spec field or ``--aot-dir``) the ctor PREPARES
   FROM ARTIFACTS: store hits are installed as the resident dispatches
   (``trace_counts`` stays 0 for them — the never-recompile contract) and
   every bucket is warmed, all BEFORE rendezvous — an elastic replacement
   never compiles under traffic (ISSUE 15). jax's persistent compilation
   cache is always on underneath either path (``aot.cache`` resolves its
   directory; ``compile_cache_dir`` names one where
   ``JAX_COMPILATION_CACHE_DIR`` does not). The process fleet's workers
   are pinned to CPU for now (``_force_cpu``; ROADMAP S7/D6);
4. publishes its address atomically into the rendezvous directory
   (``w<rank>.g<generation>.json``) together with its measured START-UP
   STAGE timings (jax init / build+restore / compile-or-load) — the
   recovery-window breakdown the bench and PERF.md quote is measured
   here, not guessed — and keeps re-reading the directory so late or
   replaced peers get dialed;
5. serves until the controller drops the ``stop`` file, then drains
   cleanly, writes a final ``w<rank>.g<generation>.status.json`` (per-
   model ``trace_counts``, artifact-loaded buckets, requests served, and
   per-model resident bytes + quant mode — the zero-recompile and the
   int8-residency assertions read THIS, from outside the corpse), and
   exits 0.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _force_cpu(mesh_workers: int) -> None:
    # must run before jax initializes a backend (trace_targets idiom)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={mesh_workers}")
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", False)


def main(argv=None) -> int:
    t0 = time.perf_counter()
    t0_wall = time.time()        # lets the controller price spawn→main
    #                              (interpreter + harp_tpu import) too
    p = argparse.ArgumentParser(prog="harp_tpu.serve.worker")
    p.add_argument("--spec", required=True, help="fleet spec JSON path")
    p.add_argument("--rank", type=int, required=True)
    p.add_argument("--generation", type=int, default=0)
    p.add_argument("--version", type=int, default=0,
                   help="factor epoch to serve (and restore, with "
                        "--restore)")
    p.add_argument("--restore", action="store_true",
                   help="spare path: zero-build the top-k stores, then "
                        "restore them through the on-device reshard engine")
    p.add_argument("--aot-dir", default=None,
                   help="artifact store to prepare dispatches from "
                        "(overrides the spec's aot_dir; '' disables)")
    p.add_argument("--compile-cache-dir", default=None,
                   help="jax persistent compilation cache directory "
                        "(overrides the spec's compile_cache_dir; "
                        "JAX_COMPILATION_CACHE_DIR wins over both)")
    args = p.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)
    _force_cpu(int(spec.get("mesh_workers", 2)))
    stages = {"jax_init_s": round(time.perf_counter() - t0, 4)}

    from harp_tpu.aot import serve_artifacts
    from harp_tpu.serve import fleet as fleet_mod
    from harp_tpu.serve.cache import TopKReplyCache
    from harp_tpu.serve.endpoints import TopKEndpoint
    from harp_tpu.serve.router import ServeWorker
    from harp_tpu.session import HarpSession

    aot_dir = (args.aot_dir if args.aot_dir is not None
               else spec.get("aot_dir")) or None
    compile_cache_dir = (args.compile_cache_dir
                         if args.compile_cache_dir is not None
                         else spec.get("compile_cache_dir")) or None
    rank = args.rank
    t1 = time.perf_counter()
    session = HarpSession(num_workers=int(spec.get("mesh_workers", 2)))
    placement = {str(m): int(r) for m, r in spec["placement"].items()}
    endpoints = {}
    model_hashes = {}
    for name, mspec in spec["models"].items():
        if placement.get(name) != rank:
            continue
        endpoints[name] = fleet_mod.build_endpoint(
            session, name, mspec, version=args.version,
            restore=args.restore)
        model_hashes[name] = serve_artifacts.model_hash_from_spec(mspec)
    stages["build_restore_s"] = round(time.perf_counter() - t1, 4)

    slo = None
    if spec.get("slo_p99_s"):
        from harp_tpu.telemetry.watchdog import SLOWatchdog

        slo = SLOWatchdog(float(spec["slo_p99_s"]), rank=rank,
                          telemetry_dir=spec.get("telemetry_dir"),
                          **(spec.get("slo_kw") or {}))
    cache = TopKReplyCache() if spec.get("cache") else None

    def on_control(frame: dict) -> None:
        if frame.get("op") != "refresh":
            return
        version = int(frame["version"])

        def _apply():
            # push_epoch's monotonic-version guard makes concurrent
            # refresh threads safe: if a newer epoch's build wins the
            # race, the older push is discarded at the swap, never
            # applied over it
            try:
                for name, ep in endpoints.items():
                    if isinstance(ep, TopKEndpoint):
                        uf, items = fleet_mod.topk_factors(
                            spec["models"][name], version)
                        ep.push_epoch(uf, items, version=version)
            except (ValueError, RuntimeError):
                import logging

                logging.getLogger("harp_tpu.serve").exception(
                    "refresh to version %s failed", version)

        # side thread: push_epoch builds the replacement state off-lock,
        # so traffic keeps being served by the old epoch while it lands
        import threading

        threading.Thread(target=_apply, daemon=True,
                         name=f"harp-serve-refresh-{rank}").start()

    overrides = {str(m): float(v) for m, v in
                 (spec.get("max_wait_overrides") or {}).items()}
    t2 = time.perf_counter()
    worker = ServeWorker(
        session, rank, endpoints, placement,
        peers={}, secret=bytes.fromhex(spec["secret"]),
        max_wait_s=float(spec.get("max_wait_s", 0.002)),
        max_wait_overrides=overrides,
        aot_store=aot_dir, aot_model_hashes=model_hashes,
        compile_cache_dir=compile_cache_dir,
        slo=slo, cache=cache, fault_exit=True, on_control=on_control)
    # with aot on, the ctor loaded/compiled AND warmed every bucket —
    # this stage is the whole artifacts-vs-compile comparison; without
    # aot it is ~0 and the first post-rendezvous dispatch pays instead
    stages["compile_or_load_s"] = round(time.perf_counter() - t2, 4)
    stages["total_to_ready_s"] = round(time.perf_counter() - t0, 4)
    stages["main_unix_ts"] = round(t0_wall, 4)

    rdv_dir = spec["rendezvous_dir"]
    my_file = os.path.join(rdv_dir, f"w{rank}.g{args.generation}.json")
    tmp = my_file + f".tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"rank": rank, "generation": args.generation,
                   "host": worker.address[0], "port": worker.address[1],
                   "pid": os.getpid(), "version": args.version,
                   "restore": bool(args.restore),
                   "aot": bool(aot_dir), "stages": stages,
                   "aot_loaded": {m: list(b) for m, b
                                  in worker.aot_loaded.items()}}, f)
    os.replace(tmp, my_file)

    stop_file = os.path.join(rdv_dir, "stop")
    dialed = {}
    try:
        while not os.path.exists(stop_file):
            # keep the peer map fresh: newest generation per rank wins (a
            # replaced peer publishes a new file; add_peer drops the stale
            # pooled connection when the address changed)
            for peer_rank, addr, gen in fleet_mod.read_rendezvous(rdv_dir):
                if peer_rank != rank and dialed.get(peer_rank, -1) < gen:
                    worker.transport.add_peer(peer_rank, addr)
                    dialed[peer_rank] = gen
            time.sleep(0.1)
    finally:
        worker.close()
        # the post-mortem surface: trace_counts per model (the zero-
        # recompile assertion reads this from OUTSIDE the process) plus
        # how much traffic the worker actually carried
        status = {
            "rank": rank, "generation": args.generation,
            "aot": bool(aot_dir),
            "aot_loaded": {m: list(b) for m, b
                           in worker.aot_loaded.items()},
            "trace_counts": {m: {str(b): int(n) for b, n
                                 in ep.trace_counts.items()}
                             for m, ep in endpoints.items()},
            # resident footprint per model (ISSUE 17): the int8-vs-f32
            # memory claim is asserted from OUTSIDE the corpse, like the
            # zero-recompile one above
            "resident_bytes": {m: int(ep.resident_bytes())
                               for m, ep in endpoints.items()},
            "quant": {m: getattr(ep, "quant", None)
                      for m, ep in endpoints.items()},
            "requests": int(worker.metrics.snapshot()["counters"].get(
                "serve.requests", 0)),
        }
        status_file = os.path.join(
            rdv_dir, f"w{rank}.g{args.generation}.status.json")
        tmp = status_file + f".tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(status, f)
        os.replace(tmp, status_file)
    return 0


if __name__ == "__main__":
    sys.exit(main())
