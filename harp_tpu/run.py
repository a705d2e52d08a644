"""Unified per-algorithm launcher surface — ``python -m harp_tpu.run <algo>``.

Reference parity: Harp shipped one CLI launcher per algorithm (``hadoop jar
harp-java-0.1.0.jar edu.iu.kmeans.regroupallgather.KMeansLauncher ...``,
README.md:148-160) with standardized arg parsing (data_aux/Initialize.java:97).
Here one subcommand per BASELINE workload family, with the algorithm-config
flags derived from the model's config dataclass (harp_tpu.config):

    python -m harp_tpu.run kmeans --num-points 100000 --num-centroids 100 \\
        --dim 100 --iterations 10 --work-dir /tmp/km
    python -m harp_tpu.run sgd_mf --num-users 8192 --num-items 8192 \\
        --epochs 10 --work-dir /tmp/mf --save-every 2      # checkpoint+resume
    python -m harp_tpu.run lda --num-docs 2048 --vocab 2000 --num-topics 32
    python -m harp_tpu.run pca --num-points 65536 --dim 256
    python -m harp_tpu.run nn --num-points 8192 --dim 64 --epochs 10

Every subcommand accepts ``--num-workers N`` (mesh size; defaults to all
devices) and ``--cpu-mesh`` (force an N-device virtual CPU mesh — the
reference's multi-mapper local mode). Data is synthetic by default
(io.datagen — the reference launchers likewise embedded generators); file
input mirrors the reference's per-algorithm datasets/ (tiny canonical
fixtures ship in ``datasets/``, regenerate with ``datasets/generate.py``):
``kmeans``/``pca`` ``--points-file``, ``svm`` ``--train-file`` (label in
the last column), ``sgd_mf``/``als`` ``--ratings-file`` (COO), ``lda``
``--corpus-file``, ``subgraph`` ``--template-file`` — each takes a file,
a directory of part-files, or a glob, local or ``scheme://`` remote
(io.loaders.list_files).

Fault tolerance: every subcommand accepts ``--max-restarts N`` — outside a
gang the job re-execs under the elastic supervisor
(parallel.supervisor) and a crash relaunches from the latest verified
checkpoint; under the gang launcher the gang-level supervisor owns
restarts. ``HARP_FAULT`` (parallel.faults) scripts deterministic faults at
the checkpointed loops' iteration boundaries (README: Fault tolerance).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Optional


def _common_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("--num-workers", type=int, default=0,
                   help="mesh size (0 = all devices; reference: map tasks)")
    p.add_argument("--cpu-mesh", action="store_true",
                   help="force a virtual CPU mesh of num-workers devices")
    p.add_argument("--work-dir", default="",
                   help="output/checkpoint directory (optional)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max-restarts", type=int, default=0,
                   help="elastic supervision: on a crash, relaunch the job "
                        "from the latest verified checkpoint up to N times "
                        "(parallel.supervisor; restart journal lands in "
                        "work-dir). Inside a gang this is handled by the "
                        "gang-level supervisor and ignored here.")
    p.add_argument("--telemetry-dir", default="",
                   help="enable gang telemetry (harp_tpu.telemetry): "
                        "per-step JSONL events + comm-volume gauges land in "
                        "DIR/rank<r>/, gang mode adds the straggler report "
                        "and the events-triggered xprof window. Empty = off "
                        "(zero overhead).")
    p.add_argument("--telemetry-interval", type=int, default=16,
                   help="telemetry cadence in CHUNK BOUNDARIES (count-based "
                        "so gang ranks stay aligned): flush + gang straggler "
                        "publish every N boundaries")
    p.add_argument("--metrics-port", type=int, default=-1,
                   help="start the per-process pull exporter "
                        "(telemetry.exporter: /metrics Prometheus text, "
                        "/snapshot JSON, /gang aggregated view in gang "
                        "mode). 0 = ephemeral port (printed at startup), "
                        ">0 = that port + this member's rank (same-host "
                        "gang members never collide), negative = off.")
    p.add_argument("--slo-p99-ms", type=float, default=0.0,
                   help="arm the SLO watchdog (telemetry.watchdog) at this "
                        "rolling p99 target over the CHUNK-BOUNDARY walls "
                        "(compiled chunk + checkpoint + any host drag): on "
                        "sustained burn it auto-arms an xprof window (the "
                        "trigger-file path, every rank), dumps the "
                        "straggler-format snapshot, and journals the "
                        "incident under --telemetry-dir. 0 = off; requires "
                        "--telemetry-dir.")
    p.add_argument("--compile-cache-dir", default="",
                   help="jax persistent compilation cache directory "
                        "(harp_tpu.aot.cache; the cache is always on): "
                        "every XLA compile this run performs is written "
                        "there and every later run — or serving worker/"
                        "spare on the same dir — loads instead of "
                        "compiling. JAX_COMPILATION_CACHE_DIR, when set, "
                        "wins over this flag; empty = one fixed "
                        "git-ignored directory in the checkout.")
    p.add_argument("--slo-window-s", type=float, default=30.0,
                   help="SLO watchdog rolling-window length, seconds")
    p.add_argument("--slo-error-budget", type=float, default=0.1,
                   help="SLO watchdog tolerated error fraction over the "
                        "window (the serving path feeds errors; training "
                        "step walls are all ok=True, so only the p99 "
                        "target fires there)")


def _session(args):
    if args.cpu_mesh:
        n = args.num_workers or 8
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count={n}")
    import jax

    if args.cpu_mesh:
        jax.config.update("jax_platforms", "cpu")
    # join the gang when launched by parallel.launch (HARP_COORDINATOR in
    # the environment — the reference's launchers always ran under the
    # gang), so
    #   python -m harp_tpu.parallel.launch nodes -- python -m harp_tpu.run …
    # trains ONE distributed model across the gang's global mesh instead of
    # N independent copies. Gated on the LAUNCHER env specifically: a
    # one-chip host may export pod-shaped variables (TPU_WORKER_HOSTNAMES)
    # without there being a gang to join
    if os.environ.get("HARP_COORDINATOR"):
        from harp_tpu.parallel import distributed

        distributed.initialize()
    from harp_tpu.aot.cache import enable_compile_cache

    cache_dir = enable_compile_cache(
        getattr(args, "compile_cache_dir", "") or None)
    from harp_tpu.session import HarpSession

    devices = jax.devices()
    n = args.num_workers or len(devices)
    if jax.process_count() > 1:
        # gang mode: --num-workers sized this member's VIRTUAL device share
        # (the cpu-mesh flag above); the session always spans the global mesh
        n = len(devices)
    sess = HarpSession(num_workers=min(n, len(devices)))
    # say which backend the run got: jax falls back to CPU with only a
    # warning when an accelerator fails to initialise, and a job that then
    # "works" on the host is the failure nobody sees (chip_smoke.py reads
    # this line)
    print("harp_tpu.run: " + json.dumps({
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "num_workers": sess.num_workers,
        "compile_cache_dir": cache_dir}), file=sys.stderr, flush=True)
    if getattr(args, "telemetry_dir", ""):
        _enable_telemetry(sess, args.telemetry_dir, args.telemetry_interval,
                          slo_p99_ms=getattr(args, "slo_p99_ms", 0.0),
                          slo_window_s=getattr(args, "slo_window_s", 30.0),
                          slo_error_budget=getattr(args, "slo_error_budget",
                                                   0.1),
                          metrics_port=getattr(args, "metrics_port", -1))
    elif getattr(args, "metrics_port", -1) >= 0:
        # the exporter is useful without the JSONL layer (scrape-only runs)
        _start_exporter(getattr(args, "metrics_port", -1), collector=None)
    return sess


def _start_exporter(metrics_port: int, collector):
    from harp_tpu.telemetry.exporter import MetricsExporter

    rank = int(os.environ.get("HARP_PROCESS_ID", "0"))
    port = metrics_port + rank if metrics_port > 0 else 0
    exporter = MetricsExporter(
        port=port, rank=rank,
        gang=collector.snapshots if collector is not None else None)
    print(f"harp_tpu.telemetry: metrics exporter on "
          f"http://{exporter.host}:{exporter.port} "
          f"(/metrics, /snapshot{', /gang' if collector else ''})",
          file=sys.stderr, flush=True)
    return exporter


def _enable_telemetry(sess, directory: str, interval: int, *,
                      slo_p99_ms: float = 0.0, slo_window_s: float = 30.0,
                      slo_error_budget: float = 0.1,
                      metrics_port: int = -1) -> None:
    """Bring up the telemetry layer for this run (harp_tpu.telemetry):
    per-step JSONL + comm gauges always; in gang mode also the straggler
    publisher and the xprof window controller as chunk-boundary hooks —
    count-based cadence, safe because every member runs the same SPMD host
    loop (same argv, shared checkpoint state). Optionally the pull
    exporter (--metrics-port) and the SLO watchdog (--slo-p99-ms) ride the
    same boundary-hook surface."""
    import jax

    from harp_tpu import telemetry

    log = telemetry.configure(directory, interval=interval)
    if log is None:
        return
    from harp_tpu.telemetry.xprof import XprofController

    # the operator trigger: `echo '{"steps": 20}' > DIR/xprof_request.json`
    # while the job runs opens a window on every rank at its next boundary
    log.add_boundary_hook(XprofController(
        sess, trigger_path=os.path.join(directory, "xprof_request.json"),
        default_dir=os.path.join(directory, "xprof")))
    collector = None
    if jax.process_count() > 1:
        from harp_tpu.telemetry.gang import GangCollector

        collector = GangCollector(sess, directory)
        log.add_boundary_hook(collector)
    if metrics_port >= 0:
        _start_exporter(metrics_port, collector)
    if slo_p99_ms > 0:
        from harp_tpu.telemetry.watchdog import SLOWatchdog

        # fed the inter-boundary wall at every chunk boundary; on burn the
        # xprof trigger file arms EVERY rank's controller (installed above).
        # min_samples=3, not the request-stream default of 20: boundaries
        # are CHUNKY (a job may only have tens of them), and 3 is the same
        # cold-rank floor the straggler detector trusts a p50 at
        wd = SLOWatchdog(slo_p99_ms / 1e3, window_s=slo_window_s,
                         error_budget=slo_error_budget, min_samples=3,
                         telemetry_dir=directory, metrics=log.metrics)
        log.add_boundary_hook(wd.boundary_hook())


def _config_from_args(cls, ns, **overrides):
    import typing
    hints = typing.get_type_hints(cls)
    kwargs = {}
    for f in dataclasses.fields(cls):
        if hints.get(f.name) not in (int, float, str, bool):
            continue
        v = getattr(ns, f.name, None)
        if v is not None:
            kwargs[f.name] = v
    kwargs.update(overrides)
    return cls(**kwargs)


def _add_config_flags(p, cls, skip=None):
    from harp_tpu.config import add_dataclass_args

    add_dataclass_args(p, cls, skip=skip)


# --------------------------------------------------------------------------- #
# Subcommands (one per BASELINE workload family)
# --------------------------------------------------------------------------- #

def run_kmeans(argv) -> int:
    from harp_tpu.models.kmeans import KMeansConfig

    p = argparse.ArgumentParser(prog="harp_tpu.run kmeans")
    _common_flags(p)
    p.add_argument("--num-points", type=int, default=100_000)
    p.add_argument("--points-file", default="")
    p.add_argument("--save-every", type=int, default=0,
                   help="checkpoint centroids every N iterations into "
                        "work-dir (resumes automatically)")
    p.add_argument("--format", default="dense", choices=["dense", "csr"],
                   help="csr = sparse-input variant "
                        "(daal_kmeans/allreducecsr); synthetic data is "
                        "sparsified at --density")
    p.add_argument("--density", type=float, default=0.05,
                   help="synthetic sparsity for --format csr")
    p.add_argument("--stream", action="store_true",
                   help="stream --points-file through the chunked "
                        "prefetching ingestion pipeline (harp_tpu.io."
                        "pipeline) instead of loading it whole: bounded "
                        "host memory, H2D overlapped with assembly, "
                        "bitwise-identical centroids")
    p.add_argument("--chunk-rows", type=int, default=65536,
                   help="rows per streamed chunk (--stream)")
    _add_config_flags(p, KMeansConfig)
    args = p.parse_args(argv)
    if args.save_every and not args.work_dir:
        # argparse usage error — fail before data gen / session / prepare
        p.error("--save-every requires --work-dir (nowhere to checkpoint)")
    if args.stream and not args.points_file:
        p.error("--stream streams part-files: it requires --points-file")
    if args.stream and args.save_every:
        p.error("--stream runs the fit as one compiled program over the "
                "assembled block — checkpointing applies to the in-memory "
                "path (drop --stream or --save-every)")
    cfg = _config_from_args(KMeansConfig, args)
    if args.format == "csr" and (args.points_file or args.save_every
                                 or cfg.comm != "regroupallgather"):
        # same fail-before-session idiom as the --save-every guard
        p.error("--format csr supports synthetic data with the fixed "
                "allreduce collective (daal_kmeans/allreducecsr) — "
                "--points-file/--save-every/--comm do not apply")
    sess = _session(args)
    import numpy as np

    from harp_tpu.io import datagen, loaders
    from harp_tpu.models import kmeans as km

    if args.format == "csr":
        from harp_tpu.models import sparse as sp

        n = args.num_points - args.num_points % sess.num_workers
        rows, cols, vals = datagen.sparse_points(n, cfg.dim, args.density,
                                                 seed=args.seed)
        dense0 = np.zeros((cfg.num_centroids, cfg.dim), np.float32)
        head = rows < cfg.num_centroids
        dense0[rows[head], cols[head]] = vals[head]
        model = sp.SparseKMeans(sess, sp.SparseKMeansConfig(
            cfg.num_centroids, cfg.dim, cfg.iterations))
        state = model.prepare(rows, cols, vals, n)
        model.fit_prepared(state, dense0)                  # compile+warm
        t0 = time.perf_counter()
        cen, costs = model.fit_prepared(state, dense0)
        dt = time.perf_counter() - t0
        print(f"kmeans[csr-allreduce] workers={sess.num_workers} n={n} "
              f"k={cfg.num_centroids} d={cfg.dim} nnz={len(vals)}: "
              f"{cfg.iterations / dt:.2f} iters/s, cost "
              f"{costs[0]:.1f} -> {costs[-1]:.1f}")
        return 0
    if args.stream:
        from harp_tpu.io import pipeline as pl

        paths = loaders.list_files(args.points_file)
        # the head part alone seeds the centroids — streaming exists so the
        # full set never sits in host memory at once
        head = loaders.load_dense_csv([paths[0]])
        cfg = dataclasses.replace(cfg, dim=head.shape[1])
        loader = pl.StreamLoader(paths, chunk_rows=args.chunk_rows)
        total = loader.total_rows
        if total is None:             # native counter unavailable, or URLs
            total = 0
            for pth in paths:
                opener = (loaders._fsspec_open(pth) if loaders._is_url(pth)
                          else open(pth, "rb"))
                with opener as f:
                    total += sum(1 for ln in f if ln.strip())
        n_fit = total - total % sess.num_workers
        if n_fit <= 0:
            p.error(f"--stream input has {total} rows, fewer than the "
                    f"{sess.num_workers}-worker mesh needs")
        cen0 = datagen.initial_centroids(head, cfg.num_centroids,
                                         seed=args.seed + 1)
        model = km.KMeans(sess, cfg)
        t0 = time.perf_counter()
        cen, costs = model.fit_from_stream(
            pl.DevicePrefetcher(loader, sess.replicate_put), cen0, n_fit)
        costs = np.asarray(costs)
        dt = time.perf_counter() - t0
        print(f"kmeans[stream/{cfg.comm}] workers={sess.num_workers} "
              f"n={n_fit} k={cfg.num_centroids} d={cfg.dim} "
              f"chunk_rows={args.chunk_rows}: {cfg.iterations / dt:.2f} "
              f"iters/s (incl stream+assembly), cost "
              f"{costs[0]:.1f} -> {costs[-1]:.1f}")
        import jax

        if args.work_dir and jax.process_index() == 0:
            os.makedirs(args.work_dir, exist_ok=True)
            np.savetxt(os.path.join(args.work_dir, "centroids.csv"),
                       np.asarray(cen), delimiter=",")
        return 0
    if args.points_file:
        # file, directory of part-files, or glob — local or scheme:// remote
        pts = loaders.load_dense_csv(loaders.list_files(args.points_file))
        cfg = dataclasses.replace(cfg, dim=pts.shape[1])
        pts = loaders.truncate_to_workers(pts, sess.num_workers)
    else:
        pts = datagen.dense_points(args.num_points, cfg.dim, seed=args.seed,
                                   num_clusters=cfg.num_centroids)
        pts = pts[: len(pts) - len(pts) % sess.num_workers]
    cen0 = datagen.initial_centroids(pts, cfg.num_centroids, seed=args.seed + 1)
    model = km.KMeans(sess, cfg)
    pts_dev, cen_dev = model.prepare(pts, cen0)
    if args.save_every:
        from harp_tpu.utils.checkpoint import Checkpointer

        ckpt = Checkpointer(os.path.join(args.work_dir, "ckpt"))
        t0 = time.perf_counter()
        cen, costs, start = model.fit_checkpointed(
            pts_dev, cen_dev, ckpt, save_every=args.save_every)
        ran = cfg.iterations - start
        dt = time.perf_counter() - t0
        timing = " (incl compile)"
    else:
        model.fit_prepared(pts_dev, cen_dev)      # compile + warmup
        t0 = time.perf_counter()
        cen, costs = model.fit_prepared(pts_dev, cen_dev)
        # dispatch is asynchronous: without the fetch the clock would stop
        # at the enqueue (the first chip run printed 41,245 iters/s for a
        # program that needs ~0.5 ms per iteration just to read its points)
        costs = np.asarray(costs)
        ran = cfg.iterations
        dt = time.perf_counter() - t0
        timing = ""
    if ran > 0:
        costs = np.asarray(costs)
        print(f"kmeans[{cfg.comm}] workers={sess.num_workers} n={len(pts)} "
              f"k={cfg.num_centroids} d={cfg.dim}: {ran / dt:.2f} "
              f"iters/s{timing}, cost {costs[0]:.1f} -> {costs[-1]:.1f}")
    else:
        print(f"kmeans[{cfg.comm}] workers={sess.num_workers}: fully "
              f"resumed from checkpoint, nothing left to run")
    import jax

    if args.work_dir and jax.process_index() == 0:
        os.makedirs(args.work_dir, exist_ok=True)
        # reference: KMUtil.storeCentroids writes the final model from the
        # MASTER (also on a fully-resumed run — the restored centroids ARE
        # the model); gang members skip the write
        np.savetxt(os.path.join(args.work_dir, "centroids.csv"),
                   np.asarray(cen), delimiter=",")
    return 0


def run_sgd_mf(argv) -> int:
    from harp_tpu.models.sgd_mf import SGDMFConfig

    p = argparse.ArgumentParser(prog="harp_tpu.run sgd_mf")
    _common_flags(p)
    p.add_argument("--num-users", type=int, default=8192)
    p.add_argument("--num-items", type=int, default=8192)
    p.add_argument("--density", type=float, default=0.01)
    p.add_argument("--ratings-file", default="",
                   help="COO 'row col value' file/dir/glob (e.g. "
                        "datasets/sgd_mf); overrides the synthetic data")
    p.add_argument("--adaptive", action="store_true",
                   help="auto-tune the per-hop budget (adjustMiniBatch analog)")
    p.add_argument("--save-every", type=int, default=0,
                   help="checkpoint every N epochs into work-dir (resumes "
                        "automatically if checkpoints exist)")
    _add_config_flags(p, SGDMFConfig)
    args = p.parse_args(argv)
    if args.save_every and not args.work_dir:
        # argparse usage error — fail before data gen / session / prepare
        # (was silently ignored here while kmeans/lda errored)
        p.error("--save-every requires --work-dir (nowhere to checkpoint)")
    sess = _session(args)
    import numpy as np

    from harp_tpu.io import datagen
    from harp_tpu.models import sgd_mf

    cfg = _config_from_args(sgd_mf.SGDMFConfig, args)
    if args.ratings_file:
        from harp_tpu.io import loaders

        rows, cols, vals = loaders.load_coo(
            loaders.list_files(args.ratings_file))
        # shapes come from the data; --num-users/--num-items are ignored
        nu, ni = int(rows.max()) + 1, int(cols.max()) + 1
    else:
        rows, cols, vals = datagen.sparse_ratings(
            args.num_users, args.num_items, rank=min(cfg.rank, 16),
            density=args.density, seed=args.seed)
        nu, ni = args.num_users, args.num_items
    model = sgd_mf.SGDMF(sess, cfg)
    state = model.prepare(rows, cols, vals, nu, ni, seed=args.seed)
    t0 = time.perf_counter()
    if args.save_every:
        from harp_tpu.utils.checkpoint import Checkpointer

        ckpt = Checkpointer(os.path.join(args.work_dir, "ckpt"))
        model.warmup_epoch(state)                 # compile outside the timing
        t0 = time.perf_counter()
        w, h, rmse, start = model.fit_checkpointed(
            state, ckpt, save_every=args.save_every)
        ran = cfg.epochs - start
    elif args.adaptive:
        w, h, rmse, tuner = model.fit_adaptive(state)
        ran = cfg.epochs
        print(f"tuned budget: {tuner.chosen} "
              f"(times {dict(sorted(tuner.times.items()))})")
    else:
        model.fit_prepared(state)                 # compile + warmup
        t0 = time.perf_counter()
        w, h, rmse = model.fit_prepared(state)
        ran = cfg.epochs
    dt = time.perf_counter() - t0
    if ran <= 0 or not len(rmse):
        print(f"sgd_mf[{model.last_layout_stats['layout']}] "
              f"workers={sess.num_workers}: fully resumed from checkpoint, "
              f"nothing left to run")
        return 0
    nnz = len(vals) - model.last_layout_stats.get("duplicates_dropped", 0)
    if args.adaptive:
        # the wall-clock region above includes per-candidate AOT compiles and
        # warm-ups; the tuner's own steady-state epoch timings are the honest
        # throughput figure (advisor r2)
        dt = tuner.times[tuner.chosen] * ran
    sps = nnz * ran / dt
    steady = " (tuner steady-state)" if args.adaptive else ""
    print(f"sgd_mf[{model.last_layout_stats['layout']}] "
          f"workers={sess.num_workers} nnz={nnz} rank={cfg.rank}: "
          f"{sps / 1e6:.2f} M samples/s{steady}, rmse {rmse[0]:.4f} -> "
          f"{rmse[-1]:.4f}")
    return 0


def run_lda(argv) -> int:
    from harp_tpu.models.lda import LDAConfig

    p = argparse.ArgumentParser(prog="harp_tpu.run lda")
    _common_flags(p)
    p.add_argument("--num-docs", type=int, default=1024)
    p.add_argument("--doc-len", type=int, default=64)
    p.add_argument("--corpus-file", default="",
                   help="token-id corpus file/dir/glob (one doc per line, "
                        "fixed length — e.g. datasets/lda); overrides the "
                        "synthetic corpus; vocab grows to fit the data")
    p.add_argument("--save-every", type=int, default=0,
                   help="checkpoint the chain (z + word-topic model) every "
                        "N epochs into work-dir (printModel parity; resumes "
                        "automatically)")
    _add_config_flags(p, LDAConfig)
    args = p.parse_args(argv)
    if args.save_every and not args.work_dir:
        # argparse usage error — fail before data gen / session / prepare
        p.error("--save-every requires --work-dir (nowhere to checkpoint)")
    sess = _session(args)
    import numpy as np

    from harp_tpu.io import datagen
    from harp_tpu.models import lda

    cfg = _config_from_args(lda.LDAConfig, args)
    if args.corpus_file:
        from harp_tpu.io import loaders

        docs = loaders.truncate_to_workers(loaders.load_corpus(
            args.corpus_file), sess.num_workers)
        num_docs = len(docs)
        if docs.size and int(docs.max()) >= cfg.vocab:
            cfg = dataclasses.replace(cfg, vocab=int(docs.max()) + 1)
    else:
        num_docs = args.num_docs - args.num_docs % sess.num_workers
        docs = datagen.lda_corpus(num_docs, cfg.vocab,
                                  max(2, cfg.num_topics // 2), args.doc_len,
                                  seed=args.seed)
    model = lda.LDA(sess, cfg)
    state = model.prepare(docs, seed=args.seed)   # host layout + H2D once
    if args.save_every:
        from harp_tpu.utils.checkpoint import Checkpointer

        ckpt = Checkpointer(os.path.join(args.work_dir, "ckpt"))
        t0 = time.perf_counter()
        _, _, ll, start = model.fit_checkpointed(
            state, ckpt, save_every=args.save_every)
        ran = cfg.epochs - start
        dt = time.perf_counter() - t0
        timing = " (incl compile)"
        if ran <= 0:
            print(f"lda[cgs] workers={sess.num_workers}: fully resumed "
                  f"from checkpoint, nothing left to run")
            return 0
    else:
        model.fit_prepared(state)                 # compile + warmup
        t0 = time.perf_counter()
        _, _, ll = model.fit_prepared(state)
        ran = cfg.epochs
        dt = time.perf_counter() - t0
        timing = ""
    toks = docs.size * ran
    print(f"lda[cgs] workers={sess.num_workers} docs={num_docs} "
          f"vocab={cfg.vocab} K={cfg.num_topics}: {toks / dt / 1e6:.2f} "
          f"M tokens/s{timing}, ll {ll[0]:.4e} -> {ll[-1]:.4e}")
    return 0


def run_pca(argv) -> int:
    p = argparse.ArgumentParser(prog="harp_tpu.run pca")
    _common_flags(p)
    p.add_argument("--num-points", type=int, default=65536)
    p.add_argument("--dim", type=int, default=256)
    p.add_argument("--iterations", type=int, default=5,
                   help="timed repeats")
    p.add_argument("--method", default="cor", choices=["cor", "svd"],
                   help="cor = cordensedistr; svd = svddensedistr "
                        "(z-score + TSQR-SVD)")
    p.add_argument("--format", default="dense", choices=["dense", "csr"],
                   help="csr = daal_pca/corcsrdistr from sparse input")
    p.add_argument("--density", type=float, default=0.05,
                   help="synthetic sparsity for --format csr")
    p.add_argument("--points-file", default="",
                   help="dense CSV file/dir/glob (e.g. datasets/pca); "
                        "overrides the synthetic data (dense format only)")
    args = p.parse_args(argv)
    if args.points_file and args.format == "csr":
        p.error("--points-file applies to --format dense only")
    sess = _session(args)
    import numpy as np

    from harp_tpu.io import datagen
    from harp_tpu.models import stats

    n = args.num_points - args.num_points % sess.num_workers
    if args.format == "csr":
        from harp_tpu.models import sparse as sp

        if args.method != "cor":
            p.error("--format csr implements the correlation method only "
                    "(daal_pca/corcsrdistr — the reference has no svd-csr "
                    "variant)")
        rows, cols, vals = datagen.sparse_points(n, args.dim, args.density,
                                                 seed=args.seed)
        t0 = time.perf_counter()
        w, comps, mean = sp.CSRPCA(sess).fit(rows, cols, vals, n, args.dim)
        dt = time.perf_counter() - t0
        print(f"pca[csr] workers={sess.num_workers} n={n} d={args.dim} "
              f"nnz={len(vals)}: fit in {dt:.2f}s (incl compile), top "
              f"eigenvalue {w[0]:.4f}")
        return 0
    if args.points_file:
        from harp_tpu.io import loaders

        x = loaders.truncate_to_workers(
            loaders.load_dense_csv(loaders.list_files(args.points_file)),
            sess.num_workers)
        n = len(x)
    else:
        x = datagen.dense_points(n, args.dim, seed=args.seed)
    # place once; re-scattering an already-placed array is a no-op, and the
    # repeats loop runs INSIDE one compiled program (stats.PCA.fit_repeated)
    # so the timing is compute, not transfers or per-call dispatch
    x_dev = sess.scatter(x)
    model = stats.PCA(sess, method=args.method)
    if args.method == "svd":
        # the repeated-fits-in-one-program harness is the correlation
        # path's benchmark surface; svd runs plain fits
        model.fit(x_dev)                          # compile + warmup
        t0 = time.perf_counter()
        w, comps, mean = model.fit(x_dev)
        dt = time.perf_counter() - t0
        print(f"pca[svd] workers={sess.num_workers} n={n} d={x.shape[1]}: "
              f"{1.0 / dt:.2f} fits/s, top eigenvalue {w[0]:.4f}")
        return 0
    model.fit_repeated(x_dev, args.iterations)    # compile + warmup
    t0 = time.perf_counter()
    w, comps, mean = model.fit_repeated(x_dev, args.iterations)
    dt = time.perf_counter() - t0
    print(f"pca workers={sess.num_workers} n={n} d={x.shape[1]}: "
          f"{args.iterations / dt:.2f} fits/s, top eigenvalue {w[0]:.4f}")
    return 0


def run_nn(argv) -> int:
    from harp_tpu.models.nn import NNConfig

    p = argparse.ArgumentParser(prog="harp_tpu.run nn")
    _common_flags(p)
    p.add_argument("--num-points", type=int, default=8192)
    p.add_argument("--dim", type=int, default=64)
    _add_config_flags(p, NNConfig)
    args = p.parse_args(argv)
    sess = _session(args)
    import numpy as np

    from harp_tpu.io import datagen
    from harp_tpu.models import nn

    cfg = _config_from_args(nn.NNConfig, args)
    n = args.num_points - args.num_points % sess.num_workers
    x, y = datagen.classification_data(n, args.dim, cfg.num_classes,
                                       seed=args.seed)
    model = nn.MLPClassifier(sess, cfg)
    model.fit(x, y, seed=args.seed)               # compile + warmup
    t0 = time.perf_counter()
    losses = model.fit(x, y, seed=args.seed)
    dt = time.perf_counter() - t0
    acc = (model.predict(x) == y).mean()
    samples = n * cfg.epochs
    print(f"nn workers={sess.num_workers} n={n} d={args.dim} "
          f"layers={cfg.layers}: {samples / dt / 1e6:.2f} M samples/s, "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
          f"train acc {acc:.3f}")
    return 0


def run_als(argv) -> int:
    from harp_tpu.models.als import ALSConfig

    p = argparse.ArgumentParser(prog="harp_tpu.run als")
    _common_flags(p)
    p.add_argument("--num-users", type=int, default=2048)
    p.add_argument("--num-items", type=int, default=2048)
    p.add_argument("--density", type=float, default=0.01)
    p.add_argument("--ratings-file", default="",
                   help="COO 'row col value' file/dir/glob (e.g. "
                        "datasets/als); overrides the synthetic data")
    _add_config_flags(p, ALSConfig)
    args = p.parse_args(argv)
    sess = _session(args)
    from harp_tpu.io import datagen
    from harp_tpu.models import als

    cfg = _config_from_args(als.ALSConfig, args)
    if args.ratings_file:
        from harp_tpu.io import loaders

        rows, cols, vals = loaders.load_coo(
            loaders.list_files(args.ratings_file))
        # shapes come from the data; --num-users/--num-items are ignored
        nu, ni = int(rows.max()) + 1, int(cols.max()) + 1
    else:
        rows, cols, vals = datagen.sparse_ratings(
            args.num_users, args.num_items, rank=min(cfg.rank, 16),
            density=args.density, seed=args.seed)
        nu, ni = args.num_users, args.num_items
    if cfg.implicit:
        import numpy as np

        vals = np.abs(vals)      # implicit mode consumes interaction counts
    model = als.ALS(sess, cfg)
    state = model.prepare(rows, cols, vals, nu, ni, seed=args.seed)
    model.train_prepared(state)                   # compile + warmup
    t0 = time.perf_counter()
    u, v, rmse = model.fit_prepared(state)
    dt = time.perf_counter() - t0
    mode = "implicit" if cfg.implicit else "explicit"
    print(f"als[{mode}] workers={sess.num_workers} nnz={len(vals)} "
          f"rank={cfg.rank}: {cfg.iterations / dt:.2f} iters/s, "
          f"rmse {rmse[0]:.4f} -> {rmse[-1]:.4f}")
    return 0


def run_ccd(argv) -> int:
    from harp_tpu.models.ccd import CCDConfig

    p = argparse.ArgumentParser(prog="harp_tpu.run ccd")
    _common_flags(p)
    p.add_argument("--num-users", type=int, default=1024)
    p.add_argument("--num-items", type=int, default=1024)
    p.add_argument("--density", type=float, default=0.02)
    _add_config_flags(p, CCDConfig)
    args = p.parse_args(argv)
    sess = _session(args)
    from harp_tpu.io import datagen
    from harp_tpu.models import ccd

    cfg = _config_from_args(ccd.CCDConfig, args)
    rows, cols, vals = datagen.sparse_ratings(
        args.num_users, args.num_items, rank=min(cfg.rank, 8),
        density=args.density, seed=args.seed)
    model = ccd.CCD(sess, cfg)
    state = model.prepare(rows, cols, vals, args.num_users, args.num_items,
                          seed=args.seed)
    model.train_prepared(state)                   # compile + warmup
    t0 = time.perf_counter()
    _, _, rmse = model.fit_prepared(state)
    dt = time.perf_counter() - t0
    print(f"ccd workers={sess.num_workers} nnz={len(vals)} rank={cfg.rank}: "
          f"{cfg.outer_iterations / dt:.2f} sweeps/s, "
          f"rmse {rmse[0]:.4f} -> {rmse[-1]:.4f}, "
          f"layout {model.last_layout_stats}")
    return 0


def run_mds(argv) -> int:
    from harp_tpu.models.mds import MDSConfig

    p = argparse.ArgumentParser(
        prog="harp_tpu.run mds",
        description="WDA-SMACOF: weighted MDS by SMACOF majorization under "
        "deterministic annealing. The temperature starts at alpha x the "
        "largest weighted distance and cools by alpha every "
        "--level-iterations iterations until it falls under --t-floor of "
        "that distance, then runs at 0; a job is that whole schedule, in "
        "calls of --iterations iterations, each solved by --cg-iters steps "
        "of CG. One line a call: the iteration reached, its temperature and "
        "the normalised stress sum w (delta - d)^2 / sum w delta^2.")
    _common_flags(p)
    p.add_argument("--num-points", type=int, default=256)
    p.add_argument("--source-dim", type=int, default=8,
                   help="dimensionality of the synthetic source points")
    _add_config_flags(p, MDSConfig)
    args = p.parse_args(argv)
    sess = _session(args)
    from harp_tpu.io import datagen
    from harp_tpu.models import mds

    cfg = _config_from_args(mds.MDSConfig, args)
    n = args.num_points - args.num_points % sess.num_workers
    pts = datagen.dense_points(n, args.source_dim, seed=args.seed)
    model = mds.WDAMDS(sess, cfg)
    t0 = time.perf_counter()
    state = model.prepare(mds.distance_matrix(pts), seed=args.seed)
    _, stress = model.fit_prepared(state, on_call=lambda done, sigma: print(
        f"mds iteration {done} T {model.temperature(done - 1):.4f} "
        f"stress {sigma[-1]:.6f}"))
    dt = time.perf_counter() - t0
    print(f"mds workers={sess.num_workers} n={n} dim={cfg.dim}: "
          f"{len(stress) / dt:.2f} iters/s (incl compile), "
          f"stress {stress[0]:.4f} -> {stress[-1]:.6f}, "
          f"layout {model.last_layout_stats}")
    return 0


def run_pagerank(argv) -> int:
    from harp_tpu.models.pagerank import PageRankConfig

    p = argparse.ArgumentParser(prog="harp_tpu.run pagerank")
    _common_flags(p)
    p.add_argument("--num-vertices", type=int, default=4096)
    p.add_argument("--num-edges", type=int, default=32768)
    _add_config_flags(p, PageRankConfig)
    args = p.parse_args(argv)
    sess = _session(args)
    import numpy as np

    from harp_tpu.models import pagerank

    cfg = _config_from_args(pagerank.PageRankConfig, args)
    rng = np.random.default_rng(args.seed)
    src = rng.integers(0, args.num_vertices, args.num_edges)
    dst = rng.integers(0, args.num_vertices, args.num_edges)
    t0 = time.perf_counter()
    ranks, deltas = pagerank.PageRank(sess, cfg).run(src, dst,
                                                     args.num_vertices)
    dt = time.perf_counter() - t0
    print(f"pagerank workers={sess.num_workers} v={args.num_vertices} "
          f"e={args.num_edges}: {cfg.iterations / dt:.2f} iters/s "
          f"(incl compile), final L1 delta {deltas[-1]:.2e}, "
          f"top rank {ranks.max():.5f}")
    return 0


def run_subgraph(argv) -> int:
    from harp_tpu.models.subgraph import SubgraphConfig

    p = argparse.ArgumentParser(prog="harp_tpu.run subgraph")
    _common_flags(p)
    p.add_argument("--num-vertices", type=int, default=256)
    p.add_argument("--num-edges", type=int, default=1024)
    p.add_argument("--template", default="",
                   help="tree edges like '0-1,1-2,1-3' (default: a path of "
                        "--template-size vertices)")
    p.add_argument("--template-file", default="",
                   help="a reference-format .template file (vertex count, "
                        "edge count, then one edge per line — the "
                        "datasets/daal_subgraph/templates format)")
    _add_config_flags(p, SubgraphConfig)
    args = p.parse_args(argv)
    sess = _session(args)
    import numpy as np

    from harp_tpu.models import subgraph

    cfg = _config_from_args(subgraph.SubgraphConfig, args)
    rng = np.random.default_rng(args.seed)
    src = rng.integers(0, args.num_vertices, args.num_edges)
    dst = rng.integers(0, args.num_vertices, args.num_edges)
    counter = subgraph.SubgraphCounter(sess, cfg)
    t0 = time.perf_counter()
    if args.template_file:
        edges = subgraph.load_template_file(args.template_file)
        est, trials = counter.count_template(edges, src, dst,
                                             args.num_vertices,
                                             seed=args.seed)
        shape = os.path.basename(args.template_file)
    elif args.template:
        edges = [tuple(map(int, e.split("-"))) for e in
                 args.template.split(",")]
        est, trials = counter.count_template(edges, src, dst,
                                             args.num_vertices,
                                             seed=args.seed)
        shape = args.template
    else:
        est, trials = counter.count_paths(src, dst, args.num_vertices,
                                          seed=args.seed)
        shape = f"path{cfg.template_size}"
    dt = time.perf_counter() - t0
    print(f"subgraph[{shape}] workers={sess.num_workers} "
          f"v={args.num_vertices} e={args.num_edges}: estimate {est:.1f} "
          f"({cfg.trials} trials in {dt:.1f}s, cv "
          f"{np.std(trials) / max(np.mean(trials), 1e-9):.2f})")
    return 0


def run_svm(argv) -> int:
    """daal_svm: ``--kernel linear`` trains the primal LinearSVM; rbf/poly
    train the dual KernelSVM; ``--num-classes > 2`` runs the one-vs-one
    MultiClassSVM (MultiClassDenseBatch parity)."""
    from harp_tpu.models.svm import KernelSVMConfig, SVMConfig

    p = argparse.ArgumentParser(prog="harp_tpu.run svm")
    _common_flags(p)
    p.add_argument("--num-points", type=int, default=4096)
    p.add_argument("--dim", type=int, default=32)
    p.add_argument("--num-classes", type=int, default=2)
    p.add_argument("--kernel", default="linear",
                   choices=["linear", "rbf", "poly"],
                   help="linear = primal subgradient; rbf/poly = dual "
                        "kernel machine (rotation-blocked Gram)")
    _add_config_flags(p, KernelSVMConfig, skip={"kernel", "iterations"})
    p.add_argument("--iterations", type=int, default=None,
                   help="default: 200 primal / 400 dual (the per-path "
                        "dataclass defaults)")
    p.add_argument("--lr", type=float, default=0.1,
                   help="primal (linear) path only")
    p.add_argument("--train-file", default="",
                   help="labeled dense CSV file/dir/glob, label in the LAST "
                        "column (e.g. datasets/svm); overrides synthetic")
    args = p.parse_args(argv)
    sess = _session(args)
    from harp_tpu.io import datagen
    from harp_tpu.models import svm

    if args.train_file:
        import numpy as np

        from harp_tpu.io import loaders

        x, y_raw = loaders.load_labeled_csv(args.train_file)
        x = loaders.truncate_to_workers(x, sess.num_workers)
        n = len(x)
        # the trainers take labels 0..k-1 (mapped internally to ±1); CSV
        # labels may use any convention (±1, 1..k) — remap via unique
        classes, y = np.unique(y_raw[:n], return_inverse=True)
        y = y.astype(np.int32)
        k = max(2, len(classes))
    else:
        n = args.num_points - args.num_points % sess.num_workers
        k = max(2, args.num_classes)
        x, y = datagen.classification_data(n, args.dim, k, seed=args.seed)
    dim = x.shape[1]
    t0 = time.perf_counter()
    if args.kernel == "linear" and k == 2:
        cfg = svm.SVMConfig(c=args.c, lr=args.lr,
                            iterations=args.iterations or 200)
        model = svm.LinearSVM(sess, cfg)
        losses = model.fit(x, y)
        dt = time.perf_counter() - t0
        acc = (model.predict(x) == y).mean()
        print(f"svm[linear-primal] workers={sess.num_workers} n={n} "
              f"d={dim}: {cfg.iterations / dt:.1f} iters/s (incl "
              f"compile), hinge {losses[0]:.4f} -> {losses[-1]:.4f}, "
              f"train acc {acc:.3f}")
        return 0
    kcfg = _config_from_args(svm.KernelSVMConfig, args, kernel=args.kernel)
    if k == 2:
        model = svm.KernelSVM(sess, kcfg)
        duals = model.fit(x, y)
        dt = time.perf_counter() - t0
        acc = (model.predict(x) == y).mean()
        print(f"svm[{args.kernel}-dual] workers={sess.num_workers} n={n} "
              f"d={dim}: {kcfg.iterations / dt:.1f} iters/s (incl "
              f"compile), dual {duals[0]:.2f} -> {duals[-1]:.2f}, "
              f"{len(model.sv_x)} SVs, train acc {acc:.3f}")
    else:
        model = svm.MultiClassSVM(sess, kcfg).fit(x, y)
        dt = time.perf_counter() - t0
        acc = (model.predict(x) == y).mean()
        print(f"svm[{args.kernel}-ovo] workers={sess.num_workers} n={n} "
              f"d={dim} classes={k}: {len(model._machines)} machines "
              f"in {dt:.1f}s, train acc {acc:.3f}")
    return 0


def run_forest(argv) -> int:
    from harp_tpu.models.forest import TreeConfig

    p = argparse.ArgumentParser(prog="harp_tpu.run forest")
    _common_flags(p)
    p.add_argument("--num-points", type=int, default=4096)
    p.add_argument("--dim", type=int, default=16)
    _add_config_flags(p, TreeConfig)
    args = p.parse_args(argv)
    sess = _session(args)
    from harp_tpu.io import datagen
    from harp_tpu.models import forest

    cfg = _config_from_args(forest.TreeConfig, args)
    n = args.num_points - args.num_points % sess.num_workers
    x, y = datagen.classification_data(n, args.dim, cfg.num_classes,
                                       seed=args.seed)
    t0 = time.perf_counter()
    if cfg.num_trees > 1:
        model = forest.RandomForest(sess, cfg).fit(x, y, seed=args.seed)
        kind = f"forest x{cfg.num_trees}"
    else:
        model = forest.DecisionTree(sess, cfg).fit(x, y)
        kind = "dtree"
    dt = time.perf_counter() - t0
    acc = (model.predict(x) == y).mean()
    print(f"forest[{kind}] workers={sess.num_workers} n={n} d={args.dim} "
          f"depth={cfg.depth}: trained in {dt:.1f}s, train acc {acc:.3f}")
    return 0


def run_boosting(argv) -> int:
    from harp_tpu.models.boosting import BoostConfig

    p = argparse.ArgumentParser(prog="harp_tpu.run boosting")
    _common_flags(p)
    p.add_argument("--kind", default="ada",
                   choices=["stump", "ada", "brown", "logit"])
    p.add_argument("--num-points", type=int, default=4096)
    p.add_argument("--dim", type=int, default=16)
    _add_config_flags(p, BoostConfig)
    args = p.parse_args(argv)
    sess = _session(args)
    from harp_tpu.io import datagen
    from harp_tpu.models import boosting

    cfg = _config_from_args(boosting.BoostConfig, args)
    n = args.num_points - args.num_points % sess.num_workers
    x, y = datagen.classification_data(n, args.dim, 2, seed=args.seed)
    cls = {"stump": boosting.DecisionStump, "ada": boosting.AdaBoost,
           "brown": boosting.BrownBoost, "logit": boosting.LogitBoost}
    t0 = time.perf_counter()
    model = cls[args.kind](sess, cfg).fit(x, y)
    dt = time.perf_counter() - t0
    acc = (model.predict(x) == y).mean()
    print(f"boosting[{args.kind}] workers={sess.num_workers} n={n} "
          f"d={args.dim} rounds={cfg.rounds}: trained in {dt:.1f}s, "
          f"train acc {acc:.3f}")
    return 0


def run_solver(argv) -> int:
    from harp_tpu.models.solvers import SolverConfig

    p = argparse.ArgumentParser(prog="harp_tpu.run solver")
    _common_flags(p)
    p.add_argument("--kind", default="lbfgs",
                   choices=["sgd", "sgd_minibatch", "sgd_momentum",
                            "adagrad", "lbfgs"])
    p.add_argument("--num-points", type=int, default=4096)
    p.add_argument("--dim", type=int, default=32)
    _add_config_flags(p, SolverConfig)
    args = p.parse_args(argv)
    sess = _session(args)
    import numpy as np

    from harp_tpu.io import datagen
    from harp_tpu.models import solvers

    cfg = _config_from_args(solvers.SolverConfig, args)
    n = args.num_points - args.num_points % sess.num_workers
    x, y, _ = datagen.regression_data(n, args.dim, seed=args.seed)
    y = y.reshape(-1)
    theta0 = np.zeros(args.dim, np.float32)
    t0 = time.perf_counter()
    theta, losses = solvers.Solver(sess, args.kind, cfg).minimize(
        solvers.mse_objective, x, y, theta0)
    dt = time.perf_counter() - t0
    print(f"solver[{args.kind}] workers={sess.num_workers} n={n} "
          f"d={args.dim}: {cfg.iterations / dt:.1f} iters/s (incl compile), "
          f"mse {losses[0]:.4f} -> {losses[-1]:.6f}")
    return 0


def run_stats(argv) -> int:
    p = argparse.ArgumentParser(prog="harp_tpu.run stats")
    _common_flags(p)
    p.add_argument("--op", default="cov",
                   choices=["cov", "moments", "zscore", "minmax", "qr",
                            "pivoted_qr", "svd", "cholesky", "quantiles",
                            "sort", "outlier"])
    p.add_argument("--num-points", type=int, default=8192)
    p.add_argument("--dim", type=int, default=64)
    args = p.parse_args(argv)
    sess = _session(args)
    import numpy as np

    from harp_tpu.io import datagen
    from harp_tpu.models import stats

    n = args.num_points - args.num_points % sess.num_workers
    x = datagen.dense_points(n, args.dim, seed=args.seed)
    t0 = time.perf_counter()
    if args.op == "cov":
        cov, mean = stats.Covariance(sess).compute(x)
        res = f"trace {np.trace(cov):.4f}"
    elif args.op == "moments":
        m = stats.LowOrderMoments(sess).compute(x)
        res = f"mean[0] {m.mean[0]:.4f} var[0] {m.variance[0]:.4f}"
    elif args.op == "zscore":
        z = stats.ZScore(sess).transform(x)
        res = f"col0 mean {z[:, 0].mean():.2e} std {z[:, 0].std():.4f}"
    elif args.op == "minmax":
        mm = stats.MinMax(sess).transform(x)
        res = f"range [{mm.min():.3f}, {mm.max():.3f}]"
    elif args.op == "qr":
        q, r = stats.QR(sess).compute(x)
        res = f"||QR-X|| {np.abs(q @ r - x).max():.2e}"
    elif args.op == "pivoted_qr":
        q, r, piv = stats.PivotedQR(sess).compute(x)
        res = f"||QR-X[:,piv]|| {np.abs(q @ r - x[:, piv]).max():.2e}"
    elif args.op == "svd":
        u, s, vt = stats.SVD(sess).compute(x)
        res = f"top sv {s[0]:.4f}"
    elif args.op == "cholesky":
        l = stats.Cholesky(sess).compute(x)
        res = f"diag[0] {l[0, 0]:.4f}"
    elif args.op == "quantiles":
        q = stats.Quantiles(sess).compute(x, [0.25, 0.5, 0.75])
        res = f"col0 quartiles {np.round(q[:, 0], 4).tolist()}"
    elif args.op == "sort":
        s = stats.Sorting(sess).compute(x)
        res = f"col0 sorted: {bool((np.diff(s[:, 0]) >= 0).all())}"
    else:
        flags = stats.OutlierDetection(sess).compute(x)
        res = f"outliers {int(flags.sum())}/{n}"
    dt = time.perf_counter() - t0
    print(f"stats[{args.op}] workers={sess.num_workers} n={n} "
          f"d={args.dim}: {res} ({dt:.1f}s incl compile)")
    return 0


def run_linear(argv) -> int:
    p = argparse.ArgumentParser(prog="harp_tpu.run linear")
    _common_flags(p)
    p.add_argument("--num-points", type=int, default=8192)
    p.add_argument("--dim", type=int, default=32)
    p.add_argument("--l2", type=float, default=0.0,
                   help="> 0 selects ridge (daal_ridgereg)")
    args = p.parse_args(argv)
    sess = _session(args)
    import numpy as np

    from harp_tpu.io import datagen
    from harp_tpu.models import linear

    n = args.num_points - args.num_points % sess.num_workers
    x, y, _ = datagen.regression_data(n, args.dim, seed=args.seed)
    t0 = time.perf_counter()
    model = linear.LinearRegression(sess, l2=args.l2).fit(x, y)
    dt = time.perf_counter() - t0
    pred = model.predict(x)
    mse = float(np.mean((pred - y.reshape(pred.shape)) ** 2))
    kind = "ridge" if args.l2 > 0 else "linreg"
    print(f"linear[{kind}] workers={sess.num_workers} n={n} d={args.dim}: "
          f"mse {mse:.6f} ({dt:.1f}s incl compile)")
    return 0


def run_classifiers(argv) -> int:
    """naive_bayes / knn / mlr / em — the remaining daal classifier families."""
    p = argparse.ArgumentParser(prog="harp_tpu.run classifiers")
    _common_flags(p)
    p.add_argument("--kind", default="mlr",
                   choices=["multinomial_nb", "gaussian_nb", "knn", "mlr",
                            "em"],
                   help="em: full-covariance EM, --num-classes components, "
                        "through EMGMM.prepare / train_prepared; on TPU the "
                        "E-step is one fused kernel (em_estep), which runs "
                        "6 M points x 100 dims x 100 components in ~1 s an "
                        "iteration on one v5e chip")
    p.add_argument("--num-points", type=int, default=4096)
    p.add_argument("--dim", type=int, default=16)
    p.add_argument("--num-classes", type=int, default=4)
    args = p.parse_args(argv)
    sess = _session(args)
    import numpy as np

    from harp_tpu.io import datagen

    n = args.num_points - args.num_points % sess.num_workers
    x, y = datagen.classification_data(n, args.dim, args.num_classes,
                                       seed=args.seed)
    t0 = time.perf_counter()
    if args.kind == "em":
        from harp_tpu.models.em import EMConfig, EMGMM

        model = EMGMM(sess, EMConfig(num_components=args.num_classes))
        state, quality = model.train_prepared(
            model.prepare(x, *model.first_model(x, seed=args.seed)))
        dt = time.perf_counter() - t0
        print(f"classifiers[em] workers={sess.num_workers} n={n} "
              f"d={args.dim} K={args.num_classes} "
              f"E-step {model.last_layout_stats['kernel']}: "
              f"ll {-quality[0]:.1f} -> {-quality[-1]:.1f} "
              f"({dt:.1f}s incl compile)")
        return 0
    if args.kind == "multinomial_nb":
        from harp_tpu.models.naive_bayes import MultinomialNB

        model = MultinomialNB(sess, num_classes=args.num_classes).fit(
            np.abs(x), y)
        pred = model.predict(np.abs(x))
    elif args.kind == "gaussian_nb":
        from harp_tpu.models.naive_bayes import GaussianNB

        model = GaussianNB(sess, num_classes=args.num_classes).fit(x, y)
        pred = model.predict(x)
    elif args.kind == "knn":
        from harp_tpu.models.knn import KNNClassifier

        model = KNNClassifier(sess, k=5, num_classes=args.num_classes
                              ).fit(x, y)
        pred = model.predict(x[:256])
        y = y[:256]
    else:
        from harp_tpu.models.logistic import MLR, MLRConfig

        model = MLR(sess, MLRConfig(num_classes=args.num_classes))
        model.fit(x, y)
        pred = model.predict(x)
    dt = time.perf_counter() - t0
    acc = (pred == y).mean()
    print(f"classifiers[{args.kind}] workers={sess.num_workers} n={n} "
          f"d={args.dim} C={args.num_classes}: train acc {acc:.3f} "
          f"({dt:.1f}s incl compile)")
    return 0


def run_apriori(argv) -> int:
    from harp_tpu.models.assoc import AprioriConfig

    p = argparse.ArgumentParser(prog="harp_tpu.run apriori")
    _common_flags(p)
    p.add_argument("--num-transactions", type=int, default=2048)
    p.add_argument("--num-items", type=int, default=32)
    _add_config_flags(p, AprioriConfig)
    args = p.parse_args(argv)
    sess = _session(args)
    import numpy as np

    from harp_tpu.models import assoc

    cfg = _config_from_args(assoc.AprioriConfig, args)
    rng = np.random.default_rng(args.seed)
    n = args.num_transactions - args.num_transactions % sess.num_workers
    # correlated items so some multi-item sets clear min_support
    base = rng.random((n, 4)) < 0.5
    tx = np.zeros((n, args.num_items), np.float32)
    for j in range(args.num_items):
        tx[:, j] = base[:, j % 4] if j < 8 else (rng.random(n) < 0.05)
    t0 = time.perf_counter()
    model = assoc.Apriori(sess, cfg).fit(tx)
    dt = time.perf_counter() - t0
    print(f"apriori workers={sess.num_workers} n={n} d={args.num_items}: "
          f"{len(model.itemsets)} frequent itemsets, {len(model.rules)} "
          f"rules ({dt:.1f}s incl compile)")
    return 0


def run_sgxsimu(argv) -> int:
    """experimental/kmeans/sgxsimu parity: K-means with modeled trusted-
    enclave (SGX/TEE) overheads (KMeansLauncher.java of that package)."""
    from harp_tpu.models.kmeans import KMeansConfig

    p = argparse.ArgumentParser(prog="harp_tpu.run sgxsimu")
    _common_flags(p)
    p.add_argument("--num-points", type=int, default=20000)
    p.add_argument("--enclave-total-mb", type=int, default=96,
                   help="total enclave capacity (reference ENCLAVE_TOTAL)")
    p.add_argument("--enclave-per-thd-mb", type=int, default=96,
                   help="effective enclave per thread (ENCLAVE_PER_THD)")
    p.add_argument("--threads-per-worker", type=int, default=1)
    p.add_argument("--page-swap", action="store_true",
                   help="include the page-swap term the reference defines "
                        "but ships commented out")
    p.add_argument("--simulate", action="store_true",
                   help="sleep the modeled overheads so the wall clock "
                        "shows the enclave-cost shape (simuOverhead parity)")
    _add_config_flags(p, KMeansConfig)
    args = p.parse_args(argv)
    sess = _session(args)
    import numpy as np

    from harp_tpu.io import datagen
    from harp_tpu.models import kmeans as km
    from harp_tpu.models.sgxsimu import SGXSimuConfig, SGXSimuKMeans

    cfg = _config_from_args(km.KMeansConfig, args)
    pts = datagen.dense_points(args.num_points, cfg.dim, seed=args.seed,
                               num_clusters=cfg.num_centroids)
    pts = pts[: len(pts) - len(pts) % sess.num_workers]
    cen0 = datagen.initial_centroids(pts, cfg.num_centroids, seed=args.seed + 1)
    simu = SGXSimuConfig(enclave_total_mb=args.enclave_total_mb,
                         enclave_per_thd_mb=args.enclave_per_thd_mb,
                         threads_per_worker=args.threads_per_worker,
                         include_page_swap=args.page_swap)
    t0 = time.perf_counter()
    cen, costs, rep = SGXSimuKMeans(sess, cfg, simu).fit(
        pts, cen0, simulate=args.simulate)
    dt = time.perf_counter() - t0
    # the reference's five LOG.info totals (KMeansCollectiveMapper.java:368)
    print(f"sgxsimu workers={sess.num_workers} n={len(pts)} "
          f"k={cfg.num_centroids} d={cfg.dim}: "
          f"init {rep['init_ms']:.1f} ms; per-iter ecall "
          f"{rep['comp_ecall_ms_per_iter']:.3f} / ocall "
          f"{rep['comp_ocall_ms_per_iter']:.3f} / swap "
          f"{rep['comp_swap_ms_per_iter']:.3f} / comm "
          f"{rep['comm_ms_per_iter']:.3f} ms; clean "
          f"{rep['clean_ms_per_iter']:.3f} ms/iter -> modeled slowdown "
          f"{rep['modeled_slowdown']:.2f}x"
          f"{' (simulated in wall clock)' if args.simulate else ''}; "
          f"cost {np.asarray(costs)[0]:.1f} -> {np.asarray(costs)[-1]:.1f} "
          f"in {dt:.1f}s")
    return 0


def run_aot(argv) -> int:
    """AOT dispatch artifacts (ISSUE 15): offline prebuild + store tools.

    ``aot warm`` exports every (model, bucket) resident serving dispatch
    of a fleet's deterministic model specs into ``--aot-dir`` — run it
    once per deploy (or per jax upgrade / mesh change), point the fleet's
    ``aot_dir`` at the store, and every worker cold start — initial OR
    elastic spare — becomes a load: no trace, compile absorbed before
    rendezvous. ``aot ls`` lists the store; ``aot check`` verifies the
    pinned compiled-program manifest (the jaxlint --artifacts-only gate).
    """
    p = argparse.ArgumentParser(prog="harp_tpu.run aot")
    p.add_argument("action", choices=["warm", "ls", "check"])
    p.add_argument("--aot-dir", default="",
                   help="artifact store directory (warm/ls)")
    p.add_argument("--spec", default="",
                   help="fleet spec JSON (a ProcessServeGang workdir's "
                        "fleet_spec.json) — models + mesh width come from "
                        "it")
    p.add_argument("--models-json", default="",
                   help="inline {model: spec} JSON instead of --spec "
                        "(fleet.build_endpoint spec shapes)")
    p.add_argument("--mesh-workers", type=int, default=2,
                   help="mesh width to export at (must match the serving "
                        "fleet's; overridden by --spec)")
    p.add_argument("--version", type=int, default=0,
                   help="factor epoch to build the endpoints at (the "
                        "PROGRAM is epoch-independent; this only seeds "
                        "the throwaway state)")
    p.add_argument("--compile-cache-dir", default="",
                   help="persistent compilation cache directory the warm "
                        "populates (see the training subcommands' flag: "
                        "JAX_COMPILATION_CACHE_DIR wins, empty = the "
                        "fixed in-checkout directory)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="ls: one JSON object per artifact (machine-"
                        "readable rows incl. the memory and hlo meta, "
                        "null-safe for artifacts exported before either "
                        "row existed) instead of the table")
    args = p.parse_args(argv)
    import json as json_mod

    if args.action == "check":
        # the manifest gate without the rest of jaxlint (CI convenience)
        from tools.jaxlint.__main__ import main as jaxlint_main

        return jaxlint_main(["--artifacts-only"])
    if not args.aot_dir:
        p.error("--aot-dir is required for warm/ls")
    if args.action == "ls":
        from harp_tpu.aot.store import ArtifactStore

        for meta in ArtifactStore(args.aot_dir).list():
            # foreign/partial metas list with placeholders — the listing
            # tool survives the same seams the store's readers do; the
            # static memory row (resident/peak HBM bytes, ISSUE 19) is
            # optional metadata, so its columns degrade the same way
            mem = meta.get("memory") or {}
            if args.as_json:
                # the stable machine row fleet tooling consumes instead
                # of screen-scraping the table: key axes + sizes, the
                # r20 res/peak columns, and the r21 hlo row — absent
                # meta (pre-r20/r21 artifacts) serializes as null, never
                # a missing key
                print(json_mod.dumps({
                    "name": meta.get("name"),
                    "format": meta.get("format"),
                    "world": meta.get("world"),
                    "device_kind": meta.get("device_kind"),
                    "jax_version": meta.get("jax_version"),
                    "quant": meta.get("quant"),
                    "payload_bytes": meta.get("payload_bytes"),
                    "content_hash": meta.get("content_hash"),
                    "resident_arg_bytes": mem.get("resident_arg_bytes"),
                    "peak_live_bytes": mem.get("peak_live_bytes"),
                    "transient_peak_ratio": mem.get(
                        "transient_peak_ratio"),
                    "hlo": meta.get("hlo"),
                }, sort_keys=False))
                continue
            resident = mem.get("resident_arg_bytes")
            peak = mem.get("peak_live_bytes")
            mem_col = (f"res={int(resident):>8d} B peak={int(peak):>8d} B"
                       if resident is not None and peak is not None
                       else "res=       ? B peak=       ? B")
            print(f"{str(meta.get('name') or '?'):32s} "
                  f"{str(meta.get('format') or '?'):18s} "
                  f"world={meta.get('world')} "
                  f"{int(meta.get('payload_bytes') or 0):>8d} B  "
                  f"{mem_col}  "
                  f"{str(meta.get('content_hash') or '')[:12]}")
        return 0
    # warm: the export traces run on a virtual CPU mesh at the fleet's
    # width — never on an accelerator a training gang may hold (the
    # serving workers themselves run CPU-forced the same way)
    mesh_workers = args.mesh_workers
    models = None
    if args.spec:
        with open(args.spec) as f:
            spec = json_mod.load(f)
        models = spec.get("models") or {}
        mesh_workers = int(spec.get("mesh_workers", mesh_workers))
    if args.models_json:
        models = json_mod.loads(args.models_json)
    if not models:
        p.error("warm needs --spec or --models-json")
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count="
                               f"{mesh_workers}").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    from harp_tpu.aot.cache import enable_compile_cache

    enable_compile_cache(args.compile_cache_dir or None)
    from harp_tpu.serve import fleet as fleet_mod

    t0 = time.perf_counter()
    warmed = fleet_mod.warm_artifacts(models, args.aot_dir,
                                      mesh_workers=mesh_workers,
                                      version=args.version)
    dt = time.perf_counter() - t0
    n = sum(len(b) for b in warmed.values())
    print(f"aot warm: exported {n} dispatch artifact(s) for "
          f"{len(warmed)} model(s) at mesh width {mesh_workers} into "
          f"{args.aot_dir} ({dt:.1f}s): " +
          ", ".join(f"{m}={b}" for m, b in sorted(warmed.items())))
    return 0


COMMANDS = {
    "aot": run_aot,
    "kmeans": run_kmeans,
    "sgxsimu": run_sgxsimu,
    "sgd_mf": run_sgd_mf,
    "lda": run_lda,
    "pca": run_pca,
    "nn": run_nn,
    "als": run_als,
    "ccd": run_ccd,
    "mds": run_mds,
    "pagerank": run_pagerank,
    "subgraph": run_subgraph,
    "svm": run_svm,
    "forest": run_forest,
    "boosting": run_boosting,
    "solver": run_solver,
    "stats": run_stats,
    "linear": run_linear,
    "classifiers": run_classifiers,
    "apriori": run_apriori,
}


def _flag_value(argv, name):
    """Last occurrence of ``--name V`` / ``--name=V`` in argv, or None."""
    val = None
    for i, tok in enumerate(argv):
        if tok == name and i + 1 < len(argv):
            val = argv[i + 1]
        elif tok.startswith(name + "="):
            val = tok.split("=", 1)[1]
    return val


def _maybe_self_supervise(argv) -> Optional[int]:
    """``--max-restarts N`` outside a gang: re-exec this job under the
    elastic supervisor (parallel.supervisor.supervise_local) so a crash —
    scripted via HARP_FAULT or real — relaunches from the latest verified
    checkpoint. Under a gang launcher (HARP_COORDINATOR) the gang-level
    supervisor owns restarts; in the supervised child (HARP_SUPERVISED)
    recursing would nest supervisors."""
    try:
        restarts = int(_flag_value(argv, "--max-restarts") or 0)
    except ValueError:
        return None                  # let the subcommand parser reject it
    if restarts <= 0 or os.environ.get("HARP_COORDINATOR") \
            or os.environ.get("HARP_SUPERVISED"):
        return None
    from harp_tpu.parallel import supervisor

    work = _flag_value(argv, "--work-dir") or ""
    outcome = supervisor.supervise_local(
        [sys.executable, "-m", "harp_tpu.run"] + argv,
        # no per-attempt deadline: an unsupervised run has none either, and
        # a long legitimate fit must not be killed just because supervision
        # was enabled (the gang CLI keeps the 1800 s default — there a hung
        # MEMBER blocks the whole gang)
        timeout=None,
        policy=supervisor.RestartPolicy(max_restarts=restarts),
        checkpoint_dir=os.path.join(work, "ckpt") if work else None,
        journal_path=(os.path.join(work, "restart_journal.jsonl")
                      if work else None),
        metrics_path=(os.path.join(work, "supervisor_metrics.json")
                      if work else None),
        telemetry_dir=_flag_value(argv, "--telemetry-dir") or None,
        echo=True)
    if outcome.ok:
        return 0
    # surface the child's own exit code (an argparse usage error must still
    # exit 2 under supervision); signal deaths report negative — map to 1
    rc = (outcome.results.first_failed_rc
          if outcome.results is not None else None)
    return rc if rc is not None and rc > 0 else 1


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        print("subcommands:", ", ".join(sorted(COMMANDS)))
        return 0
    cmd = argv[0]
    if cmd not in COMMANDS:
        print(f"unknown subcommand {cmd!r}; choose from "
              f"{', '.join(sorted(COMMANDS))}", file=sys.stderr)
        return 2
    supervised = _maybe_self_supervise(argv)
    if supervised is not None:
        return supervised
    return COMMANDS[cmd](argv[1:])


if __name__ == "__main__":
    sys.exit(main())
