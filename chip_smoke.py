#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that harp-tpu still starts on the chip.

    python3 chip_smoke.py            # every leg, one child process at a time
    python3 chip_smoke.py --leg NAME # one leg, in this process

Drives the main path once through the entry points a user calls
(``python -m harp_tpu.run``), at the flagship widths, and checks what comes
out by the repo's own means. The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
and the exit code 0 — or there is no such line and the exit code is not 0.

One process per chip, by construction: the parent never imports jax. Each
leg is a child, started after the previous one has exited, with
``JAX_PLATFORMS=tpu`` unless the caller set the variable — a missing chip
is then jax's own hard error, not a CPU run. Every leg prints the device
jax gave it and fails unless the platform is ``tpu``. No leg is wrapped in
a catch: a failure is a traceback and a non-zero exit. All legs run even
after one fails (a chip call is too dear to learn one failure at a time);
any failure makes the whole script exit 1 without the result line.

Children share ONE persistent compile cache, the directory
``harp_tpu.aot.cache`` resolves (``JAX_COMPILATION_CACHE_DIR`` when set,
else ``.jax_compile_cache/`` in the checkout). What the legs write lands
under ``chiprun_out/chip_smoke/``.

Legs (main path first):
  device     jax sees a TPU; wall of an already-compiled trivial dispatch
  kmeans     harp_tpu.run kmeans, n=1M k=100 d=100 f32, 20 iterations
  sgd_mf     harp_tpu.run sgd_mf, 32768x32768 density 0.01 rank 32, and
             7157x1069 rank 100 (MovieLens-10M's geometry, on no tile): each
             compiled program contains the Mosaic call (the Pallas hop ran)
  kernels    flash attention L=16384 H=8 causal (Dh=64 packed, Dh=128) and
             the rank-32 and rank-100 SPD solves: Mosaic in the compiled text, results
             within a stated tolerance of their XLA/numpy references
  restart    harp_tpu.run kmeans --max-restarts 1 with a scripted crash:
             the first child dies holding the chip, the second resumes
  serve      the in-process 2-worker gang answers mixed requests
  multichip, multichip_ring   (only when >= 4 chips are visible)
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# leg -> wall limit of its child, seconds (compilation included). The whole
# script must end inside 1200 s; the one-chip legs sum to less.
LEGS = {
    "device": 120, "kmeans": 300, "sgd_mf": 360, "kernels": 360,
    "restart": 360, "serve": 300,
    "multichip": 600, "multichip_ring": 420,
}
MULTICHIP_LEGS = ("multichip", "multichip_ring")
PLATFORM = "tpu"          # what every leg must find

# smoke sizes (the benchmark's cells are larger: BENCHMARK.json); the
# restart leg runs reduced
KMEANS = {"n": 1_000_000, "k": 100, "d": 100, "iterations": 20}
SGD_MF = {"users": 32768, "items": 32768, "density": 0.01, "rank": 32,
          "nmb": 8, "epochs": 3}
# MovieLens-10M's geometry at a tenth of its rows and columns: no stripe
# (895 rows), block (1069 columns) or rank on a tile of the fused hop kernel
SGD_MF_UNALIGNED = {"users": 7157, "items": 1069, "density": 0.05,
                    "rank": 100, "nmb": 8, "epochs": 3}
# the cell sgdmf-k100.ml20m-x4 as four chips store it: 8 stripes of 4,352
# rows a worker, column blocks of 6,912 (27 tiles of 256), rank 100 as 104.
# Few ratings: the dense hop kernel's time does not depend on how many of
# its cells hold one
SGD_MF_X4 = {"users": 138_493, "items": 26_744, "density": 2e-4,
             "rank": 100, "nmb": 8, "epochs": 5, "calls": 10}
RESTART = {"n": 100_000, "k": 100, "d": 100, "iterations": 6, "crash_at": 3}
FLASH = {"l": 16384, "h": 8}
# ring attention block length per chip: below and at the flash crossover
# (ops/pallas_kernels.use_flash_pallas: L >= 8192)
RING_BLOCK = {"xla": 512, "flash": 8192}


# --------------------------------------------------------------------------- #
# parent: no jax, children one at a time
# --------------------------------------------------------------------------- #

def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def _run_child(cmd, env, timeout: float, log_path: str,
               merge_stderr: bool = False):
    """Run one child in its own process group; returns (rc, stdout). The
    child's stdout is captured (its last line is the leg's JSON result) and
    copied to our stderr and ``log_path``; its stderr passes straight
    through, or joins the captured text with ``merge_stderr``. On timeout
    the whole group is killed — nothing the smoke starts outlives it."""
    proc = subprocess.Popen(
        cmd, env=env, cwd=ROOT, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT if merge_stderr else None,
        text=True, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
        rc = proc.returncode
    except subprocess.TimeoutExpired:
        _kill_group(proc)
        out, rc = f"TIMEOUT after {timeout:.0f}s\n", 124
    finally:
        if proc.poll() is None:
            _kill_group(proc)
    sys.stderr.write(out)
    sys.stderr.flush()
    with open(log_path, "w") as f:
        f.write(out)
    return rc, out


def _last_json(text: str):
    for line in reversed(text.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    return None


def run_all() -> int:
    os.makedirs(OUT_DIR, exist_ok=True)
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "tpu")
    t_start = time.perf_counter()
    results, failed, device = {}, [], None
    for leg, limit in LEGS.items():
        if leg in MULTICHIP_LEGS and (device is None or device["count"] < 4):
            continue
        t0 = time.perf_counter()
        rc, out = _run_child([sys.executable, os.path.abspath(__file__),
                              "--leg", leg], env, limit,
                             os.path.join(OUT_DIR, f"{leg}.log"))
        res = _last_json(out) if rc == 0 else None
        wall = round(time.perf_counter() - t0, 1)
        if res is None or not res.get("ok"):
            failed.append(leg)
            print(f"chip_smoke: leg {leg} FAILED rc={rc} wall={wall}s",
                  file=sys.stderr, flush=True)
            if leg == "device":
                # no accelerator (or no repo around this script): nothing
                # else can run, and no result is printed
                return 1
            continue
        res["wall_s"] = wall
        results[leg] = res
        if leg == "device":
            device = res["device"]
        print(f"chip_smoke: leg {leg} ok wall={wall}s",
              file=sys.stderr, flush=True)
    summary = {"legs": results, "failed": failed,
               "wall_s": round(time.perf_counter() - t_start, 1)}
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    if failed:
        print(f"chip_smoke: FAILED legs: {', '.join(failed)}",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps(summary))
    print(json.dumps({"ok": True, "device": device}))
    return 0


# --------------------------------------------------------------------------- #
# legs: each runs in its own process and owns the chip while it lives
# --------------------------------------------------------------------------- #

def _device(leg: str) -> dict:
    """The device jax gave this leg, printed; anything but a TPU fails."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"[{leg}] device {json.dumps(info)}", file=sys.stderr, flush=True)
    if info["platform"] != PLATFORM:
        raise SystemExit(f"chip_smoke: leg {leg} needs platform "
                         f"'{PLATFORM}'; jax found platform "
                         f"'{info['platform']}' ({info['kind']} "
                         f"x{info['count']})")
    return info


class _CompileStats:
    """Persistent-cache hits/misses and backend compile seconds of this
    process since this object was made, from the program's own records of
    the compile path (``harp_tpu.telemetry.host_spans``: the
    ``program.compile`` records of its ring, the ``program.cache.*``
    counters), which it takes from jax's monitoring events."""

    def __init__(self):
        from harp_tpu import telemetry  # noqa: F401  (registers the listeners)

        self._since = time.perf_counter()
        self._before = self._counts()

    @staticmethod
    def _counts() -> tuple:
        from harp_tpu.utils.metrics import DEFAULT

        counters = DEFAULT.snapshot()["counters"]
        return (int(counters.get("program.cache.hits", 0)),
                int(counters.get("program.cache.misses", 0)))

    def row(self) -> dict:
        import jax

        from harp_tpu import telemetry

        hits, misses = (now - before for now, before
                        in zip(self._counts(), self._before))
        compile_s = telemetry.union_seconds(
            telemetry.phases(self._since), "program.compile")
        return {"dir": jax.config.jax_compilation_cache_dir,
                "hits": hits, "misses": misses,
                "compile_s": round(compile_s, 2)}


def _cli(argv) -> str:
    """``harp_tpu.run.main(argv)`` in this process; returns what it printed
    on stdout (echoed to our stderr). A non-zero return is a failure."""
    import contextlib
    import io

    from harp_tpu import run

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(list(argv))
    text = buf.getvalue()
    sys.stderr.write(text)
    if rc != 0:
        raise SystemExit(f"harp_tpu.run {argv[0]} returned {rc}")
    return text


def _kmeans_argv(c: dict, *extra) -> list:
    return ["kmeans", "--num-points", str(c["n"]), "--num-centroids",
            str(c["k"]), "--dim", str(c["d"]), "--iterations",
            str(c["iterations"]), *extra]


def _sgd_mf_argv(c: dict, *extra) -> list:
    return ["sgd_mf", "--num-users", str(c["users"]), "--num-items",
            str(c["items"]), "--density", str(c["density"]), "--rank",
            str(c["rank"]),
            "--minibatches-per-hop", str(c["nmb"]), "--epochs",
            str(c["epochs"]), *extra]


def _first_last(text: str, label: str):
    """``(first, last)`` of the CLI's ``<label> A -> B`` summary."""
    import re

    m = re.search(label + r" ([-+.\dnaife]+) -> ([-+.\dnaife]+)", text)
    if m is None:
        raise SystemExit(f"no '{label} A -> B' in the launcher's output")
    return float(m.group(1)), float(m.group(2))


def _assert_mosaic(text: str, what: str) -> None:
    """The compiled program's text carries the Mosaic custom call — the
    Pallas kernel is what ran, not the XLA path beside it."""
    if "tpu_custom_call" not in text:
        raise SystemExit(f"{what}: compiled program has no tpu_custom_call "
                         f"— the XLA path was compiled, not the Pallas "
                         f"kernel")


def _finite(*values) -> None:
    import math

    for v in values:
        if not math.isfinite(v):
            raise SystemExit(f"non-finite value {v}")


def leg_device() -> dict:
    import jax
    import jax.numpy as jnp

    from harp_tpu.aot.cache import enable_compile_cache

    info = _device("device")
    cache_dir = enable_compile_cache()
    add_one = jax.jit(lambda x: x + 1.0)
    x = jnp.zeros((8,), jnp.float32)
    add_one(x).block_until_ready()                 # compile
    walls = []
    for _ in range(50):
        t0 = time.perf_counter()
        add_one(x).block_until_ready()
        walls.append(time.perf_counter() - t0)
    walls.sort()
    # block_until_ready blocks on this machine (the chip is attached to the
    # process). An observation for ROADMAP D2, not a metric of the system.
    return {"device": info, "compile_cache_dir": cache_dir,
            "trivial_dispatch_wall_us_observation": {
                "what": "already-compiled x+1 on 8 floats, one dispatch "
                        "ending in block_until_ready",
                "median": round(walls[len(walls) // 2] * 1e6, 1),
                "max": round(walls[-1] * 1e6, 1)}}


def leg_kmeans() -> dict:
    info = _device("kmeans")
    stats = _CompileStats()
    first, last = _first_last(_cli(_kmeans_argv(KMEANS)), "cost")
    _finite(first, last)
    if not last <= first:
        raise SystemExit(f"k-means cost rose: {first} -> {last}")
    return {"device": info, "cost": [first, last], "cache": stats.row()}


def leg_sgd_mf() -> dict:
    import numpy as np

    from harp_tpu.aot import hlo_audit
    from harp_tpu.models import sgd_mf
    from harp_tpu.session import HarpSession

    info = _device("sgd_mf")
    stats = _CompileStats()
    out = {"device": info}
    for name, c in (("aligned", SGD_MF), ("unaligned", SGD_MF_UNALIGNED)):
        text = _cli(_sgd_mf_argv(c))
        if "sgd_mf[dense]" not in text:
            raise SystemExit(f"sgd_mf ({name}) did not take the dense "
                             "masked-stripe layout")
        first, last = _first_last(text, "rmse")
        _finite(first, last)
        if not last < first:
            raise SystemExit(f"SGD-MF ({name}) RMSE did not fall: "
                             f"{first} -> {last}")
        # the program the launcher just ran, rebuilt at the same geometry
        # and config (its shapes do not depend on the ratings, so a handful
        # suffice) and read back as compiled text
        model = sgd_mf.SGDMF(HarpSession(), sgd_mf.SGDMFConfig(
            rank=c["rank"], minibatches_per_hop=c["nmb"], epochs=c["epochs"]))
        ids = np.arange(64, dtype=np.int64)
        layout, data, w0, h0, meta = model.prepare(
            ids, ids, np.ones(64, np.float32), c["users"], c["items"])
        key = model._program(layout, c["nmb"], c["epochs"], meta[6])
        _assert_mosaic(hlo_audit.lower_fn_text(model._compiled[key],
                                               (*data, w0, h0)),
                       f"sgd_mf dense hop ({name})")
        layout_stats = model.last_layout_stats
        if not layout_stats["fused_hop"]:
            raise SystemExit(f"sgd_mf ({name}): last_layout_stats says the "
                             f"XLA stripe scan runs: {layout_stats}")
        out[name] = {"rmse": [first, last], "mosaic": True,
                     "col_tile": layout_stats["col_tile"],
                     "pad_overhead": layout_stats["pad_overhead"]}
    out["cache"] = stats.row()
    return out


def leg_kernels() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harp_tpu.aot.cache import enable_compile_cache
    from harp_tpu.models import als
    from harp_tpu.parallel import ring_attention as ra

    info = _device("kernels")
    stats = _CompileStats()
    enable_compile_cache()
    out = {"device": info}

    # flash attention through its dispatcher at FLASH's shape. Reference:
    # the XLA scan path with f32 matmuls. Tolerance: the repo's bf16-operand
    # tolerance (tests/test_aux_sp.py) — the chip multiplies f32 operands in
    # bf16 passes by default.
    tol = 3e-2
    l, h = FLASH["l"], FLASH["h"]
    flash = jax.jit(lambda q, k, v: ra.blocked_attention(q, k, v, True))

    def xla_ref(q, k, v):
        with jax.default_matmul_precision("highest"):
            return ra.blocked_attention_xla(q, k, v, True)

    for dh in (64, 128):
        rng = np.random.default_rng(dh)
        q, k, v = (jnp.asarray(rng.standard_normal((l, h, dh)), jnp.float32)
                   for _ in range(3))
        exe = flash.lower(q, k, v).compile()
        _assert_mosaic(exe.as_text(), f"flash attention Dh={dh}")
        got = np.asarray(exe(q, k, v))
        want = np.asarray(jax.jit(xla_ref)(q, k, v))
        err = float(np.max(np.abs(got - want)))
        _finite(err)
        if got.shape != (l, h, dh) or err > tol:
            raise SystemExit(f"flash attention Dh={dh}: shape {got.shape}, "
                             f"max |err| {err:.3e} > {tol}")
        out[f"flash_dh{dh}"] = {"max_abs_err": err, "tol": tol,
                                "mosaic": True}

    # the ALS normal-equation solve through the solver dispatch ALS itself
    # calls, which packs the systems to the block-upper triangle the kernel
    # reads: 8192 systems at rank 32 (4 sublane groups) and 4096 at the
    # benchmark cell's rank 100 (stored 104: 13 groups, 5,824 packed rows);
    # reference: numpy float64. Tolerance as tests/test_aux_sp.py.
    tol = 2e-3
    for n, rank in ((8192, 32), (4096, 100)):
        rng = np.random.default_rng(7)
        vmat = rng.standard_normal((n, 2 * rank, rank)).astype(np.float32)
        a = np.matmul(vmat.transpose(0, 2, 1), vmat) \
            + 0.5 * np.eye(rank, dtype=np.float32)
        b = rng.standard_normal((n, rank)).astype(np.float32)
        cfg = als.ALSConfig(rank=rank)
        solve = jax.jit(lambda a_, b_, cfg=cfg: als._spd_solve(a_, b_, cfg))
        aj, bj = jnp.asarray(a), jnp.asarray(b)
        exe = solve.lower(aj, bj).compile()
        _assert_mosaic(exe.as_text(), f"spd solve rank {rank}")
        got = np.asarray(exe(aj, bj))
        want = np.linalg.solve(a.astype(np.float64),
                               b.astype(np.float64)[..., None])[..., 0]
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
        out[f"spd_solve_rank{rank}"] = {
            "max_abs_err": float(np.max(np.abs(got - want))), "tol": tol,
            "mosaic": True}
    # the fused K-means E-step against its XLA twin at the benchmark cell's
    # widths and precision (k 100 of 128, d 100 of 128, `highest`): a block
    # its tiles divide and one whose last tile is masked. Two float32
    # implementations may part on a near tie: a few points of 10^5 may sit
    # with another centroid; the cost may not move.
    from harp_tpu.ops import distance, kmeans_kernels, lane_pack

    for n in (128_000, 100_003):
        rng = np.random.default_rng(n)
        cen = 0.2 * rng.standard_normal((100, 100))
        pts = cen[rng.integers(0, 100, n)] + rng.standard_normal((n, 100))
        x = lane_pack.pad_cols(jnp.asarray(pts, jnp.float32), 128)
        c = lane_pack.pad_rows(lane_pack.pad_cols(jnp.asarray(
            cen + 0.5 * rng.standard_normal((100, 100)), jnp.float32),
            128), 128)
        with jax.default_matmul_precision("highest"):
            fused = jax.jit(lambda x_, c_: kmeans_kernels.estep_pallas(
                x_, c_, valid_k=100, valid_d=100)).lower(x, c).compile()
            _assert_mosaic(fused.as_text(), f"kmeans estep n={n}")
            got = fused(x, c)
            want = jax.jit(lambda x_, c_: distance.partial_sums_counts(
                x_, c_, valid_k=100, valid_d=100))(x, c)
        moved = float(jnp.sum(jnp.abs(got[1] - want[1]))) / 2
        cost = abs(float(got[2]) / float(want[2]) - 1.0)
        _finite(moved, cost)
        if float(jnp.sum(got[1])) != n or moved > 1e-4 * n or cost > 1e-6:
            raise SystemExit(f"kmeans estep n={n}: {moved} points moved, "
                             f"cost off by {cost:.3e}")
        out[f"kmeans_estep_n{n}"] = {"points_moved": moved,
                                     "cost_rel_err": cost, "mosaic": True}
    out["cache"] = stats.row()
    return out




def leg_restart() -> dict:
    """This leg's process stays off jax: the launcher's supervisor (also
    backend-free) starts the children that need the chip, one at a time.
    An unfaulted run first, then the same job with a scripted crash under
    ``--max-restarts 1``; the resumed model must equal the unfaulted one."""
    import shutil

    c = RESTART
    work = os.path.join(OUT_DIR, "restart")
    shutil.rmtree(work, ignore_errors=True)
    env = {k: v for k, v in os.environ.items() if k != "HARP_FAULT"}

    def launch(sub, extra, env):
        rc, text = _run_child(
            [sys.executable, "-m", "harp_tpu.run",
             *_kmeans_argv(c, "--save-every", "1", "--work-dir",
                           os.path.join(work, sub), *extra)],
            env, 170, os.path.join(OUT_DIR, f"restart_{sub}.log"),
            merge_stderr=True)
        if rc != 0:
            raise SystemExit(f"restart leg: the {sub} run exited {rc}")
        return text

    ref_text = launch("ref", [], env)
    text = launch("faulted", ["--max-restarts", "1"],
                  {**env,
                   "HARP_FAULT": f"crash@epoch={c['crash_at']}:rank=0"})
    # the launcher names the backend each attempt got: one unfaulted run,
    # the crashed attempt, the relaunched attempt
    attempts = [json.loads(line.split("harp_tpu.run: ", 1)[1])
                for line in (ref_text + text).splitlines()
                if line.startswith("harp_tpu.run: {")]
    if len(attempts) != 3 or \
            any(a["platform"] != PLATFORM for a in attempts):
        raise SystemExit(f"restart leg: expected 3 attempts on {PLATFORM}, "
                         f"the launcher reported {attempts}")
    with open(os.path.join(work, "faulted", "restart_journal.jsonl")) as f:
        journal = [json.loads(line) for line in f if line.strip()]
    restarts = [r for r in journal if r["event"] == "restart"]
    if len(restarts) != 1 or \
            restarts[0]["resumed_step"] != c["crash_at"] - 1:
        raise SystemExit(f"restart leg: journal says {restarts}")
    with open(os.path.join(work, "faulted", "centroids.csv"), "rb") as f:
        got = f.read()
    with open(os.path.join(work, "ref", "centroids.csv"), "rb") as f:
        want = f.read()
    if not got or got != want:
        raise SystemExit("restart leg: the resumed run's centroids differ "
                         "from the unfaulted run's")
    _finite(*_first_last(text, "cost"))
    last = attempts[-1]
    return {"device": {"platform": last["platform"],
                       "kind": last["device_kind"],
                       "count": last["device_count"]},
            "attempts": len(attempts),
            "crash_rc": restarts[0]["first_rc"],
            "resumed_step": restarts[0]["resumed_step"],
            "bitwise_equal_to_unfaulted": True,
            "compile_cache_dir": last["compile_cache_dir"]}


def leg_serve() -> dict:
    import jax
    import numpy as np

    from harp_tpu.benchmark import serving_load
    from harp_tpu.models import nn
    from harp_tpu.serve import OP_CLASSIFY, OP_TOPK
    from harp_tpu.session import HarpSession

    info = _device("serve")
    stats = _CompileStats()
    sess = HarpSession()
    # (1) the load generator's own row: 3 concurrent clients, mixed traffic
    row = serving_load.measure(sess, requests_per_mix=102, num_clients=3,
                               mixes={"mixed": 0.5}, trace_sample=0)
    mix = row["mixes"]["mixed"]
    traces = {m: b["trace_counts"] for m, b in row["batching"].items()}
    if row["device"] != PLATFORM or mix["errors"] or mix["requests"] != 102:
        raise SystemExit(f"serve leg: load row {row['device']} {mix}")
    # every bucket was traced once, in warm-up: a retrace under traffic
    # would read 2 (serving_load module docstring)
    if any(n != 1 for per in traces.values() for n in per.values()):
        raise SystemExit(f"serve leg: retrace under traffic {traces}")
    # (2) answers against a reference. The chip multiplies f32 operands in
    # bf16 passes, so near-ties may swap: every returned item (label) must
    # score within `tol` of the true k-th best (best) in f32 arithmetic
    tol = 0.1
    workers, make_client, meta = serving_load.build_gang(sess, seed=0)
    client = make_client()
    true_scores = (meta["user_factors"].astype(np.float64)
                   @ meta["item_factors"].astype(np.float64).T)
    params = meta["classify_params"]
    eps = meta["endpoints"]
    try:
        for op, model, data in ((OP_TOPK, serving_load.TOPK_MODEL, 0),
                                (OP_CLASSIFY, serving_load.CLASSIFY_MODEL,
                                 np.zeros(meta["classify_dim"], np.float32))):
            client.request(op, model, data, timeout=120.0)      # warm-up
        warm = {m: dict(ep.trace_counts) for m, ep in eps.items()}
        rq = np.random.default_rng(1)
        for i in range(40):
            if i % 2:
                u = int(rq.integers(0, meta["num_users"]))
                res = client.request(OP_TOPK, serving_load.TOPK_MODEL, u,
                                     timeout=60.0)
                kth = np.sort(true_scores[u])[-meta["k"]]
                items = res["items"]
                if len(set(items)) != meta["k"] or \
                        true_scores[u, items].min() < kth - tol:
                    raise SystemExit(f"serve leg: top-k for user {u} "
                                     f"wrong: {items}")
            else:
                x = rq.normal(size=(meta["classify_dim"],)
                              ).astype(np.float32)
                label = client.request(OP_CLASSIFY,
                                       serving_load.CLASSIFY_MODEL, x,
                                       timeout=60.0)
                with jax.default_matmul_precision("highest"):
                    logits = np.asarray(nn.forward(params, x[None]))[0]
                if logits[int(label)] < logits.max() - tol:
                    raise SystemExit(f"serve leg: label {label} for "
                                     f"logits {logits}")
        if {m: dict(ep.trace_counts) for m, ep in eps.items()} != warm:
            raise SystemExit("serve leg: retrace after warm-up")
    finally:
        client.close()
        for w in workers:
            w.close()
    return {"device": info, "load_row": {"device": row["device"],
                                         "requests": mix["requests"],
                                         "errors": mix["errors"],
                                         "trace_counts": traces},
            "checked_requests": 40, "tol": tol, "cache": stats.row()}


def _shard_devices(x) -> int:
    return len({s.device for s in x.addressable_shards})


def leg_multichip() -> dict:
    """Four chips, one process: both trainers at the flagship global sizes
    through the launcher (4 workers vs 1), where their operands and results
    live, and the Table collectives against numpy."""
    import jax
    import numpy as np

    from harp_tpu.collectives import table_ops
    from harp_tpu.io import datagen
    from harp_tpu.models import kmeans as km
    from harp_tpu.models import sgd_mf
    from harp_tpu.session import HarpSession
    from harp_tpu.table import Table

    info = _device("multichip")
    if info["count"] < 4:
        raise SystemExit("multichip leg needs >= 4 chips")
    stats = _CompileStats()
    out = {"device": info}
    c = KMEANS
    costs = {}
    for w in (1, 4):
        costs[w] = _first_last(
            _cli(_kmeans_argv(c, "--num-workers", str(w))), "cost")
        _finite(*costs[w])
    np.testing.assert_allclose(costs[4][1], costs[1][1], rtol=1e-3)
    out["kmeans_cost"] = {"w1": costs[1], "w4": costs[4]}
    s = SGD_MF
    text = _cli(_sgd_mf_argv(s, "--num-workers", "4"))
    first, last = _first_last(text, "rmse")
    _finite(first, last)
    if "sgd_mf[dense]" not in text or not last < first:
        raise SystemExit(f"4-chip sgd_mf: {text}")
    out["sgd_mf_rmse_w4"] = [first, last]

    # where the sharded operands and results live
    sess = HarpSession(num_workers=4)
    pts = datagen.dense_points(c["n"], c["d"], seed=0,
                               num_clusters=c["k"])
    model = km.KMeans(sess, km.KMeansConfig(
        num_centroids=c["k"], dim=c["d"], iterations=2))
    pts_dev, cen_dev = model.prepare(
        pts, datagen.initial_centroids(pts, c["k"], seed=1))
    cen, _ = model.fit_prepared(pts_dev, cen_dev)
    placed = {"kmeans.points": _shard_devices(pts_dev),
              "kmeans.centroids": _shard_devices(cen)}
    mf = sgd_mf.SGDMF(sess, sgd_mf.SGDMFConfig(
        rank=s["rank"], minibatches_per_hop=s["nmb"], epochs=1))
    rows, cols, vals = datagen.sparse_ratings(
        8192, 8192, rank=16, density=s["density"], seed=0)
    state = mf.prepare(rows, cols, vals, 8192, 8192)
    w_dev, h_dev, _ = mf.train_prepared(state)
    for name, arr in (("sgd_mf.slab", state[1][0]), ("sgd_mf.w0", state[2]),
                      ("sgd_mf.h0", state[3]), ("sgd_mf.w", w_dev),
                      ("sgd_mf.h", h_dev)):
        placed[name] = _shard_devices(arr)
    if any(n != 4 for n in placed.values()):
        raise SystemExit(f"not on four distinct devices: {placed}")
    out["shards_on_distinct_devices"] = placed

    # the Table collectives against numpy
    rng = np.random.default_rng(3)
    contribs = rng.normal(size=(4, 16, 3, 5)).astype(np.float32)
    blocks = rng.normal(size=(16, 3, 5)).astype(np.float32)

    def local_op(fn, out_specs):
        return np.asarray(sess.spmd(
            lambda x: fn(Table.local(x[0], num_workers=4)).data,
            in_specs=(sess.shard(),), out_specs=out_specs)(contribs))

    np.testing.assert_allclose(
        local_op(table_ops.allreduce, sess.replicate()), contribs.sum(0),
        rtol=2e-5)
    np.testing.assert_allclose(
        local_op(table_ops.regroup, sess.shard()), contribs.sum(0),
        rtol=2e-5)
    np.testing.assert_allclose(
        local_op(lambda t: table_ops.allgather(table_ops.regroup(t)),
                 sess.replicate()), contribs.sum(0), rtol=2e-5)
    rot = np.asarray(sess.spmd(
        lambda b: table_ops.rotate(Table.sharded(b, num_workers=4)).data,
        in_specs=(sess.shard(),), out_specs=sess.shard())(blocks))
    np.testing.assert_array_equal(rot.reshape(4, 4, 3, 5),
                                  np.roll(blocks.reshape(4, 4, 3, 5), 1, 0))
    out["table_ops"] = "allreduce regroup allgather rotate == numpy"
    out["cache"] = stats.row()
    return out


def leg_multichip_ring() -> dict:
    """The fused ring-DMA surface on four chips, each op bitwise against
    its lax twin and Mosaic in the compiled text. A kernel Mosaic refuses
    or that hangs fails this leg — there is no fallback to hide behind."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harp_tpu.aot.cache import enable_compile_cache
    from harp_tpu.collectives import lax_ops
    from harp_tpu.ops import ring_dma
    from harp_tpu.parallel import ring_attention as ra
    from harp_tpu.session import HarpSession

    info = _device("multichip_ring")
    if info["count"] < 4:
        raise SystemExit("multichip_ring leg needs >= 4 chips")
    stats = _CompileStats()
    enable_compile_cache()
    sess = HarpSession(num_workers=4)
    out = {"device": info}

    def pair(fused, twin, *args, what):
        exe = sess.spmd(fused, in_specs=(sess.shard(),) * len(args),
                        out_specs=sess.shard()).lower(*args).compile()
        _assert_mosaic(exe.as_text(), what)
        got = np.asarray(exe(*args))
        want = np.asarray(sess.spmd(
            twin, in_specs=(sess.shard(),) * len(args),
            out_specs=sess.shard())(*args))
        if got.shape != want.shape or got.tobytes() != want.tobytes():
            raise SystemExit(f"{what}: not bitwise equal to its lax twin")
        print(f"[multichip_ring] {what}: bitwise == lax twin",
              file=sys.stderr, flush=True)
        out[what] = "bitwise == lax twin"

    rng = np.random.default_rng(11)
    for dtype in (np.float32, np.int32, jnp.bfloat16):
        x = sess.scatter(np.asarray(
            rng.integers(-1000, 1000, size=(4 * 512, 256)), dtype))
        name = np.dtype(dtype).name
        pair(lambda a: ring_dma.hop(a, 1), lambda a: lax_ops.rotate(a, 1),
             x, what=f"ring_dma.hop[{name}]")
    pair(lambda a: ring_dma.hop(a, -1), lambda a: lax_ops.rotate(a, -1),
         x, what="ring_dma.hop[shift=-1]")
    xf = sess.scatter(rng.standard_normal((4 * 256, 128)).astype(np.float32))
    pair(lambda a: ring_dma.ring_allgather(a)[None],
         lambda a: jax.lax.all_gather(a, "workers", tiled=True)[None],
         xf, what="ring_dma.ring_allgather")

    # ring attention, the fused hop both ways: out of the kernel
    # (ring_dma.hop around the XLA block attention, short blocks) and in
    # the flash kernel's epilogue (block length 8192: the flash crossover)
    def qkv(l, h, dh):
        return tuple(sess.scatter(rng.standard_normal((l, h, dh)
                                                      ).astype(np.float32))
                     for _ in range(3))

    def mha(fused):
        return lambda q, k, v: ra.ring_attention_mha(q, k, v, causal=True,
                                                     fused_dma=fused)

    pair(mha(True), mha(False), *qkv(4 * RING_BLOCK["xla"], 4, 64),
         what="ring_attention_mha[fused hop, XLA blocks]")
    pair(mha(True), mha(False), *qkv(4 * RING_BLOCK["flash"], 8, 64),
         what="ring_attention_mha[hop fused into flash]")

    # the dense SGD-MF hop kernel ships the updated H block from its own
    # epilogue (fused_dma=True), at a shape no tile divides: the factors it
    # returns equal the ppermute schedule's bit for bit, and the compiled
    # program holds no collective-permute
    from harp_tpu.io import datagen
    from harp_tpu.models import sgd_mf

    c = SGD_MF_UNALIGNED
    users, items = 2 * c["users"], 2 * c["items"]
    rows, cols, vals = datagen.sparse_ratings(users, items, rank=8,
                                              density=0.02, seed=1)
    factors = {}
    for fused in (False, True):
        model = sgd_mf.SGDMF(sess, sgd_mf.SGDMFConfig(
            rank=c["rank"], lr=1e-3, epochs=c["epochs"],
            minibatches_per_hop=c["nmb"], fused_dma=fused))
        layout, data, w0, h0, meta = state = model.prepare(
            rows, cols, vals, users, items, seed=3)
        key = model._program(layout, c["nmb"], c["epochs"], meta[6])
        text = model._compiled[key].lower(*data, w0, h0).compile().as_text()
        _assert_mosaic(text, f"sgd_mf dense hop, fused_dma={fused}")
        if fused == ("collective-permute" in text):
            raise SystemExit(f"sgd_mf fused_dma={fused}: the compiled "
                             "program's ring hop is not where it should be")
        factors[fused] = model.fit_prepared(state)
    if not all(a.tobytes() == b.tobytes()
               for a, b in zip(factors[False], factors[True])):
        raise SystemExit("sgd_mf in-kernel ring hop: factors differ from "
                         "the ppermute schedule's")
    out["sgd_mf[hop fused into the dense kernel]"] = "bitwise == ppermute"
    _sgd_mf_wires(sess, out)
    out["cache"] = stats.row()
    return out


def _sgd_mf_wires(sess, out: dict) -> None:
    """The four-chip cell's stored shape on its three wires: the ppermute
    schedule (``fused_dma=False``), the hop kernel sending its H block whole
    after its last stripe, and the program's own choice, the kernel sending
    each column tile as the last stripe finishes it. Each wire's factors
    equal the ppermute schedule's bit for bit, the two in-kernel programs
    hold no collective-permute, and each wire prints one timing line: the
    median wall of ``calls`` training calls of ``epochs`` epochs."""
    import statistics

    from harp_tpu.io import datagen
    from harp_tpu.models import sgd_mf
    from harp_tpu.ops import ring_dma
    from harp_tpu.utils import metrics

    c = SGD_MF_X4
    rows, cols, vals = datagen.sparse_ratings(
        c["users"], c["items"], rank=8, density=c["density"], seed=2)
    tiled = ring_dma.stream_hop

    def whole(*args):           # args[7]: the block's column tiles
        return tiled(*args, tiles_per_send=args[7])

    state, factors = None, {}
    for wire, fused_dma, send in (("ppermute", False, tiled),
                                  ("in_kernel, whole block", None, whole),
                                  ("in_kernel, per tile", None, tiled)):
        model = sgd_mf.SGDMF(sess, sgd_mf.SGDMFConfig(
            rank=c["rank"], lr=1e-4, epochs=c["epochs"],
            minibatches_per_hop=c["nmb"], fused_dma=fused_dma))
        if state is None:
            state = model.prepare(rows, cols, vals, c["users"], c["items"],
                                  seed=3)
            stats = model.last_layout_stats
            if (stats["col_tile"], stats["ring_hop"]) != (256, "ppermute"):
                raise SystemExit(f"sgd_mf x4 shape: {stats}")
        layout, data, w0, h0, meta = state
        if meta[6].cpb_store != 6912 or meta[6].s_store != 4352:
            raise SystemExit(f"sgd_mf x4 shape: stored as {meta[6]}")
        counter = "sgd_mf.ring." + wire.split(",")[0]
        if "sgd_mf.ring." + model._ring_wire(True, 4) != counter:
            raise SystemExit(f"sgd_mf x4: the program picks "
                             f"{model._ring_wire(True, 4)}, not {wire}")
        before = metrics.DEFAULT.counters.get(counter, 0)
        ring_dma.stream_hop = send
        try:
            key = model._program(layout, c["nmb"], c["epochs"], meta[6])
            text = model._compiled[key].lower(*data, w0, h0).compile(
            ).as_text()
            factors[wire] = model.fit_prepared(state)
            walls = []
            for _ in range(c["calls"]):
                t0 = time.perf_counter()
                model.train_prepared(state)
                walls.append(time.perf_counter() - t0)
        finally:
            ring_dma.stream_hop = tiled
        _assert_mosaic(text, f"sgd_mf x4, {wire}")
        if (wire == "ppermute") != ("collective-permute" in text):
            raise SystemExit(f"sgd_mf x4, {wire}: the compiled program's "
                             "ring hop is not where it should be")
        counted = metrics.DEFAULT.counters.get(counter, 0) - before
        ms = statistics.median(walls) * 1e3
        print(f"[multichip_ring] sgd_mf x4 wire {wire}: {ms:.3f} ms a call "
              f"of {c['epochs']} epochs ({ms / c['epochs']:.4f} ms an "
              f"epoch), median of {c['calls']}; sgd_mf.ring counted "
              f"{counted:g} traced hop bodies", file=sys.stderr, flush=True)
        out[f"sgd_mf x4 ms a call [{wire}]"] = round(ms, 3)
    want = factors["ppermute"]
    for wire, got in factors.items():
        if not all(a.tobytes() == b.tobytes() for a, b in zip(want, got)):
            raise SystemExit(f"sgd_mf x4, {wire}: factors differ from the "
                             "ppermute schedule's")
    out["sgd_mf x4 [three wires]"] = "bitwise == ppermute"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--leg", choices=sorted(LEGS), default=None,
                   help="run one leg in this process (what the parent "
                        "starts as a child)")
    args = p.parse_args(argv)
    if args.leg is None:
        return run_all()
    os.makedirs(OUT_DIR, exist_ok=True)
    res = globals()[f"leg_{args.leg}"]()
    print(json.dumps({"leg": args.leg, "ok": True, **res}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
