"""Quantized-serving comparison — f32 vs int8 residents at recsys scale.

The ISSUE 17 bench group: two serving gangs built from the SAME seed and
shapes (``serving_load.build_gang``), one with f32 resident state and one
with ``quant="int8"`` (packed int8 factor rows + int8 classify params, the
int8 dispatch wire, and f16-encoded reply scores via ``accept_enc``), and
three comparisons between them:

* **answer parity** — the top-k item lists for a sample of user ids, scored
  through the full gang (route -> int8 dot -> route back -> encoded reply).
  The row carries mean/min top-k OVERLAP vs the f32 gang's lists; the r17
  acceptance bar is mean >= 0.95 at the recsys bench shapes.
* **resident footprint** — ``Endpoint.resident_bytes()`` per model per
  mode, plus the f32/int8 ratio. At the bench shapes (rank 64) the packed
  row is ``64 + 4`` int8 bytes vs ``64 * 4`` f32 bytes, so the table
  reduction approaches 3.76x (the +4 per-row scale is the only overhead).
* **throughput/latency** — the same closed-loop mixed-traffic protocol as
  :mod:`harp_tpu.benchmark.serving_load` (shared ``_client_loop``), so the
  f32 and int8 QPS/p99 columns are measured by identical machinery.

Shapes default to the RECSYS BENCH scale (2048 users x 512 items at rank
64, k=10) — large enough that the resident-bytes ratio reflects the table
term, not the per-row scale overhead. On a CPU-mesh session the latency
columns price CPU dispatches (the row says so); the resident-bytes and
overlap columns are device-independent.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

import numpy as np

from harp_tpu.benchmark.serving_load import (
    CLASSIFY_MODEL, TOPK_MODEL, _client_loop, build_gang)

# the two traffic mixes the f32-vs-int8 columns are compared at
DEFAULT_MIXES: Dict[str, float] = {"topk_heavy": 0.8, "mixed": 0.5}


def _overlap(a, b) -> float:
    """|a ∩ b| / k for two same-k top-k item lists (order-insensitive:
    int8 rounding may swap near-tied neighbours without being wrong)."""
    if not a and not b:
        return 1.0
    k = max(len(a), len(b))
    return len(set(a) & set(b)) / k if k else 1.0


def _run_mode(session, quant, *, num_users, num_items, rank, k,
              requests_per_mix, num_clients, mixes, max_wait_s,
              request_timeout, seed, overlap_ids) -> dict:
    """One gang, one mode: warm it, probe the overlap ids through the full
    request path, run every mix closed-loop. Returns the mode column plus
    the probed top-k lists (for the cross-mode overlap computed by the
    caller)."""
    from harp_tpu.serve import OP_CLASSIFY, OP_TOPK
    from harp_tpu.utils.metrics import Metrics

    metrics = Metrics()          # fresh registry per mode: exact columns
    workers, make_client, meta = build_gang(
        session, num_users=num_users, num_items=num_items, rank=rank, k=k,
        max_wait_s=max_wait_s, metrics=metrics, seed=seed, quant=quant,
        accept_enc=(("f16",) if quant == "int8" else None))
    clients = [make_client() for _ in range(num_clients)]
    mix_rows: Dict[str, dict] = {}
    try:
        # warm the reachable buckets + per-client transport, exactly like
        # serving_load.measure — compiles must not pollute a latency sample
        for name, ep in meta["endpoints"].items():
            top = ep.bucket_for(min(num_clients, ep.max_batch))
            for bucket in ep.bucket_sizes:
                if bucket > top:
                    break
                if name == TOPK_MODEL:
                    ep.dispatch(np.zeros(bucket, np.int64))
                else:
                    ep.dispatch(np.zeros(
                        (bucket, meta["classify_dim"]), np.float32))
        for c in clients:
            c.request(OP_TOPK, TOPK_MODEL, 0, timeout=request_timeout)
            c.request(OP_CLASSIFY, CLASSIFY_MODEL,
                      np.zeros(meta["classify_dim"], np.float32),
                      timeout=request_timeout)
        # parity probe through the FULL gang (route + quantized dispatch +
        # encoded reply + client decode), one id at a time on one client
        topk_lists = {}
        for uid in overlap_ids:
            r = clients[0].request(OP_TOPK, TOPK_MODEL, int(uid),
                                   timeout=request_timeout)
            topk_lists[int(uid)] = list(r["items"])
        for mix, frac in mixes.items():
            timer = f"serve.latency.{mix}"
            per_client = max(1, requests_per_mix // num_clients)
            errors: list = []
            barrier = threading.Barrier(num_clients + 1)
            thread_regs = [Metrics() for _ in clients]
            threads = [threading.Thread(
                target=_client_loop,
                args=(c, per_client, frac, meta, seed + 100 + i,
                      thread_regs[i], timer, errors, barrier,
                      request_timeout, None),
                name=f"harp-serve-quant-{mix}-{i}", daemon=True)
                for i, c in enumerate(clients)]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            done = 0
            for reg in thread_regs:
                tr = reg.timers.get(timer)
                if tr is not None:
                    done += tr.count
                metrics.merge(reg)
            timing = metrics.timing(timer)
            mix_rows[mix] = {
                "topk_fraction": frac,
                "requests": done,
                "errors": len(errors),
                "qps": round(done / wall, 1) if wall > 0 else None,
                "p50_ms": round(timing["p50_s"] * 1e3, 3) if timing
                else None,
                "p99_ms": round(timing["p99_s"] * 1e3, 3) if timing
                else None,
            }
        resident = {name: int(ep.resident_bytes())
                    for name, ep in meta["endpoints"].items()}
        enc_counters = {
            key: int(n) for key, n in
            metrics.snapshot()["counters"].items()
            if key.startswith("serve.reply_encoded.")}
    finally:
        for c in clients:
            c.close()
        for w in workers:
            w.close()
    return {"mixes": mix_rows, "resident_bytes": resident,
            "reply_encoded": enc_counters, "topk_lists": topk_lists}


def measure(session=None, *, num_users: int = 2048, num_items: int = 512,
            rank: int = 64, k: int = 10, requests_per_mix: int = 600,
            num_clients: int = 3, mixes: Optional[Dict[str, float]] = None,
            max_wait_s: float = 0.002, request_timeout: float = 60.0,
            seed: int = 0, overlap_sample: int = 128) -> dict:
    """Run both modes; returns the ``serving_quant`` bench row (module
    docstring). The two gangs never coexist — f32 tears down before int8
    builds, so the resident-bytes columns are honest per-mode figures."""
    import jax

    if session is None:
        from harp_tpu.session import HarpSession

        session = HarpSession()
    mixes = dict(DEFAULT_MIXES if mixes is None else mixes)
    rng = np.random.default_rng(seed + 7)
    overlap_ids = rng.choice(num_users, size=min(overlap_sample, num_users),
                             replace=False)
    modes = {}
    for mode in ("f32", "int8"):
        modes[mode] = _run_mode(
            session, None if mode == "f32" else "int8",
            num_users=num_users, num_items=num_items, rank=rank, k=k,
            requests_per_mix=requests_per_mix, num_clients=num_clients,
            mixes=mixes, max_wait_s=max_wait_s,
            request_timeout=request_timeout, seed=seed,
            overlap_ids=overlap_ids)
    overlaps = [_overlap(modes["f32"]["topk_lists"][uid],
                         modes["int8"]["topk_lists"][uid])
                for uid in (int(u) for u in overlap_ids)]
    for col in modes.values():
        del col["topk_lists"]    # the row keeps the summary, not the lists
    reduction = {
        name: round(modes["f32"]["resident_bytes"][name]
                    / modes["int8"]["resident_bytes"][name], 3)
        for name in modes["f32"]["resident_bytes"]}
    device = ("tpu" if any(d.platform == "tpu" for d in jax.devices())
              else jax.devices()[0].platform)
    row = {
        "shapes": {"num_users": num_users, "num_items": num_items,
                   "rank": rank, "k": k},
        "gang": f"2 workers + {num_clients} closed-loop clients per mode, "
                f"loopback authenticated p2p, max_wait_s={max_wait_s}, "
                f"int8 clients accept_enc=('f16',)",
        "device": device,
        "modes": modes,
        "resident_reduction": reduction,
        "topk_overlap": {"k": k, "sampled_ids": len(overlaps),
                         "mean": round(float(np.mean(overlaps)), 4),
                         "min": round(float(np.min(overlaps)), 4)},
    }
    if device != "tpu":
        row["note"] = (
            f"{device}-mesh session: the QPS/p99 columns price the router "
            f"+ micro-batcher + {device} dispatch stack, not a TPU's "
            f"(resident_bytes and topk_overlap are device-independent)")
    return row
