"""Measure the ring-attention KV hop cost and the fused ring-DMA win.

The ring-attention twin of :mod:`harp_tpu.benchmark.lda_overlap` (ISSUE 9
overlap ablation — hidden comm time on a second workload). Three timings of
the same sequence-sharded attention:

  * ``unfused``  — the shipping schedule: per-hop KV ``ppermute`` + the
    flash/XLA hop compute (``fused_dma=False``)
  * ``no_rot``   — the identical compute schedule with the hop ablated
    (``ablate_rotation=True``; results are wrong, timing-only), so
    ``(unfused - no_rot) / unfused`` bounds the non-overlapped hop share
  * ``fused``    — ``fused_dma=True``: on TPU with the flash kernel live,
    the hop fuses INTO the kernel (``flash_attention_pallas(ring_hop=True)``
    — the remote copy streams while the grid computes); otherwise the
    out-of-kernel fused hop engine

``(unfused - fused) / (unfused - no_rot)`` is the fraction of the measured
hop cost the fusion hides. Off TPU the fused path is the engine's tagged
lax fallback, so the CPU-mesh numbers measure dispatch structure only —
the driver's on-chip ``bench.py --only ring_dma_overlap`` is the real
ablation.

Run on whatever backend is live::

    python -m harp_tpu.benchmark.ring_overlap

Prints one JSON line; PERF.md records the numbers.
"""

from __future__ import annotations

import json
import os
import sys
import time


def measure(l_local=512, heads=8, dh=64, reps=3, use_flash=None,
            causal=True) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harp_tpu.parallel import ring_attention as ra
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    w = sess.num_workers
    l_full = w * l_local
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((l_full, heads, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((l_full, heads, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((l_full, heads, dh)), jnp.float32)
    qs, ks, vs = sess.scatter(q), sess.scatter(k), sess.scatter(v)

    def build(fused, ablate):
        fn = sess.spmd(
            lambda a, b, c: ra.ring_attention_mha(
                a, b, c, causal, use_flash=use_flash, fused_dma=fused,
                ablate_rotation=ablate),
            in_specs=(sess.shard(),) * 3, out_specs=sess.shard())
        jax.block_until_ready(fn(qs, ks, vs))     # compile + warm

        def timer():
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(qs, ks, vs))
                best = min(best, time.perf_counter() - t0)
            return best

        return timer()

    t_unfused = build(fused=False, ablate=False)
    t_norot = build(fused=False, ablate=True)
    t_fused = build(fused=True, ablate=False)
    hop_cost = max(t_unfused - t_norot, 1e-12)
    return {
        "workers": w,
        "config": f"L={l_full} (local {l_local}) H={heads} Dh={dh} "
                  f"causal={causal}",
        "unfused_s": round(t_unfused, 5),
        "no_rotation_s": round(t_norot, 5),
        "fused_s": round(t_fused, 5),
        "hop_share": round(max(0.0, hop_cost / t_unfused), 4),
        "fused_speedup": round(t_unfused / t_fused, 4),
        "fused_hidden_fraction": round(
            min(1.0, max(0.0, (t_unfused - t_fused) / hop_cost)), 4),
    }


def main() -> None:
    # must run before jax initializes a backend: force the virtual CPU
    # mesh when one is requested (lda_overlap.main does the same)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    json.dump(measure(), sys.stdout)
    print()


if __name__ == "__main__":
    main()
