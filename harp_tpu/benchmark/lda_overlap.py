"""Measure LDA's rotation cost and the numModelSlices=2 overlap win.

VERDICT r2 item 5: the reference pipelines the word-topic table as 2 slices
(LDAMPCollectiveMapper.java:257 wTableMap) so rotation overlaps sampling;
harp-tpu's single-slice deviation claimed XLA's async collective scheduling
already buys the overlap — this harness MEASURES that claim instead of
asserting it. Three timings of the same corpus/epoch budget:

  * ``single``  — num_model_slices=1 (rotate_scan; the shipping default)
  * ``no_rot``  — same compute schedule with the ppermute ablated
    (``ablate_rotation=True``; results are wrong, timing-only), so
    ``(single - no_rot) / single`` bounds the NON-overlapped rotation share
  * ``two_slice`` — num_model_slices=2 on pipelined_rotation (the
    reference's schedule: half-width blocks, one in flight while the other
    is sampled)

r10 adds the fused ring-DMA twins (``fused=True``, the default):

  * ``fused_single`` / ``fused_two_slice`` — the same two schedules with
    ``LDAConfig(fused_dma=True)``: wt-block hops ride the in-kernel
    ``make_async_remote_copy`` engine (ops/ring_dma) instead of ppermute.
    ``(single - fused_single) / (single - no_rot)`` is the fraction of the
    measured hop cost the fused transport hides — the ISSUE 9 overlap
    ablation. Off TPU the engine lowers to the tagged lax fallback, so the
    CPU-mesh fused deltas measure dispatch structure only; the on-chip
    driver run is the real ablation (bench.py --only ring_dma_overlap).

Run on the virtual 8-device CPU mesh (host collectives price higher relative
to compute than ICI would, so the measured rotation share is an UPPER bound
for real multi-chip TPU)::

    python -m harp_tpu.benchmark.lda_overlap

Prints one JSON line; PERF.md records the numbers.
"""

from __future__ import annotations

import json
import os
import sys
import time


def measure(num_docs=256, vocab=4096, num_topics=32, doc_len=64, epochs=8,
            reps=3, fused=True) -> dict:
    import numpy as np

    from harp_tpu.io import datagen
    from harp_tpu.models import lda
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    docs = datagen.lda_corpus(num_docs=num_docs, vocab=vocab,
                              num_topics=num_topics, doc_len=doc_len, seed=0)

    def time_variant(**kw):
        cfg = lda.LDAConfig(num_topics=num_topics, vocab=vocab, alpha=0.5,
                            beta=0.1, epochs=epochs, **kw)
        model = lda.LDA(sess, cfg)
        state = model.prepare(docs, seed=1)
        model.fit_prepared(state)                 # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            model.fit_prepared(state)
            best = min(best, time.perf_counter() - t0)
        return best

    t_single = time_variant(num_model_slices=1)
    t_norot = time_variant(num_model_slices=1, ablate_rotation=True)
    t_two = time_variant(num_model_slices=2)
    rot_share = max(0.0, (t_single - t_norot) / t_single)
    row = {
        "workers": sess.num_workers,
        "tokens": int(docs.size),
        "epochs": epochs,
        "single_s": round(t_single, 4),
        "no_rotation_s": round(t_norot, 4),
        "two_slice_s": round(t_two, 4),
        # non-overlapped rotation share of a single-slice fit (upper bound
        # for ICI); VERDICT's build-the-2-slice threshold was 10%
        "rotation_share": round(rot_share, 4),
        "two_slice_speedup": round(t_single / t_two, 4),
    }
    if fused:
        t_fused = time_variant(num_model_slices=1, fused_dma=True)
        t_fused_two = time_variant(num_model_slices=2, fused_dma=True)
        hop_cost = max(t_single - t_norot, 1e-12)
        row.update({
            "fused_single_s": round(t_fused, 4),
            "fused_two_slice_s": round(t_fused_two, 4),
            "fused_speedup": round(t_single / t_fused, 4),
            # fraction of the measured hop cost the fused transport hides
            # (clipped: CPU-mesh noise can push the delta past the hop)
            "fused_hidden_fraction": round(
                min(1.0, max(0.0, (t_single - t_fused) / hop_cost)), 4),
        })
    return row


def main() -> None:
    # must run before jax initializes a backend: force the virtual CPU
    # mesh (scaling.main does the same)
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
