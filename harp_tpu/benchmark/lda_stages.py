"""Per-stage budget of the LDA hop on the real chip (VERDICT r4 item 1).

The r2 profiling asserted gather 1.5 ms / scatter 2.7 ms / sample 1.0 ms per
262k-token pass; this harness MEASURES the budget by stage ablation
(``LDAConfig.ablate_stage`` — results are wrong, timing-only) on the exact
bench.py config, so the optimization target is picked by data:

  * ``full``      — the shipping path
  * ``no_scatter``— word-topic write (segment_sum / one-hot GEMM) ablated
  * ``no_gather`` — word-topic read (row gather / one-hot GEMM) ablated
  * ``no_sample`` — categorical build + inverse-CDF draw replaced by a cheap
    shift that still consumes the gather and feeds the scatter
  * ``minimal``   — gather+scatter both ablated (sample + bookkeeping floor)

Run on whatever backend is live (the real chip by default)::

    python -m harp_tpu.benchmark.lda_stages

Prints one JSON line; PERF.md records the numbers.
"""

from __future__ import annotations

import json
import sys


def measure(num_docs=2048, vocab=2000, doc_len=128, num_topics=32, epochs=100,
            reps=3, wt_access="auto") -> dict:
    from harp_tpu.io import datagen
    from harp_tpu.models import lda
    from harp_tpu.session import HarpSession

    sess = HarpSession()
    num_docs -= num_docs % sess.num_workers
    docs = datagen.lda_corpus(num_docs, vocab, max(2, num_topics // 2),
                              doc_len, seed=3)
    tokens = docs.size * epochs

    def time_variant(**kw):
        """Two-point per-epoch seconds (epochs/4 vs epochs) on the shared
        alternating protocol (benchmark/timing.py): the per-call
        dispatch+fetch constant cancels."""
        from harp_tpu.benchmark.timing import two_point

        def build(ne):
            cfg = lda.LDAConfig(num_topics=num_topics, vocab=vocab, epochs=ne,
                                wt_access=wt_access, **kw)
            model = lda.LDA(sess, cfg)
            state = model.prepare(docs, seed=1)
            model.fit_prepared(state)             # compile + warm

            def timer():
                model.fit_prepared(state)
            return timer

        tp = two_point(build, max(epochs // 4, 1), epochs, 1.0, reps=reps)
        return tp["per_iter_ms"] / 1e3 * epochs

    t = {
        "full": time_variant(),
        "no_scatter": time_variant(ablate_stage="scatter"),
        "no_gather": time_variant(ablate_stage="gather"),
        "no_sample": time_variant(ablate_stage="sample"),
        "minimal": time_variant(ablate_stage="gather+scatter"),
    }
    ms = {k: round(v / epochs * 1e3, 3) for k, v in t.items()}
    return {
        "config": {"num_docs": num_docs, "vocab": vocab, "doc_len": doc_len,
                   "num_topics": num_topics, "epochs": epochs,
                   "wt_access": wt_access,
                   "tokens_per_epoch": docs.size},
        "epoch_ms": ms,
        "stage_ms": {
            "scatter": round(ms["full"] - ms["no_scatter"], 3),
            "gather": round(ms["full"] - ms["no_gather"], 3),
            "sample": round(ms["full"] - ms["no_sample"], 3),
            "floor": ms["minimal"],
        },
        "tokens_per_sec": {k: round(tokens / v) for k, v in t.items()},
    }


if __name__ == "__main__":
    kw = {}
    for a in sys.argv[1:]:
        k, _, v = a.lstrip("-").partition("=")
        if not v:
            sys.exit(f"usage: lda_stages [key=value ...] with keys "
                     f"num_docs vocab doc_len num_topics epochs reps "
                     f"wt_access (got {a!r})")
        kw[k] = v if k == "wt_access" else int(v)
    print(json.dumps(measure(**kw)))
