"""Algorithmic work of one collapsed-Gibbs sweep over N tokens and K topics,
from the configuration's shapes alone, whatever implements the step:

* the conditional, ``(n_dk + α)(n_wk + β) / (n_k + Wβ)`` for every topic of
  every token, its running sum and the draw's compare: two products, a sum
  and a compare a (token, topic) pair, ``4 N K`` operations (VPU work: this
  share's peak is the MXU's, so it reads low by design);
* the least bytes: the token stream read and each token's topic written
  (word, document, old and new topic, int32: ``16 N``), and both count tables
  read and written once, int32: ``8 (W + D) K``.

The fused sampling kernel does all of it, the count writes included: its
shares are the same numbers."""


def work(config: dict, traffic: dict) -> dict:
    params = traffic["params"]
    k = int(config["num_topics"])
    n = int(round(params["docs"] * params["mean_doc_len"]))
    flops = 4.0 * n * k
    nbytes = 16.0 * n + 8.0 * (int(params["vocab"]) + int(params["docs"])) * k
    return {"flops_per_epoch": flops, "bytes_per_epoch": nbytes,
            "samples_per_epoch": n,
            "sample_flops_per_epoch": flops, "sample_bytes_per_epoch": nbytes}
