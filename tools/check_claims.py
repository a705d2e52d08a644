#!/usr/bin/env python
"""Claims honesty check: README/PERF headline throughput numbers must match
the latest committed bench record.

VERDICT r5 #8: PERF.md claimed "every BASELINE workload clears the ... bound
by >=6x" while the official record read ALS 5.22 and LDA 5.44 — numeric
prose drifts the moment a number is retyped instead of checked. This tool
pins every headline claim to the committed ``BENCH_local.json``: each entry
below names the doc, a regex whose single capture group is the claimed
number (K/M/G/B suffixes understood), where the recorded value lives in the
bench record, and the relative band the claim must sit inside (default 10%
— wider than any committed spread column, narrower than any real drift
class; entries quoting run-to-run bands in prose still check their headline
number).

Failure modes are all loud:
  * claimed number outside the band          → the prose drifted (or the
    record moved and the prose was not updated with it);
  * regex no longer matches the doc          → stale checker entry (the
    claim was reworded without updating this table — same rule as
    lint_scatter's stale-allowlist check);
  * bench value missing or null              → the claim asserts a number
    the committed record does not (yet) back — unmeasured rows must not be
    quoted as measured.

Usage: ``python tools/check_claims.py [repo_root]`` — exits nonzero on any
violation. ``tests/test_check_claims.py`` runs it in tier-1.
"""

from __future__ import annotations

import json
import os
import re
import sys
from typing import Callable, List, NamedTuple, Optional, Union

_SUFFIX = {"K": 1e3, "M": 1e6, "G": 1e9, "B": 1e9}

BENCH_FILE = "BENCH_local.json"


class Claim(NamedTuple):
    claim_id: str
    doc: str                    # repo-relative doc path
    pattern: str                # regex; group(1) = the claimed number
    source: Union[tuple, Callable]   # key path into the record, or a
    #   callable(record) -> float for derived quantities (e.g. Xeon lbs)
    rel_tol: float = 0.10
    file: str = BENCH_FILE      # which committed record backs the claim:
    #   BENCH_local.json (measured rates) or tools/collective_budget.json
    #   (traced per-step comm volumes — exact, so those claims use tol 0)


def _xeon_lb(rate_key: str, anchor_key: str):
    return lambda b: b[rate_key]["rate"] / b[anchor_key] / 36.0


CLAIMS: List[Claim] = [
    # README headline table ("Headline rows from the committed benchmark
    # record") — one claim per row that states a number
    Claim("kmeans_flagship", "README.md",
          r"\| K-means regroupallgather \(flagship\) \|[^|]*\| (\S+) iters/s",
          ("kmeans", "rate")),
    Claim("sgd_mf", "README.md",
          r"\| SGD-MF dense masked-stripe \|[^|]*\| (\S+) ratings/s",
          ("sgd_mf", "rate")),
    Claim("lda", "README.md",
          r"\| CGS-LDA \(gemm_scatter count writes\) \|[^|]*\| (\S+) "
          r"tokens/s",
          ("lda", "rate")),
    Claim("lda_clueweb", "README.md",
          r"\| CGS-LDA clueweb-regime \|[^|]*\| (\S+) tokens/s",
          ("lda_large", "rate")),
    Claim("als", "README.md",
          r"\| ALS implicit \(pallas lane Cholesky\) \|[^|]*\| (\S+) "
          r"iters/s",
          ("als", "rate")),
    Claim("pca", "README.md",
          r"\| PCA correlation \|[^|]*\| (\S+) fits/s",
          ("pca", "rate")),
    Claim("nn", "README.md",
          r"\| Mini-batch NN \|[^|]*\| (\S+) samples/s",
          ("nn", "rate")),
    Claim("attention", "README.md",
          r"\| Flash attention \(pallas\) \|[^|]*\| (\S+) tokens/s",
          ("attention", "rate")),
    Claim("kmeans_csr", "README.md",
          r"\| K-means CSR densify / CSR covariance \|[^|]*\| (\S+) iters/s",
          ("kmeans_csr", "rate")),
    Claim("csr_cov", "README.md",
          r"\| K-means CSR densify / CSR covariance \|[^|]*\|[^|]*iters/s "
          r"/ (\S+) passes/s",
          ("csr_covariance", "rate")),
    Claim("native_parse", "README.md",
          r"\| Native CSV parse \|[^|]*\| (\S+) MB/s",
          ("kmeans_from_files", "load_native_mb_per_sec")),
    # README architecture-table prose rates
    Claim("sgd_mf_arch_row", "README.md",
          r"fused pallas hop — (\S+) samples/s on one v5e chip",
          ("sgd_mf", "rate")),
    Claim("lda_arch_row", "README.md",
          r"bitwise-exact, 2× the hop — (\S+) tokens/s on one chip",
          ("lda", "rate")),
    Claim("kmeans_csr_arch_row", "README.md",
          r"scatter-free block-densify-GEMM default — (\S+) iters/s on chip",
          ("kmeans_csr", "rate")),
    # PERF.md: the smallest Xeon lower bound, stated per workload (the
    # ">=6x" drift class this checker exists to kill)
    Claim("min_xeon_lb_als", "PERF_ROUNDS.md",
          r"workloads: ALS (\S+)×",
          _xeon_lb("als", "als_cpu_anchor_iters_per_sec")),
    Claim("min_xeon_lb_lda", "PERF_ROUNDS.md",
          r"workloads: ALS \S+×, LDA (\S+)×",
          _xeon_lb("lda", "lda_cpu_anchor_tokens_per_sec")),
    # PERF.md r8 comm-volume stage math: per-step collective operand bytes
    # at tier-1 shapes, pinned to the traced manifest (jaxlint JL203 keeps
    # the manifest honest; this table keeps the PROSE honest). Traced bytes
    # are exact — zero tolerance.
    Claim("comm_kmeans_allreduce_f32", "PERF_ROUNDS.md",
          r"K-means allreduce \(W=8 tier-1\) \| (\S+) B",
          ("targets", "kmeans_allreduce", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("comm_kmeans_allreduce_int8", "PERF_ROUNDS.md",
          r"K-means allreduce \(W=8 tier-1\) \| \S+ B \| (\S+) B",
          ("targets", "kmeans_allreduce_int8", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("comm_kmeans_rga_f32", "PERF_ROUNDS.md",
          r"K-means regroupallgather \| (\S+) B",
          ("targets", "kmeans_regroupallgather", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("comm_kmeans_rga_bf16", "PERF_ROUNDS.md",
          r"K-means regroupallgather \| \S+ B \| (\S+) B",
          ("targets", "kmeans_regroupallgather_bf16", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("comm_sgd_mf_f32", "PERF_ROUNDS.md",
          r"SGD-MF rotation hop \| (\S+) B",
          ("targets", "sgd_mf_dense", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("comm_sgd_mf_int8", "PERF_ROUNDS.md",
          r"SGD-MF rotation hop \| \S+ B \| (\S+) B",
          ("targets", "sgd_mf_dense_int8", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    # PERF.md r10 fused ring-DMA table: per-step wire bytes + the share
    # moved by in-kernel DMA, pinned to the traced manifest's fused rows
    # (a fused target reverting to ppermute changes the manifest and
    # fails jaxlint; this keeps the PROSE tied to the same numbers).
    Claim("comm_lda_f32_baseline", "PERF_ROUNDS.md",
          r"LDA CGS hop \(f32 ppermute baseline\) \| (\S+) B",
          ("targets", "lda_cgs", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("comm_lda_fused_total", "PERF_ROUNDS.md",
          r"LDA CGS hop, fused \(lda_cgs_fused\) \| (\S+) B",
          ("targets", "lda_cgs_fused", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("comm_lda_fused_dma", "PERF_ROUNDS.md",
          r"LDA CGS hop, fused \(lda_cgs_fused\) \| \S+ B \| (\S+) B",
          ("targets", "lda_cgs_fused", "fused_dma_bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("comm_lda_quantwt", "PERF_ROUNDS.md",
          r"LDA CGS hop, quantized wt \(lda_cgs_quantwt_int8\) \| (\S+) B",
          ("targets", "lda_cgs_quantwt_int8", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("comm_sgd_fused_total", "PERF_ROUNDS.md",
          r"SGD-MF rotation hop, fused \(sgd_mf_dense_fused\) \| (\S+) B",
          ("targets", "sgd_mf_dense_fused", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("comm_sgd_fused_dma", "PERF_ROUNDS.md",
          r"SGD-MF rotation hop, fused \(sgd_mf_dense_fused\) \| \S+ B "
          r"\| (\S+) B",
          ("targets", "sgd_mf_dense_fused", "fused_dma_bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    # README "Online serving" + PERF.md r11 (ISSUE 10): the committed
    # CPU-mesh serving latency/QPS rows (the bench group always measures —
    # the router/batcher stack is host-side; the on-chip re-measure
    # rewrites the record AND must update this prose, by design), plus the
    # serve dispatch byte pins against the traced manifest (exact, tol 0 —
    # the classify dispatch is pinned at ZERO collective bytes).
    Claim("serving_mixed_p50", "README.md",
          r"mixed traffic p50 (\S+) ms",
          ("serving", "mixes", "mixed", "p50_ms")),
    Claim("serving_mixed_p99", "README.md",
          r"mixed traffic p50 \S+ ms\s*/ p99 (\S+) ms",
          ("serving", "mixes", "mixed", "p99_ms")),
    Claim("serving_mixed_qps", "README.md",
          r"at (\S+) QPS",
          ("serving", "mixes", "mixed", "qps")),
    Claim("serving_perf_topk_heavy_p50", "PERF_ROUNDS.md",
          r"\| topk_heavy \(0\.8\) \| (\S+) ms",
          ("serving", "mixes", "topk_heavy", "p50_ms")),
    Claim("serving_perf_mixed_p50", "PERF_ROUNDS.md",
          r"\| mixed \(0\.5\) \| (\S+) ms",
          ("serving", "mixes", "mixed", "p50_ms")),
    Claim("serving_perf_mixed_qps", "PERF_ROUNDS.md",
          r"\| mixed \(0\.5\) \| \S+ ms \| \S+ ms \| (\S+) \|",
          ("serving", "mixes", "mixed", "qps")),
    # PERF.md r13 (ISSUE 12): the serving-plane observability rows — the
    # per-stage latency breakdown from sampled request spans and its
    # reconciliation against the measured end-to-end (the stage durations
    # partition each span, so the mean ratio is ~1.0 by construction and
    # the p50 ratio sits inside a stated 25% band; both are pinned here so
    # the prose can never quote a breakdown the record doesn't back).
    Claim("serving_stage_coalesce_p50", "PERF_ROUNDS.md",
          r"\| coalesce wait \| (\S+) ms",
          ("serving", "stage_breakdown", "coalesce", "p50_ms")),
    Claim("serving_stage_dispatch_p50", "PERF_ROUNDS.md",
          r"\| dispatch \(resident compiled fn\) \| (\S+) ms",
          ("serving", "stage_breakdown", "dispatch", "p50_ms")),
    Claim("serving_stage_reply_hop_p50", "PERF_ROUNDS.md",
          r"\| reply hop \| (\S+) ms",
          ("serving", "stage_breakdown", "reply_hop", "p50_ms")),
    Claim("serving_span_mean_ratio", "PERF_ROUNDS.md",
          r"stage-mean sum / span mean = (\S+)",
          ("serving", "reconciliation", "mean_ratio"), rel_tol=0.02),
    Claim("serving_span_p50_ratio", "PERF_ROUNDS.md",
          r"stage-p50 sum / span p50 = (\S+)",
          ("serving", "reconciliation", "p50_ratio")),
    # PERF.md r15 (ISSUE 14): the serving-fleet rows — recovery blip
    # (separate-process gang, scripted kill, reshard-engine spare
    # restore), refresh-under-load, and the hot-key cache's hot-subset
    # tail. The recovery timings vary run to run (subprocess start +
    # compile), so those bands are wider; the zero-failure counts are
    # asserted by the bench itself and tier-1, not here.
    Claim("fleet_recovery_steady_p99", "PERF_ROUNDS.md",
          r"steady p99 (\S+) ms; controller-side",
          ("serving_fleet", "recovery", "steady", "p99_ms")),
    Claim("fleet_recovery_controller_s", "PERF_ROUNDS.md",
          r"placement pushed\) (\S+) s; observed",
          ("serving_fleet", "recovery", "recovery_s"), rel_tol=0.5),
    Claim("fleet_recovery_observed_s", "PERF_ROUNDS.md",
          r"recovery window (\S+) s end-to-end",
          ("serving_fleet", "recovery", "observed_recovery_s"),
          rel_tol=0.5),
    Claim("fleet_recovery_blip_p99", "PERF_ROUNDS.md",
          r"p99 (\S+) ms — the blip",
          ("serving_fleet", "recovery", "recovery_window", "p99_ms"),
          rel_tol=0.5),
    Claim("fleet_refresh_p99", "PERF_ROUNDS.md",
          r"/ p99 (\S+) ms at \S+ QPS \(indistinguishable",
          ("serving_fleet", "refresh", "p99_ms")),
    Claim("fleet_refresh_qps", "PERF_ROUNDS.md",
          r"at (\S+) QPS \(indistinguishable",
          ("serving_fleet", "refresh", "qps")),
    Claim("fleet_hotkey_hit_rate", "PERF_ROUNDS.md",
          r"\| cached \(hit rate (\S+)\)",
          ("serving_fleet", "hotkey", "cached", "cache", "hit_rate")),
    Claim("fleet_hotkey_cached_hot_p99", "PERF_ROUNDS.md",
          r"\| cached \(hit rate \S+\) \| \S+ ms \| \S+ ms \| \S+ ms "
          r"\| (\S+) ms \|",
          ("serving_fleet", "hotkey", "cached", "hot_keys", "p99_ms")),
    Claim("fleet_hotkey_hot_p99_speedup", "PERF_ROUNDS.md",
          r"Hot-subset p99 improves (\S+)x",
          ("serving_fleet", "hotkey", "hot_p99_speedup")),
    # PERF.md r16 + README "Instant cold start" (ISSUE 15): the
    # restart-to-first-reply comparison (artifacts off / on / on+compile
    # cache), the serving-window collapse, the artifacts-on recovery
    # window, and the pinned-artifact count against the manifest itself.
    # Cold-start totals are subprocess timings (moderate bands); the
    # serving-window and recovery numbers inherit the r15 recovery bands.
    Claim("restart_no_aot_total", "PERF_ROUNDS.md",
          r"\| no artifacts \| (\S+) s",
          ("serving_fleet", "restart", "no_aot",
           "restart_to_first_reply_s"), rel_tol=0.25),
    Claim("restart_no_aot_window", "PERF_ROUNDS.md",
          r"\| no artifacts \| \S+ s \| (\S+) s",
          ("serving_fleet", "restart", "no_aot",
           "rendezvous_to_first_reply_s"), rel_tol=0.5),
    Claim("restart_aot_total", "PERF_ROUNDS.md",
          r"\| artifacts \| (\S+) s",
          ("serving_fleet", "restart", "aot",
           "restart_to_first_reply_s"), rel_tol=0.25),
    Claim("restart_aot_window", "PERF_ROUNDS.md",
          r"\| artifacts \| \S+ s \| (\S+) s",
          ("serving_fleet", "restart", "aot",
           "rendezvous_to_first_reply_s"), rel_tol=0.5),
    Claim("restart_aot_cache_total", "PERF_ROUNDS.md",
          r"\| artifacts \+ compile cache \| (\S+) s",
          ("serving_fleet", "restart", "aot_cache",
           "restart_to_first_reply_s"), rel_tol=0.25),
    Claim("restart_window_speedup", "PERF_ROUNDS.md",
          r"rendezvous→first reply drops \S+ s → \S+ s \((\S+)x\)",
          ("serving_fleet", "restart", "serving_window_speedup"),
          rel_tol=0.5),
    Claim("restart_window_speedup_readme", "README.md",
          r"drops \S+ s → \S+ s \((\S+)×\)",
          ("serving_fleet", "restart", "serving_window_speedup"),
          rel_tol=0.5),
    Claim("recovery_aot_observed_s", "PERF_ROUNDS.md",
          r"observed window (\S+) s",
          ("serving_fleet", "recovery_aot", "observed_recovery_s"),
          rel_tol=0.5),
    Claim("artifact_manifest_count", "README.md",
          r"content-hashes the (\S+) registry programs",
          lambda m: float(len(m["artifacts"])), rel_tol=0.0,
          file="tools/artifact_manifest.json"),
    Claim("comm_serve_classify", "PERF_ROUNDS.md",
          r"Serve classify dispatch \(serve_classify_nn\) \| (\S+) B",
          ("targets", "serve_classify_nn", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("comm_serve_topk", "PERF_ROUNDS.md",
          r"Serve top-k lookup \(serve_topk_mf\) \| (\S+) B",
          ("targets", "serve_topk_mf", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    # PERF.md r18 + README "Quantized serving" (ISSUE 17): the int8
    # dispatch wire pinned against the traced manifest (exact — a silent
    # f32 revert moves the manifest and fails jaxlint first, this table
    # second), and the committed serving_quant row's headline pair: the
    # resident-footprint reduction (deterministic byte counts, tight
    # band) and the sampled top-k overlap vs the f32 gang.
    Claim("comm_serve_topk_int8", "PERF_ROUNDS.md",
          r"Serve top-k lookup, int8 \(serve_topk_mf_int8\) \| (\S+) B",
          ("targets", "serve_topk_mf_int8", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("quant_topk_reduction", "PERF_ROUNDS.md",
          r"top-k table shrinks (\S+)×",
          ("serving_quant", "resident_reduction", "topk"), rel_tol=0.01),
    Claim("quant_topk_overlap", "PERF_ROUNDS.md",
          r"mean top-10 overlap (\S+)",
          ("serving_quant", "topk_overlap", "mean"), rel_tol=0.05),
    Claim("quant_f32_qps", "PERF_ROUNDS.md",
          r"\| f32 residents \| (\S+) \|",
          ("serving_quant", "modes", "f32", "mixes", "topk_heavy", "qps"),
          rel_tol=0.25),
    Claim("quant_int8_qps", "PERF_ROUNDS.md",
          r"\| int8 residents \| (\S+) \|",
          ("serving_quant", "modes", "int8", "mixes", "topk_heavy",
           "qps"), rel_tol=0.25),
    Claim("quant_topk_reduction_readme", "README.md",
          r"resident\s+footprint is (\S+)× smaller",
          ("serving_quant", "resident_reduction", "topk"), rel_tol=0.01),
    Claim("quant_topk_overlap_readme", "README.md",
          r"mean top-10 overlap\s+(\S+) against the f32 gang",
          ("serving_quant", "topk_overlap", "mean"), rel_tol=0.05),
    # README "On-device resharding" + PERF.md r12 (ISSUE 11): the measured
    # CPU-mesh reshard row (the on-chip GB-scale re-measure rewrites the
    # record AND this prose, by design) plus the traced per-round byte pins
    # — the bounded-round contract: a schedule degrading toward a full
    # gather grows these exact numbers and fails jaxlint first, this table
    # second.
    Claim("reshard_seconds", "README.md",
          r"W4→W8 world change in (\S+) s",
          ("reshard", "cpu_mesh", "reshard_seconds")),
    Claim("reshard_speedup", "README.md",
          r"(\S+)× the host gather-and-resplit",
          ("reshard", "cpu_mesh", "host_vs_device_speedup")),
    Claim("reshard_perf_seconds", "PERF_ROUNDS.md",
          r"\| device all_to_all rounds \| (\S+) s",
          ("reshard", "cpu_mesh", "reshard_seconds")),
    Claim("reshard_perf_host_seconds", "PERF_ROUNDS.md",
          r"\| host gather-and-resplit \| (\S+) s",
          ("reshard", "cpu_mesh", "host_gather_seconds")),
    Claim("comm_reshard_a2a", "PERF_ROUNDS.md",
          r"Reshard round \(reshard_factor_a2a\) \| (\S+) B",
          ("targets", "reshard_factor_a2a", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("comm_reshard_ring", "PERF_ROUNDS.md",
          r"Reshard ring schedule \(reshard_factor_ring\) \| (\S+) B",
          ("targets", "reshard_factor_ring", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("comm_topk_rebalanced", "PERF_ROUNDS.md",
          r"Rebalanced top-k lookup \(serve_topk_mf_rebalanced\) \| (\S+) B",
          ("targets", "serve_topk_mf_rebalanced", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    # PERF.md r17 + README "Overload resilience" (ISSUE 16): the autoscale
    # ramp row. Throughput/latency/request-count inherit the wide recovery
    # bands (a time-bounded closed-loop ramp on a loaded CPU varies run to
    # run); the SHAPE claims are exact — peak/final worker count, the
    # scale-up's zero-trace AOT install (summed over whichever model moved;
    # the picked model varies with load), and the scale-down's placement
    # version. A re-measure that changes the shape must rewrite the prose.
    Claim("autoscale_requests", "PERF_ROUNDS.md",
          r"(\S+)\s+requests answered",
          ("serving_fleet", "autoscale", "requests"), rel_tol=0.5),
    Claim("autoscale_qps", "PERF_ROUNDS.md",
          r"(\S+) QPS at p50",
          ("serving_fleet", "autoscale", "qps"), rel_tol=0.5),
    Claim("autoscale_p50", "PERF_ROUNDS.md",
          r"QPS at p50 (\S+) ms",
          ("serving_fleet", "autoscale", "p50_ms"), rel_tol=0.5),
    Claim("autoscale_peak", "PERF_ROUNDS.md",
          r"\(peak (\d+), final",
          ("serving_fleet", "autoscale", "peak_workers"), rel_tol=0.0),
    Claim("autoscale_final", "PERF_ROUNDS.md",
          r"peak \d+, final (\d+)\)",
          ("serving_fleet", "autoscale", "final_workers"), rel_tol=0.0),
    Claim("autoscale_up_traces", "PERF_ROUNDS.md",
          r"`trace_counts = (\d+)`",
          lambda b: float(sum(b["serving_fleet"]["autoscale"]["scale_up"]
                              ["trace_counts"].values())), rel_tol=0.0),
    Claim("autoscale_up_aot_buckets", "PERF_ROUNDS.md",
          r"`aot_loaded = (\d+)`",
          lambda b: float(sum(b["serving_fleet"]["autoscale"]["scale_up"]
                              ["aot_loaded"].values())), rel_tol=0.0),
    Claim("autoscale_prebuild_s", "PERF_ROUNDS.md",
          r"pre-warmed offline in (\S+) s",
          ("serving_fleet", "autoscale", "prebuild_s"), rel_tol=0.5),
    Claim("autoscale_down_version", "PERF_ROUNDS.md",
          r"driving placement\s+version (\d+)",
          ("serving_fleet", "autoscale", "scale_down", "placement_version"),
          rel_tol=0.0),
    Claim("autoscale_peak_readme", "README.md",
          r"drive workers 1 → (\d+) → 1",
          ("serving_fleet", "autoscale", "peak_workers"), rel_tol=0.0),
    # PERF.md r19 + README "Ingestion pipeline" (ISSUE 18): the streaming
    # engine's committed 1 GB row — drain rate and e2e wall quoted in both
    # docs (e2e is a full-pipeline wall on a loaded host, wider band), the
    # row's nnz/regroup wall, and the regroup schedule's per-step bytes
    # pinned against the traced manifest (exact — a regroup degrading
    # toward a full gather moves the manifest and fails jaxlint first,
    # this table second).
    Claim("ingest_drain_readme", "README.md",
          r"bounded-queue drain sustains (\S+) MB/s",
          ("ingest", "stream_load_mb_per_sec")),
    Claim("ingest_e2e_readme", "README.md",
          r"stream→assemble→fit run takes (\S+) s end to end",
          ("ingest", "e2e_stream_fit_wall_s"), rel_tol=0.25),
    Claim("ingest_drain_perf", "PERF_ROUNDS.md",
          r"no device work\) sustains \*\*(\S+) MB/s\*\*",
          ("ingest", "stream_load_mb_per_sec")),
    Claim("ingest_e2e_perf", "PERF_ROUNDS.md",
          r"Lloyd fit runs \*\*(\S+) s\*\* end to end",
          ("ingest", "e2e_stream_fit_wall_s"), rel_tol=0.25),
    Claim("ingest_rows", "PERF_ROUNDS.md",
          r"part-files, (\d+) rows × 128 features",
          ("ingest", "total_rows"), rel_tol=0.0),
    Claim("ingest_overlap_eff", "PERF_ROUNDS.md",
          r"measured\s+efficiency (\S+) here",
          ("ingest", "overlap_efficiency"), rel_tol=0.5),
    Claim("ingest_regroup_nnz", "PERF_ROUNDS.md",
          r"committed row moves (\d+) nnz",
          ("ingest", "regroup", "nnz"), rel_tol=0.0),
    Claim("ingest_regroup_wall", "PERF_ROUNDS.md",
          r"nnz \(8192 rows\)\s+in (\S+) s on the CPU mesh",
          ("ingest", "regroup", "wall_s"), rel_tol=0.5),
    Claim("comm_ingest_regroup", "PERF_ROUNDS.md",
          r"Ingest COO regroup round \(ingest_coo_regroup\) \| (\S+) B",
          ("targets", "ingest_coo_regroup", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("comm_ingest_regroup_readme", "README.md",
          r"`ingest_coo_regroup` target, (\S+) B/step",
          ("targets", "ingest_coo_regroup", "bytes_per_step"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    # PERF.md r20 (ISSUE 19): the static memory table — per-target
    # resident/peak/ratio rows pinned to the manifest's `memory` section
    # (jaxlint JL401 keeps the manifest honest against the traced
    # programs; these keep the PROSE honest against the manifest). Static
    # rows are exact — zero tolerance.
    Claim("mem_serve_topk_resident", "PERF_ROUNDS.md",
          r"serve_topk_mf \(f32 dispatch\) \| (\S+) B",
          ("memory", "serve_topk_mf", "resident_arg_bytes"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("mem_serve_topk_peak", "PERF_ROUNDS.md",
          r"serve_topk_mf \(f32 dispatch\) \| \S+ B \| (\S+) B",
          ("memory", "serve_topk_mf", "peak_live_bytes"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("mem_serve_topk_int8_resident", "PERF_ROUNDS.md",
          r"serve_topk_mf_int8 \(quantized\) \| (\S+) B",
          ("memory", "serve_topk_mf_int8", "resident_arg_bytes"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("mem_serve_topk_int8_peak", "PERF_ROUNDS.md",
          r"serve_topk_mf_int8 \(quantized\) \| \S+ B \| (\S+) B",
          ("memory", "serve_topk_mf_int8", "peak_live_bytes"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("mem_serve_classify_resident", "PERF_ROUNDS.md",
          r"serve_classify_nn \| (\S+) B",
          ("memory", "serve_classify_nn", "resident_arg_bytes"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("mem_kmeans_allreduce_peak", "PERF_ROUNDS.md",
          r"\| kmeans_allreduce \| \S+ B \| (\S+) B",
          ("memory", "kmeans_allreduce", "peak_live_bytes"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("mem_kmeans_int8_peak", "PERF_ROUNDS.md",
          r"\| kmeans_allreduce_int8 \| \S+ B \| (\S+) B",
          ("memory", "kmeans_allreduce_int8", "peak_live_bytes"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("mem_kmeans_int8_ratio", "PERF_ROUNDS.md",
          r"\| kmeans_allreduce_int8 \| \S+ B \| \S+ B \| (\S+) \|",
          ("memory", "kmeans_allreduce_int8", "transient_peak_ratio"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("mem_gang_rga_peak", "PERF_ROUNDS.md",
          r"\| gang2x4_kmeans_regroupallgather \| \S+ B \| (\S+) B",
          ("memory", "gang2x4_kmeans_regroupallgather", "peak_live_bytes"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("mem_ingest_regroup_resident", "PERF_ROUNDS.md",
          r"\| ingest_coo_regroup \| (\S+) B",
          ("memory", "ingest_coo_regroup", "resident_arg_bytes"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    # PERF.md r21 (ISSUE 20): the compiled-collective table — per-target
    # post-SPMD cost rows pinned to the manifest's `hlo` section (jaxlint
    # JL502/JL504 keep the manifest honest against what the partitioner
    # emits; these keep the PROSE honest against the manifest). Compiled
    # rows are exact per jax version — zero tolerance; the op COUNTS are
    # baked into the regex literals, so a changed count goes stale-loud
    # instead of silently matching.
    Claim("hlo_kmeans_bytes", "PERF_ROUNDS.md",
          r"\| kmeans_allreduce \| 2× all-reduce \| (\S+) B",
          ("hlo", "targets", "kmeans_allreduce", "collective_bytes_total"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("hlo_kmeans_instrs", "PERF_ROUNDS.md",
          r"\| kmeans_allreduce \| 2× all-reduce \| \S+ B \| (\d+) \|",
          ("hlo", "targets", "kmeans_allreduce", "instruction_count"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("hlo_topk_bytes", "PERF_ROUNDS.md",
          r"\| serve_topk_mf \| 3× all-to-all \| (\S+) B",
          ("hlo", "targets", "serve_topk_mf", "collective_bytes_total"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("hlo_topk_int8_bytes", "PERF_ROUNDS.md",
          r"\| serve_topk_mf_int8 \| 3× all-to-all \| (\S+) B",
          ("hlo", "targets", "serve_topk_mf_int8",
           "collective_bytes_total"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("hlo_topk_int8_instrs", "PERF_ROUNDS.md",
          r"\| serve_topk_mf_int8 \| 3× all-to-all \| \S+ B \| (\d+) \|",
          ("hlo", "targets", "serve_topk_mf_int8", "instruction_count"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("hlo_gang_rga_bytes", "PERF_ROUNDS.md",
          r"\| gang2x4_kmeans_regroupallgather \| AG 65536 \+ RS 8256 "
          r"\+ AR 4 \| (\S+) B",
          ("hlo", "targets", "gang2x4_kmeans_regroupallgather",
           "collective_bytes_total"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("hlo_ingest_regroup_bytes", "PERF_ROUNDS.md",
          r"\| ingest_coo_regroup \| 1× all-to-all \| (\S+) B",
          ("hlo", "targets", "ingest_coo_regroup",
           "collective_bytes_total"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    # the device-kind dispatch matrix rows (JL504's pins, cpu kind)
    Claim("hlo_dispatch_b8_bytes", "PERF_ROUNDS.md",
          r"\| serve/mf/b8 \| 3× all-to-all \| (\S+) B",
          ("hlo", "device_kinds", "cpu", "serve/mf/b8",
           "collective_bytes_total"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("hlo_dispatch_b32_bytes", "PERF_ROUNDS.md",
          r"\| serve/mf/b32 \| 3× all-to-all \| (\S+) B",
          ("hlo", "device_kinds", "cpu", "serve/mf/b32",
           "collective_bytes_total"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("hlo_dispatch_b128_bytes", "PERF_ROUNDS.md",
          r"\| serve/mf/b128 \| 3× all-to-all \| (\S+) B",
          ("hlo", "device_kinds", "cpu", "serve/mf/b128",
           "collective_bytes_total"),
          rel_tol=0.0, file="tools/collective_budget.json"),
    Claim("hlo_dispatch_nn_b8_instrs", "PERF_ROUNDS.md",
          r"\| serve/nn/b8 \| none \| \S+ B \| (\d+) \|",
          ("hlo", "device_kinds", "cpu", "serve/nn/b8",
           "instruction_count"),
          rel_tol=0.0, file="tools/collective_budget.json"),
]


def parse_value(text: str) -> Optional[float]:
    """'1397' → 1397.0; '1.11M' → 1.11e6; '3.05B'/'3.05G' → 3.05e9."""
    m = re.fullmatch(r"(\d+(?:\.\d+)?)([KMGB])?", text)
    if not m:
        return None
    return float(m.group(1)) * _SUFFIX.get(m.group(2) or "", 1.0)


def _lookup(bench: dict, source) -> Optional[float]:
    if callable(source):
        try:
            return float(source(bench))
        except (KeyError, TypeError, ZeroDivisionError):
            return None
    node = bench
    for key in source:
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    return float(node) if isinstance(node, (int, float)) else None


def check_claim(claim: Claim, doc_text: str, bench: dict) -> Optional[str]:
    """One claim against one doc + bench record; None = consistent."""
    m = re.search(claim.pattern, doc_text)
    if not m:
        return (f"{claim.doc}: claim '{claim.claim_id}' not found — the "
                f"prose was reworded; update its entry in "
                f"tools/check_claims.py (pattern {claim.pattern!r})")
    claimed = parse_value(m.group(1))
    if claimed is None:
        return (f"{claim.doc}: claim '{claim.claim_id}' captured "
                f"{m.group(1)!r}, not a number — fix the pattern")
    recorded = _lookup(bench, claim.source)
    if recorded is None:
        return (f"{claim.doc}: claim '{claim.claim_id}' states "
                f"{m.group(1)} but the bench record has no measured value "
                f"for it (missing/null) — unmeasured rows must not be "
                f"quoted as numbers")
    if abs(claimed - recorded) > claim.rel_tol * abs(recorded):
        return (f"{claim.doc}: claim '{claim.claim_id}' states "
                f"{m.group(1)} but the committed record reads "
                f"{recorded:.4g} (> {100 * claim.rel_tol:.0f}% off) — "
                f"update the prose or re-measure")
    return None


def check(repo: str, claims: Optional[List[Claim]] = None) -> List[str]:
    records = {}
    docs = {}
    violations = []
    for claim in claims if claims is not None else CLAIMS:
        if claim.file not in records:
            with open(os.path.join(repo, claim.file)) as f:
                records[claim.file] = json.load(f)
        if claim.doc not in docs:
            with open(os.path.join(repo, claim.doc)) as f:
                docs[claim.doc] = f.read()
        v = check_claim(claim, docs[claim.doc], records[claim.file])
        if v:
            violations.append(v)
    return violations


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    repo = argv[0] if argv else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    violations = check(repo)
    for v in violations:
        print(v)
    if violations:
        print(f"{len(violations)} claim(s) out of sync with {BENCH_FILE}")
        return 1
    print(f"all {len(CLAIMS)} headline claims within their "
          f"{BENCH_FILE} bands")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
