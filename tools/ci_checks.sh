#!/usr/bin/env bash
# One-exit-code CI gate for harp_tpu (ISSUE 5 satellite):
#
#   1. jaxlint      — AST + jaxpr static analysis (collective divergence,
#                     axis names, retrace hazards, host syncs, broad
#                     excepts, scatters, collective-budget pinning, dtype
#                     policy, and JL203 byte budgets: per-step collective
#                     operand BYTES incl. the quantized trace targets — a
#                     quantized path silently reverting to f32 fails here.
#                     r10: the manifest also pins fused ring-DMA targets
#                     (lda_cgs_fused, sgd_mf_dense_fused, and the
#                     quantized-wt lda_cgs_quantwt_int8): their rotation
#                     hops are booked as the `fused_dma` kind with explicit
#                     fused_dma_bytes_per_step rows, so a fused schedule
#                     silently reverting to bare ppermute moves bytes
#                     between kinds and fails here too.
#                     r11: the manifest also pins the ONLINE-SERVING
#                     dispatch programs (harp_tpu/serve/):
#                     serve_classify_nn at ZERO collectives and
#                     serve_topk_mf at exactly the keyval-lookup
#                     all_to_all x3 + overflow psum — a collective
#                     sneaking into the resident predict dispatch, or its
#                     bytes growing, fails JL201/JL203; the one-compile-
#                     per-(model,bucket) retrace contract is asserted by
#                     tests/test_serve.py in stage 5.
#                     r12: the manifest also pins the ON-DEVICE RESHARD
#                     step programs (collectives/reshard.py):
#                     reshard_factor_a2a at ONE all_to_all whose operand
#                     bytes ARE the per-round chunk budget (512 B at the
#                     traced shape), reshard_factor_ring at the per-shift
#                     ppermute schedule, and serve_topk_mf_rebalanced at
#                     the SAME 3 all_to_alls as serve_topk_mf — a reshard
#                     schedule silently degrading toward a full gather,
#                     or a rebalance adding a collective to the request
#                     path, fails JL201/JL203; bitwise parity vs the
#                     numpy oracle is asserted by tests/test_reshard.py
#                     in stage 5);
#                     nonzero on any finding or stale allowlist entry.
#                     r13 (ISSUE 13): stage 1 also runs the JL3xx
#                     CONCURRENCY engine (checkers_threads.py) over the
#                     threaded host plane (serve/, telemetry/, parallel/,
#                     sched/): unguarded shared writes (JL301),
#                     unsynchronized read-modify-writes (JL302), lock-order
#                     inversions (JL303), and thread-lifecycle hygiene
#                     (JL304) — the hand-review race class of PRs 10-12 is
#                     now a lint, with every benign exception individually
#                     justified in the allowlist.
#   2. telemetry    — the jaxpr engine re-run with the gang telemetry layer
#                     ENABLED (HARP_TELEMETRY_DIR set): the instrumented
#                     step programs must reproduce the pinned manifest
#                     exactly — telemetry is host-boundary-only by design,
#                     and this gate makes that a checked contract, not a
#                     comment (ISSUE 7). r13: the same invocation also sets
#                     HARP_TRACE_REQUESTS=1, extending the zero-drift gate
#                     to the serving observability plane — request tracing
#                     stamps host boundaries in the serve router/batcher,
#                     so the serve_* dispatch targets (and everything else)
#                     must stay byte-identical with per-request spans on.
#                     The exporter /metrics//snapshot//gang schema smoke
#                     and the watchdog/skew/span tests ride stage 5
#                     (tests/test_serve_observability.py).
#   3. gang budgets — the jaxpr engine's GANG MODE only (ISSUE 13, the
#                     carried "jaxlint multi-host budgets" item): the
#                     dryrun_multichip step programs traced on the virtual
#                     2-host x 4-device mesh with the workers axis hinted
#                     DCN, pinned per target as collective counts,
#                     per-process shard shapes, and bytes_by_kind split by
#                     LINK CLASS (DCN vs ICI, mesh.axis_link_class) — a
#                     gang program whose DCN bytes grow, or whose
#                     per-process shard shape drifts, fails JL203/JL201
#                     exactly like the single-process targets.
#   4. check_claims — the numbers README.md quotes from the committed
#                     manifests (tools/collective_budget.json,
#                     tools/artifact_manifest.json) are the manifests'.
#   5. tier-1       — the ROADMAP.md verify suite (which itself re-runs
#                     jaxlint's clean-repo + budget checks as tests, so
#                     DOTS_PASSED captures them).
#   7. aot round-trip — ISSUE 15: the compiled-program artifact story
#                     end to end (tools/aot_roundtrip_smoke.py): export
#                     the registry's serving dispatches → content hashes
#                     must match the pinned tools/artifact_manifest.json
#                     (also checked inside stage 1's full jaxlint run:
#                     a silently changed compiled program is a finding,
#                     `python -m tools.jaxlint --update-artifacts`
#                     regenerates deliberately) → load into FRESH
#                     endpoints (every bucket hits, trace_counts stays 0
#                     — the never-recompile contract) → loaded dispatch
#                     answers bit-identically to the freshly compiled
#                     one.
#   6. serving chaos — ISSUE 14: a scripted kill-under-load on the
#                     in-process serving gang (HARP_FAULT=kill@request=N
#                     through the serving fault grammar): the LocalFleet
#                     supervisor must replace the dead worker, restore
#                     its shard through the on-device reshard engine,
#                     re-route the placement, and the retrying client
#                     must lose ZERO requests. Note the serve_* trace
#                     targets are re-verified byte-identical with the
#                     versioned-swap (push_epoch) code in place by
#                     stages 1-2: version state is host-side only and
#                     never enters a traced dispatch.
#   8. overload chaos — ISSUE 16: the overload-resilient serving story
#                     end to end (tools/overload_chaos_smoke.py): a QPS
#                     ramp with scripted wire faults (netdrop) AND a
#                     scripted kill, while the demand-driven autoscaler
#                     grows/shrinks the fleet through the versioned-
#                     placement push — every request answered correctly
#                     or cleanly shed with a retryable ``overloaded``
#                     reply (0 failed / 0 wrong / 0 hung), worker count
#                     follows the ramp up AND down, the kill recovers
#                     mid-storm, and fresh workers install untraced
#                     (trace_counts 0) behind a versioned placement.
#
# r18 (ISSUE 17): stage 1's manifest additionally pins the QUANTIZED
# serving dispatch — serve_topk_mf_int8 at the SAME 3 all_to_alls +
# overflow psum as serve_topk_mf but 172 B/step vs 356 B (the packed
# int8 rows ride the route/route-back wire): an int8 endpoint silently
# reverting to f32 payloads re-widens the wire at unchanged counts,
# which is exactly the JL203 byte-drift signature (tier-1 doctors one in
# tests/test_serve_quant.py to prove the gate fires). The int8 scoring dot
# accumulates in int32 via preferred_element_type, which the JL202 dtype
# policy accepts by construction (it flags bf16-accumulating dots, not
# integer dots).
#
#   10. memory budgets — ISSUE 19 (r20): the STATIC MEMORY engine (JL4xx,
#                     tools/jaxlint/checkers_memory.py) as its own
#                     attributable stage: liveness analysis over every
#                     traced program in BOTH registries pins per-target
#                     resident_arg_bytes / peak_live_bytes /
#                     transient_peak_ratio rows in the manifest's `memory`
#                     section (JL401 — drift fails exactly like JL203
#                     byte-drift; a grown static peak is the OOM that
#                     would otherwise ship invisibly, a grown resident set
#                     changes what the model mall can co-locate), audits
#                     every donate_argnums buffer for provable
#                     output aliasing (JL402 — XLA drops a mismatched
#                     donation with only a warning, doubling the buffer
#                     the caller believes is reused), flags closed-over
#                     constants ≥ 64 KiB baked into jaxprs (JL403), and
#                     flags any program whose liveness peak exceeds 20x
#                     its resident argument bytes (JL404 — the static
#                     signature of an accidental full gather/broadcast
#                     materialization). Stages 1-2 already run the engine
#                     inside their full/telemetry passes; this pass gives
#                     memory-budget failures their own CI banner. The same
#                     static rows ride each AOT artifact's meta (store
#                     metadata, never a key axis) and are cross-checked
#                     against Endpoint.resident_bytes() in tier-1.
#
#   9. ingest smoke — ISSUE 18: the streaming ingestion engine end to end
#                     (tools/ingest_smoke.py): part-files through the
#                     bounded reader pool must reproduce the in-memory
#                     load row for row; the stream-fed
#                     KMeans.fit_from_stream (through the DevicePrefetcher
#                     H2D thread) must match the in-memory fit BITWISE;
#                     and the device COO regroup on the jaxlint-pinned
#                     ingest_coo_regroup bounded all_to_all schedule (480
#                     B/step at the traced shape — degrading toward a full
#                     gather fails stage 1's JL203) must match the
#                     host-shuffle oracle nnz for nnz, with the
#                     distributed COO→CSR matching the per-block
#                     counting-sort oracle exactly.
#
#   11. hlo gate   — ISSUE 20 (r21): the LOWERED-HLO engine (JL5xx,
#                     tools/jaxlint/checkers_hlo.py) as its own
#                     attributable stage: every cached trace target in
#                     BOTH registries is compiled post-SPMD
#                     (jax.jit(...).lower().compile() — compilation only,
#                     nothing executes) and the optimized HLO is parsed
#                     for what the PARTITIONER actually emitted. A
#                     compiled collective kind no traced primitive maps
#                     to is a JL501 finding (GSPMD inserted communication
#                     after tracing — the layer every jaxpr-pinned byte
#                     budget is blind to), per-target compiled cost rows
#                     (collective counts + result bytes, instruction
#                     count, while count) are pinned in the manifest's
#                     `hlo` section (JL502 — drift fails exactly like
#                     JL203), an operand declared sharded that compiled
#                     REPLICATED is a JL503 finding (the static signature
#                     of a silent full broadcast), and the 6 pinned
#                     serving dispatches are lowered per reachable device
#                     kind into the `device_kinds` matrix (JL504 — cpu in
#                     CI; TPU kinds pin when lint runs there and are
#                     carried forward, never stale, by CPU regenerates).
#                     Stage 1 already runs the engine inside its full
#                     pass; this pass gives compiled-contract failures
#                     their own CI banner. The same hlo rows ride each
#                     AOT artifact's meta (store metadata, never a key
#                     axis).
#
# Any stage failing fails the script; all stages always run (a lint
# finding must not hide a test regression or vice versa).

set -u
cd "$(dirname "$0")/.."
rc=0

echo "== [1/11] jaxlint (AST + JL3xx concurrency + jaxpr + gang budgets + artifact manifest) =="
python -m tools.jaxlint || rc=1

echo "== [2/11] jaxlint budget with telemetry + request tracing ON (zero drift) =="
tele_dir="$(mktemp -d /tmp/_tele_gate.XXXXXX)"
HARP_TELEMETRY_DIR="$tele_dir" HARP_TRACE_REQUESTS=1 \
    python -m tools.jaxlint --jaxpr-only || rc=1

echo "== [3/11] gang-mode collective budgets (virtual multi-process mesh) =="
# ISSUE 13: the dryrun_multichip gang-mode step programs traced on the
# virtual 2-host x 4-device mesh with the workers axis hinted DCN —
# counts, per-process shard shapes, and the DCN/ICI link-class byte split
# all pinned against tools/collective_budget.json's gang_targets rows
# (JL201/JL203). --update-budget regenerates the gang rows with the rest.
# Stages 1 and 2 DO already trace the gang registry; this dedicated pass
# (4 targets, seconds) exists so a gang-budget failure is attributable to
# its own stage banner in CI output instead of buried in stage 1's.
python -m tools.jaxlint --gang-only || rc=1

echo "== [4/11] check_claims =="
python tools/check_claims.py || rc=1

echo "== [5/11] tier-1 tests =="
set -o pipefail
t1_log="$(mktemp /tmp/_t1.XXXXXX.log)"   # unique per run: concurrent CI
trap 'rm -f "$t1_log"; rm -rf "$tele_dir"' EXIT   # must not clobber the count
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee "$t1_log" || rc=1
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$t1_log" \
    | tr -cd . | wc -c)"

echo "== [6/11] serving-chaos smoke (scripted kill under load, zero failures) =="
# bounded like stage 5: a wedged recovery (the exact machinery this smoke
# exercises) must fail CI, never hang it
timeout -k 10 300 python -m tools.serving_chaos_smoke || rc=1

echo "== [7/11] aot artifact round-trip (export -> hash-check -> load -> parity) =="
timeout -k 10 300 python -m tools.aot_roundtrip_smoke || rc=1

echo "== [8/11] overload + network chaos smoke (QPS ramp + netdrop + kill, autoscale up/down, zero failures) =="
timeout -k 10 300 python -m tools.overload_chaos_smoke || rc=1

echo "== [9/11] streaming-ingestion smoke (chunk stream, stream-vs-memory bitwise fit, device COO regroup) =="
timeout -k 10 300 python -m tools.ingest_smoke || rc=1

echo "== [10/11] static memory budgets (JL4xx: liveness rows vs manifest, donation audit, const bloat, transient blowup) =="
# ISSUE 19: stages 1-2 already run the memory engine inside their full/
# telemetry passes; this dedicated pass (analysis over cached traces,
# seconds) exists so a memory-budget failure is attributable to its own
# stage banner in CI output instead of buried in stage 1's.
python -m tools.jaxlint --memory-only || rc=1

echo "== [11/11] lowered-HLO gate (JL5xx: compiler-inserted collectives, pinned hlo rows, sharding propagation, device-kind matrix) =="
# ISSUE 20: stage 1 already runs the hlo engine inside its full pass; this
# dedicated pass (lowering over cached traces, ~30s) exists so a
# compiled-contract failure is attributable to its own stage banner in CI
# output instead of buried in stage 1's.
python -m tools.jaxlint --hlo-only || rc=1

if [ "$rc" -ne 0 ]; then
    echo "ci_checks: FAILED"
else
    echo "ci_checks: all stages passed"
fi
exit $rc
