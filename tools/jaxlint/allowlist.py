"""The shared jaxlint allowlist — every exemption in one place.

Keys are ``(repo-relative file, enclosing function, finding code)``; values
are MANDATORY justifications (core.MIN_JUSTIFICATION chars minimum — "ok"
is not a reason). Entries whose key matches no live finding FAIL the run as
stale: prune the entry when the exempted code is fixed, or it silently
pre-approves the next violation in that function.

JL106 (scatter) entries migrated verbatim from the r6 tools/lint_scatter.py
ALLOWLIST — same functions, same reasons, now carrying the code column.
"""

from __future__ import annotations

from tools.jaxlint.core import Allowlist

ALLOWLIST: Allowlist = {
    # -- JL106 scatter: cold prepare-side layout or gated legacy strategies --
    ("harp_tpu/models/sgd_mf.py", "densify", "JL106"):
        "prepare-time slab densification: runs ONCE per layout, scatters "
        "into a slab too wide for a one-hot GEMM (slab_elems lanes); the "
        "per-epoch hot path is pure stripe GEMMs",
    ("harp_tpu/models/sgd_mf.py", "mb_step", "JL106"):
        "legacy layout='sparse' minibatch update, kept for data too large "
        "to densify; documented ~25M samples/s gather/scatter wall — the "
        "dense masked-stripe layout IS the hot path",
    ("harp_tpu/models/sparse.py", "sparse_kmeans_stats", "JL106"):
        "strategy='gather' phantom-count correction: the gated legacy "
        "strategy for very-sparse-very-wide data (default is the "
        "lane_pack densify-GEMM, 13x faster on the bench shape)",
    ("harp_tpu/models/solvers.py", "bwd", "JL106"):
        "L-BFGS two-loop recursion alpha write: O(history) scalars per "
        "OUTER optimizer step, not per-sample work",
    ("harp_tpu/models/solvers.py", "step", "JL106"):
        "L-BFGS (s, y, rho) ring-buffer history write: O(history) rows "
        "per outer step",
    ("harp_tpu/models/forest.py", "one_tree", "JL106"):
        "per-tree feature mask init: O(dim) bits once per tree build, "
        "never inside the per-sample scoring loop",
    ("harp_tpu/ops/linalg.py", "body", "JL106"):
        "distributed-sort permutation bookkeeping: O(W) control-plane "
        "rows per merge round, not data-plane traffic",
    ("harp_tpu/models/lda.py", "_first_model", "JL106"):
        "stream-layout first model: the counts of z at sweep 0, scattered "
        "ONCE per prepare; every sweep's count writes on TPU are the "
        "lda_sample kernel's one-hot GEMMs",
    ("harp_tpu/ops/lda_kernels.py", "sample_xla", "JL106"):
        "the lda_sample kernel's jax.numpy twin (CPU, the mesh, shapes the "
        "kernel does not take): its exact int32 count writes are the "
        "segment form the kernel's one-hot GEMM was timed against",

    # -- JL103 retrace-hazard: sanctioned one-shot / per-config compiles ----
    ("harp_tpu/session.py", "run", "JL103"):
        "session.run IS the documented one-shot entry point (compile and "
        "invoke once, for scripts and prepare-time programs); callers that "
        "need the trace cache hold the callable from session.spmd instead",
    # -- JL104 host-sync-hot-loop: syncs that ARE the semantics ------------
    ("harp_tpu/models/kmeans.py", "fit_checkpointed", "JL104"):
        "chunk-boundary checkpoint write: the D2H snapshot of the "
        "replicated centroids is the save payload — one sync per "
        "save_every-iteration compiled chunk, not per iteration",
    ("harp_tpu/models/lda.py", "fit_checkpointed", "JL104"):
        "chunk-boundary checkpoint write of the chain state (z, wt): the "
        "D2H fetch is the save payload, once per save_every-epoch chunk",
    ("harp_tpu/models/sgd_mf.py", "fit_checkpointed", "JL104"):
        "chunk-boundary checkpoint write of the factor blocks: the D2H "
        "fetch is the save payload, once per save_every-epoch chunk",
    ("harp_tpu/models/sgd_mf.py", "fit_adaptive", "JL104"):
        "the per-epoch sync is the MEASUREMENT: the hop-budget tuner "
        "(reference adjustMiniBatch) times each compiled epoch on the host "
        "to pick the next budget — without the sync there is no signal",
    ("harp_tpu/models/sgxsimu.py", "fit", "JL104"):
        "the per-iteration sync is the SIMULATION: the enclave-cost model "
        "sleeps the modeled overhead after each COMPLETED chunk "
        "(reference's concurrent simuOverhead); unsynced dispatches would "
        "overlap the sleeps and void the model",

    # -- JL3xx concurrency: benign-by-design cross-thread state ------------
    ("harp_tpu/parallel/failure.py", "_loop", "JL301"):
        "sticky fail-stop flag: the heartbeat thread only ever flips "
        "failed False->True and the main thread only reads it in ok() — "
        "monotonic single-writer boolean, GIL-atomic store, and a missed "
        "read costs one extra probe interval, never a lost failure (ok() "
        "keeps raising once set); a lock would add nothing but overhead "
        "on the per-iteration hot path",
    ("harp_tpu/telemetry/xprof.py", "_start", "JL301"):
        "XprofController state (trace_dir, remaining) is single-threaded "
        "by the StepLog contract: boundary hooks run ONLY on the training "
        "loop thread (add_boundary_hook docstring), and the cross-thread "
        "handoff is the trigger FILE polled by (mtime, size) token — the "
        "controller attrs never cross a thread",
    ("harp_tpu/telemetry/xprof.py", "_stop", "JL301"):
        "same StepLog single-thread contract as _start: remaining is only "
        "touched from boundary hooks on the training loop thread; the "
        "operator-facing side is the atomically-replaced trigger file, "
        "not these attributes",
    ("harp_tpu/telemetry/xprof.py", "__call__", "JL302"):
        "remaining -= 1 runs only on the training loop thread (StepLog "
        "boundary hooks are single-threaded by contract); the __call__ "
        "hook heuristic assumes callbacks may cross threads, which the "
        "xprof controller deliberately never does (its module docstring "
        "calls out why collective ops must stay boundary-aligned)",

    # -- JL105 broad-except: blast radius deliberately wide ----------------
    ("harp_tpu/io/pipeline.py", "_run", "JL105"):
        "the prefetch thread envelopes ANY producer failure (parse error, "
        "fsspec IO, device_put OOM) into the chunk queue so it re-raises "
        "on the CONSUMER's thread — same contract as DynamicScheduler's "
        "_TaskError; a narrowed except would hang the consumer on a "
        "missing sentinel instead",
    ("harp_tpu/aot/store.py", "load", "JL105"):
        "deserializing a stale/foreign artifact payload can raise "
        "anything the jax.export/StableHLO loader reaches; the contract "
        "is degrade-to-compile with a metered miss, never crash a "
        "starting worker over a bad cache file",
    ("harp_tpu/parallel/p2p.py", "_reader", "JL105"):
        "an undecodable peer payload (gang version skew) can raise "
        "anything pickle-reachable; the frame boundary is intact, so the "
        "reader logs and survives instead of killing the event plane",
    ("harp_tpu/parallel/failure.py", "_run", "JL105"):
        "the device-probe thread exists to classify ARBITRARY backend "
        "failures on a poisoned device — any exception IS the positive "
        "detection signal, recorded and surfaced to the watchdog",
    ("harp_tpu/utils/checkpoint.py", "verify_step_dir", "JL105"):
        "a corrupt/torn orbax step can fail restore with any backend "
        "error class; verify must report False (skip the step for "
        "resume), never crash the relaunch",
    ("harp_tpu/utils/checkpoint.py", "restore_latest_valid", "JL105"):
        "resume-time payload reads of possibly-corrupt steps: any "
        "load/parse error means 'skip this step and try the previous "
        "one' — crashing here would defeat the elastic-restart journal",
    ("harp_tpu/benchmark/scaling.py", "measure", "JL105"):
        "sweep harness: one failing width config must record its error "
        "string and let the remaining grid points run (bench must not "
        "die mid-sweep)",
    ("harp_tpu/benchmark/serving_load.py", "_client_loop", "JL105"):
        "closed-loop load thread: any per-request failure (ServeError, "
        "timeout, transport reset) must be counted into the row's errors "
        "field and the mix kept running — a dying generator would turn a "
        "server-side error into a missing measurement",
    ("harp_tpu/benchmark/serving_fleet.py", "client_loop", "JL105"):
        "fleet chaos-scenario load threads (recovery/refresh): the row's "
        "ZERO-failures acceptance IS the tally of these catches — any "
        "per-request failure past the retry layer must land in the "
        "errors field, and a dying generator would hide exactly the "
        "failed request the scenario exists to count",
    ("harp_tpu/benchmark/serving_fleet.py", "loop", "JL105"):
        "hot-key pass load thread: same zero-failures tally contract as "
        "client_loop — per-request failures are the measurement, not a "
        "crash",
    ("harp_tpu/benchmark/serving_fleet.py", "load", "JL105"):
        "autoscale-ramp load thread: same zero-failures tally contract "
        "as client_loop — anything past the shed/retry classification "
        "must land in the errors field or the closed loop's join hangs "
        "and the row loses the failed request it exists to count",
    ("harp_tpu/serve/batcher.py", "_dispatch", "JL105"):
        "a malformed query payload in a coalesced serving batch can raise "
        "anything from dtype casts to shape errors deep in the dispatch; "
        "the micro-batcher must reply dispatch-error to the batch and keep "
        "serving live traffic, never die mid-stream",
    ("harp_tpu/serve/batcher.py", "_safe_reply", "JL105"):
        "a reply-path failure (malformed reply_to past the router guard, "
        "transport edge case) must cost exactly one reply, logged and "
        "counted — never the batcher thread or the rest of a served "
        "batch's replies",
    ("harp_tpu/serve/router.py", "_loop", "JL105"):
        "the worker's receive thread is its lifeline: a malformed request "
        "frame (missing id, unhashable model) beyond the typed guards "
        "must cost one dropped frame — logged and counted — never kill "
        "the serving loop and blackhole all subsequent traffic",
    ("harp_tpu/serve/router.py", "_close_at_exit", "JL105"):
        "interpreter-exit cleanup over the live worker/client set: one "
        "wedged close (drain timeout, dead socket) must not skip closing "
        "the remaining objects — each gets its attempt, failures logged",
    ("harp_tpu/sched/dynamic.py", "_monitor", "JL105"):
        "BaseException on purpose: a failing task must still fill its "
        "output slot or consumers block forever in wait_for_output; the "
        "error is re-raised on the CALLER's thread when the slot is "
        "claimed",
}
