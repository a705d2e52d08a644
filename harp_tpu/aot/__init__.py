"""AOT dispatch artifacts — compiled programs as first-class, shippable
files (ISSUE 15, the ROADMAP "AOT dispatch artifacts" item).

Harp's execution model is long-running resident workers; PR 14 made ours
an elastic fleet — and made the cost of a COLD resident visible: the
committed recovery blip is dominated by spare jax start + first-dispatch
compile. This package takes the SNIPPETS.md eval_shape→compiled-resident-fn
pattern to its conclusion, the way DrJAX (arXiv:2403.07128) treats
staged-out programs as reusable first-class artifacts rather than
per-process compile events:

* :mod:`~harp_tpu.aot.store` — the artifact store: every resident serving
  dispatch (and any step program) is exported ONCE via ``jax.export``
  (serialized-executable bytes where export is unsupported) and written
  keyed by (name, world, layout, jax version, device kind, model hash).
  A later process LOADS instead of compiling; every key-axis mismatch is
  a LOUD, metered miss (``aot.store.miss_<reason>``) that falls back to
  the compile path — a stale artifact can never be served silently.
* :mod:`~harp_tpu.aot.serve_artifacts` — the serving glue: export every
  (model, bucket) resident dispatch of an endpoint; install store hits
  into a fresh endpoint's compiled-fn cache so the replacement worker
  never traces (``trace_counts`` stays 0 for artifact-loaded buckets —
  asserted, not hoped), and optionally WARM each loaded bucket before the
  worker rendezvouses.
* :mod:`~harp_tpu.aot.manifest` — the pinned compiled-program manifest
  (``tools/artifact_manifest.json``): content hashes of the registry's
  exported programs, checked by jaxlint the way collective budgets are —
  a silently changed compiled program is a CI finding;
  ``--update-artifacts`` regenerates.
* :mod:`~harp_tpu.aot.cache` — jax's persistent compilation cache, on by
  default for every entry point, its directory chosen by ONE resolver
  (``JAX_COMPILATION_CACHE_DIR`` when set, else one fixed git-ignored
  directory in the checkout): distinct from and composable with the
  export path — export kills the TRACE, the compile cache kills the XLA
  compile of whatever still lowers.
"""

from __future__ import annotations

from harp_tpu.aot.cache import enable_compile_cache
from harp_tpu.aot.store import (ArtifactKey, ArtifactStore, device_kind,
                                layout_of)

__all__ = [
    "ArtifactKey", "ArtifactStore", "device_kind", "enable_compile_cache",
    "layout_of",
]
