"""jax persistent compilation cache — where it lives is decided HERE, once.

Distinct from and composable with the export-artifact path: an export
artifact kills the TRACE (the Python body never runs on load), but its
shipped StableHLO still XLA-compiles once per process; the persistent
compilation cache turns that compile — and every other compile the process
performs, artifact-backed or not — into a disk load.

The cache is ON by default for every entry point (``harp_tpu.run``, the
serving workers, ``chip_smoke.py`` legs); :func:`resolve_cache_dir` is the
one place its directory is chosen:

* ``JAX_COMPILATION_CACHE_DIR`` set — that directory, placed from outside
  (the machine that owns the chip decides where compiled programs survive).
  No code path sets another: an explicit ``--compile-cache-dir`` / spec
  field that disagrees is ignored with one log line.
* unset — the explicit directory if a caller named one, else ONE fixed
  directory inside the checkout (:data:`DEFAULT_DIR`, git-ignored). Never a
  temp name, pid or timestamp: a cache that moves between runs never hits.

The one exception is the CPU backend, where the DEFAULT directory is not
used (a directory named by the environment or the caller still is):
XLA:CPU logs a multi-kilobyte "machine type doesn't match" error for every
executable it loads from the cache, CPU compiles at test shapes take
milliseconds, and a checkout full of host executables is dead weight in
the copy that goes to the chip.

jax gates cache writes on minimum compile time / entry size by default
(tuned for large programs); serving dispatches at tier-1 shapes compile in
milliseconds, so :func:`enable_compile_cache` zeroes both floors — the
point here is cold-start latency, not disk economy. To run WITHOUT the
cache (a cold-compile measurement), use jax's own switch:
``JAX_ENABLE_COMPILATION_CACHE=false``.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

LOG = logging.getLogger("harp_tpu.aot")

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_compile_cache — harp_tpu/aot/cache.py is two levels down
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache")

_enabled_dir: Optional[str] = None
_warned_ignored: set = set()


def resolve_cache_dir(explicit: Optional[str] = None) -> str:
    """The directory the compile cache lives in (module docstring): the
    environment's when set, else ``explicit``, else :data:`DEFAULT_DIR`."""
    env = os.environ.get(ENV_VAR)
    if not env:
        return explicit or DEFAULT_DIR
    if (explicit and os.path.abspath(explicit) != os.path.abspath(env)
            and explicit not in _warned_ignored):
        _warned_ignored.add(explicit)
        LOG.warning("compile cache: %s=%s is set; ignoring the explicit "
                    "directory %s", ENV_VAR, env, explicit)
    return env


def enable_compile_cache(explicit: Optional[str] = None) -> Optional[str]:
    """Point jax's persistent compilation cache at the resolved directory
    (created if missing) and return it — or None on the CPU backend when
    nobody named a directory (module docstring). Initialises the backend
    to learn which it is; call it where the process is about to use its
    devices anyway. Idempotent: a repeat call that resolves to the
    directory already enabled changes nothing."""
    global _enabled_dir
    import jax

    directory = resolve_cache_dir(explicit)
    if directory == DEFAULT_DIR and jax.default_backend() == "cpu":
        return None
    if (directory == _enabled_dir
            and jax.config.jax_compilation_cache_dir == directory):
        return directory
    os.makedirs(directory, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", directory)
    # zero the write floors: serving dispatches are small and fast to
    # compile — exactly the programs a cold start pays for one by one
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # jax latches its cache decision at the FIRST compile of the process
    # (sticky _cache_initialized/_cache_checked flags): a process that
    # already compiled anything before this call — a serving worker
    # enabling the cache at ctor time inside a long-lived controller —
    # would silently keep the cache off without this reset
    from jax._src import compilation_cache as _cc

    _cc.reset_cache()
    _enabled_dir = directory
    return directory
