"""Artifact store — serialized compiled programs keyed by everything that
could invalidate them.

One artifact = one exported program (a resident serving dispatch, a model
step program) written as two files under the store root::

    <root>/<name>.json      # the key + content hash + format (the meta)
    <root>/<name>.bin       # the serialized program bytes (the payload)

``name`` may contain ``/`` (e.g. ``serve/mf/b8``) — artifacts nest in
subdirectories. Writes are tmp+rename atomic (the rendezvous-file idiom),
so a concurrent reader can never see a torn artifact.

The KEY is the invalidation matrix (ISSUE 15 satellite): an artifact is
only served when every axis matches the loading process —

* ``jax_version``  — StableHLO/runtime compatibility is jax's contract
  per version; a mismatched load is rejected (``miss_jax_version``);
* ``device_kind``  — a program exported for one accelerator generation
  must not run on another (``miss_device_kind``);
* ``world``        — the mesh width baked into the program
  (``miss_world``);
* ``quant``        — the resident quant mode the program was exported
  under (``"f32"`` or ``"int8"`` — ISSUE 17): an int8 artifact must never
  warm an f32 endpoint or vice versa (``miss_quant``). Checked BEFORE
  layout so a pure quant flip names itself instead of surfacing as the
  layout drift its dtype shift also causes;
* ``layout``       — the full abstract signature: shape/dtype/sharding of
  every argument, :func:`layout_of` (``miss_layout``);
* ``model_hash``   — the model identity the program serves; the caller's
  content hash of the model spec/structure (``miss_model_hash``).

Every miss is LOUD: a warning log naming the axis and both values, and an
``aot.store.miss_<reason>`` metric — then the caller falls back to the
compile path. A hit counts ``aot.store.hit``. Nothing in this module can
make a worker serve a stale program silently.

Formats: ``jax_export`` (primary — ``jax.export`` serialized StableHLO;
portable across processes, still XLA-compiles at load, which the
persistent compilation cache then absorbs) and ``pickled_executable``
(fallback where export is unsupported on the running jax —
``jax.experimental.serialize_executable``: zero compile at load but
pinned to the exact device topology). :meth:`ArtifactStore.export_fn`
picks automatically; the meta records which.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import re
from dataclasses import asdict, dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

LOG = logging.getLogger("harp_tpu.aot")

META_VERSION = 1
FMT_EXPORT = "jax_export"
FMT_PICKLED = "pickled_executable"

# the key axes checked at load, in check order: the FIRST mismatching axis
# names the miss (a stale artifact usually fails several; one clear reason
# beats four)
KEY_AXES = ("jax_version", "device_kind", "world", "quant", "layout",
            "model_hash")


def jax_version() -> str:
    import jax

    return jax.__version__


def device_kind() -> str:
    """The accelerator generation the running backend exposes (e.g.
    ``TPU v5e`` / ``cpu``) — programs are compiled FOR a device kind."""
    import jax

    return str(jax.devices()[0].device_kind)


def layout_of(args) -> str:
    """Fingerprint of an argument pytree's abstract signature: treedef
    plus shape, dtype, and sharding spec per leaf — ANY layout drift (a
    resized bucket, a re-sharded state arg, an owner-map arg appearing
    after a rebalance, a restructured parameter tree) changes this string
    and invalidates the artifact."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    parts = [str(treedef)]
    for a in leaves:
        spec = getattr(getattr(a, "sharding", None), "spec", None)
        parts.append(f"{tuple(a.shape)}:{a.dtype}:{spec}")
    return ";".join(parts)


# MLIR debug info is NOT part of the program: loc() records carry source
# file paths, line numbers, and per-process location-counter ids, all of
# which shift with import order, trace count, and checkout path while the
# ops stay identical. The content hash must pin the PROGRAM, so the
# canonical text drops every loc record before hashing (verified: the
# same registry exported from different entry points differs ONLY in loc
# lines).
_LOC_DEF = re.compile(r"^#loc\d* = loc\(.*\)$\n?", re.MULTILINE)
_LOC_REF = re.compile(
    r" loc\((?:#loc\d*|unknown|callsite\(#loc\d* at #loc\d*\)"
    r"|\".*?\"(?:\(.*?\))?)\)")


def canonical_program_text(mlir_text: str) -> str:
    """The location-stripped module text whose sha256 is the artifact
    content hash — deterministic for a given program + jax version +
    platform, regardless of which process traced it."""
    return _LOC_REF.sub("", _LOC_DEF.sub("", mlir_text))


@dataclass(frozen=True)
class ArtifactKey:
    """Everything that must match for a stored program to be servable."""

    name: str                   # e.g. "serve/mf/b8" or "step/kmeans"
    world: int                  # mesh width the program was exported at
    layout: str                 # layout_of(args) at export time
    model_hash: str             # caller's model-identity content hash
    jax_version: str = field(default_factory=jax_version)
    device_kind: str = field(default_factory=device_kind)
    quant: str = "f32"          # resident quant mode ("f32" | "int8")


def _check_name(name: str) -> str:
    # names become paths under the store root; keep them rooted there
    if not name or name.startswith(("/", ".")) or ".." in name.split("/"):
        raise ValueError(f"artifact name must be a relative path without "
                         f"'..' segments; got {name!r}")
    return name


class ArtifactStore:
    """File-backed store of exported programs (module docstring)."""

    def __init__(self, root: str, metrics=None):
        if metrics is None:
            from harp_tpu.utils.metrics import DEFAULT as metrics
        self.root = root
        self.metrics = metrics

    # -- paths --------------------------------------------------------------

    def _paths(self, name: str) -> Tuple[str, str]:
        base = os.path.join(self.root, _check_name(name))
        return base + ".json", base + ".bin"

    def list(self) -> List[dict]:
        """Every artifact's meta (sorted by name); unreadable/torn metas
        are skipped — listing must survive any seam."""
        metas = []
        for dirpath, _dirs, files in os.walk(self.root):
            for fn in sorted(files):
                if not fn.endswith(".json"):
                    continue
                try:
                    with open(os.path.join(dirpath, fn)) as f:
                        metas.append(json.load(f))
                except (OSError, ValueError):
                    continue
        return sorted(metas, key=lambda m: m.get("name", ""))

    # -- export (build the payload from a live compiled fn) -----------------

    def export_fn(self, fn: Callable, args) -> Tuple[bytes, str, str]:
        """Serialize a jitted ``fn`` at ``args``'s abstract signature →
        ``(payload, content_hash, format)``. ``args`` may be concrete
        arrays or ShapeDtypeStructs (shape/dtype/sharding is all that is
        read). The content hash is over the lowered StableHLO module text
        — deterministic for a given jax version/platform, which is what
        lets the manifest pin it across processes."""
        try:
            from jax import export as jax_export
        except ImportError:          # pragma: no cover — this jax has it
            jax_export = None
        if jax_export is not None:
            exported = jax_export.export(fn)(*args)
            content_hash = hashlib.sha256(canonical_program_text(
                exported.mlir_module()).encode()).hexdigest()
            return exported.serialize(), content_hash, FMT_EXPORT
        # serialized-bytes fallback: pickle the compiled executable
        # (topology-pinned; the key's device_kind/world axes gate it)
        from jax.experimental import serialize_executable as sx

        lowered = fn.lower(*args)
        content_hash = hashlib.sha256(canonical_program_text(
            lowered.as_text()).encode()).hexdigest()
        payload, _, _ = sx.serialize(lowered.compile())
        return bytes(payload), content_hash, FMT_PICKLED

    def load_fn(self, payload: bytes, fmt: str) -> Callable:
        """Deserialize a payload back into a dispatchable callable. The
        ``jax_export`` format re-enters through ``jax.jit`` (one XLA
        compile of the shipped StableHLO — no TRACE, so a loaded
        endpoint's ``trace_counts`` stays 0; the persistent compilation
        cache absorbs the compile); ``pickled_executable`` is the
        already-compiled executable."""
        import jax

        if fmt == FMT_EXPORT:
            from jax import export as jax_export

            exported = jax_export.deserialize(bytearray(payload))
            return jax.jit(exported.call)
        if fmt == FMT_PICKLED:
            from jax.experimental import serialize_executable as sx

            compiled = sx.deserialize_and_load(payload)
            return compiled
        raise ValueError(f"unknown artifact format {fmt!r}")

    # -- put/load -----------------------------------------------------------

    def put(self, key: ArtifactKey, payload: bytes, content_hash: str,
            fmt: str, memory: Optional[dict] = None,
            hlo: Optional[dict] = None) -> dict:
        """Write one artifact atomically; returns the meta written.

        ``memory`` is the program's static memory row
        (``harp_tpu.aot.static_memory.memory_row``:
        resident_arg_bytes / peak_live_bytes / transient_peak_ratio) and
        ``hlo`` its compiled-HLO cost row
        (``harp_tpu.aot.hlo_audit.hlo_row``: compiler-emitted collective
        counts/bytes, instruction count, while count) — both recorded as
        METADATA (placement planning / fleet tooling), never a key axis:
        a differing or absent row must not turn a load into a miss
        (``load_meta`` checks only ``KEY_AXES``)."""
        meta_path, bin_path = self._paths(key.name)
        os.makedirs(os.path.dirname(meta_path) or ".", exist_ok=True)
        meta = {"v": META_VERSION, **asdict(key),
                "content_hash": content_hash, "format": fmt,
                "payload_bytes": len(payload),
                "payload_sha256": hashlib.sha256(payload).hexdigest()}
        if memory is not None:
            meta["memory"] = dict(memory)
        if hlo is not None:
            meta["hlo"] = dict(hlo)
        tmp = bin_path + f".tmp-{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(payload)
        os.replace(tmp, bin_path)
        tmp = meta_path + f".tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(meta, f, indent=1)
        os.replace(tmp, meta_path)
        self.metrics.count("aot.store.put")
        return meta

    def export_and_put(self, key: ArtifactKey, fn: Callable, args,
                       memory: Optional[dict] = None,
                       hlo: Optional[dict] = None) -> dict:
        payload, content_hash, fmt = self.export_fn(fn, args)
        return self.put(key, payload, content_hash, fmt, memory=memory,
                        hlo=hlo)

    def _miss(self, key: ArtifactKey, reason: str, detail: str) -> None:
        # LOUD by contract: the metric names the axis, the log names both
        # values — a fleet quietly recompiling everything is an incident
        # in the making, and this is its first signal
        self.metrics.count(f"aot.store.miss_{reason}")
        LOG.warning("aot artifact %r rejected (%s): %s — falling back to "
                    "compile", key.name, reason, detail)

    def load_meta(self, key: ArtifactKey) -> Optional[dict]:
        """The meta for ``key`` IF every key axis matches; None (with the
        metered miss) otherwise."""
        meta_path, _bin_path = self._paths(key.name)
        try:
            with open(meta_path) as f:
                meta = json.load(f)
        except OSError:
            self._miss(key, "absent", f"no artifact at {meta_path}")
            return None
        except ValueError:
            self._miss(key, "corrupt", f"unparseable meta at {meta_path}")
            return None
        want = asdict(key)
        for axis in KEY_AXES:
            if meta.get(axis) != want[axis]:
                self._miss(key, axis,
                           f"artifact has {axis}={meta.get(axis)!r}, this "
                           f"process needs {want[axis]!r}")
                return None
        return meta

    def load(self, key: ArtifactKey) -> Optional[Tuple[Callable, dict]]:
        """``(callable, meta)`` for a fresh hit; None on ANY mismatch or
        unreadable payload (all metered — the caller compiles instead)."""
        meta = self.load_meta(key)
        if meta is None:
            return None
        _meta_path, bin_path = self._paths(key.name)
        try:
            with open(bin_path, "rb") as f:
                payload = f.read()
        except OSError as e:
            self._miss(key, "corrupt", f"payload unreadable: {e}")
            return None
        if hashlib.sha256(payload).hexdigest() != meta.get("payload_sha256"):
            self._miss(key, "corrupt", "payload bytes do not match meta "
                                       "(torn or tampered)")
            return None
        try:
            fn = self.load_fn(payload, meta["format"])
        except Exception as e:  # noqa: BLE001 — deserialize failures of a
            #   stale/foreign payload must degrade to compile, never crash
            #   a starting worker; the miss is metered and logged
            self._miss(key, "corrupt", f"deserialize failed: {e!r}")
            return None
        self.metrics.count("aot.store.hit")
        return fn, meta

    # -- summary ------------------------------------------------------------

    def stats(self) -> Dict[str, float]:
        """Hit/miss counters (this process, this registry)."""
        snap = self.metrics.snapshot().get("counters", {})
        return {k: v for k, v in snap.items() if k.startswith("aot.store.")}
