"""Streaming ingestion engine — chunked prefetch with host-to-device overlap.

Harp's input story starts at ``MultiFileInputFormat`` + the MTReader pool;
:mod:`loaders` already ports the whole-files-per-worker load, but it still
materializes every byte before the first device op runs.  This module turns a
part-file set into a BOUNDED chunk stream instead:

* **reader pool** — the existing :class:`sched.dynamic.DynamicScheduler`
  (native parser underneath, GIL released) parses part-files concurrently
  into a bounded output queue; a slow consumer backpressures the pool, so
  parsed-but-unconsumed data never exceeds ``queue_depth`` files plus one
  in-flight file per thread.
* **chunker** — a reorder stage restores strict path order (determinism: the
  chunk sequence is independent of thread count and completion order) and
  re-slices files into fixed-row-budget :class:`Chunk` s, each carrying its
  global row offset and valid-row count.  Fixed shapes mean ONE compiled
  program downstream, never a retrace per ragged tail.
* **prefetch** — :class:`DevicePrefetcher` double-buffers ``device_put``:
  chunk N+1's parse + H2D transfer overlaps chunk N's compute (the DrJAX-
  style unbounded-stream discipline, PAPERS.md arXiv:2403.07128).
* **distributed COO→CSR** — :func:`regroup_coo_device` routes nonzeros to
  their owning worker through the SAME chunk-bounded ``all_to_all`` schedule
  the reshard engine proved out (``collectives/reshard.py``, ≤ 1 MiB per
  round; jaxlint pins the ``ingest_coo_regroup`` trace target), then the
  native counting-sort CSR build runs per worker — replacing the whole-table
  host shuffle of ``loaders.regroup_coo_by_row`` for multi-worker loads.

Every stage (list/count/read/parse/chunk/regroup/H2D/compute) runs under a
:class:`utils.metrics.Metrics` timer and is flushed to the telemetry step
log as ``kind: "timing"`` events via :func:`flush_stage_timings`.
"""

from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
from typing import Callable, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from harp_tpu.io import loaders
from harp_tpu.sched.dynamic import DynamicScheduler, Task
from harp_tpu.utils.metrics import Metrics

#: Stage names every timer in this module uses; flush_stage_timings and the
#: bench ingestion-stage table iterate this list.
STAGES = ("ingest.list", "ingest.count", "ingest.read", "ingest.parse",
          "ingest.chunk", "ingest.regroup", "ingest.h2d", "ingest.compute")


@dataclasses.dataclass
class Chunk:
    """One fixed-budget slice of the stream.

    ``data`` is ``(budget, cols)`` — always the FULL budget shape (the tail
    chunk is zero-padded) so every downstream program compiles once.
    ``rows`` counts the valid leading rows; ``offset`` is the global row
    index of ``data[0]`` across the whole part-file set, in path order.
    """

    index: int
    offset: int
    rows: int
    data: object              # np.ndarray host-side; jax.Array after H2D
    nbytes: int


def _read_part(path: str, sep: str, metrics: Metrics) -> np.ndarray:
    """Parse one part-file to a (rows, cols) f32 array, timing the remote
    byte fetch (``ingest.read``) separately from tokenization
    (``ingest.parse``); local files mmap, so read rides the parse timer."""
    if loaders._is_url(path):
        import io as _io

        with metrics.timer("ingest.read"):
            with loaders._fsspec_open(path) as f:
                raw = f.read()
        with metrics.timer("ingest.parse"):
            return np.loadtxt(_io.BytesIO(raw), delimiter=sep,
                              dtype=np.float32, ndmin=2)
    with metrics.timer("ingest.parse"):
        return loaders.load_dense_csv_one(path, sep)


class StreamLoader:
    """Bounded-queue chunk stream over a part-file set.

    Iterating yields :class:`Chunk` s in deterministic path order.  The
    reader pool runs at most ``queue_depth`` parsed files ahead of the
    consumer (DynamicScheduler ``out_capacity`` backpressure), so memory
    stays flat no matter how far the disk outruns the device.

    ``count=True`` (local + native only) runs the cheap native counting
    pass up front, filling :attr:`total_rows` / :attr:`num_cols` — the
    stream-fed K-means path needs the total to size its device block.
    """

    def __init__(self, paths: Sequence[str], *, chunk_rows: int = 65536,
                 sep: str = ",", num_threads: int = 4, queue_depth: int = 4,
                 count: bool = True, serial: bool = False,
                 metrics: Optional[Metrics] = None):
        self.paths = list(paths)
        if not self.paths:
            raise FileNotFoundError(
                "StreamLoader: no input files (empty path list)")
        if chunk_rows <= 0:
            raise ValueError(f"chunk_rows must be positive, got {chunk_rows}")
        self.chunk_rows = int(chunk_rows)
        self.sep = sep
        self.num_threads = max(1, int(num_threads))
        self.queue_depth = max(1, int(queue_depth))
        # serial=True: no reader pool, no readahead — every part parses on
        # the CONSUMER thread when its rows are demanded.  This is the
        # prefetch-off twin the overlap bench measures against.
        self.serial = bool(serial)
        self.metrics = metrics if metrics is not None else Metrics()
        self.total_rows: Optional[int] = None
        self.num_cols: Optional[int] = None
        if count:
            self._count_pass()

    def _count_pass(self) -> None:
        from harp_tpu.io import native_bridge

        if any(loaders._is_url(p) for p in self.paths) \
                or not native_bridge.available():
            return
        with self.metrics.timer("ingest.count"):
            shapes = [native_bridge.count_csv(p, self.sep)
                      for p in self.paths]
        if any(s is None for s in shapes):
            return
        widths = {c for r, c in shapes if r > 0}
        if len(widths) > 1:
            raise ValueError(
                f"part files disagree on column count: {sorted(widths)}")
        self.total_rows = sum(r for r, _ in shapes)
        self.num_cols = widths.pop() if widths else 0

    def __iter__(self) -> Iterator[Chunk]:
        return self.chunks()

    def chunks(self) -> Iterator[Chunk]:
        """Generator over fixed-budget chunks.  Runs on the CALLER's thread:
        pulling the next chunk is what grants the reader pool room to run
        ahead (bounded by ``queue_depth``)."""
        source = (self._serial_arrays() if self.serial
                  else self._pooled_arrays())
        return self._slice(source)

    def _serial_arrays(self) -> Iterator[np.ndarray]:
        for path in self.paths:
            yield _read_part(path, self.sep, self.metrics)

    def _pooled_arrays(self) -> Iterator[np.ndarray]:
        """Path-order arrays from the bounded reader pool: completion order
        is nondeterministic, so a reorder buffer restores path order (the
        chunk stream must be byte-identical at any thread count)."""
        sep, metrics = self.sep, self.metrics

        class _ParseTask(Task[Tuple[int, str], Tuple[int, np.ndarray]]):
            def run(self, item):
                idx, path = item
                return idx, _read_part(path, sep, metrics)

        sched = DynamicScheduler(
            [_ParseTask() for _ in
             range(min(self.num_threads, len(self.paths)))],
            out_capacity=self.queue_depth)
        self._sched = sched           # introspection seam (backpressure test)
        sched.start()
        sched.submit_all(enumerate(self.paths))
        pending: dict = {}
        try:
            for next_idx in range(len(self.paths)):
                while next_idx not in pending:
                    idx, arr = sched.wait_for_output()
                    pending[idx] = arr
                yield pending.pop(next_idx)
        finally:
            sched.stop()

    def _slice(self, arrays: Iterable[np.ndarray]) -> Iterator[Chunk]:
        budget = self.chunk_rows
        parts: List[np.ndarray] = []     # parsed rows not yet emitted
        have = 0
        cols: Optional[int] = None
        index = 0
        offset = 0

        def _fill(out: np.ndarray, want: int) -> None:
            filled = 0
            while filled < want:
                head = parts[0]
                take = min(len(head), want - filled)
                out[filled:filled + take] = head[:take]
                if take == len(head):
                    parts.pop(0)
                else:
                    parts[0] = head[take:]
                filled += take

        for arr in arrays:
            if not len(arr):
                continue
            if cols is None:
                cols = arr.shape[1]
            elif arr.shape[1] != cols:
                raise ValueError(
                    f"part files disagree on column count: "
                    f"[{cols}, {arr.shape[1]}]")
            parts.append(arr)
            have += len(arr)
            while have >= budget:
                with self.metrics.timer("ingest.chunk"):
                    out = np.empty((budget, cols), np.float32)
                    _fill(out, budget)
                have -= budget
                yield Chunk(index, offset, budget, out, out.nbytes)
                offset += budget
                index += 1
        if have:
            with self.metrics.timer("ingest.chunk"):
                out = np.zeros((budget, cols), np.float32)
                _fill(out, have)
            yield Chunk(index, offset, have, out, have * cols * 4)


class _PrefetchDone:
    pass


class _PrefetchError:
    def __init__(self, error: BaseException):
        self.error = error


class DevicePrefetcher:
    """Double-buffered H2D stage: a background thread pulls host chunks and
    ``device_put`` s them into a bounded queue, so chunk N+1's parse + H2D
    transfer overlaps chunk N's compute on the consumer thread.

    ``place`` maps a host ``(budget, cols)`` array to its device residence
    (e.g. ``session.replicate_put`` for the stream-fed fit, or
    ``session.scatter`` for row-sharded minibatches).  ``enabled=False`` is
    the serialized twin the overlap bench compares against: same code path,
    placement happens inline on the consumer thread.
    """

    def __init__(self, chunks: Iterable[Chunk], place: Callable,
                 *, depth: int = 2, enabled: bool = True,
                 metrics: Optional[Metrics] = None):
        self._place = place
        self._metrics = metrics if metrics is not None else Metrics()
        self._enabled = bool(enabled)
        self._done = False
        if self._enabled:
            self._stop = threading.Event()
            self._q: "queue.Queue[object]" = queue.Queue(
                maxsize=max(1, int(depth)))
            self._src = iter(chunks)
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()
        else:
            self._it = iter(chunks)

    def _to_device(self, ch: Chunk) -> Chunk:
        import jax

        with self._metrics.timer("ingest.h2d"):
            dev = self._place(ch.data)
            jax.block_until_ready(dev)
        return dataclasses.replace(ch, data=dev)

    def _run(self) -> None:
        try:
            for ch in self._src:
                item: object = self._to_device(ch)
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
            self._q.put(_PrefetchDone())
        except BaseException as e:      # noqa: BLE001 — envelope to consumer
            try:
                self._q.put(_PrefetchError(e), timeout=1.0)
            except queue.Full:
                pass

    def __iter__(self) -> "DevicePrefetcher":
        return self

    def __next__(self) -> Chunk:
        if self._done:
            raise StopIteration
        if not self._enabled:
            try:
                return self._to_device(next(self._it))
            except StopIteration:
                self._done = True
                raise
        got = self._q.get()
        if isinstance(got, _PrefetchDone):
            self._done = True
            raise StopIteration
        if isinstance(got, _PrefetchError):
            self._done = True
            raise got.error
        return got

    def close(self) -> None:
        """Stop the background thread (early-exit consumers)."""
        if not self._enabled:
            return
        self._stop.set()
        try:
            self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)


def flush_stage_timings(metrics: Metrics, extra: Optional[dict] = None
                        ) -> None:
    """Emit one ``kind: "timing"`` telemetry event per ingestion stage that
    recorded samples (no-op when telemetry is off, like every host-boundary
    emitter)."""
    from harp_tpu import telemetry

    for stage in STAGES:
        if metrics.timing(stage).get("count"):
            telemetry.record_timing(stage, metrics=metrics, extra=extra)


# --------------------------------------------------------------------------- #
# Stream-fed assembly (the bitwise-parity seam for KMeans.fit_from_stream)
# --------------------------------------------------------------------------- #

def assemble_stream(session, chunks: Iterable[Chunk], total_rows: int,
                    padded_cols: int, dtype="float32", *,
                    metrics: Optional[Metrics] = None):
    """Stream chunks into ONE row-sharded device block of ``total_rows``
    rows (feature-padded to ``padded_cols``), exactly as
    ``KMeans.prepare`` would have placed the same data loaded in memory —
    the returned buffer is BITWISE-identical to ``session.scatter`` of the
    padded in-memory array, so running the unchanged fit program on it is
    bitwise-equal to the in-memory fit.

    One donated scatter program compiles per (budget, cols) shape; each
    chunk's rows land at ``offset`` with rows past ``total_rows`` (or past
    the chunk's valid count) masked into a trash row.  H2D rides the
    ``ingest.h2d`` timer, the masked scatter the ``ingest.regroup`` one.
    """
    import jax
    import jax.numpy as jnp

    from harp_tpu.collectives import lax_ops

    metrics = metrics if metrics is not None else Metrics()
    w = session.num_workers
    if total_rows <= 0 or total_rows % w:
        raise ValueError(f"total_rows {total_rows} must be a positive "
                         f"multiple of {w} workers (truncate at ingest)")
    if total_rows >= 2 ** 31:
        raise ValueError("row offsets are int32 on device (x64 disabled)")
    local_n = total_rows // w
    out_dtype = jnp.dtype(dtype)
    buf = session.scatter(jnp.zeros((total_rows, padded_cols), out_dtype))
    it = iter(chunks)
    first = next(it, None)
    if first is None:
        return buf
    budget, cols = np.shape(first.data)
    if cols > padded_cols:
        raise ValueError(f"chunk has {cols} cols, block holds {padded_cols}")

    def prog(local, chunk, off, nvalid):
        # identical value path to prepare(): zero-pad features, then convert
        # to the storage dtype (XLA convert == jnp.asarray's convert)
        chunk = jnp.pad(chunk, ((0, 0), (0, padded_cols - cols)))
        chunk = chunk.astype(out_dtype)
        pos = off + jnp.arange(budget, dtype=jnp.int32) \
            - lax_ops.worker_id() * local_n
        valid = ((jnp.arange(budget) < nvalid)
                 & (pos >= 0) & (pos < local_n))
        posc = jnp.where(valid, pos, local_n)     # trash row
        ext = jnp.concatenate(
            [local, jnp.zeros((1, padded_cols), local.dtype)], axis=0)
        return ext.at[posc].set(chunk)[:local_n]

    place = session.spmd(
        prog,
        in_specs=(session.shard(), session.replicate(),
                  session.replicate(), session.replicate()),
        out_specs=session.shard(),
        donate_argnums=(0,))
    for ch in itertools.chain([first], it):
        if isinstance(ch.data, jax.Array):
            dev = ch.data             # a DevicePrefetcher already placed it
        else:
            with metrics.timer("ingest.h2d"):
                dev = session.replicate_put(
                    np.asarray(ch.data, np.float32))
                jax.block_until_ready(dev)
        with metrics.timer("ingest.regroup"):
            buf = place(buf, dev, np.int32(ch.offset), np.int32(ch.rows))
    jax.block_until_ready(buf)
    return buf


# --------------------------------------------------------------------------- #
# Distributed COO -> CSR (device regroup + native per-worker counting sort)
# --------------------------------------------------------------------------- #

def pack_coo(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray
             ) -> np.ndarray:
    """Pack (row i64, col i64, val f32) into (n, 5) int32 records — the
    fixed 20 B wire row the regroup all_to_all moves.  Pure bit reinterpret
    (numpy views), exact round-trip through :func:`unpack_coo`."""
    n = len(rows)
    rec = np.empty((n, 5), np.int32)
    rec[:, 0:2] = np.ascontiguousarray(rows, np.int64).view(
        np.int32).reshape(n, 2)
    rec[:, 2:4] = np.ascontiguousarray(cols, np.int64).view(
        np.int32).reshape(n, 2)
    rec[:, 4] = np.ascontiguousarray(vals, np.float32).view(np.int32)
    return rec


def unpack_coo(rec: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    rec = np.ascontiguousarray(rec, np.int32)
    rows = np.ascontiguousarray(rec[:, 0:2]).view(np.int64).reshape(-1)
    cols = np.ascontiguousarray(rec[:, 2:4]).view(np.int64).reshape(-1)
    vals = np.ascontiguousarray(rec[:, 4]).view(np.float32)
    return rows, cols, vals


def regroup_coo_device(session, rows: np.ndarray, cols: np.ndarray,
                       vals: np.ndarray, *, num_rows: Optional[int] = None,
                       chunk_bytes: Optional[int] = None,
                       metrics: Optional[Metrics] = None
                       ) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Route nonzeros to their row-block owner ON DEVICE: packed 20 B
    records ride the reshard engine's chunk-bounded per-round ``all_to_all``
    (≤ ``chunk_bytes`` of foreign rows per round — the jaxlint-pinned
    ``ingest_coo_regroup`` budget), replacing the whole-table host shuffle
    of ``loaders.regroup_coo_by_row`` for multi-worker loads.

    Returns per-worker (rows, cols, vals) triples — each worker's slice is
    exactly the host oracle's, nnz for nnz, in global parse order.
    """
    from harp_tpu.collectives import reshard as rs

    metrics = metrics if metrics is not None else Metrics()
    w = session.num_workers
    rows = np.asarray(rows, np.int64)
    if num_rows is None:
        num_rows = int(rows.max()) + 1 if rows.size else w
    if not rows.size:
        e = (np.empty(0, np.int64), np.empty(0, np.int64),
             np.empty(0, np.float32))
        return [e for _ in range(w)]
    plan, counts, cap = rs.plan_coo_regroup(
        rows, num_rows, w,
        chunk_bytes=(rs.DEFAULT_CHUNK_BYTES if chunk_bytes is None
                     else chunk_bytes))
    rec = pack_coo(rows, cols, vals)
    fill = session.scatter(np.zeros((w * cap, 5), np.int32))
    with metrics.timer("ingest.regroup"):
        fn, args = rs.prepare_reshard(session, rec, plan, fill)
        moved = np.asarray(fn(*args))
    out = []
    for wi in range(w):
        got = unpack_coo(moved[wi * cap: wi * cap + int(counts[wi])])
        out.append(got)
    return out


def coo_to_csr_distributed(session, rows: np.ndarray, cols: np.ndarray,
                           vals: np.ndarray, *,
                           num_rows: Optional[int] = None,
                           chunk_bytes: Optional[int] = None,
                           metrics: Optional[Metrics] = None
                           ) -> List[Tuple[np.ndarray, np.ndarray,
                                           np.ndarray]]:
    """End-to-end distributed COO→CSR: device regroup to row-block owners,
    then the native counting-sort CSR build per worker over LOCAL row ids.
    Worker ``w`` owns global rows ``[w*block, min((w+1)*block, num_rows))``
    with ``block = ceil(num_rows / W)``; its (indptr, indices, values)
    covers that block with row 0 = its first global row."""
    w = session.num_workers
    rows = np.asarray(rows, np.int64)
    if num_rows is None:
        num_rows = int(rows.max()) + 1 if rows.size else w
    block = -(-max(int(num_rows), 1) // w)
    grouped = regroup_coo_device(session, rows, cols, vals,
                                 num_rows=num_rows, chunk_bytes=chunk_bytes,
                                 metrics=metrics)
    out = []
    for wi, (r, c, v) in enumerate(grouped):
        local_rows = min(block, max(0, int(num_rows) - wi * block))
        out.append(loaders.coo_to_csr(r - wi * block, c, v,
                                      num_rows=max(local_rows, 0)))
    return out
