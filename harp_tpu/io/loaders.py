"""Input pipeline — whole-files-per-worker loading, TPU-native.

Reference parity: Harp's ``MultiFileInputFormat``/``MultiFileSplit`` (one split = a
list of whole files per worker; fileformat/ in harp-daal-interface, duplicated in
ml/java and contrib) and ``HarpDAALDataSource`` (datasource/HarpDAALDataSource.java:64)
which read dense CSV / COO / CSR with a multithreaded reader pool (MTReader).

TPU-native: files are assigned to workers by the same contiguous-split rule, read by
a host thread pool (sched.dynamic.DynamicScheduler — the MTReader equivalent), and
the resulting host arrays are placed sharded on the mesh via HarpSession.scatter.
A native C++ fast path for CSV/COO parsing lives in harp_tpu/native (see
native/loader.cpp); this module transparently uses it when built.

Remote object stores (the HDFS role): every reference byte rode HDFS
(HarpDAALDataSource.java:64; third_party/hdfs shipped libhdfs to each worker
— SURVEY §2.5 maps this to a GCS/posix seam). Here any path containing a
``://`` scheme (``gs://``, ``s3://``, ``memory://``, ``file://``) routes
through :mod:`fsspec`; plain local paths keep the native C++ fast path. The
reader thread pool is scheme-agnostic, so remote part-files overlap their
downloads exactly like the reference's MTReader over libhdfs. Use
:func:`list_files` for directory/glob expansion on either kind of path.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence, Tuple

import numpy as np

from harp_tpu.sched.dynamic import DynamicScheduler, Task


def _is_url(path: str) -> bool:
    return "://" in path


def _fsspec(path: str):
    try:
        import fsspec
    except ImportError as e:          # pragma: no cover — baked in this image
        raise ImportError(
            f"reading {path!r} needs fsspec (remote-store seam; local paths "
            f"work without it)") from e
    return fsspec


def _fsspec_open(path: str, mode: str = "rb"):
    return _fsspec(path).open(path, mode)


def _hidden(path: str) -> bool:
    """Hadoop input-format convention: basenames starting with ``_`` or
    ``.`` are metadata (``_SUCCESS``, ``_README``, ``.crc``), not data."""
    base = os.path.basename(path.rstrip("/"))
    return base.startswith(("_", "."))


def list_files(spec: str) -> List[str]:
    """Expand a path/glob/directory into concrete file paths, local or remote.

    The HDFS-directory-of-part-files idiom: ``list_files("gs://b/data/")``
    or ``list_files("gs://b/data/part-*")`` returns sorted member files with
    the scheme re-attached, ready for :func:`load_dense_csv`/`load_coo`.
    """
    if _is_url(spec):
        fs, path = _fsspec(spec).core.url_to_fs(spec)
        if fs.isdir(path):
            # detail=True: one listing RPC, not one isdir stat per entry
            entries = fs.ls(path, detail=True)
        elif "*" not in path and "?" not in path and fs.exists(path):
            return [spec]        # an explicitly named file is never hidden
        else:
            # fs.glob(detail=True) only exists on recent fsspec (ADVICE r4);
            # plain glob + per-entry info keeps older releases working
            try:
                got = fs.glob(path, detail=True)
            except TypeError:
                got = None
            if isinstance(got, dict):
                entries = got.values()
            else:
                entries = [fs.info(n) for n in (got if got is not None
                                                else fs.glob(path))]
        names = [e["name"] for e in entries if e.get("type") != "directory"
                 and not _hidden(e["name"])]
        return sorted(fs.unstrip_protocol(n) for n in names)
    import glob as _glob

    if os.path.isfile(spec):
        return [spec]            # an explicitly named file is never hidden
    if os.path.isdir(spec):
        return sorted(os.path.join(spec, n) for n in os.listdir(spec)
                      if os.path.isfile(os.path.join(spec, n))
                      and not _hidden(n))
    return sorted(p for p in _glob.glob(spec) if not _hidden(p))


def split_files(paths: Sequence[str], num_workers: int) -> List[List[str]]:
    """MultiFileInputFormat semantics: contiguous whole-file groups per worker."""
    paths = sorted(paths)
    out: List[List[str]] = [[] for _ in range(num_workers)]
    base, extra = divmod(len(paths), num_workers)
    i = 0
    for w in range(num_workers):
        n = base + (1 if w < extra else 0)
        out[w] = list(paths[i:i + n])
        i += n
    return out


def load_dense_csv_one(path: str, sep: str = ",") -> np.ndarray:
    if _is_url(path):
        with _fsspec_open(path) as f:
            return np.loadtxt(f, delimiter=sep, dtype=np.float32, ndmin=2)
    from harp_tpu.io import native_bridge

    arr = native_bridge.parse_csv(path, sep)
    if arr is not None:
        return arr
    return np.loadtxt(path, delimiter=sep, dtype=np.float32, ndmin=2)


def truncate_to_workers(arr: np.ndarray, num_workers: int) -> np.ndarray:
    """Trim leading-axis length to a worker multiple (the load-then-shard
    idiom every file-input CLI path uses)."""
    n = len(arr) - len(arr) % num_workers
    if n == 0:
        raise ValueError(
            f"{len(arr)} rows cannot shard over {num_workers} workers "
            f"(need at least one row per worker)")
    return arr[:n]


def _assemble_rows(parts: List[Optional[np.ndarray]], dtype) -> np.ndarray:
    """Concatenate-without-the-2x-copy: preallocate the output from summed
    row counts and copy each part into its slice, releasing parts as they
    are consumed — peak memory is total + one part, not two full copies
    (the GB-scale complaint against ``np.concatenate``)."""
    kept = [p for p in parts if p is not None]
    if not kept:
        raise ValueError("need at least one array to assemble")
    widths = {p.shape[1] for p in kept if len(p)} or {kept[0].shape[1]}
    if len(widths) > 1:
        raise ValueError(
            f"part files disagree on column count: {sorted(widths)}")
    total = sum(len(p) for p in kept)
    out = np.empty((total, widths.pop()), dtype)
    off = 0
    for i, p in enumerate(kept):
        out[off:off + len(p)] = p
        off += len(p)
        kept[i] = None            # free each part as soon as it is copied
    return out


def _load_dense_csv_prealloc(paths: List[str], num_threads: int,
                             sep: str) -> Optional[np.ndarray]:
    """Zero-extra-copy dense load: a native counting pass sizes ONE
    (total_rows, cols) block up front, then the reader pool parses every
    file directly into its row-offset view (native_bridge.parse_csv_into —
    the parse-into-caller-buffer entry point). None when any file defeats
    the native counter; the caller falls back to the per-file path."""
    from harp_tpu.io import native_bridge

    shapes = [native_bridge.count_csv(p, sep) for p in paths]
    if any(s is None for s in shapes):
        return None
    widths = {c for r, c in shapes if r > 0}
    if len(widths) > 1:
        raise ValueError(
            f"part files disagree on column count: {sorted(widths)}")
    total = sum(r for r, _ in shapes)
    out = np.empty((total, widths.pop() if widths else 0), np.float32)
    offsets = np.concatenate([[0], np.cumsum([r for r, _ in shapes])])

    class _ParseIntoTask(Task[Tuple[int, str], Tuple[int, int]]):
        def run(self, item):
            idx, path = item
            nrows = shapes[idx][0]
            view = out[offsets[idx]:offsets[idx] + nrows]
            if nrows and not native_bridge.parse_csv_into(path, view, sep):
                # file changed between count and parse (or ragged): redo via
                # the robust single-file loader and shape-check the result
                arr = np.loadtxt(path, delimiter=sep, dtype=np.float32,
                                 ndmin=2)
                if arr.shape != view.shape:
                    raise ValueError(
                        f"{path}: shape changed during load "
                        f"({view.shape} counted, {arr.shape} parsed)")
                view[:] = arr
            return idx, nrows

    sched = DynamicScheduler(
        [_ParseIntoTask() for _ in range(min(num_threads, len(paths)))])
    sched.start()
    sched.submit_all(enumerate(paths))
    try:
        sched.drain()
    finally:
        sched.stop()
    return out


def load_dense_csv(paths: Sequence[str], num_threads: int = 4,
                   sep: str = ",") -> np.ndarray:
    """Multithreaded dense CSV load (HarpDAALDataSource.createDenseNumericTable:76).

    Returns the row-concatenation of all files, in path order.

    GB-scale memory: with the native parser built and all paths local, a
    counting pass preallocates the full (total_rows, cols) block and each
    file parses directly into its row-offset view — no per-file
    intermediates and no extra full-dataset copy. Otherwise per-file
    arrays are assembled into one preallocated output with each part
    released as it is copied (peak = total + one part, not 2x total).
    """
    paths = list(paths)
    if not paths:
        raise FileNotFoundError(
            "load_dense_csv: no input files (empty path list — check the "
            "path/glob; note _/.-prefixed basenames are skipped as hidden)")
    from harp_tpu.io import native_bridge

    if native_bridge.available() and not any(_is_url(p) for p in paths):
        got = _load_dense_csv_prealloc(paths, num_threads, sep)
        if got is not None:
            return got
    results: List[Optional[np.ndarray]] = [None] * len(paths)

    class _ReadTask(Task[Tuple[int, str], Tuple[int, np.ndarray]]):
        """ReadDenseCSVTask equivalent (datasource/ReadDenseCSVTask.java)."""

        def run(self, item):
            idx, path = item          # indexed item: duplicate paths stay
            return idx, load_dense_csv_one(path, sep)

    sched = DynamicScheduler([_ReadTask() for _ in range(num_threads)])
    sched.start()
    sched.submit_all(enumerate(paths))
    for idx, arr in sched.drain():
        results[idx] = arr
    sched.stop()
    return _assemble_rows(results, np.float32)


def _load_coo_one(path: str, sep: str
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    if _is_url(path):
        with _fsspec_open(path) as f:
            m = np.loadtxt(f, delimiter=None if sep == " " else sep, ndmin=2)
        return (m[:, 0].astype(np.int64), m[:, 1].astype(np.int64),
                m[:, 2].astype(np.float32))
    from harp_tpu.io import native_bridge

    triple = native_bridge.parse_coo(path, sep)
    if triple is None:
        m = np.loadtxt(path, delimiter=None if sep == " " else sep, ndmin=2)
        triple = (m[:, 0].astype(np.int64), m[:, 1].astype(np.int64),
                  m[:, 2].astype(np.float32))
    return triple


def _load_coo_prealloc(paths: List[str], sep: str, num_threads: int
                       ) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Zero-extra-copy COO load: native line counts size the three output
    arrays once; each file parses into its offset views
    (native_bridge.parse_coo_into). None when counting isn't possible."""
    from harp_tpu.io import native_bridge

    counts = [native_bridge.count_lines(p) for p in paths]
    if any(c is None for c in counts):
        return None
    offsets = np.concatenate([[0], np.cumsum(counts)])
    total = int(offsets[-1])
    rows = np.empty(total, np.int64)
    cols = np.empty(total, np.int64)
    vals = np.empty(total, np.float32)

    class _ParseCOOIntoTask(Task[Tuple[int, str], Tuple[int, int]]):
        def run(self, item):
            idx, path = item
            lo, hi = offsets[idx], offsets[idx + 1]
            if hi > lo and not native_bridge.parse_coo_into(
                    path, rows[lo:hi], cols[lo:hi], vals[lo:hi]):
                r, c, v = _load_coo_one(path, sep)
                if len(r) != hi - lo:
                    raise ValueError(
                        f"{path}: line count changed during load "
                        f"({hi - lo} counted, {len(r)} parsed)")
                rows[lo:hi], cols[lo:hi], vals[lo:hi] = r, c, v
            return idx, int(hi - lo)

    sched = DynamicScheduler(
        [_ParseCOOIntoTask() for _ in range(min(num_threads, len(paths)))])
    sched.start()
    sched.submit_all(enumerate(paths))
    try:
        sched.drain()
    finally:
        sched.stop()
    return rows, cols, vals


def load_coo(paths: Sequence[str], sep: str = " ", num_threads: int = 4
             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """COO triple load (HarpDAALDataSource.loadCOOFiles:317): each line
    ``row col value``. Returns (rows, cols, vals), concatenated in path
    order. Files are read by the MTReader-equivalent thread pool — ctypes
    releases the GIL, so the native per-file parsers genuinely overlap.

    Like :func:`load_dense_csv`, the native path preallocates the three
    output arrays from summed per-file line counts and parses into offset
    views — no per-file intermediates, no extra full copy."""
    paths = list(paths)
    if not paths:
        raise FileNotFoundError(
            "load_coo: no input files (empty path list — check the "
            "path/glob; note _/.-prefixed basenames are skipped as hidden)")
    from harp_tpu.io import native_bridge

    if (native_bridge.available() and sep in (" ", "\t")
            and not any(_is_url(p) for p in paths)):
        got = _load_coo_prealloc(paths, sep, num_threads)
        if got is not None:
            return got
    results: List[Optional[Tuple]] = [None] * len(paths)

    class _ReadCOOTask(Task[Tuple[int, str], Tuple[int, Tuple]]):
        """ReadCOOTask equivalent (datasource/ReadCOOTask.java)."""

        def run(self, item):
            idx, path = item          # indexed item: duplicate paths stay
            return idx, _load_coo_one(path, sep)

    sched = DynamicScheduler(
        [_ReadCOOTask() for _ in range(min(num_threads, max(len(paths), 1)))])
    sched.start()
    sched.submit_all(enumerate(paths))
    for idx, triple in sched.drain():
        results[idx] = triple
    sched.stop()
    got = [r for r in results if r is not None]
    total = sum(len(t[0]) for t in got)
    rows = np.empty(total, np.int64)
    cols = np.empty(total, np.int64)
    vals = np.empty(total, np.float32)
    off = 0
    for i, (r, c, v) in enumerate(got):
        rows[off:off + len(r)] = r
        cols[off:off + len(r)] = c
        vals[off:off + len(r)] = v
        off += len(r)
        got[i] = None             # free each part as soon as it is copied
    return rows, cols, vals


def coo_to_csr(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
               num_rows: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """COO→CSR conversion (HarpDAALDataSource.COOToCSR:439).

    Returns (indptr[num_rows+1], indices, values) with rows sorted ascending
    and each row's entries in input order (STABLE — duplicate semantics
    upstream rely on it). Uses the native parallel counting sort
    (O(nnz + rows), threaded) when libharp_native is built; numpy stable
    argsort otherwise.
    """
    if num_rows is None:
        num_rows = int(rows.max()) + 1 if rows.size else 0
    if rows.size and (rows.min() < 0 or rows.max() >= num_rows):
        # the numpy fallback would otherwise wrap negatives into indptr[0]
        # silently; validate up front on BOTH paths
        raise ValueError(f"row ids must be in [0, {num_rows}); got "
                         f"[{rows.min()}, {rows.max()}]")
    vals = np.asarray(vals, np.float32)   # one output dtype on both paths
    if rows.size:
        from harp_tpu.io import native_bridge

        native = native_bridge.coo_to_csr(rows, cols, vals, num_rows)
        if native is not None:
            return native
    order = np.argsort(rows, kind="stable")
    rows, cols, vals = rows[order], cols[order], vals[order]
    indptr = np.zeros(num_rows + 1, dtype=np.int64)
    # bincount is a single vectorized counting pass; np.add.at's buffered
    # fancy-index path is ~10x slower at large nnz. Row range was validated
    # above, so minlength pins the length exactly.
    indptr[1:] = np.bincount(rows, minlength=num_rows)
    np.cumsum(indptr, out=indptr)
    return indptr, cols.astype(np.int64), vals


def regroup_coo_by_row(rows, cols, vals, num_workers: int):
    """Distributed COO regroup (HarpDAALDataSource.regroupCOOList:399): route each
    nonzero to the worker owning its row block, returning per-worker COO triples.

    The reference did this with a Harp regroup collective over the network; here the
    host pre-shuffles (cheap) and the device pipeline receives balanced blocks —
    variable-split all_to_all on TPU would force worst-case padding (SURVEY §7).
    """
    num_rows = int(rows.max()) + 1 if rows.size else num_workers
    block = -(-num_rows // num_workers)
    owner = np.minimum(rows // block, num_workers - 1)
    out = []
    for w in range(num_workers):
        m = owner == w
        out.append((rows[m], cols[m], vals[m]))
    return out


def load_corpus(spec: str, num_threads: int = 4) -> np.ndarray:
    """Rectangular token-id corpus: one document per line, space-separated
    integer token ids, every line the SAME length (the fixture/bench format
    — LDA's blocked layout takes a dense (D, L) token matrix; see
    datasets/lda/). ``spec`` may be a file, directory, or glob, local or
    remote (list_files). Parts read through the same MTReader-equivalent
    thread pool as load_dense_csv, so remote fsspec parts overlap their
    downloads instead of fetching serially."""
    paths = list_files(spec)
    if not paths:
        raise FileNotFoundError(f"no corpus files match {spec!r}")
    results: List[Optional[np.ndarray]] = [None] * len(paths)

    class _ReadCorpusTask(Task[Tuple[int, str], Tuple[int, np.ndarray]]):
        def run(self, item):
            idx, path = item
            if _is_url(path):
                with _fsspec_open(path) as f:
                    return idx, np.loadtxt(f, dtype=np.int64, ndmin=2)
            return idx, np.loadtxt(path, dtype=np.int64, ndmin=2)

    sched = DynamicScheduler(
        [_ReadCorpusTask() for _ in range(min(num_threads, len(paths)))])
    sched.start()
    sched.submit_all(enumerate(paths))
    for idx, arr in sched.drain():
        results[idx] = arr
    sched.stop()
    widths = {p.shape[1] for p in results if p is not None}
    if len(widths) > 1:
        raise ValueError(
            f"corpus files disagree on document length: {sorted(widths)} "
            f"(the dense token-matrix format needs one fixed length)")
    return _assemble_rows(results, np.int64)


def is_docword(path: str) -> bool:
    """Whether ``path`` is in the UCI bag-of-words ``docword.*.txt`` format:
    three header lines of one integer each (documents, words, nonzeros)."""
    with (_fsspec_open(path, "rt") if _is_url(path) else open(path)) as fh:
        head = [fh.readline().split() for _ in range(3)]
    return all(len(h) == 1 and h[0].isdigit() for h in head)


def load_docword(path: str) -> Tuple[np.ndarray, np.ndarray, int]:
    """A UCI bag-of-words corpus (``docword.nytimes.txt``, ``.pubmed``,
    ...): three header lines (D, W, NNZ), then ``docID wordID count``
    triplets with ids from 1. Returns the CSR corpus ``(doc_ptr (D + 1,)
    int64, words (N,) int32, W)``: each document's tokens back to back,
    word ids from 0, a word repeated by its count, in file order within a
    document."""
    with (_fsspec_open(path, "rt") if _is_url(path) else open(path)) as fh:
        d, w, nnz = (int(fh.readline()) for _ in range(3))
        triplets = np.loadtxt(fh, dtype=np.int64, ndmin=2)
    if triplets.shape != (nnz, 3):
        raise ValueError(f"{path}: header says {nnz} triplets, the file "
                         f"holds {triplets.shape[0]} rows of "
                         f"{triplets.shape[1:]}")
    doc, word, count = triplets.T
    if nnz and (doc.min() < 1 or doc.max() > d or word.min() < 1
                or word.max() > w or count.min() < 1):
        raise ValueError(f"{path}: ids outside 1..{d} / 1..{w} or a count "
                         f"below 1")
    order = np.argsort(doc, kind="stable")
    doc, word, count = doc[order], word[order], count[order]
    doc_ptr = np.zeros(d + 1, np.int64)
    np.cumsum(np.bincount(doc - 1, weights=count, minlength=d).astype(
        np.int64), out=doc_ptr[1:])
    return doc_ptr, np.repeat(word - 1, count).astype(np.int32), w


def load_labeled_csv(spec: str, num_threads: int = 4
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Dense CSV with the LABEL in the last column (the daal_svm/daal_naive
    fixture format): returns (x (N, D) f32, y (N,) int32)."""
    m = load_dense_csv(list_files(spec), num_threads=num_threads)
    if m.shape[1] < 2:
        raise ValueError("labeled CSV needs >= 2 columns (features, label)")
    return m[:, :-1], m[:, -1].astype(np.int32)
