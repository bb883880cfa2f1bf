"""Out-of-core corpora: sharded on-disk padded CSR + double-buffered prefetch.

The streaming engine consumes a corpus one column chunk at a time, but until
this layer existed the *corpus itself* had to be resident — ``column_block``
carved every chunk from a fully-loaded padded-CSR matrix, so the scale
ceiling was host RAM, not disk.  This module is the data-pipeline front end
that removes it, in the spirit of gensim's streamed-corpus online NMF and
Nguyen & Ho's limited-internal-memory distributed NMF (arXiv:1506.08938):

* :func:`write_corpus` spills an SpCSR / dense / scipy matrix to a sharded
  directory layout — one pre-carved column chunk per shard, each stored as
  a pair of ``.npy`` files (the padded-CSR ``values``/``cols`` grids) plus
  a ``meta.json`` manifest.  All chunks share one slot capacity (the max
  per-chunk row occupancy), so every chunk has the same (n, cap) array
  shape and the jitted online step compiles exactly once for the stream.
* :class:`MmapCorpus` opens that layout memory-mapped: ``load(i)`` returns
  the chunk as an ``SpCSR`` over ``np.load(..., mmap_mode="r")`` arrays,
  so the host touches one chunk's pages at a time, never O(corpus) bytes.
* :class:`ResidentChunks` / :class:`DenseChunks` give in-memory matrices
  the same ``ChunkSource`` face (shape / schedule / load), built on
  :class:`repro.sparse.ColumnSlicer` so carving the whole stream is
  O(nnz log nnz) once + O(chunk nnz) per chunk.
* :class:`Prefetcher` double-buffers the host side of the stream: a worker
  thread runs the chunk *packer* (mmap page-in + operand packing +
  ``device_put`` — for mesh runs the full per-device shard distribute) and
  parks results in a bounded queue, so chunk N+1's ingest and transfer
  ride under chunk N's in-flight ``online_als_step``.  Host memory is
  O(queue depth) chunks, never O(corpus); prefetch on/off run the *same*
  pack function on the same inputs, so results are bit-identical either
  way.

The estimator front door accepts a corpus directory path, an
:class:`MmapCorpus`, or any ``ChunkSource`` anywhere the ``streaming``
solver accepts a matrix (``EnforcedNMF.fit`` / the ``nmf_run --corpus-dir``
CLI).
"""
from __future__ import annotations

import dataclasses
import json
import os
import queue
import threading
import time
import warnings
import zlib
from pathlib import Path
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.robustness import faults
from repro.sparse.csr import ColumnSlicer, SpCSR, from_dense, from_scipy

__all__ = [
    "CORPUS_FORMAT", "ChunkPackError", "ChunkSource", "CorpusIntegrityError",
    "DenseChunks", "MmapCorpus", "PackedChunk", "Prefetcher",
    "ResidentChunks", "as_chunk_source", "chunk_schedule", "is_corpus_input",
    "open_corpus", "write_corpus",
]

#: manifest format tag; bump on incompatible layout changes.  v2 adds
#: per-shard crc32 checksums (``crc_values`` / ``crc_cols`` per chunk
#: entry), validated lazily on first load of each shard.
CORPUS_FORMAT = "repro-corpus-v2"
_FORMAT_V1 = "repro-corpus-v1"
_META = "meta.json"

#: set to "1" to turn unreadable / corrupt chunks into a warning + skip
#: instead of a hard failure (the stream then fits on the surviving
#: chunks — degraded results, but a live run)
SKIP_BAD_CHUNKS_ENV = "REPRO_STREAM_SKIP_BAD_CHUNKS"

#: host spans of the :class:`Prefetcher` in a profiler trace: each call of
#: the packer (on the worker thread, or inline with prefetch off), and the
#: consumer blocked on the worker's queue
PACK_SPAN = "nmf.stream.pack"
STALL_SPAN = "nmf.stream.stall"


class CorpusIntegrityError(RuntimeError):
    """A shard's bytes no longer match the checksum recorded when the
    corpus was written (bit rot, truncated copy, torn write)."""


class ChunkPackError(RuntimeError):
    """A chunk failed to pack after exhausting its retry budget.  Carries
    ``item`` (the scheduled work item — for corpus streams, the chunk
    index) and ``index`` (the item's position in the schedule); the
    original failure rides as ``__cause__``."""

    def __init__(self, message: str, item=None, index: Optional[int] = None):
        super().__init__(message)
        self.item = item
        self.index = index


def _crc_array(x) -> int:
    """crc32 of an array's raw bytes (C-contiguous view)."""
    return zlib.crc32(np.ascontiguousarray(x).view(np.uint8).reshape(-1))


def chunk_schedule(m: int, chunk_docs: int) -> List[Tuple[int, int]]:
    """The ``[lo, hi)`` column ranges a width-``chunk_docs`` stream visits
    over an ``m``-document corpus (final chunk ragged).  Writer, resident
    sources, and the on-disk manifest all derive from this one function, so
    "same chunk schedule" is a structural guarantee, not a convention."""
    if chunk_docs <= 0:
        raise ValueError(f"chunk_docs must be positive, got {chunk_docs}")
    return [(lo, min(lo + chunk_docs, m)) for lo in range(0, m, chunk_docs)]


# ---------------------------------------------------------------------------
# Chunk sources: one face over resident matrices and on-disk corpora
# ---------------------------------------------------------------------------

class ChunkSource:
    """Protocol: a replayable chunked view of an (n, m) corpus.

    * ``shape`` — global ``(n_terms, m_docs)``.
    * ``chunk_docs`` — nominal chunk width (final chunk may be ragged).
    * ``schedule`` — the ``[(lo, hi), ...]`` column ranges, in order.
    * ``load(i)`` — chunk ``i`` as a host operand (``SpCSR`` or dense)
      with columns rebased to ``[0, hi - lo)``.

    Replayability (``load`` by index, any number of times) is what lets the
    streaming fit make its second frozen-U fold-in pass and lets a paused /
    early-stopped stream leave no dangling state — a one-shot iterator
    cannot offer that; feed those through ``partial_fit`` directly.
    """

    shape: Tuple[int, int]
    chunk_docs: int

    @property
    def schedule(self) -> List[Tuple[int, int]]:
        return chunk_schedule(self.shape[1], self.chunk_docs)

    def __len__(self) -> int:
        return len(self.schedule)

    def load(self, i: int):
        raise NotImplementedError


class ResidentChunks(ChunkSource):
    """A resident ``SpCSR`` corpus as a ``ChunkSource``: one
    :class:`~repro.sparse.ColumnSlicer` index up front, then every chunk is
    an O(chunk nnz) carve at the shared per-schedule slot capacity — the
    same chunk arrays :func:`write_corpus` spills, so resident and
    streamed-from-disk fits see bit-identical operands."""

    def __init__(self, a: SpCSR, chunk_docs: int):
        self.shape = a.shape
        self.chunk_docs = int(chunk_docs)
        self._slicer = ColumnSlicer(a)
        self.cap = self._slicer.chunk_cap(self.schedule)

    def load(self, i: int) -> SpCSR:
        faults.fire("chunk-load", i)
        lo, hi = self.schedule[i]
        return self._slicer.block(lo, hi, cap=self.cap)


class DenseChunks(ChunkSource):
    """A resident dense matrix as a ``ChunkSource`` (column slices)."""

    def __init__(self, a, chunk_docs: int):
        self.shape = tuple(a.shape)
        self.chunk_docs = int(chunk_docs)
        self._a = a

    def load(self, i: int):
        lo, hi = self.schedule[i]
        return self._a[:, lo:hi]


class MmapCorpus(ChunkSource):
    """A :func:`write_corpus` directory, opened memory-mapped.

    ``load(i)`` wraps shard ``i``'s ``values``/``cols`` files with
    ``np.load(mmap_mode="r")`` — the OS pages in exactly the bytes the
    online step touches, so opening a corpus costs O(manifest) and
    streaming it costs O(chunk) resident bytes at a time.

    v2 corpora record a crc32 per shard file; ``load`` verifies each
    shard's bytes against it the *first* time the shard is read (later
    loads — the fold-in pass, a rollback replay — skip the re-hash) and
    raises :class:`CorpusIntegrityError` on mismatch.  v1 corpora load
    unchanged, with a one-time warning that they carry no checksums."""

    def __init__(self, path):
        self.path = Path(path)
        try:
            meta = json.loads((self.path / _META).read_text())
        except FileNotFoundError:
            raise FileNotFoundError(
                f"{self.path} is not a corpus directory (no {_META}); "
                "write one with repro.data.corpus.write_corpus") from None
        fmt = meta.get("format")
        if fmt not in (CORPUS_FORMAT, _FORMAT_V1):
            raise ValueError(
                f"{self.path / _META}: format {fmt!r} is not "
                f"{CORPUS_FORMAT!r} (or the legacy {_FORMAT_V1!r})")
        self.format = fmt
        self.shape = (int(meta["n"]), int(meta["m"]))
        self.chunk_docs = int(meta["chunk_docs"])
        self.cap = int(meta["cap"])
        self.dtype = np.dtype(meta["dtype"])
        self._chunks = meta["chunks"]
        #: per-shard [crc_values, crc_cols] pairs (None for v1 corpora) —
        #: also what the checkpoint fingerprint digests, so a resumed fit
        #: transitively pins the corpus *content*
        self.checksums = ([[c["crc_values"], c["crc_cols"]]
                           for c in self._chunks]
                          if fmt == CORPUS_FORMAT else None)
        self._validated: set = set()
        if self.checksums is None:
            warnings.warn(
                f"{self.path}: legacy {_FORMAT_V1} corpus carries no shard "
                "checksums; integrity cannot be verified (re-write with "
                "write_corpus to upgrade)", UserWarning)
        if [(c["lo"], c["hi"]) for c in self._chunks] != self.schedule:
            raise ValueError(
                f"{self.path / _META}: shard ranges disagree with the "
                f"chunk_docs={self.chunk_docs} schedule")

    def load(self, i: int) -> SpCSR:
        faults.fire("chunk-load", i)
        c = self._chunks[i]
        values = np.load(self.path / c["values"], mmap_mode="r")
        cols = np.load(self.path / c["cols"], mmap_mode="r")
        if faults.should_fire("corrupt-shard", i):
            # deterministic chaos: hand the validator a bit-flipped copy,
            # as if the shard rotted on disk
            values = np.array(values)
            values.view(np.uint8).reshape(-1)[0] ^= 0xFF
        if self.checksums is not None and i not in self._validated:
            got = (_crc_array(values), _crc_array(cols))
            want = tuple(self.checksums[i])
            if got != want:
                raise CorpusIntegrityError(
                    f"{self.path}: shard {i} ({c['values']} / {c['cols']}) "
                    f"checksum mismatch (stored crc32 {want}, got {got}); "
                    "the corpus is corrupt — re-write it or restore from "
                    "backup")
            self._validated.add(i)
        return SpCSR(values, cols, (self.shape[0], c["hi"] - c["lo"]))

    @property
    def nbytes(self) -> int:
        """Total stored bytes across all shards (for memory accounting)."""
        n = self.shape[0]
        itemsize = self.dtype.itemsize + np.dtype(np.int32).itemsize
        return len(self._chunks) * n * self.cap * itemsize

    @property
    def chunk_nbytes(self) -> int:
        """Stored bytes of one (full-width) chunk."""
        itemsize = self.dtype.itemsize + np.dtype(np.int32).itemsize
        return self.shape[0] * self.cap * itemsize


def write_corpus(a, out_dir, chunk_docs: Optional[int] = None,
                 dtype=np.float32) -> Path:
    """Spill a matrix to the sharded on-disk corpus layout.

    ``a`` may be ``SpCSR``, dense (numpy / jax), or scipy sparse.  The
    corpus is carved into ``chunk_docs``-wide column chunks (default: the
    streaming solver's 8-chunk schedule), each stored as one shard —
    ``shard-00000.values.npy`` / ``shard-00000.cols.npy`` — at one shared
    slot capacity (the max per-chunk row occupancy), plus a ``meta.json``
    manifest.  Returns ``out_dir``.

    The shards are exactly the chunks a resident ``streaming`` fit carves
    (:class:`ResidentChunks`), so fitting from disk reproduces the resident
    trajectory bit-for-bit under the same schedule.
    """
    from repro.nmf.solvers import default_chunk_docs

    if hasattr(a, "tocoo"):          # scipy sparse, without a hard import
        sp = from_scipy(a)
    elif isinstance(a, SpCSR):
        sp = a
    else:                            # already-dense input (numpy / jax)
        sp = from_dense(np.asarray(a))
    n, m = sp.shape
    w = int(chunk_docs) if chunk_docs is not None else default_chunk_docs(m)
    source = ResidentChunks(sp, w)
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    chunks = []
    for i, (lo, hi) in enumerate(source.schedule):
        blk = source.load(i)
        vname, cname = f"shard-{i:05d}.values.npy", f"shard-{i:05d}.cols.npy"
        values = np.asarray(blk.values, dtype=dtype)
        cols = np.asarray(blk.cols, dtype=np.int32)
        np.save(out / vname, values)
        np.save(out / cname, cols)
        chunks.append({"lo": lo, "hi": hi, "values": vname, "cols": cname,
                       "crc_values": _crc_array(values),
                       "crc_cols": _crc_array(cols)})
    meta = {"format": CORPUS_FORMAT, "n": n, "m": m, "cap": source.cap,
            "chunk_docs": w, "dtype": np.dtype(dtype).name, "chunks": chunks}
    (out / _META).write_text(json.dumps(meta, indent=1))
    return out


def open_corpus(path) -> MmapCorpus:
    """Open a :func:`write_corpus` directory memory-mapped."""
    return MmapCorpus(path)


def is_corpus_input(a) -> bool:
    """True when ``a`` names or is an out-of-core corpus / chunk source —
    the inputs the estimator must stream rather than coerce resident."""
    return isinstance(a, (str, os.PathLike, ChunkSource))


def as_chunk_source(a, chunk_docs: Optional[int] = None) -> ChunkSource:
    """Normalize any streaming-fit input to a ``ChunkSource``.

    Paths open memory-mapped (``chunk_docs`` must then be unset or match
    the width the corpus was written with — the on-disk shards *are* the
    schedule); resident ``SpCSR`` / dense matrices wrap in
    :class:`ResidentChunks` / :class:`DenseChunks` at ``chunk_docs`` (or
    the default 8-chunk width)."""
    from repro.nmf.solvers import default_chunk_docs

    if isinstance(a, (str, os.PathLike)):
        a = open_corpus(a)
    if isinstance(a, ChunkSource):
        if (chunk_docs is not None and getattr(a, "chunk_docs", None)
                not in (None, int(chunk_docs))):
            raise ValueError(
                f"chunk_docs={chunk_docs} disagrees with the corpus's "
                f"stored chunk width {a.chunk_docs}; re-write the corpus "
                "or drop the override")
        return a
    w = int(chunk_docs) if chunk_docs is not None else \
        default_chunk_docs(a.shape[1])
    if isinstance(a, SpCSR):
        return ResidentChunks(a, w)
    return DenseChunks(a, w)


# ---------------------------------------------------------------------------
# Packed chunks and the double-buffered prefetcher
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PackedChunk:
    """A chunk already packed for the target backend and mesh, ahead of the
    step that consumes it: ``operand`` is the distributed shard grid
    (``DistCSR`` / ``DistBSR``) or local device operand, ``m_docs`` the
    chunk's *true* document count (the operand may be padded to the mesh
    grid), and ``host`` the original host-side chunk (kept for per-chunk
    error metrics; one chunk's bytes, dropped with the chunk)."""

    operand: object
    m_docs: int
    host: object = None


class Prefetcher:
    """Double-buffer host-side chunk packing against in-flight compute.

    ``Prefetcher(items, pack)`` iterates ``pack(item)`` for each scheduled
    item, with a worker thread running ``pack`` — mmap page-in, operand
    packing, ``device_put`` / shard distribute — up to ``depth`` items
    ahead of the consumer, parked in a bounded queue.  While the online
    step for chunk N is on device, chunk N+1's ingest and host→device
    transfer ride under it; host memory holds at most ``depth`` queued
    chunks plus the one being packed and the one being consumed — O(depth),
    never O(corpus).

    ``enabled=False`` degrades to calling ``pack`` inline (synchronous
    carving) — the same function on the same inputs, so prefetch on/off are
    bit-identical and the toggle is purely a scheduling knob.  Worker
    exceptions re-raise in the consumer; early exits (``close`` / context
    manager / ``tol`` early-stop breaking the loop) stop the worker without
    draining the corpus.

    I/O failures inside ``pack`` (``OSError`` — a flaky mount, an evicted
    page) are retried up to ``retries`` times with exponential backoff
    (``retry_backoff * 2**attempt`` seconds) before giving up; exhaustion
    raises :class:`ChunkPackError` carrying the failed item and schedule
    position, chained to the original error.  Setting the environment
    variable ``REPRO_STREAM_SKIP_BAD_CHUNKS=1`` downgrades exhaustion (and
    non-I/O pack failures) to a warning and drops the chunk from the
    stream — the fit survives on the remaining chunks, with accordingly
    degraded results.  A worker that dies without reporting (the moral
    equivalent of a segfault) is caught by a liveness watchdog on the
    consumer side rather than hanging the fit.

    Under a profiler trace each pack is the host span :data:`PACK_SPAN` and
    each wait of the consumer on the queue :data:`STALL_SPAN`.
    """

    _DONE = object()
    _SKIPPED = object()

    def __init__(self, items: Sequence, pack: Callable, depth: int = 2,
                 enabled: bool = True, retries: int = 2,
                 retry_backoff: float = 0.05):
        if depth <= 0:
            raise ValueError(f"prefetch depth must be positive, got {depth}")
        if retries < 0:
            raise ValueError(f"retries must be >= 0, got {retries}")
        self._items = list(items)
        self._pack = pack
        self._enabled = bool(enabled)
        self._retries = int(retries)
        self._backoff = float(retry_backoff)
        #: instrumentation: ``packed`` items, ``max_queued`` high-water mark,
        #: ``pack_s`` wall time inside ``pack`` (the ingest work),
        #: ``stall_s`` time the consumer spent blocked waiting for a chunk —
        #: ``1 - stall_s / pack_s`` is the fraction of ingest wall time the
        #: double-buffering hid under compute
        #: — plus ``retries`` (I/O retry attempts) and ``skipped`` (chunks
        #: dropped via the skip hatch)
        self.stats = {"packed": 0, "max_queued": 0, "pack_s": 0.0,
                      "stall_s": 0.0, "retries": 0, "skipped": 0}
        if not self._enabled:
            return
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="repro-corpus-prefetch")
        self._thread.start()

    def _pack_one(self, item, index: int):
        """``pack(item)`` with bounded I/O retry; returns ``_SKIPPED`` when
        the skip hatch swallows a failure."""
        attempt = 0
        while True:
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(PACK_SPAN):
                    packed = self._pack(item)
            except OSError as exc:
                self.stats["pack_s"] += time.perf_counter() - t0
                if attempt < self._retries:
                    self.stats["retries"] += 1
                    time.sleep(self._backoff * (2 ** attempt))
                    attempt += 1
                    continue
                wrapped = ChunkPackError(
                    f"chunk {item!r} (schedule position {index}) failed to "
                    f"pack after {attempt + 1} attempt(s): {exc}",
                    item=item, index=index)
                if os.environ.get(SKIP_BAD_CHUNKS_ENV) == "1":
                    self.stats["skipped"] += 1
                    warnings.warn(
                        f"{wrapped}; skipping it ({SKIP_BAD_CHUNKS_ENV}=1 — "
                        "results degrade to the surviving chunks)",
                        RuntimeWarning)
                    return self._SKIPPED
                raise wrapped from exc
            except Exception as exc:
                self.stats["pack_s"] += time.perf_counter() - t0
                if os.environ.get(SKIP_BAD_CHUNKS_ENV) == "1":
                    self.stats["skipped"] += 1
                    warnings.warn(
                        f"chunk {item!r} (schedule position {index}) failed "
                        f"to pack: {exc}; skipping it ({SKIP_BAD_CHUNKS_ENV}"
                        "=1 — results degrade to the surviving chunks)",
                        RuntimeWarning)
                    return self._SKIPPED
                raise ChunkPackError(
                    f"chunk {item!r} (schedule position {index}) failed to "
                    f"pack: {exc}", item=item, index=index) from exc
            self.stats["pack_s"] += time.perf_counter() - t0
            self.stats["packed"] += 1
            return packed

    def _put(self, payload) -> bool:
        """Queue ``payload`` unless the consumer has gone away."""
        while not self._stop.is_set():
            try:
                self._q.put(payload, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self):
        try:
            for index, item in enumerate(self._items):
                if self._stop.is_set():
                    return
                if faults.should_fire("prefetch-worker", item):
                    return  # injected silent death — no _DONE, no error
                packed = self._pack_one(item, index)
                if packed is self._SKIPPED:
                    continue
                if not self._put((packed, None)):
                    return
            self._put((self._DONE, None))
        except BaseException as exc:  # re-raised in the consumer
            self._put((None, exc))

    def __iter__(self):
        if not self._enabled:
            for index, item in enumerate(self._items):
                t0 = time.perf_counter()
                packed = self._pack_one(item, index)
                self.stats["stall_s"] += time.perf_counter() - t0
                if packed is self._SKIPPED:
                    continue
                yield packed
            return
        while True:
            self.stats["max_queued"] = max(self.stats["max_queued"],
                                           self._q.qsize())
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(STALL_SPAN):
                while True:
                    try:
                        packed, exc = self._q.get(timeout=1.0)
                        break
                    except queue.Empty:
                        if not self._thread.is_alive():
                            self._stop.set()
                            raise RuntimeError(
                                "prefetch worker died without reporting a "
                                "result or an error; the stream cannot "
                                "continue") from None
            self.stats["stall_s"] += time.perf_counter() - t0
            if exc is not None:
                self._stop.set()  # the raise abandons the stream mid-flight
                raise exc
            if packed is self._DONE:
                return
            yield packed

    def close(self):
        """Stop the worker (idempotent).  Safe mid-stream: the queue is
        drained so a blocked ``put`` wakes, then the thread is joined."""
        if not self._enabled:
            return
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=10.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
