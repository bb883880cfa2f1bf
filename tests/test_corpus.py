"""Out-of-core corpus layer: writer/mmap round trip, streamed-fit parity.

The contract under test: a ``streaming`` fit fed a :func:`write_corpus`
directory is **bit-identical** to the same fit over the resident matrix —
locally, on the 2x2 / 4x1 forced-host meshes (subprocess, ragged final
chunk), and with the prefetcher on or off.  Plus the pipeline pieces in
isolation: shard files reproduce ``ResidentChunks``'s carve exactly, the
``Prefetcher`` preserves order / propagates worker exceptions / shuts down
cleanly mid-stream, and a second streamed-from-disk fit compiles nothing.
"""
import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from repro.analysis import recompile_guard
from repro.data import synthetic_journal_corpus
from repro.data.corpus import (
    DenseChunks, MmapCorpus, PackedChunk, Prefetcher, ResidentChunks,
    as_chunk_source, chunk_schedule, is_corpus_input, open_corpus,
    write_corpus,
)
from repro.nmf import EnforcedNMF, NMFConfig, Sparsity
from repro.sparse import SpCSR, to_dense

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(n, code):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.fixture(scope="module")
def corpus():
    a_sp, _ = synthetic_journal_corpus(n_terms=96, n_docs=60,
                                       n_journals=4, seed=5)
    return a_sp


@pytest.fixture()
def corpus_dir(corpus, tmp_path):
    return write_corpus(corpus, tmp_path / "corpus", chunk_docs=16)


# ---------------------------------------------------------------------------
# writer -> mmap round trip
# ---------------------------------------------------------------------------

def test_write_corpus_round_trip(corpus, corpus_dir):
    disk = open_corpus(corpus_dir)
    res = ResidentChunks(corpus, 16)
    assert disk.shape == corpus.shape
    assert disk.schedule == res.schedule == chunk_schedule(corpus.shape[1], 16)
    assert disk.cap == res.cap
    for i in range(len(disk)):
        got, want = disk.load(i), res.load(i)
        assert got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got.values),
                                      np.asarray(want.values))
        np.testing.assert_array_equal(np.asarray(got.cols),
                                      np.asarray(want.cols))


def test_corpus_cap_is_per_chunk_not_per_corpus(tmp_path):
    """One dense hot document must not inflate every shard's slot count:
    the stored cap is the max *per-chunk* row occupancy."""
    n, m = 32, 40
    dense = np.zeros((n, m), dtype=np.float32)
    dense[0, :] = 1.0                 # row 0: one nnz in every document
    disk = open_corpus(write_corpus(dense, tmp_path / "c", chunk_docs=8))
    assert disk.cap == 8              # chunk width, not m
    np.testing.assert_array_equal(
        np.asarray(to_dense(disk.load(2))), dense[:, 16:24])


def test_open_corpus_rejects_non_corpus_and_bad_format(tmp_path, corpus_dir):
    with pytest.raises(FileNotFoundError, match="not a corpus directory"):
        open_corpus(tmp_path)         # exists, but holds no meta.json
    meta_path = corpus_dir / "meta.json"
    meta = json.loads(meta_path.read_text())
    meta["format"] = "somebody-elses-layout"
    meta_path.write_text(json.dumps(meta))
    with pytest.raises(ValueError, match="format"):
        open_corpus(corpus_dir)


def test_mmap_load_touches_one_chunk(corpus_dir):
    """load(i) returns mmap-backed arrays — the corpus is never resident."""
    disk = open_corpus(corpus_dir)
    blk = disk.load(0)
    assert isinstance(blk.values, np.memmap)
    assert isinstance(blk.cols, np.memmap)
    assert disk.chunk_nbytes * len(disk) == disk.nbytes


# ---------------------------------------------------------------------------
# input normalization
# ---------------------------------------------------------------------------

def test_as_chunk_source_dispatch(corpus, corpus_dir):
    assert isinstance(as_chunk_source(str(corpus_dir)), MmapCorpus)
    assert isinstance(as_chunk_source(corpus_dir), MmapCorpus)  # PathLike
    assert isinstance(as_chunk_source(corpus, chunk_docs=16), ResidentChunks)
    dense = np.asarray(to_dense(corpus))
    assert isinstance(as_chunk_source(dense, chunk_docs=16), DenseChunks)
    src = as_chunk_source(corpus_dir)
    assert as_chunk_source(src) is src
    assert is_corpus_input(str(corpus_dir)) and is_corpus_input(src)
    assert not is_corpus_input(corpus) and not is_corpus_input(dense)


def test_as_chunk_source_rejects_mismatched_width(corpus_dir):
    with pytest.raises(ValueError, match="chunk_docs"):
        as_chunk_source(corpus_dir, chunk_docs=7)  # corpus was written at 16
    assert as_chunk_source(corpus_dir, chunk_docs=16).chunk_docs == 16


# ---------------------------------------------------------------------------
# streamed-from-disk fit parity (local; mesh parity below in a subprocess)
# ---------------------------------------------------------------------------

def _fit(a, prefetch=True, **overrides):
    cfg = NMFConfig(k=4, iters=8, solver="streaming", chunk_docs=16,
                    sparsity=Sparsity(t_u=48, t_v=60), prefetch=prefetch,
                    **overrides)
    return EnforcedNMF(cfg).fit(a)


def test_disk_fit_matches_resident_bitwise(corpus, corpus_dir):
    res = _fit(corpus)
    disk = _fit(str(corpus_dir))
    sync = _fit(str(corpus_dir), prefetch=False)
    for other in (disk, sync):
        np.testing.assert_array_equal(np.asarray(res.u_),
                                      np.asarray(other.u_))
        np.testing.assert_array_equal(np.asarray(res.v_),
                                      np.asarray(other.v_))
        assert (res.result_.final_error == other.result_.final_error)
    assert disk.v_.shape == (corpus.shape[1], 4)


def test_corpus_input_requires_streaming_solver(corpus_dir):
    with pytest.raises(ValueError, match="stream"):
        EnforcedNMF(NMFConfig(k=4, solver="enforced")).fit(str(corpus_dir))


def test_packed_chunk_requires_mesh(corpus):
    model = EnforcedNMF(NMFConfig(k=4, solver="streaming"))
    packed = PackedChunk(operand=object(), m_docs=16)
    with pytest.raises(ValueError, match="mesh"):
        model.partial_fit(packed)


def test_second_streamed_fit_compiles_nothing(corpus, tmp_path):
    """The prefetch-fed stream draws the same cached executables as any
    other fit: warming from disk once, an identical second fit — new
    estimator, same corpus directory — must compile nothing."""
    out = write_corpus(corpus, tmp_path / "cc", chunk_docs=16)
    _fit(str(out))
    with recompile_guard() as counter:
        model = _fit(str(out))
    assert counter.count == 0
    assert model.u_ is not None


def test_second_streamed_fit_on_pallas_bsr_compiles_nothing(corpus,
                                                             tmp_path):
    """Every chunk of a stream ingests into one tile grid and one nnz
    bucket, so the device-side tile fill compiles with the first fit and
    a second fit from disk compiles nothing, ingest included."""
    out = write_corpus(corpus, tmp_path / "cc", chunk_docs=16)
    _fit(str(out), backend="pallas-bsr")
    with recompile_guard() as counter:
        model = _fit(str(out), backend="pallas-bsr")
    assert counter.count == 0
    assert model.result_.stream_stats["ingest_bytes"] > 0


def test_streamed_fit_uploads_a_tenth_of_its_tiles(tmp_path, monkeypatch):
    """``stream_stats["ingest_bytes"]`` counts what the fit's chunk ingests
    sent to the device, over the stream, the fold-in and the seed
    statistics: the entries and block tables, under a tenth of the tile
    volume they built there."""
    import scipy.sparse as sp

    import repro.kernels.bsr as bsr_mod

    built = []
    build = bsr_mod._build

    def spy(*args, **kw):
        out = build(*args, **kw)
        built.append(sum(b.tiles.nbytes for b in out))
        return out

    monkeypatch.setattr(bsr_mod, "_build", spy)
    a = sp.random(4096, 1024, density=0.004, random_state=2, format="csr",
                  dtype=np.float32)
    out = write_corpus(a, tmp_path / "wide", chunk_docs=512)
    model = EnforcedNMF(NMFConfig(
        k=4, iters=1, solver="streaming", chunk_docs=512,
        sparsity=Sparsity(t_u=400, t_v=200),
        backend="pallas-bsr")).fit(str(out))
    assert len(built) == 3 * 2  # three passes over two chunks
    ingest = model.result_.stream_stats["ingest_bytes"]
    assert 0 < ingest < sum(built) / 10


# ---------------------------------------------------------------------------
# the prefetcher in isolation
# ---------------------------------------------------------------------------

def test_prefetcher_preserves_order_and_counts():
    for enabled in (True, False):
        with Prefetcher(range(20), lambda i: i * i, depth=3,
                        enabled=enabled) as pf:
            assert list(pf) == [i * i for i in range(20)]
        assert pf.stats["packed"] == 20
        assert pf.stats["max_queued"] <= 3


def test_prefetcher_bounds_inflight_packs():
    """At most depth + 1 packs may start before the consumer takes one."""
    started = []
    gate = threading.Event()

    def pack(i):
        started.append(i)
        gate.wait(timeout=5.0)
        return i

    pf = Prefetcher(range(10), pack, depth=2)
    time.sleep(0.3)                   # worker packs, fills the queue, blocks
    gate.set()
    try:
        assert len(started) <= 3      # depth queued + one in flight
        assert list(pf) == list(range(10))
    finally:
        pf.close()


def test_prefetcher_propagates_pack_exception():
    def pack(i):
        if i == 3:
            raise RuntimeError("shard went missing")
        return i

    for enabled in (True, False):
        got = []
        with pytest.raises(RuntimeError, match="shard went missing"):
            with Prefetcher(range(10), pack, enabled=enabled) as pf:
                for x in pf:
                    got.append(x)
        assert got == [0, 1, 2]


def test_prefetcher_close_mid_stream_stops_worker():
    pf = Prefetcher(range(1000), lambda i: i, depth=2)
    it = iter(pf)
    assert next(it) == 0
    pf.close()                        # tol early-stop path: no drain needed
    assert not pf._thread.is_alive()
    pf.close()                        # idempotent


def test_prefetcher_rejects_bad_depth():
    with pytest.raises(ValueError, match="depth"):
        Prefetcher([1], lambda i: i, depth=0)
    with pytest.raises(ValueError, match="prefetch_depth"):
        NMFConfig(k=4, prefetch_depth=0)


# ---------------------------------------------------------------------------
# analyzer scope
# ---------------------------------------------------------------------------

def test_no_densify_scope_covers_corpus_layer():
    from repro.analysis.rules.no_densify import _SCOPE_RE

    assert _SCOPE_RE.search("src/repro/data/corpus.py")
    assert not _SCOPE_RE.search("src/repro/data/textpipe.py")


# ---------------------------------------------------------------------------
# mesh parity: disk == resident == sync on 2x2 and 4x1, ragged final chunk
# ---------------------------------------------------------------------------

_MESH_DISK_CODE = """
    import json, tempfile
    import numpy as np
    from repro.data import synthetic_journal_corpus, write_corpus
    from repro.nmf import EnforcedNMF, NMFConfig, Sparsity

    a_sp, _ = synthetic_journal_corpus(n_terms=128, n_docs=96,
                                       n_journals=4, seed=3)
    tmp = tempfile.mkdtemp()
    write_corpus(a_sp, tmp, chunk_docs=31)  # ragged: 31+31+31+3

    def fit(a, mesh_shape, prefetch=True):
        cfg = NMFConfig(k=4, iters=10, solver="streaming", chunk_docs=31,
                        sparsity=Sparsity(t_u=64, t_v=96),
                        mesh_shape=mesh_shape, prefetch=prefetch,
                        backend="jnp-csr" if mesh_shape != (1, 1) else None)
        return EnforcedNMF(cfg).fit(a)

    rec = {}
    for shape in [(2, 2), (4, 1)]:
        res, disk = fit(a_sp, shape), fit(tmp, shape)
        sync = fit(tmp, shape, prefetch=False)
        eq = lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y)))
        rec["%dx%d" % shape] = {
            "disk_eq_resident": eq(res.u_, disk.u_) and eq(res.v_, disk.v_),
            "sync_eq_prefetch": eq(disk.u_, sync.u_) and eq(disk.v_, sync.v_),
            "err_eq": float(res.result_.final_error)
                      == float(disk.result_.final_error),
            "v_shape": list(disk.v_.shape),
        }
    print(json.dumps(rec))
"""


def test_mesh_disk_parity_and_ragged_chunks():
    rec = json.loads(run_with_devices(
        4, textwrap.dedent(_MESH_DISK_CODE)).strip().splitlines()[-1])
    for shape in ("2x2", "4x1"):
        assert rec[shape]["disk_eq_resident"], shape
        assert rec[shape]["sync_eq_prefetch"], shape
        assert rec[shape]["err_eq"], shape
        assert rec[shape]["v_shape"] == [96, 4]
