"""Property-based structural invariants of the padded-CSR and BSR formats.

Hypothesis drives randomized cases when installed (CI installs it via
``requirements-dev.txt``); without it the ``@given`` tests skip through
``tests/_hypothesis_compat`` while the seeded deterministic sweeps below
keep the same invariants covered locally.

Invariants pinned here:

* **caps respected** — ingest never stores more than ``cap`` entries per
  CSR row / ``bcap`` tiles per BSR row-block, and overflow keeps the
  largest-magnitude (CSR) / largest-Frobenius (BSR) survivors;
* **slot ordering** — occupied BSR slots hold strictly ascending
  block-columns within every row-block (the layout the Pallas kernels
  stream by);
* **oracle agreement** — every format and both ``BSROperand``
  orientations reconstruct the dense matrix exactly, and the ``SpCSR``
  products ``A @ U`` and ``A.T @ W`` match the dense ones;
* **carve equivalence** — ``ColumnSlicer`` (the reusable column-sorted
  index the streaming sources carve through) produces bit-identical
  chunks to the one-shot ``column_block`` scan, and a
  :func:`repro.data.corpus.write_corpus` directory read back memory-mapped
  reproduces those chunks exactly.
"""
import shutil
import tempfile

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.data.corpus import ResidentChunks, open_corpus, write_corpus
from repro.kernels.bsr import (
    BSR, bsr_from_dense, bsr_operand, bsr_to_coo, bsr_to_dense,
    bsr_transpose,
)
from repro.sparse.csr import (
    ColumnSlicer, SpCSR, column_block, from_coo, from_dense, spmm, spmm_t,
    to_dense,
)


def random_sparse(seed: int, n: int, m: int, density: float) -> np.ndarray:
    rng = np.random.RandomState(seed)
    a = rng.randn(n, m).astype(np.float32)
    a[rng.rand(n, m) >= density] = 0.0
    return a


def dense_of_csr(a: SpCSR) -> np.ndarray:
    return np.asarray(to_dense(a))


def dense_of_bsr(a: BSR, shape) -> np.ndarray:
    return np.asarray(bsr_to_dense(a))[: shape[0], : shape[1]]


# ---------------------------------------------------------------------------
# invariant checkers (shared by hypothesis and deterministic drivers)
# ---------------------------------------------------------------------------

def check_csr_invariants(a_dense: np.ndarray, cap: int):
    n, m = a_dense.shape
    rows, cols = np.nonzero(a_dense)
    vals = a_dense[rows, cols]
    row_nnz = np.bincount(rows, minlength=n)
    will_truncate = (row_nnz > cap).any()
    ctx = (pytest.warns(UserWarning, match="largest-magnitude")
           if will_truncate else _nullcontext())
    with ctx:
        sp = from_coo(rows, cols, vals, (n, m), cap=cap)
    # cap respected structurally
    assert sp.values.shape == (n, cap) and sp.cols.shape == (n, cap)
    assert int(np.max(np.sum(np.asarray(sp.values) != 0, axis=1),
                      initial=0)) <= cap
    back = dense_of_csr(sp)
    for i in range(n):
        nz = np.flatnonzero(a_dense[i])
        keep = nz[np.argsort(-np.abs(a_dense[i, nz]), kind="stable")][:cap]
        expect = np.zeros(m, a_dense.dtype)
        expect[keep] = a_dense[i, keep]
        # largest-magnitude survivors, exactly (ties broken stably is NOT
        # guaranteed across sort kinds — compare by magnitude multiset)
        assert np.isclose(np.abs(back[i]).sum(), np.abs(expect).sum())
        assert np.sum(back[i] != 0) == len(keep)
    if not will_truncate:
        np.testing.assert_array_equal(back, a_dense)


def check_bsr_invariants(a_dense: np.ndarray, bm: int, bk: int, bcap: int):
    n, m = a_dense.shape
    nrb = -(-n // bm)
    ncb = -(-m // bk)
    pad = np.zeros((nrb * bm, ncb * bk), a_dense.dtype)
    pad[:n, :m] = a_dense
    blocked = pad.reshape(nrb, bm, ncb, bk).transpose(0, 2, 1, 3)
    block_sq = (blocked.astype(np.float64) ** 2).sum(axis=(2, 3))
    occupancy = (block_sq > 0).sum(axis=1)
    will_truncate = (occupancy > bcap).any()
    ctx = (pytest.warns(UserWarning, match="largest-Frobenius")
           if will_truncate else _nullcontext())
    with ctx:
        a = bsr_from_dense(a_dense, bm=bm, bk=bk, bcap=bcap)
    assert a.tiles.shape == (nrb, bcap, bm, bk)
    tiles = np.asarray(a.tiles)
    bcols = np.asarray(a.block_cols)
    back = dense_of_bsr(a, (n, m))
    for rb in range(nrb):
        occupied = np.flatnonzero((tiles[rb] != 0).any(axis=(1, 2)))
        # slot ordering: ascending block-cols over occupied slots
        occ_cols = bcols[rb, occupied]
        assert (np.diff(occ_cols) > 0).all(), (
            f"row-block {rb}: occupied slots not ascending: {occ_cols}")
        # truncation keeps the bcap largest-Frobenius blocks
        expect_cols = np.flatnonzero(block_sq[rb] > 0)
        if len(expect_cols) > bcap:
            top = expect_cols[
                np.argsort(-block_sq[rb, expect_cols], kind="stable")][:bcap]
            expect_cols = np.sort(top)
        np.testing.assert_array_equal(occ_cols, expect_cols)
    if not will_truncate:
        np.testing.assert_array_equal(back, a_dense)


def check_operand_orientations(a_dense: np.ndarray, bm: int, bk: int):
    n, m = a_dense.shape
    op = bsr_operand(a_dense, bm=bm, bk=bk)
    assert op.shape == (n, m)
    np.testing.assert_array_equal(dense_of_bsr(op.bsr, (n, m)), a_dense)
    np.testing.assert_array_equal(dense_of_bsr(op.bsr_t, (m, n)), a_dense.T)


def check_slicer_matches_one_shot(a_dense: np.ndarray, chunk_docs: int):
    """``ColumnSlicer.block`` must be *bit-identical* (values, cols, padding
    slots) to the one-shot ``column_block`` scan it replaced — the streaming
    trajectory depends on the packed layout, not just the dense content."""
    sp = from_dense(a_dense)
    slicer = ColumnSlicer(sp)
    m = a_dense.shape[1]
    schedule = [(lo, min(lo + chunk_docs, m)) for lo in range(0, m, chunk_docs)]
    cap = slicer.chunk_cap(schedule)
    assert cap <= max(sp.cap, 1)
    for lo, hi in schedule:
        got = slicer.block(lo, hi, cap=cap)
        want = column_block(sp, lo, hi, cap=cap)
        np.testing.assert_array_equal(np.asarray(got.values),
                                      np.asarray(want.values))
        np.testing.assert_array_equal(np.asarray(got.cols),
                                      np.asarray(want.cols))
        assert got.shape == want.shape == (a_dense.shape[0], hi - lo)


def check_corpus_round_trip(a_dense: np.ndarray, chunk_docs: int):
    """writer -> mmap round trip: the shards read back are the exact
    arrays ``ResidentChunks`` carves, and they reassemble the dense
    oracle."""
    sp = from_dense(a_dense)
    res = ResidentChunks(sp, chunk_docs)
    tmp = tempfile.mkdtemp()
    try:
        disk = open_corpus(write_corpus(sp, tmp, chunk_docs=chunk_docs))
        assert disk.shape == sp.shape and disk.cap == res.cap
        assert disk.schedule == res.schedule
        for i, (lo, hi) in enumerate(disk.schedule):
            got, want = disk.load(i), res.load(i)
            np.testing.assert_array_equal(np.asarray(got.values),
                                          np.asarray(want.values))
            np.testing.assert_array_equal(np.asarray(got.cols),
                                          np.asarray(want.cols))
            np.testing.assert_array_equal(dense_of_csr(got),
                                          a_dense[:, lo:hi])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


# ---------------------------------------------------------------------------
# hypothesis drivers (skip cleanly without hypothesis)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 24),
       m=st.integers(1, 24), cap=st.integers(1, 8),
       density=st.floats(0.0, 0.9))
def test_csr_cap_and_truncation_properties(seed, n, m, cap, density):
    check_csr_invariants(random_sparse(seed, n, m, density), cap)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 40),
       m=st.integers(1, 40), bm=st.integers(2, 8), bk=st.integers(2, 8),
       bcap=st.integers(1, 4), density=st.floats(0.0, 0.6))
def test_bsr_slot_order_and_cap_properties(seed, n, m, bm, bk, bcap, density):
    check_bsr_invariants(random_sparse(seed, n, m, density), bm, bk, bcap)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 32),
       m=st.integers(1, 32), bm=st.integers(2, 8), bk=st.integers(2, 8),
       density=st.floats(0.05, 0.7))
def test_bsr_operand_orientations_property(seed, n, m, bm, bk, density):
    check_operand_orientations(random_sparse(seed, n, m, density), bm, bk)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 24),
       m=st.integers(1, 32), chunk_docs=st.integers(1, 12),
       density=st.floats(0.0, 0.9))
def test_column_slicer_matches_one_shot_property(seed, n, m, chunk_docs,
                                                 density):
    check_slicer_matches_one_shot(random_sparse(seed, n, m, density),
                                  chunk_docs)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 20),
       m=st.integers(1, 24), chunk_docs=st.integers(1, 10),
       density=st.floats(0.0, 0.9))
def test_corpus_round_trip_property(seed, n, m, chunk_docs, density):
    check_corpus_round_trip(random_sparse(seed, n, m, density), chunk_docs)


# ---------------------------------------------------------------------------
# deterministic sweeps: same invariants, always run (no hypothesis needed)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,n,m,cap,density", [
    (0, 12, 9, 3, 0.5),     # truncating: rows exceed cap
    (1, 8, 16, 16, 0.4),    # cap >= m: lossless
    (2, 1, 5, 2, 0.9),      # single row
    (3, 10, 10, 1, 0.2),    # cap=1: one survivor per row
    (4, 6, 6, 4, 0.0),      # empty matrix
])
def test_csr_invariants_deterministic(seed, n, m, cap, density):
    check_csr_invariants(random_sparse(seed, n, m, density), cap)


@pytest.mark.parametrize("seed,n,m,bm,bk,bcap,density", [
    (0, 20, 20, 4, 4, 2, 0.5),   # truncating row-blocks
    (1, 16, 24, 8, 8, 3, 0.3),   # lossless (3 col-blocks, bcap=3)
    (2, 5, 7, 4, 4, 2, 0.8),     # ragged padding
    (3, 12, 12, 4, 4, 1, 0.1),   # bcap=1
])
def test_bsr_invariants_deterministic(seed, n, m, bm, bk, bcap, density):
    check_bsr_invariants(random_sparse(seed, n, m, density), bm, bk, bcap)


@pytest.mark.parametrize("seed,n,m,bm,bk", [
    (0, 16, 12, 4, 4),
    (1, 9, 17, 8, 4),    # ragged both ways, asymmetric blocks
    (2, 4, 4, 4, 4),     # single block
])
def test_bsr_operand_orientations_deterministic(seed, n, m, bm, bk):
    check_operand_orientations(random_sparse(seed, n, m, 0.4), bm, bk)


@pytest.mark.parametrize("seed,n,m,chunk_docs,density", [
    (0, 14, 20, 6, 0.4),    # ragged final chunk (20 = 6+6+6+2)
    (1, 8, 16, 16, 0.5),    # one chunk covering everything
    (2, 10, 9, 1, 0.8),     # one document per chunk
    (3, 6, 6, 4, 0.0),      # empty matrix
])
def test_column_slicer_matches_one_shot_deterministic(seed, n, m, chunk_docs,
                                                      density):
    check_slicer_matches_one_shot(random_sparse(seed, n, m, density),
                                  chunk_docs)


@pytest.mark.parametrize("seed,n,m,chunk_docs,density", [
    (0, 14, 20, 6, 0.4),
    (1, 10, 9, 3, 0.0),     # empty shards round-trip too
    (2, 5, 12, 5, 0.9),
])
def test_corpus_round_trip_deterministic(seed, n, m, chunk_docs, density):
    check_corpus_round_trip(random_sparse(seed, n, m, density), chunk_docs)


def test_column_block_matches_dense_slice():
    a_dense = random_sparse(11, 14, 20, 0.4)
    sp = from_dense(a_dense)
    for lo, hi in [(0, 20), (5, 12), (19, 20), (0, 1)]:
        blk = column_block(sp, lo, hi)
        assert blk.shape == (14, hi - lo)
        np.testing.assert_array_equal(dense_of_csr(blk), a_dense[:, lo:hi])


def test_bsr_to_coo_reconstructs_dense():
    a_dense = random_sparse(5, 13, 10, 0.5)
    a = bsr_from_dense(a_dense, bm=4, bk=4)
    rows, cols, vals = (np.asarray(x) for x in bsr_to_coo(a))
    back = np.zeros((16, 12), np.float32)
    np.add.at(back, (rows, cols), vals)
    np.testing.assert_array_equal(back[:13, :10], a_dense)


def test_transpose_agrees_with_dense_oracle():
    a_dense = random_sparse(6, 12, 18, 0.35)
    a = bsr_from_dense(a_dense, bm=4, bk=4)
    at = bsr_transpose(a)
    np.testing.assert_array_equal(dense_of_bsr(at, (18, 12)), a_dense.T)


# ---------------------------------------------------------------------------
# SpCSR roundtrip and products vs the dense oracle
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=20)
@given(n=st.integers(2, 40), m=st.integers(2, 40),
       density=st.floats(0.02, 0.5), seed=st.integers(0, 999))
def test_csr_roundtrip(n, m, density, seed):
    rng = np.random.default_rng(seed)
    a = rng.random((n, m)).astype(np.float32)
    a[rng.random((n, m)) > density] = 0
    sp = from_dense(a)
    np.testing.assert_allclose(np.asarray(to_dense(sp)), a)
    assert int(sp.nnz()) == int((a != 0).sum())


@settings(deadline=None, max_examples=15)
@given(n=st.integers(2, 30), m=st.integers(2, 30), k=st.integers(1, 6),
       seed=st.integers(0, 999))
def test_csr_matmuls_match_dense(n, m, k, seed):
    rng = np.random.default_rng(seed)
    a = rng.random((n, m)).astype(np.float32)
    a[rng.random((n, m)) > 0.3] = 0
    u = rng.standard_normal((m, k)).astype(np.float32)
    w = rng.standard_normal((n, k)).astype(np.float32)
    sp = from_dense(a)
    np.testing.assert_allclose(np.asarray(spmm(sp, jnp.asarray(u))), a @ u,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(spmm_t(sp, jnp.asarray(w))), a.T @ w,
                               rtol=1e-4, atol=1e-4)
