"""Matmul-backend layer: registry/selection rules, pallas-bsr parity with
the dense oracle (spmm / spmm_t / gram across awkward shapes, empty
row-blocks, cap-overflow rows, f32/bf16), tile-wise BSR ingest (scipy
direct, transpose without densifying), sparse-ingest truncation policy,
no-densify distributed sharding, and the end-to-end
``EnforcedNMF(backend="pallas-bsr")`` fit matching the jnp backend."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.backend import (
    available_backends, default_backend_name, get_backend, resolve_backend,
    select_backend,
)
from repro.kernels.bsr import (
    BSR, BSROperand, bsr_from_dense, bsr_from_scipy, bsr_operand,
    bsr_to_dense, bsr_transpose,
)
from repro.kernels.bsr_spmm import bsr_spmm, bsr_spmm_t
from repro.nmf import EnforcedNMF, NMFConfig, Sparsity
from repro.sparse import SpCSR, from_coo, from_dense, from_scipy, to_dense

sps = pytest.importorskip("scipy.sparse")


def _rand_sparse(rng, n, m, density=0.05, dtype=np.float32):
    a = rng.random((n, m)).astype(dtype)
    a[rng.random((n, m)) > density] = 0
    return a


@pytest.fixture(scope="module")
def corpus():
    from repro.data import synthetic_journal_corpus

    a_sp, dj = synthetic_journal_corpus(n_terms=300, n_docs=200,
                                        n_journals=5, seed=1)
    return a_sp


# ---------------------------------------------------------------------------
# Registry and selection rules
# ---------------------------------------------------------------------------

def test_registry_lists_backends():
    assert {"jnp-dense", "jnp-csr", "pallas-bsr"} <= set(available_backends())
    with pytest.raises(ValueError, match="unknown matmul backend"):
        get_backend("nope")


def test_select_backend_by_operand_type():
    rng = np.random.default_rng(0)
    a = _rand_sparse(rng, 32, 16)
    assert select_backend(jnp.asarray(a)).name == "jnp-dense"
    assert select_backend(from_dense(a)).name == "jnp-csr"
    op = bsr_operand(a, bm=16, bk=16)
    assert select_backend(op).name == "pallas-bsr"
    with pytest.raises(TypeError, match="no registered matmul backend"):
        select_backend("not a matrix")


def test_resolve_backend_rejects_mismatched_operand():
    a = jnp.ones((8, 8))
    with pytest.raises(TypeError, match="cannot consume"):
        resolve_backend(a, "pallas-bsr")


def test_default_backend_for_scipy_off_tpu():
    m = sps.random(10, 8, density=0.5, random_state=0, format="csr")
    expect = "pallas-bsr" if jax.default_backend() == "tpu" else "jnp-csr"
    assert default_backend_name(m) == expect


def test_config_validates_backend():
    with pytest.raises(ValueError, match="unknown backend"):
        NMFConfig(backend="bogus")
    with pytest.raises(ValueError, match="sequential"):
        NMFConfig(backend="pallas-bsr", solver="sequential")
    with pytest.raises(ValueError, match="jnp-csr"):
        NMFConfig(backend="jnp-dense", solver="distributed")
    with pytest.raises(ValueError, match="jnp-csr"):
        NMFConfig(backend="jnp-dense", solver="streaming", mesh_shape=(2, 2))
    NMFConfig(backend="pallas-bsr", solver="enforced")  # fine
    # BSR shard ingest: the Pallas kernels run inside every mesh shard
    NMFConfig(backend="pallas-bsr", solver="distributed")
    NMFConfig(backend="pallas-bsr", solver="streaming", mesh_shape=(2, 2))


# ---------------------------------------------------------------------------
# pallas-bsr parity with the dense oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,k", [(128, 128, 8), (257, 129, 33),
                                   (64, 512, 96), (100, 70, 5)])
def test_pallas_spmm_and_spmm_t_match_dense(n, m, k):
    rng = np.random.default_rng(n + m)
    a = _rand_sparse(rng, n, m)
    a[: min(40, n)] = 0  # empty rows -> empty row-blocks at bm=32
    be = get_backend("pallas-bsr")
    op = bsr_operand(a, bm=32, bk=32)
    v = jnp.asarray(rng.standard_normal((m, k)), dtype=jnp.float32)
    u = jnp.asarray(rng.standard_normal((n, k)), dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(be.matmul(op, v)), a @ np.asarray(v),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(be.matmul_t(op, u)),
                               a.T @ np.asarray(u), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,k", [(513, 40), (64, 5), (256, 33)])
def test_pallas_gram_matches_dense(n, k):
    u = jax.random.normal(jax.random.PRNGKey(n + k), (n, k))
    got = get_backend("pallas-bsr").gram(u)
    assert got.dtype == u.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(u.T @ u),
                               rtol=1e-4, atol=1e-3)


def test_pallas_spmm_t_bf16():
    rng = np.random.default_rng(3)
    a = _rand_sparse(rng, 128, 96)
    op = bsr_operand(a, bm=32, bk=32, dtype=np.float32)
    op = BSROperand(
        BSR(op.bsr.tiles.astype(jnp.bfloat16), op.bsr.block_cols, op.bsr.shape),
        BSR(op.bsr_t.tiles.astype(jnp.bfloat16), op.bsr_t.block_cols,
            op.bsr_t.shape),
        op.shape)
    u = jnp.asarray(rng.standard_normal((128, 16)), dtype=jnp.bfloat16)
    out = bsr_spmm_t(op, u, interpret=True)
    expect = a.T.astype(np.float32) @ np.asarray(u, dtype=np.float32)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32), expect,
                               rtol=5e-2, atol=1e-1)


def test_pallas_handles_cap_overflow_rows(corpus):
    """SpCSR built with a tight cap (overflowing rows truncated to their
    largest entries) still round-trips through the BSR operand exactly."""
    a_dense = np.asarray(to_dense(corpus))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        tight = from_scipy(sps.csr_matrix(a_dense), cap=8)
    op = get_backend("pallas-bsr").prepare(tight)
    np.testing.assert_allclose(np.asarray(bsr_to_dense(op.bsr)),
                               np.asarray(to_dense(tight)), rtol=1e-6)
    u = jnp.asarray(np.random.default_rng(0).standard_normal(
        (a_dense.shape[0], 4)), dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(bsr_spmm_t(op, u, interpret=True)),
        np.asarray(to_dense(tight)).T @ np.asarray(u), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Tile-wise BSR ingest
# ---------------------------------------------------------------------------

def test_bsr_from_scipy_matches_from_dense():
    rng = np.random.default_rng(7)
    a = _rand_sparse(rng, 257, 129)
    b1 = bsr_from_dense(a, bm=32, bk=32)
    b2 = bsr_from_scipy(sps.csr_matrix(a), bm=32, bk=32)
    np.testing.assert_array_equal(np.asarray(b1.tiles), np.asarray(b2.tiles))
    np.testing.assert_array_equal(np.asarray(b1.block_cols),
                                  np.asarray(b2.block_cols))


def test_bsr_from_scipy_bcap_keeps_largest_blocks():
    dense = np.zeros((32, 96), np.float32)
    dense[0, 0] = 1.0   # block (0,0), Frobenius 1
    dense[0, 32] = 5.0  # block (0,1), Frobenius 5
    dense[0, 64] = 3.0  # block (0,2), Frobenius 3
    with pytest.warns(UserWarning, match="largest-Frobenius"):
        b = bsr_from_scipy(sps.csr_matrix(dense), bm=32, bk=32, bcap=2)
    np.testing.assert_array_equal(np.asarray(b.block_cols)[0], [1, 2])
    kept = sorted(float(t.max()) for t in np.asarray(b.tiles)[0])
    assert kept == [3.0, 5.0]


def test_bsr_transpose_tile_wise_no_densify(monkeypatch):
    """The transposed-format copy is built from occupied tiles only — the
    old implementation round-tripped through a dense (n, m) host matrix and
    OOMed at scale."""
    import repro.kernels.bsr as bsr_mod

    def boom(*a, **kw):
        raise AssertionError("bsr_transpose densified the matrix")

    monkeypatch.setattr(bsr_mod, "bsr_to_dense", boom)
    monkeypatch.setattr(bsr_mod, "bsr_from_dense", boom)
    rng = np.random.default_rng(1)
    a = _rand_sparse(rng, 200, 150)
    b = bsr_from_dense(a, bm=32, bk=32)
    monkeypatch.undo()  # only the transpose itself is under test
    monkeypatch.setattr(bsr_mod, "bsr_to_dense", boom)
    at = bsr_transpose(b)
    monkeypatch.undo()
    np.testing.assert_allclose(np.asarray(bsr_to_dense(at)), a.T)


def test_bsr_transpose_bcap_keeps_largest_tiles():
    """Explicit-bcap truncation follows the same keep-largest-Frobenius
    policy (with a warning) as bsr_from_scipy, not silent first-i-wins."""
    dense = np.zeros((96, 32), np.float32)
    dense[0, 0] = 1.0   # source block (0,0) -> dest row-block 0, i=0
    dense[32, 0] = 5.0  # source block (1,0) -> i=1
    dense[64, 0] = 3.0  # source block (2,0) -> i=2
    b = bsr_from_dense(dense, bm=32, bk=32)
    with pytest.warns(UserWarning, match="largest-Frobenius"):
        at = bsr_transpose(b, bcap=2)
    np.testing.assert_array_equal(np.asarray(at.block_cols)[0], [1, 2])
    expect = dense.T.copy()
    expect[:, :32] = 0  # the norm-1 tile is the one dropped
    np.testing.assert_allclose(np.asarray(bsr_to_dense(at)), expect)


def test_sequential_rejects_bsr_operand(corpus):
    """The sequential engine still dispatches on dense/SpCSR only; the
    distributed solver now *accepts* BSR operands (tile-sharded per device
    — see tests/test_bsr_sharded.py)."""
    op = get_backend("pallas-bsr").prepare(corpus)
    model = EnforcedNMF(NMFConfig(k=5, iters=3, solver="sequential",
                                  sparsity=Sparsity(t_u=55)))
    with pytest.raises(TypeError, match="does not support BSR"):
        model.fit(op)


def test_bsr_relative_error_matches_dense(corpus):
    from repro.core.nmf import _relative_error, _sqnorm

    a = np.asarray(to_dense(corpus))
    op = get_backend("pallas-bsr").prepare(corpus)
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.random((300, 5)), dtype=jnp.float32)
    v = jnp.asarray(rng.random((200, 5)), dtype=jnp.float32)
    got = float(_relative_error(op, u, v))
    expect = float(np.linalg.norm(a - np.asarray(u) @ np.asarray(v).T)
                   / np.linalg.norm(a))
    np.testing.assert_allclose(got, expect, rtol=1e-5)


def test_bsr_transpose_empty_and_huge_logical_shape():
    """A matrix whose dense form would be 1.6 GB transposes instantly when
    only a handful of blocks are occupied."""
    m = sps.coo_matrix(
        (np.ones(3, np.float32), ([5, 20000 - 1, 9000], [17, 3, 19999])),
        shape=(20000, 20000))
    b = bsr_from_scipy(m, bm=128, bk=128)
    at = bsr_transpose(b)
    assert at.shape == (20000, 20000)
    assert int(at.nnz()) == 3


# ---------------------------------------------------------------------------
# Device-side ingest against the numpy tile fill it replaced
# ---------------------------------------------------------------------------

def _numpy_operand(sp_matrix, bm, bk, bcap=None):
    """The host ingest the device fill replaced, kept as the reference:
    the forward tiles zero-filled and scattered on the host, then the
    transpose ranked by its tiles' norms and transposed tile by tile.
    Returns ``((tiles, block_cols), (tiles_t, block_cols_t))``."""
    from repro.kernels.bsr import _keep_top_per_group

    coo = sp_matrix.tocoo()
    coo.sum_duplicates()
    coo.eliminate_zeros()
    n, m = coo.shape
    data = coo.data.astype(np.float32)
    nrb, ncb = -(-n // bm), -(-m // bk)
    bi, bj = coo.row // bm, coo.col // bk
    uniq, inv = np.unique(bi.astype(np.int64) * ncb + bj, return_inverse=True)
    ubi, ubj = uniq // ncb, uniq % ncb
    sq = np.zeros(len(uniq))
    np.add.at(sq, inv, data.astype(np.float64) ** 2)
    cap = bcap or max(int(np.bincount(ubi, minlength=nrb).max(initial=1)), 1)
    keep, slot, _ = _keep_top_per_group(ubi, sq, nrb, cap)
    tiles = np.zeros((nrb, cap, bm, bk), np.float32)
    bcols = np.zeros((nrb, cap), np.int32)
    k = keep[inv]
    np.add.at(tiles, (bi[k], slot[inv[k]], coo.row[k] % bm, coo.col[k] % bk),
              data[k])
    bcols[ubi[keep], slot[keep]] = ubj[keep]
    tile_sq = (tiles.astype(np.float64) ** 2).sum(axis=(2, 3))
    oi, os_ = np.nonzero(tile_sq > 0)
    oj = bcols[oi, os_].astype(np.int64)
    cap_t = max(int(np.bincount(oj, minlength=ncb).max(initial=1)), 1)
    keep_t, slot_t, _ = _keep_top_per_group(oj, tile_sq[oi, os_], ncb, cap_t)
    tiles_t = np.zeros((ncb, cap_t, bk, bm), np.float32)
    bcols_t = np.zeros((ncb, cap_t), np.int32)
    j, s_t = oj[keep_t], slot_t[keep_t]
    tiles_t[j, s_t] = tiles[oi[keep_t], os_[keep_t]].transpose(0, 2, 1)
    bcols_t[j, s_t] = oi[keep_t]
    return (tiles, bcols), (tiles_t, bcols_t)


def _ingest_case(name):
    """``(input, scipy view of it, bm, bk, bcap)`` of one parity case."""
    rng = np.random.default_rng(11)
    if name == "odd_shapes":
        a = sps.csr_matrix(_rand_sparse(rng, 257, 129))
        return a, a, 32, 32, None
    if name == "empty_row_blocks":
        d = _rand_sparse(rng, 200, 150)
        d[32:96] = 0
        d[160:] = 0
        return sps.csr_matrix(d), sps.csr_matrix(d), 32, 32, None
    if name == "ragged_last_col_block":
        a = sps.csr_matrix(_rand_sparse(rng, 64, 100, density=0.2))
        return a, a, 16, 32, None
    if name == "duplicates":
        # repeated coordinates sum; an explicit zero and a pair that sums
        # to zero are no entries
        rows = np.array([0, 0, 5, 5, 40, 40, 7, 70, 3])
        cols = np.array([1, 1, 2, 2, 33, 33, 9, 60, 50])
        vals = np.array([1.0, 2.5, 1.0, -1.0, 0.25, 0.5, 0.0, 3.0, 2.0],
                        np.float32)
        a = sps.coo_matrix((vals, (rows, cols)), shape=(72, 65))
        return a, a, 32, 32, None
    if name == "bcap_truncates":
        d = np.zeros((64, 96), np.float32)
        d[0, 0], d[1, 32], d[2, 64] = 1.0, 5.0, 3.0   # row-block 0: 3 blocks
        d[33, 40], d[40, 70] = 2.0, 4.0               # row-block 1: 2 blocks
        return sps.csr_matrix(d), sps.csr_matrix(d), 32, 32, 2
    if name == "dense":
        d = _rand_sparse(rng, 90, 70, density=0.1)
        return d, sps.csr_matrix(d), 32, 32, None
    assert name == "spcsr"
    a = sps.csr_matrix(_rand_sparse(rng, 130, 97))
    return from_scipy(a), a, 32, 32, None


@pytest.mark.parametrize("case", [
    "odd_shapes", "empty_row_blocks", "ragged_last_col_block", "duplicates",
    "bcap_truncates", "dense", "spcsr"])
def test_device_ingest_is_bitwise_the_numpy_fill(case):
    """Both orientations' tiles and block columns, bit for bit, as the
    host fill built them; an explicit ``bcap`` truncates with the same
    warning, and the transpose holds the kept tiles only."""
    from repro.backend.pallas_bsr import PallasBsrBackend

    a, as_scipy, bm, bk, bcap = _ingest_case(case)
    with warnings.catch_warnings(record=True) as seen:
        warnings.simplefilter("always")
        if isinstance(a, SpCSR):
            op = PallasBsrBackend(bm=bm, bk=bk).prepare(a)
        else:
            op = bsr_operand(a, bm=bm, bk=bk, bcap=bcap, dtype=np.float32)
    said = [str(w.message) for w in seen]
    if bcap is None:
        assert not said
    else:
        assert said == [
            "bsr_from_scipy: 1 row-blocks exceed bcap=2; keeping the 2 "
            "largest-Frobenius-norm blocks per row-block"]
    want = _numpy_operand(as_scipy, bm, bk, bcap)
    n, m = as_scipy.shape
    assert op.bsr.shape == (n, m) and op.bsr_t.shape == (m, n)
    for got, (tiles, bcols) in zip((op.bsr, op.bsr_t), want):
        assert got.tiles.dtype == np.float32
        np.testing.assert_array_equal(
            np.asarray(got.tiles).view(np.uint32), tiles.view(np.uint32))
        np.testing.assert_array_equal(np.asarray(got.block_cols), bcols)


def _full_grid_chunk(rng, extra):
    """A 256 x 64 matrix with every 32 x 32 block occupied (so one grid and
    one ``bcap`` in both orientations) and ``extra`` more entries."""
    d = np.zeros((256, 64), np.float32)
    d[::32, ::32] = 1.0
    flat = rng.choice(d.size, size=extra, replace=False)
    d.flat[flat] += rng.random(extra).astype(np.float32) + 0.5
    return sps.csr_matrix(d)


def test_same_grid_operands_of_other_nnz_compile_nothing():
    """The fill compiles once for a tile grid and an nnz bucket: a second
    chunk of the same grid with another nnz reuses it."""
    from repro.analysis import recompile_guard

    rng = np.random.default_rng(3)
    first, second = _full_grid_chunk(rng, 500), _full_grid_chunk(rng, 900)
    assert first.nnz != second.nnz
    bsr_operand(first, bm=32, bk=32)
    with recompile_guard() as counter:
        op = bsr_operand(second, bm=32, bk=32)
        jax.block_until_ready(op)
    assert counter.count == 0
    np.testing.assert_array_equal(np.asarray(bsr_to_dense(op.bsr)),
                                  second.toarray())


def test_ingest_uploads_entries_not_tiles():
    """What goes to the device is the padded entries (value, in-tile offset,
    the transpose's order) and, per orientation, where each tile's entries
    start, not the tile volume: ``ingest_bytes`` counts it."""
    from repro.kernels.bsr import NNZ_BUCKET

    rng = np.random.default_rng(4)
    a = sps.csr_matrix(_rand_sparse(rng, 4096, 1024, density=0.01))
    stats = {}
    op = bsr_operand(a, bm=128, bk=128, stats=stats)
    starts = 4 * sum(b.nrb * b.bcap + 1 for b in (op.bsr, op.bsr_t))
    block_cols = 4 * (op.bsr.block_cols.size + op.bsr_t.block_cols.size)
    assert stats == {"ingest_bytes": 12 * NNZ_BUCKET + starts + block_cols}
    assert stats["ingest_bytes"] < (op.bsr.tiles.nbytes
                                    + op.bsr_t.tiles.nbytes) / 10


# ---------------------------------------------------------------------------
# Sparse-ingest truncation policy (the corpus-corruption bugfixes)
# ---------------------------------------------------------------------------

def test_from_scipy_keeps_largest_magnitude_on_overflow():
    row = np.array([[1.0, -9.0, 3.0, -5.0, 2.0, 0.5]], np.float32)
    with pytest.warns(UserWarning, match="largest-magnitude"):
        sp = from_scipy(sps.csr_matrix(row), cap=3)
    # the 3 largest magnitudes survive: -9, -5, 3 (the old code kept the
    # first 3 in column order — 1, -9, 3 — silently dropping the -5)
    got = np.asarray(to_dense(sp))[0]
    np.testing.assert_array_equal(got, [0, -9.0, 3.0, -5.0, 0, 0])
    assert sp.cap == 3


def test_from_coo_vectorized_matches_dense_accumulation():
    rng = np.random.default_rng(0)
    nnz = 500
    rows = rng.integers(0, 40, nnz)
    cols = rng.integers(0, 30, nnz)
    vals = rng.standard_normal(nnz).astype(np.float32)
    sp = from_coo(rows, cols, vals, (40, 30))
    dense = np.zeros((40, 30), np.float32)
    np.add.at(dense, (rows, cols), vals)
    np.testing.assert_allclose(np.asarray(to_dense(sp)), dense,
                               rtol=1e-5, atol=1e-6)


def test_from_scipy_accepts_bool_matrices():
    """Regression: the magnitude sort key must not apply unary minus to a
    bool array (numpy rejects it) — indicator/adjacency matrices ingest."""
    from repro.sparse import to_scipy

    dense = np.random.default_rng(0).random((10, 8)) > 0.6
    sp = from_scipy(sps.csr_matrix(dense))
    np.testing.assert_array_equal(to_scipy(sp).toarray(), dense)


def test_from_coo_overflow_keeps_largest():
    with pytest.warns(UserWarning, match="largest-magnitude"):
        sp = from_coo([0, 0, 0, 0], [0, 1, 2, 3], [1.0, -9.0, 3.0, -5.0],
                      (2, 4), cap=2)
    got = np.asarray(to_dense(sp))[0]
    np.testing.assert_array_equal(got, [0, -9.0, 0, -5.0])


# ---------------------------------------------------------------------------
# Distributed sharding without densifying
# ---------------------------------------------------------------------------

def _shards_to_dense(vals, cols, loc_rows, loc_cols):
    vals, cols = np.asarray(vals), np.asarray(cols)
    r, c = vals.shape[:2]
    out = np.zeros((r, c, loc_rows, loc_cols), np.float32)
    for i in range(r):
        for j in range(c):
            for lr in range(loc_rows):
                np.add.at(out[i, j, lr], cols[i, j, lr], vals[i, j, lr])
    return out


def test_distribute_csr_from_padded_matches_dense_ingest(corpus):
    from repro.core.distributed import distribute_csr, distribute_csr_from_padded

    a = np.asarray(to_dense(corpus))
    d1 = distribute_csr(a, 2, 2)
    d2 = distribute_csr_from_padded(corpus, 2, 2)
    np.testing.assert_allclose(
        _shards_to_dense(d1.values, d1.cols, 150, 100),
        _shards_to_dense(d2.values, d2.cols, 150, 100))
    np.testing.assert_allclose(
        _shards_to_dense(d1.values_t, d1.cols_t, 100, 150),
        _shards_to_dense(d2.values_t, d2.cols_t, 100, 150))


def test_sequential_solver_threads_backend(corpus):
    """Regression: the sequential engine used to drop ``config.backend`` on
    the floor, resolving products from the operand type only.  An explicit
    ``backend="jnp-csr"`` (dense input ingested to SpCSR) must agree with
    the dense run."""
    a_dense = jnp.asarray(to_dense(corpus))
    cfg = dict(k=4, iters=6, solver="sequential", block_size=2,
               sparsity=Sparsity(t_u=40, t_v=120))
    ref = EnforcedNMF(NMFConfig(**cfg)).fit(a_dense)
    csr = EnforcedNMF(NMFConfig(backend="jnp-csr", **cfg)).fit(a_dense)
    assert csr.result_.solver == ref.result_.solver == "sequential"
    np.testing.assert_allclose(csr.result_.final_error,
                               ref.result_.final_error, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(csr.result_.residual),
                               np.asarray(ref.result_.residual), atol=1e-3)


def test_solve_distributed_spcsr_never_densifies(corpus, monkeypatch):
    import repro.core.distributed as dist_mod
    import repro.sparse.csr as csr_mod

    def boom(*a, **kw):
        raise AssertionError("solve_distributed densified SpCSR input")

    monkeypatch.setattr(csr_mod, "to_dense", boom)
    monkeypatch.setattr(dist_mod, "distribute_csr", boom)
    model = EnforcedNMF(NMFConfig(k=5, iters=4, solver="distributed",
                                  sparsity=Sparsity(t_u=55))).fit(corpus)
    assert model.u_.shape == (300, 5)
    assert np.isfinite(model.result_.final_error)


# ---------------------------------------------------------------------------
# End-to-end: the Pallas BSR production path
# ---------------------------------------------------------------------------

def test_enforced_nmf_pallas_backend_matches_jnp(corpus):
    """Acceptance: a scipy CSR corpus through EnforcedNMF(backend=
    "pallas-bsr") runs BSR spmm/spmm_t + gram + fused epilogue end-to-end
    (interpret mode on CPU) and its residual history matches the jnp
    backend to <= 1e-4."""
    from repro.sparse import to_scipy

    a_scipy = to_scipy(corpus)
    cfg = NMFConfig(k=5, iters=8, solver="enforced",
                    sparsity=Sparsity(t_u=55, t_v=600))
    m_jnp = EnforcedNMF(cfg).fit(a_scipy)
    m_pal = EnforcedNMF(cfg.replace(backend="pallas-bsr")).fit(a_scipy)
    np.testing.assert_allclose(np.asarray(m_pal.result_.residual),
                               np.asarray(m_jnp.result_.residual), atol=1e-4)
    np.testing.assert_allclose(np.asarray(m_pal.result_.error),
                               np.asarray(m_jnp.result_.error), atol=1e-4)
    assert int(jnp.sum(m_pal.u_ != 0)) <= 55 + 5
    # fold-in and scoring work on the BSR operand too
    v = m_pal.transform(a_scipy)
    assert v.shape == (200, 5)
    assert m_pal.score(a_scipy) < 1.0


def test_pallas_backend_dense_input_roundtrip(corpus):
    """Explicit backend="pallas-bsr" with dense input converts at ingest."""
    a = to_dense(corpus)
    cfg = NMFConfig(k=5, iters=5, solver="als", backend="pallas-bsr")
    m = EnforcedNMF(cfg).fit(a)
    m_ref = EnforcedNMF(cfg.replace(backend=None)).fit(a)
    np.testing.assert_allclose(np.asarray(m.result_.residual),
                               np.asarray(m_ref.result_.residual), atol=1e-4)


# ---------------------------------------------------------------------------
# Chunked / bf16 capacity-axis spmm (the deleted distributed fork's local
# spmm, folded into the jnp-csr backend)
# ---------------------------------------------------------------------------

def test_spmm_chunked_matches_plain_einsum(corpus):
    """Capacity-axis chunked accumulation == the plain gather einsum, up to
    f32 summation order, across chunk widths that do / don't divide cap."""
    from repro.sparse import spmm, spmm_chunked, spmm_t, spmm_t_chunked

    x = jax.random.uniform(jax.random.PRNGKey(3), (corpus.m, 5))
    u = jax.random.uniform(jax.random.PRNGKey(4), (corpus.n, 5))
    ref = spmm(corpus, x)
    ref_t = spmm_t(corpus, u)
    for chunk in (1, 3, corpus.cap, 10 * corpus.cap):
        np.testing.assert_allclose(np.asarray(spmm_chunked(corpus, x, chunk)),
                                   np.asarray(ref), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(spmm_t_chunked(corpus, u, chunk)),
            np.asarray(ref_t), rtol=1e-5, atol=1e-5)
    # prime cap: the remainder tail keeps the peak temporary at ~chunk
    # width instead of silently collapsing to one full-width slice
    from repro.sparse.csr import _cap_chunking

    assert _cap_chunking(13, 4) == (3, 4, 1)
    assert _cap_chunking(127, 64) == (1, 64, 63)
    rng = np.random.default_rng(8)
    dense = rng.random((40, 30)).astype(np.float32)
    dense[rng.random((40, 30)) > 0.4] = 0
    prime = from_dense(jnp.asarray(dense), cap=13)
    assert prime.cap == 13
    xp = jax.random.uniform(jax.random.PRNGKey(9), (30, 5))
    np.testing.assert_allclose(np.asarray(spmm_chunked(prime, xp, chunk=4)),
                               np.asarray(spmm(prime, xp)),
                               rtol=1e-5, atol=1e-5)
    up = jax.random.uniform(jax.random.PRNGKey(10), (40, 5))
    np.testing.assert_allclose(
        np.asarray(spmm_t_chunked(prime, up, chunk=4)),
        np.asarray(spmm_t(prime, up)), rtol=1e-5, atol=1e-5)


def test_spmm_chunked_bf16_parity(corpus):
    """bf16 gather with f32 accumulation tracks the f32 path within bf16
    tolerance (the fork's traffic-halving trick)."""
    from repro.sparse import spmm, spmm_chunked

    x = jax.random.uniform(jax.random.PRNGKey(5), (corpus.m, 5))
    ref = np.asarray(spmm(corpus, x))
    out = np.asarray(spmm_chunked(corpus, x, chunk=4,
                                  compute_dtype=jnp.bfloat16))
    assert out.dtype == ref.dtype  # result dtype is preserved
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2 * ref.max())


def test_jnp_csr_backend_size_trigger(monkeypatch, corpus):
    """Once the (rows, cap, k) temporary crosses the trigger, the jnp-csr
    backend products switch to the chunked path — same results."""
    from repro.backend import jnp_backends

    be = get_backend("jnp-csr")
    x = jax.random.uniform(jax.random.PRNGKey(6), (corpus.m, 5))
    u = jax.random.uniform(jax.random.PRNGKey(7), (corpus.n, 5))
    plain = np.asarray(be.matmul(corpus, x))
    plain_t = np.asarray(be.matmul_t(corpus, u))
    monkeypatch.setattr(jnp_backends, "SPMM_CHUNK_ELEMS", 1)
    monkeypatch.setattr(jnp_backends, "SPMM_CHUNK_WIDTH", 3)
    assert jnp_backends._chunked_spmm_config(corpus, 5) == (True, None)
    np.testing.assert_allclose(np.asarray(be.matmul(corpus, x)), plain,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(be.matmul_t(corpus, u)), plain_t,
                               rtol=1e-5, atol=1e-5)
    # default trigger leaves small problems on the one-shot einsum path
    monkeypatch.undo()
    assert jnp_backends._chunked_spmm_config(corpus, 5) == (False, None)
