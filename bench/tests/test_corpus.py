"""The vectorised planted-journal generator against the program's
per-document loop, and its determinism per seed."""
import numpy as np
import pytest

from bench import corpus as C

SHAPE = dict(n_terms=2000, n_docs=600, n_journals=5, terms_per_doc=60,
             topic_strength=0.7, zipf_exponent=1.1)


def _block_share(a, journal, n_terms, n_journals):
    """Share of each document's stored mass in its own journal's block."""
    block = n_terms // n_journals
    csc = a.tocsc()
    shares = []
    for j in range(a.shape[1]):
        rows = csc[:, j].indices
        own = (rows // block) == journal[j]
        shares.append(own.mean())
    return float(np.mean(shares))


def test_same_seed_same_corpus_other_seed_other_corpus():
    big = 2**31 + 12345
    a = C.journal_corpus(big, **SHAPE).a
    b = C.journal_corpus(big, **SHAPE).a
    c = C.journal_corpus(big + 1, **SHAPE).a
    assert (a != b).nnz == 0
    assert a.shape == c.shape and (a != c).nnz > 0


def test_matches_the_program_generator_in_shape_and_planted_blocks():
    from repro.data import synthetic_journal_corpus
    from repro.sparse.csr import to_scipy

    ours = C.journal_corpus(3, **SHAPE)
    theirs, journal = synthetic_journal_corpus(
        n_terms=2000, n_docs=600, n_journals=5, terms_per_doc=60,
        topic_strength=0.7, seed=3)
    theirs = to_scipy(theirs).tocsr()
    assert ours.a.shape == theirs.shape
    assert ours.a.dtype == np.float32
    assert abs(ours.a.nnz - theirs.nnz) / theirs.nnz < 0.03
    # both put about the same share of a document's terms in its block
    s_ours = _block_share(ours.a, ours.journal, 2000, 5)
    s_theirs = _block_share(theirs, journal, 2000, 5)
    assert s_ours == pytest.approx(s_theirs, abs=0.03)
    assert s_ours > 0.5
    # rows divided by their nnz: a row's values are its counts over its df
    for a in (ours.a, theirs):
        row = a.getrow(int(np.argmax(np.diff(a.indptr))))
        assert row.data.min() == pytest.approx(1.0 / row.nnz, rel=1e-6)


def test_int32_seed_takes_any_whole_number():
    s = C.int32_seed(2**40 + 3, C.FACTORS)
    assert 0 <= s < 2**31
    assert s == C.int32_seed(2**40 + 3, C.FACTORS)
    assert s != C.int32_seed(3, C.FACTORS)
