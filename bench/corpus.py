"""Planted-journal term x document corpora, made from a seed.

A vectorised copy of the program's ``synthetic_journal_corpus``: every
journal owns a block of ``n_terms // n_journals`` signature terms; a
document of journal j draws a binomial ``topic_strength`` share of its
terms Zipf-distributed inside j's block and the rest from a Zipf background
over the whole vocabulary; repeated draws of a term add up to its count;
each term row is divided by its number of documents (row normalisation by
nnz).  The draws are made for all documents at once, so a corpus of the
paper's PubMed-journals width is built in well under a second.

Every stream is keyed by ``(seed, stream)``, so a seed gives the same
corpus and initial factors in every run, and any whole
number is a valid seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sp

#: independent random streams drawn from one seed
CORPUS, FACTORS, SAMPLE = range(3)


def rng_for(seed: int, stream: int, *more: int) -> np.random.Generator:
    """The generator of one stream of ``seed``."""
    return np.random.default_rng([int(seed) % (1 << 64), stream, *more])


def int32_seed(seed: int, stream: int, *more: int) -> int:
    """A non-negative 32-bit integer drawn from ``(seed, stream)``, for
    APIs such as ``jax.random.key`` that take no wider seed."""
    return int(rng_for(seed, stream, *more).integers(0, 2**31 - 1))


def zipf_weights(size: int, exponent: float) -> np.ndarray:
    w = 1.0 / np.arange(1, size + 1, dtype=np.float64) ** exponent
    return w / w.sum()


def draw_documents(rng: np.random.Generator, lengths: np.ndarray,
                   n_terms: int, n_journals: int, topic_strength: float,
                   zipf_exponent: float):
    """Term counts of documents of the given lengths (term draws each).

    Returns ``(doc, term, count, journal)``: one entry per distinct
    (document, term) pair, sorted by document then term, and each
    document's journal."""
    lengths = np.asarray(lengths, np.int64)
    m = lengths.shape[0]
    block = n_terms // n_journals
    journal = rng.integers(0, n_journals, size=m)
    n_topic = rng.binomial(lengths, topic_strength)
    n_bg = lengths - n_topic
    topic = (rng.choice(block, size=int(n_topic.sum()),
                        p=zipf_weights(block, zipf_exponent))
             + np.repeat(journal * block, n_topic))
    bg = rng.choice(n_terms, size=int(n_bg.sum()),
                    p=zipf_weights(n_terms, zipf_exponent))
    doc = np.concatenate([np.repeat(np.arange(m), n_topic),
                          np.repeat(np.arange(m), n_bg)])
    key, count = np.unique(doc * n_terms + np.concatenate([topic, bg]),
                           return_counts=True)
    return key // n_terms, key % n_terms, count, journal


@dataclasses.dataclass(frozen=True)
class Corpus:
    a: sp.csr_matrix        # (n_terms, n_docs) float32, rows divided by nnz
    journal: np.ndarray     # (n_docs,) planted journal of each document
    df: np.ndarray          # (n_terms,) documents holding each term


def journal_corpus(seed: int, n_terms: int, n_docs: int, n_journals: int,
                   terms_per_doc: int, topic_strength: float,
                   zipf_exponent: float) -> Corpus:
    """The training corpus of ``seed``."""
    doc, term, count, journal = draw_documents(
        rng_for(seed, CORPUS), np.full(n_docs, terms_per_doc), n_terms,
        n_journals, topic_strength, zipf_exponent)
    df = np.bincount(term, minlength=n_terms)
    vals = (count.astype(np.float32)
            / np.maximum(df[term], 1).astype(np.float32))
    a = sp.csr_matrix((vals, (term, doc)), shape=(n_terms, n_docs),
                      dtype=np.float32)
    return Corpus(a=a, journal=journal, df=df)

