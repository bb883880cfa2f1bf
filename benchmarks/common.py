"""Shared helpers for the paper-figure benchmarks."""
from __future__ import annotations

import jax

from repro.core import init_u0
from repro.data import synthetic_journal_corpus


def reuters_like(seed=0):
    """Reuters-21578-scale matrix (6424 x 1985, §3.1) — synthetic stand-in."""
    a_sp, dj = synthetic_journal_corpus(
        n_terms=6424, n_docs=1985, n_journals=5, terms_per_doc=80, seed=seed
    )
    return a_sp, dj


def pubmed_like(seed=0, small=False):
    """PubMed-journals-scale matrix (20112 x 7510, §3.2)."""
    if small:  # fast variant for CI-style runs
        return synthetic_journal_corpus(
            n_terms=4000, n_docs=1500, n_journals=5, terms_per_doc=70, seed=seed
        )
    return synthetic_journal_corpus(
        n_terms=20112, n_docs=7510, n_journals=5, terms_per_doc=90, seed=seed
    )


def u0_for(a, k, seed=2, nnz=None):
    return init_u0(jax.random.PRNGKey(seed), a.shape[0], k, nnz=nnz)
