"""Operations and bytes of a streamed fit's product launches, from each
chunk's nnz and shapes, counted with :func:`bench.work.halfstep` as the
algorithm needs them (so a share of the roofline stays under 100% whatever
operand format carries the work).

Per chunk ``A_c`` (n terms x m_c documents, nnz_c entries), a streamed fit
runs ``passes`` online passes of both half-steps (``A_c^T U`` with
``U^T U``, then ``A_c V_c`` with ``V_c^T V_c``), then one product in the
fold-in (``A_c^T U``; its Gram ``U^T U`` is taken once a fit, outside the
product launches) and one in the seed statistics (``A_c V_c``; its Gram
too is taken outside them): each of those two a half-step without its
Gram.
"""
from __future__ import annotations

from typing import Sequence

from bench.work import VALUE_BYTES, Work, halfstep


def product(nnz: int, rows_out: int, cols_in: int, k: int) -> Work:
    """A half-step's sparse product alone: :func:`halfstep` less its Gram's
    operations and the Gram's write."""
    h = halfstep(nnz, rows_out, cols_in, k)
    return Work(h.flops - 2 * cols_in * k * k,
                h.bytes - k * k * VALUE_BYTES)


def fit_products(n: int, chunks: Sequence[Sequence[int]], k: int,
                 passes: int) -> Work:
    """The product launches of one streamed fit; ``chunks`` lists each
    chunk's ``(documents, nnz)``."""
    total = Work(0.0, 0.0)
    for m_c, nnz in chunks:
        total = (total
                 + (halfstep(nnz, m_c, n, k) + halfstep(nnz, n, m_c, k))
                 * passes
                 + product(nnz, m_c, n, k) + product(nnz, n, m_c, k))
    return total
