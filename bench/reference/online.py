"""Plain online ALS over document chunks with sufficient statistics, the
reference that the streamed cell is checked against.

The memory-limited formulation of Nguyen & Ho (arXiv:1506.08938): the
corpus ``A`` (n terms x m documents) is read in document chunks ``A_c``
(n x m_c), ``[lo, hi)`` of width ``chunk_docs`` in order (the last one
ragged), and ``U`` is refined against two statistics of everything read so
far, ``S_B = sum A_c V_c`` (n x k) and ``S_G = sum V_c^T V_c`` (k x k).
From ``U_0`` and zero statistics, for each chunk, ``passes`` times::

    V_c = top-t_c(relu(A_c^T U (U^T U)^-1))      t_c = max(1, round(t_v m_c / m))
    G   = forget S_G + V_c^T V_c
    B   = forget S_B + A_c V_c
    U   = top-t_u(relu(B G^-1))

every pass starting again from the statistics before the chunk, and only
the last pass's ``G``, ``B`` becoming the new ``S_G``, ``S_B``.  The
loadings returned are the fold-in of the whole corpus with the final
``U`` frozen, under the whole-corpus budget::

    V = top-t_v(relu(A^T U (U^T U)^-1))

Dense float32 on the device, products at :mod:`bench.reference.precision`'s
named precision, ``k x k`` systems solved directly, exact sort-based top-t
keeping every entry equal to the t-th (``"global"`` budgets); nothing of
the program.

Departures from arXiv:1506.08938, which alternates exact non-negative
least-squares solves over all blocks until convergence:

* each subproblem is solved as unconstrained least squares and projected
  (``relu``), then held to its non-zero budget (top-t), the enforced-sparsity
  ALS of arXiv:1510.05237;
* the corpus is read once (one epoch): a chunk refines ``U`` against the
  statistics of the chunks read so far, ``passes`` times, and is not
  revisited (online, gensim-style), so the result depends on chunk order;
* ``t_v`` binds each chunk's loadings pro rata to its documents, and the
  loadings returned come from one frozen-U pass over the whole corpus;
* ``forget`` below 1 decays older chunks' statistics.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.als import _solve, top_t
from bench.reference.precision import dot


class Stream(NamedTuple):
    u: jax.Array   # (n, k) after the last chunk
    v: jax.Array   # (m, k) the fold-in with that U frozen


def schedule(m: int, chunk_docs: int) -> List[Tuple[int, int]]:
    """The ``[lo, hi)`` document ranges of the chunks, in order."""
    return [(lo, min(lo + chunk_docs, m)) for lo in range(0, m, chunk_docs)]


def chunk_budget(t_v: Optional[int], m_c: int, m: int) -> Optional[int]:
    """A chunk's share of the whole-corpus budget ``t_v``."""
    return None if t_v is None else max(1, round(t_v * m_c / m))


def _loadings(a, u, t, precision):
    return top_t(jnp.maximum(_solve(dot(u.T, u, precision),
                                    dot(a.T, u, precision)), 0.0), t)


@functools.partial(jax.jit, static_argnames=("passes", "t_u", "t_c",
                                             "precision"))
def chunk_step(a_c, u, s_g, s_b, forget, passes: int, t_u: Optional[int],
               t_c: Optional[int], precision: str = "highest"):
    """``passes`` passes over the chunk ``a_c`` from the statistics
    ``(s_g, s_b)`` before it; ``(U, G, B)`` after the last pass."""
    def body(carry, _):
        u, _g, _b = carry
        v = _loadings(a_c, u, t_c, precision)
        g = forget * s_g + dot(v.T, v, precision)
        b = forget * s_b + dot(a_c, v, precision)
        return (top_t(jnp.maximum(_solve(g, b), 0.0), t_u), g, b), None

    (u, g, b), _ = jax.lax.scan(body, (u, s_g, s_b), None, length=passes)
    return u, g, b


@functools.partial(jax.jit, static_argnames=("t_v", "precision"))
def fold_in(a, u, t_v: Optional[int], precision: str = "highest"):
    """The loadings of every document of ``a`` with ``u`` frozen."""
    return _loadings(a, u, t_v, precision)


def stream(a: jax.Array, u0, chunk_docs: int, passes: int,
           t_u: Optional[int], t_v: Optional[int], forget: float = 1.0,
           precision: str = "highest") -> Stream:
    """One epoch over the dense corpus ``a`` from ``u0``, then the
    fold-in."""
    n, m = a.shape
    k = u0.shape[1]
    u = jnp.asarray(u0, jnp.float32)
    s_g = jnp.zeros((k, k), jnp.float32)
    s_b = jnp.zeros((n, k), jnp.float32)
    for lo, hi in schedule(m, chunk_docs):
        u, s_g, s_b = chunk_step(a[:, lo:hi], u, s_g, s_b,
                                 jnp.float32(forget), passes, t_u,
                                 chunk_budget(t_v, hi - lo, m), precision)
    return Stream(u, fold_in(a, u, t_v, precision))


def stream_host(a: jax.Array, u0, chunk_docs: int, passes: int,
                t_u: Optional[int], t_v: Optional[int], forget: float = 1.0,
                precision: str = "highest") -> Stream:
    """:func:`stream` with its result fetched to the host as float64.
    XLA's own products and solves follow ``precision`` too."""
    with jax.default_matmul_precision(precision):
        res = stream(a, u0, chunk_docs, passes, t_u, t_v, forget, precision)
    return Stream(*(np.asarray(x, np.float64) for x in res))


def fold_in_host(a: jax.Array, u, t_v: Optional[int],
                 precision: str = "highest") -> np.ndarray:
    """:func:`fold_in` of the host factor ``u``, fetched as float64."""
    with jax.default_matmul_precision(precision):
        v = fold_in(a, jnp.asarray(u, jnp.float32), t_v, precision)
    return np.asarray(v, np.float64)
