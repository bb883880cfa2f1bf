"""Plain enforced-sparsity ALS (arXiv:1510.05237, Alg. 2), the reference
that the fit cells are checked against.

Dense float32 on the device, products at :mod:`bench.reference.precision`'s
named precision, exact sort-based top-t, no kernels and nothing of the
program.  One iteration, from ``U``::

    V = top-t_v(relu(A^T U (U^T U)^-1))
    U = top-t_u(relu(A V (V^T V)^-1))
    E = ||A - U V^T||_F / ||A||_F

The ``k x k`` systems are solved directly (``jnp.linalg.solve``).  The
top-t keeps every entry at or above the t-th largest value of the whole
factor (``mode="global"`` budgets).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.precision import dot


class Fit(NamedTuple):
    u: jax.Array       # (n, k)
    v: jax.Array       # (m, k)
    error: jax.Array   # (iters,) E after each iteration


@functools.partial(jax.jit, static_argnums=3)
def _scatter(rows, cols, vals, shape):
    return jnp.zeros(shape, jnp.float32).at[rows, cols].add(vals)


def dense(a) -> jax.Array:
    """The scipy sparse matrix ``a`` as a dense float32 device array, built
    on the device from its COO entries.  The entries are padded with zeros
    to a power of two, so that corpora of one shape whose nnz differ by seed
    share one compiled scatter."""
    coo = a.tocoo()
    size = 1 << max(coo.nnz - 1, 0).bit_length()

    def padded(x, dtype):
        out = np.zeros(size, dtype)
        out[:coo.nnz] = x
        return jnp.asarray(out)

    return _scatter(padded(coo.row, np.int32), padded(coo.col, np.int32),
                    padded(coo.data, np.float32), coo.shape)


def top_t(x: jax.Array, t: Optional[int]) -> jax.Array:
    """Keep the ``t`` largest entries of the non-negative ``x``, and every
    entry equal to the t-th."""
    if t is None or t >= x.size:
        return x
    tau = jnp.sort(x.ravel())[x.size - t]
    return jnp.where(x >= tau, x, 0.0)


def _solve(gram: jax.Array, rhs: jax.Array) -> jax.Array:
    """``X`` with ``X @ gram = rhs``."""
    return jnp.linalg.solve(gram, rhs.T).T


@functools.partial(jax.jit, static_argnames=("iters", "t_u", "t_v",
                                             "precision"))
def als(a: jax.Array, u0: jax.Array, iters: int, t_u: Optional[int],
        t_v: Optional[int], precision: str = "highest") -> Fit:
    """``iters`` iterations from ``u0``; E after each."""
    a_norm = jnp.sqrt(jnp.sum(a * a))

    def body(carry, _):
        u, _v = carry
        v = top_t(jnp.maximum(_solve(dot(u.T, u, precision),
                                     dot(a.T, u, precision)), 0.0), t_v)
        u = top_t(jnp.maximum(_solve(dot(v.T, v, precision),
                                     dot(a, v, precision)), 0.0), t_u)
        r = a - dot(u, v.T, precision)
        return (u, v), jnp.sqrt(jnp.sum(r * r)) / a_norm

    v0 = jnp.zeros((a.shape[1], u0.shape[1]), jnp.float32)
    (u, v), err = jax.lax.scan(body, (u0.astype(jnp.float32), v0), None,
                               length=iters)
    return Fit(u, v, err)


def fit_host(a: jax.Array, u0, iters: int, t_u: Optional[int],
             t_v: Optional[int], precision: str = "highest"):
    """:func:`als` with its result fetched to the host as float64.  XLA's
    own products and solves follow ``precision`` too."""
    with jax.default_matmul_precision(precision):
        res = als(a, jnp.asarray(u0), iters, t_u, t_v, precision)
    return Fit(*(np.asarray(x, np.float64) for x in res))
