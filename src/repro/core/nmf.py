"""Projected ALS NMF (paper Algorithm 1) and the shared ALS engine.

The engine runs a fixed number of jit-compiled iterations (the paper's
"do until convergence" with a max-iteration budget) and records the paper's
metrics per iteration: relative residual R, relative error E, and the running
max NNZ(U)+NNZ(V) (Fig. 6).  Sparsity enforcement (Algorithm 2) is injected
as ``sparsify_u`` / ``sparsify_v`` callables — identity recovers Algorithm 1.

The hot-spot products A @ V / A^T @ U / X^T X dispatch through the pluggable
matmul-backend layer (:mod:`repro.backend`): dense XLA, padded-CSR
gather/scatter, or the Pallas BSR MXU kernels, auto-selected from the
operand type or forced with ``backend=...``.

The engine is mesh-native: all residual / error / nnz bookkeeping and the
Gram reductions go through the backend's ``reduce_u`` / ``reduce_v`` /
``reduce_all`` hooks, which are identity for the local backends and mesh
``psum``s for :class:`repro.backend.sharded.ShardedBackend` — so the same
scan loop runs single-device or SPMD inside a shard_map, with sharding as
an execution property rather than a second algorithm.  The streaming
sibling (:mod:`repro.core.online`) shares ``solve_gram`` / ``_epilogue`` /
``_resolve`` and the same backend discipline for its sufficient-statistics
update.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.core import metrics as M
from repro.kernels.bsr import BSROperand
from repro.sparse.csr import SpCSR

Sparsifier = Callable[[jax.Array], jax.Array]
Matrix = Union[jax.Array, SpCSR, BSROperand]

__all__ = ["NMFResult", "init_u0", "als_nmf", "factor_gram", "solve_gram"]


class NMFResult(NamedTuple):
    u: jax.Array           # (n, k)
    v: jax.Array           # (m, k)
    residual: jax.Array    # (iters,) R per iteration
    error: jax.Array       # (iters,) E per iteration
    max_nnz: jax.Array     # scalar — max NNZ(U)+NNZ(V) over the run
    nnz_u: jax.Array       # (iters,)
    nnz_v: jax.Array       # (iters,)
    health: jax.Array = jnp.int32(-1)  # first unhealthy iteration, -1 = ok


#: relative-residual ceiling for the in-scan health monitor; R is
#: ||U_i - U_{i-1}||_F / ||U_i||_F, which sits in [0, O(1)] for any sane
#: trajectory — crossing this means the factors are diverging even if
#: every entry is still technically finite
_RESIDUAL_BLOWUP = 1e6


def init_u0(key: jax.Array, n: int, k: int, nnz: Optional[int] = None) -> jax.Array:
    """Random non-negative initial guess with ``nnz`` nonzeros (paper Fig. 6
    varies the initial-guess sparsity)."""
    u0 = jax.random.uniform(key, (n, k), minval=0.0, maxval=1.0)
    if nnz is not None and nnz < n * k:
        from repro.core.topk import topk_project_exact

        u0 = topk_project_exact(u0, nnz)
    return u0


def factor_gram(x: jax.Array) -> jax.Array:
    """``x^T x`` contracted at full float32 precision (``HIGHEST``), like
    the kernels' Grams: XLA's default precision on a TPU is one bfloat16
    pass, whose rounding the solve against this Gram then carries."""
    return jnp.dot(x.T, x, precision=jax.lax.Precision.HIGHEST)


def solve_gram(gram: jax.Array, rhs: jax.Array, ridge: float = 1e-8) -> jax.Array:
    """Solve  X @ gram = rhs  for X, i.e. X = rhs @ gram^{-1}, via Cholesky
    with a scale-aware ridge (gram is k x k PSD; k is small)."""
    k = gram.shape[0]
    jitter = ridge * (jnp.trace(gram) / k + 1e-30)
    g = gram + jitter * jnp.eye(k, dtype=gram.dtype)
    cho = jax.scipy.linalg.cho_factor(g)
    # gram is symmetric: solve gram @ X^T = rhs^T
    return jax.scipy.linalg.cho_solve(cho, rhs.T).T


def _resolve(a: Matrix, backend):
    """Backend for ``a``: a registry name, an already-constructed
    :class:`~repro.backend.base.MatmulBackend` instance (how the sharded
    execution layer injects its mesh-collective hooks), or ``None`` for
    type-based auto-selection."""
    if backend is not None and not isinstance(backend, str):
        return backend
    from repro.backend import resolve_backend

    return resolve_backend(a, backend)


def _matmul_t(a: Matrix, u: jax.Array, backend: Optional[str] = None) -> jax.Array:
    """A^T @ u through the backend layer."""
    return _resolve(a, backend).matmul_t(a, u)


def _matmul(a: Matrix, v: jax.Array, backend: Optional[str] = None) -> jax.Array:
    """A @ v through the backend layer."""
    return _resolve(a, backend).matmul(a, v)


def _sqnorm(a: Matrix) -> jax.Array:
    """||A||_F^2 without densifying sparse operands."""
    if isinstance(a, (SpCSR, BSROperand)):
        return a.sqnorm()
    return jnp.sum(a.astype(jnp.float32) ** 2)


def _bsr_relative_error(a: BSROperand, u: jax.Array, v: jax.Array,
                        a_sqnorm: jax.Array) -> jax.Array:
    """||A - UV^T||_F / ||A||_F with the cross term <A, UV^T> contracted
    tile-wise (:func:`repro.kernels.bsr.bsr_dot_uv`), which mattered at
    exactly the large-A scale this operand targets."""
    from repro.kernels.bsr import bsr_dot_uv

    uf = u.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    cross = bsr_dot_uv(a.bsr, u, v)
    hi = jax.lax.Precision.HIGHEST
    approx_sq = jnp.sum(jnp.dot(uf.T, uf, precision=hi)
                        * jnp.dot(vf.T, vf, precision=hi))
    err_sq = jnp.maximum(a_sqnorm - 2.0 * cross + approx_sq, 0.0)
    return jnp.sqrt(err_sq) / jnp.sqrt(jnp.maximum(a_sqnorm, 1e-30))


def _relative_error(a: Matrix, u: jax.Array, v: jax.Array,
                    a_sqnorm: Optional[jax.Array] = None) -> jax.Array:
    """E = ||A - U V^T||_F / ||A||_F for any operand type."""
    if a_sqnorm is None:
        a_sqnorm = _sqnorm(a)
    if isinstance(a, BSROperand):
        return _bsr_relative_error(a, u, v, a_sqnorm)
    if isinstance(a, SpCSR):
        rows = jnp.broadcast_to(jnp.arange(a.n)[:, None], a.cols.shape)
        return M.relative_error_sparse(
            a.values.ravel(), rows.ravel(), a.cols.ravel(), a_sqnorm, u, v)
    return M.relative_error(a, u, v)


def _epilogue(x: jax.Array, sparsify: Optional[Sparsifier]) -> jax.Array:
    """Non-negativity projection + sparsity enforcement.  Sparsifiers that
    declare ``fuses_relu`` (e.g. :class:`repro.core.topk.FusedReluTopK`)
    own the relu too, running both as one fused pass."""
    if sparsify is None:
        return jnp.maximum(x, 0.0)
    if getattr(sparsify, "fuses_relu", False):
        return sparsify(x)
    return sparsify(jnp.maximum(x, 0.0))


@functools.partial(
    jax.jit,
    static_argnames=("iters", "sparsify_u", "sparsify_v", "track_error",
                     "backend"),
)
def als_nmf(
    a: Matrix,
    u0: jax.Array,
    iters: int = 75,
    sparsify_u: Optional[Sparsifier] = None,
    sparsify_v: Optional[Sparsifier] = None,
    track_error: bool = True,
    backend: Optional[str] = None,
) -> NMFResult:
    """Projected ALS (Alg. 1) / Enforced Sparsity ALS (Alg. 2).

    One iteration:
      V = relu(A^T U (U^T U)^{-1});  V = sparsify_v(V)
      U = relu(A V (V^T V)^{-1});    U = sparsify_u(U)

    ``backend`` names a registered matmul backend (``"jnp-dense"``,
    ``"jnp-csr"``, ``"pallas-bsr"``) or is a ``MatmulBackend`` instance
    (the sharded execution layer passes one carrying its mesh axes);
    ``None`` auto-selects from the operand type, which reproduces the
    legacy dispatch bit-for-bit.

    All scalar bookkeeping is phrased through the backend's reduction
    hooks, so under a shard_map the residual / error / nnz traces are the
    *global* quantities while ``a``, ``u``, and ``v`` stay local shards.
    """
    be = _resolve(a, backend)
    n, k = u0.shape
    m = a.shape[1]
    with jax.named_scope("als.error"):
        a_sqnorm = be.sqnorm(a)  # E's constant, once a fit

    def error_of(u, v):
        if not track_error:
            return jnp.float32(0.0)
        return be.relative_error(a, u, v, a_sqnorm)

    def body(carry, _):
        u, _v, max_nnz, health, it = carry
        # each half-step's sparse product and Gram read the same factor, so
        # they come from one backend hook: fused into a single kernel sweep
        # on the Pallas path, separate matmul+gram calls (bit-for-bit the
        # previous body) everywhere else.  The named scopes only label the
        # ops (their ``op_name`` metadata) for a profiler trace.
        with jax.named_scope("als.v"):
            with jax.named_scope("product"):
                atu, gu = be.matmul_t_with_gram(a, u)
            with jax.named_scope("solve"):
                v = solve_gram(be.reduce_u(gu), atu)
            with jax.named_scope("topk"):
                v = _epilogue(v, sparsify_v)

        with jax.named_scope("als.u"):
            with jax.named_scope("product"):
                av, gv = be.matmul_with_gram(a, v)
            with jax.named_scope("solve"):
                u_new = solve_gram(be.reduce_v(gv), av)
            with jax.named_scope("topk"):
                u_new = _epilogue(u_new, sparsify_u)

        with jax.named_scope("als.health"):
            # relative residual R = ||U_i - U_{i-1}||_F / ||U_i||_F with
            # the squared norms reduced over U's shard axes (identity
            # locally)
            num = be.reduce_u(jnp.sum(jnp.square(u_new - u)))
            den = be.reduce_u(jnp.sum(jnp.square(u_new)))
            r = jnp.sqrt(num) / jnp.maximum(jnp.sqrt(den), 1e-30)
        with jax.named_scope("als.error"):
            e = error_of(u_new, v)
        with jax.named_scope("als.health"):
            nu = be.reduce_u(jnp.sum(u_new != 0))
            nv = be.reduce_v(jnp.sum(v != 0))
            max_nnz = jnp.maximum(max_nnz, nu + nv)

            # FitHealth monitor: record the first iteration whose factors
            # went non-finite or whose residual exploded.  Counting
            # non-finite entries (rather than jnp.all(isfinite)) keeps the
            # check a plain sum, so it rides the existing psum reduction
            # hooks on a mesh.
            bad_u = be.reduce_u(
                jnp.sum(~jnp.isfinite(u_new)).astype(jnp.int32))
            bad_v = be.reduce_v(jnp.sum(~jnp.isfinite(v)).astype(jnp.int32))
            bad = ((bad_u + bad_v > 0) | ~jnp.isfinite(r)
                   | (r > _RESIDUAL_BLOWUP))
            health = jnp.where((health < 0) & bad, it, health)
        return (u_new, v, max_nnz, health, it + 1), (r, e, nu, nv)

    init_nnz = be.reduce_u(jnp.sum(u0 != 0))
    v0 = jnp.zeros((m, k), dtype=u0.dtype)
    (u, v, max_nnz, health, _), (rs, es, nus, nvs) = jax.lax.scan(
        body,
        (u0, v0, init_nnz.astype(jnp.int32), jnp.int32(-1), jnp.int32(0)),
        None, length=iters,
    )
    return NMFResult(u, v, rs, es, max_nnz, nus, nvs, health)
