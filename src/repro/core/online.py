"""Online (streaming) ALS engine: backend-aware sufficient-statistics NMF.

The batch engine (:func:`repro.core.nmf.als_nmf`) needs the whole corpus
resident; the online engine needs only one document mini-batch at a time
plus two sufficient-statistics accumulators — the memory-limited
distributed-NMF formulation of Nguyen & Ho (arXiv:1506.08938):

    stats.av = sum_c A_c V_c      (n, k)   — row-sharded like U on a mesh
    stats.gv = sum_c V_c^T V_c    (k, k)   — replicated on a mesh

One :func:`online_als_step` refines ``U`` against the *whole stream seen so
far* (not just the newest chunk, gensim-style online NMF) with ``iters``
inner passes over the chunk:

    V_c = top-t_v( relu( A_c^T U G_U^{-1} ) )        G_U = reduce_u(U^T U)
    G_V = forget * stats.gv + reduce_v(V_c^T V_c)
    AV  = forget * stats.av + A_c V_c
    U   = top-t_u( relu( AV G_V^{-1} ) )

Every product and every reduction goes through the pluggable
:class:`~repro.backend.base.MatmulBackend` protocol, exactly like the batch
engine: with a local backend (``jnp-dense`` / ``jnp-csr`` / ``pallas-bsr``)
the ``reduce_*`` hooks are identity and the step is the legacy
single-device ``partial_fit`` loop, to float32 rounding; with a
:class:`repro.backend.sharded.ShardedBackend` (inside a shard_map — see
:func:`repro.backend.sharded.make_sharded_online`) the chunk's columns are
sharded over the mesh's ``cols`` axis, the statistics reductions become
``psum``s, and the *same* scan loop is online NMF on a pod.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.core.nmf import Matrix, Sparsifier, _epilogue, _resolve, solve_gram

__all__ = ["OnlineStats", "OnlineStepResult", "init_online_stats",
           "online_als_step", "seed_online_stats"]


class OnlineStats(NamedTuple):
    """Sufficient statistics of the stream seen so far (a jax pytree)."""

    av: jax.Array  # (n, k)  sum over chunks of A_c @ V_c
    gv: jax.Array  # (k, k)  sum over chunks of V_c^T @ V_c


class OnlineStepResult(NamedTuple):
    u: jax.Array        # (n, k) refined factor
    v: jax.Array        # (m_chunk, k) loadings of this chunk's documents
    stats: OnlineStats  # accumulators including this chunk's contribution
    health: jax.Array = jnp.int32(-1)  # first unhealthy inner pass, -1 = ok


def init_online_stats(n: int, k: int, dtype=jnp.float32) -> OnlineStats:
    """Zero accumulators for a fresh stream."""
    return OnlineStats(av=jnp.zeros((n, k), dtype),
                       gv=jnp.zeros((k, k), dtype))


def seed_online_stats(a: Matrix, v: jax.Array,
                      backend=None) -> OnlineStats:
    """Statistics equivalent to having streamed ``a`` with loadings ``v`` —
    how ``fit`` seeds ``partial_fit`` continuation (one extra backend spmm,
    ~1/(2*iters) of the fit, instead of pinning the corpus)."""
    be = _resolve(a, backend)
    av, gv = be.matmul_with_gram(a, v)
    return OnlineStats(av=av, gv=be.reduce_v(gv))


@functools.partial(
    jax.jit,
    static_argnames=("iters", "sparsify_u", "sparsify_v", "backend"),
)
def online_als_step(
    a_chunk: Matrix,
    u: jax.Array,
    stats: OnlineStats,
    forget: Union[jax.Array, float] = 1.0,
    *,
    iters: int = 1,
    sparsify_u: Optional[Sparsifier] = None,
    sparsify_v: Optional[Sparsifier] = None,
    backend=None,
) -> OnlineStepResult:
    """One online-ALS update over a document mini-batch (n, m_chunk).

    Each of the ``iters`` inner passes recomputes the chunk statistics from
    the *pre-chunk* accumulators (so inner refinement never double-counts
    the chunk); only the final pass's contribution is committed into the
    returned :class:`OnlineStats`.  ``forget`` < 1 exponentially decays the
    old stream (traced, so sweeping it does not recompile).

    ``backend`` follows the batch-engine convention: a registry name, a
    ``MatmulBackend`` instance (how the sharded execution layer injects its
    mesh collectives), or ``None`` for operand-type auto-selection — which
    reproduces the legacy estimator loop on one device (to float32
    rounding: XLA fuses the compiled loop's arithmetic, which the eager
    loop ran op by op).

    The loop body names its work for a profiler trace, as the batch engine
    does: ``online.v/product``, ``online.v/solve``, ``online.v/topk``, the
    same three under ``online.u`` (its ``solve`` adds the chunk to the
    statistics), and ``online.health``.
    """
    be = _resolve(a_chunk, backend)
    k = u.shape[1]
    m_chunk = a_chunk.shape[1]
    forget = jnp.asarray(forget, dtype=u.dtype)

    def body(carry, _):
        u, _v, _gv, _av, health, it = carry
        # fused half-step pairs, like the batch engine: one kernel sweep
        # computes the chunk product and the Gram on the Pallas path
        with jax.named_scope("online.v"):
            with jax.named_scope("product"):
                atu, gu = be.matmul_t_with_gram(a_chunk, u)
            with jax.named_scope("solve"):
                v = solve_gram(be.reduce_u(gu), atu)
            with jax.named_scope("topk"):
                v = _epilogue(v, sparsify_v)
        with jax.named_scope("online.u"):
            with jax.named_scope("product"):
                av_c, gv_c = be.matmul_with_gram(a_chunk, v)
            # the normal equations of the whole stream: the pre-chunk
            # statistics plus this chunk's
            with jax.named_scope("solve"):
                gv = forget * stats.gv + be.reduce_v(gv_c)
                av = forget * stats.av + av_c
                u_new = solve_gram(gv, av)
            with jax.named_scope("topk"):
                u_new = _epilogue(u_new, sparsify_u)

        # FitHealth monitor (mirrors the batch engine): plain sums over the
        # factors plus the replicated gv accumulator, phrased through the
        # reduce hooks so the same check psums on a mesh.
        with jax.named_scope("online.health"):
            bad_u = be.reduce_u(
                jnp.sum(~jnp.isfinite(u_new)).astype(jnp.int32))
            bad_v = be.reduce_v(jnp.sum(~jnp.isfinite(v)).astype(jnp.int32))
            bad = (bad_u + bad_v > 0) | ~jnp.isfinite(jnp.sum(gv))
            health = jnp.where((health < 0) & bad, it, health)
        return (u_new, v, gv, av, health, it + 1), None

    v0 = jnp.zeros((m_chunk, k), dtype=u.dtype)
    (u, v, gv, av, health, _), _ = jax.lax.scan(
        body, (u, v0, stats.gv, stats.av, jnp.int32(-1), jnp.int32(0)),
        None, length=max(int(iters), 1)
    )
    return OnlineStepResult(u=u, v=v, stats=OnlineStats(av=av, gv=gv),
                            health=health)
