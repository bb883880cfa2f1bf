"""Top-k gradient compression with error feedback — the paper's enforced
top-t projection applied to the data-parallel gradient exchange.

Each DP rank keeps only the top ``density`` fraction of gradient entries by
magnitude (bisection threshold select, same primitive as Alg. 2) before the
cross-replica reduction; the truncated remainder is fed back into the next
step's gradient (error feedback, which preserves convergence the same way
the paper's per-iteration projection preserves ALS fixed points).  The
all-reduce volume drops to ``density`` x dense (+ index metadata on a real
sparse-collective transport; on TPU the masked-dense psum still saves when
paired with sparsity-aware compression at the ICI boundary — see
EXPERIMENTS.md §Perf for the measured collective-bytes accounting).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.topk import topk_project_bisect

Params = Any


def sparsify_tree(grads: Params, density: float) -> Tuple[Params, Params]:
    """Per-leaf top-k projection; returns (sparse_grads, new_error)."""
    def proj(g):
        t = max(int(g.size * density), 1)
        return topk_project_bisect(g, t)

    sparse = jax.tree.map(proj, grads)
    err = jax.tree.map(lambda g, s: g - s, grads, sparse)
    return sparse, err


@functools.lru_cache(maxsize=None)
def _compressed_shard_fn(loss_fn, mesh, data_axes, density,
                         params_def, batch_def, err_def, err_ndims):
    # module-level keyed cache: the shard_mapped callable's identity is the
    # executable-cache key, so it must be reused across grad_fn calls — a
    # rebuild per step recompiles per step.  Keyed on the structural facts
    # the specs depend on (treedefs + error-leaf ranks).
    ndp = 1
    for a in data_axes:
        ndp *= mesh.shape[a]

    def local_fn(params, batch, err):
        # err leaves: (1, *param.shape) — leading replica axis sharded away
        loss, g = jax.value_and_grad(loss_fn)(params, batch)
        g = jax.tree.map(lambda gi, ei: gi + ei[0].astype(gi.dtype), g, err)
        g_sparse, new_err = sparsify_tree(g, density)
        g_avg = jax.tree.map(
            lambda gi: jax.lax.psum(gi, data_axes) / ndp, g_sparse
        )
        loss = jax.lax.pmean(loss, data_axes)
        new_err = jax.tree.map(lambda e: e[None], new_err)
        return loss, g_avg, new_err

    def replicated(treedef):
        return jax.tree.unflatten(treedef, [P()] * treedef.num_leaves)

    err_specs = jax.tree.unflatten(
        err_def, [P(data_axes, *([None] * (nd - 1))) for nd in err_ndims])
    in_specs = (
        replicated(params_def),
        jax.tree.unflatten(batch_def, [P(data_axes)] * batch_def.num_leaves),
        err_specs,
    )
    out_specs = (P(), replicated(params_def), err_specs)
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def make_compressed_grad_fn(
    loss_fn: Callable,            # (params, batch) -> scalar loss
    mesh: jax.sharding.Mesh,
    data_axes: Tuple[str, ...],
    density: float = 0.01,
):
    """Manual-DP gradient with top-k compression + error feedback.

    params are replicated across ``data_axes``; the batch is sharded on its
    leading axis; the error-feedback state has a *sharded leading replica
    axis* (one slot per DP rank — this is error feedback's real memory cost,
    one extra param copy per rank).

    Returns ``grad_fn(params, batch, err_state) -> (loss, grads, err_state)``
    suitable to feed any optimizer.  The shard_mapped step comes from a
    module-level cache keyed on ``(loss_fn, mesh, data_axes, density,
    treedefs)``, so repeated steps reuse one compiled executable.
    """
    data_axes = tuple(data_axes)

    def grad_fn(params, batch, err_state):
        err_leaves, err_def = jax.tree.flatten(err_state)
        fn = _compressed_shard_fn(
            loss_fn, mesh, data_axes, density,
            jax.tree.structure(params), jax.tree.structure(batch),
            err_def, tuple(l.ndim for l in err_leaves))
        return fn(params, batch, err_state)

    return grad_fn


def init_error_state(params: Params, ndp: int) -> Params:
    """(ndp, *shape) zero error-feedback buffers (leading axis -> DP ranks)."""
    return jax.tree.map(
        lambda p: jnp.zeros((ndp,) + p.shape, jnp.float32), params
    )
