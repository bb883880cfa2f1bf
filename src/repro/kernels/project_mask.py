"""Pallas TPU kernel: fused non-negativity projection + top-t threshold mask.

Fuses the two epilogue passes of every enforced-sparsity ALS half-iteration
(paper Alg. 2 steps 1+2 / 3+4):  ``y = relu(x); y = where(y >= tau, y, 0)``
into a single VMEM-tiled elementwise pass, halving epilogue HBM traffic.
``tau`` comes from the bisection threshold select (``core.topk``) and is a
scalar in SMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import resolve_tiles


def _project_mask_kernel(tau_ref, x_ref, out_ref):
    tau = tau_ref[0]
    y = jnp.maximum(x_ref[...], 0.0)
    out_ref[...] = jnp.where(y >= tau, y, 0.0)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def _project_mask_impl(
    x: jax.Array, tau: jax.Array, bm: int, bk: int, interpret: bool
) -> jax.Array:
    n, k = x.shape
    n_pad, k_pad = (-n) % bm, (-k) % bk
    x_p = jnp.pad(x, ((0, n_pad), (0, k_pad)))
    grid = (x_p.shape[0] // bm, x_p.shape[1] // bk)
    out = pl.pallas_call(
        _project_mask_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec((bm, bk), lambda i, j, tau: (i, j))],
            out_specs=pl.BlockSpec((bm, bk), lambda i, j, tau: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct(x_p.shape, x.dtype),
        interpret=interpret,
        name="project_mask",
    )(jnp.reshape(tau.astype(x.dtype), (1,)), x_p)
    return out[:n, :k]


def project_mask(
    x: jax.Array, tau: jax.Array, bm: Optional[int] = None,
    bk: Optional[int] = None, interpret: bool = False
) -> jax.Array:
    """relu + threshold mask over a 2-D array, tiled (bm, bk) in VMEM.

    ``bm=None`` / ``bk=None`` resolve the tile through the autotune ledger
    (``mask_bm`` / ``mask_bk``, default 256x256)."""
    if bm is None or bk is None:
        tiles = resolve_tiles(x.shape[0], None, x.shape[1])
        bm = tiles.mask_bm if bm is None else bm
        bk = tiles.mask_bk if bk is None else bk
    return _project_mask_impl(x, tau, bm=bm, bk=bk, interpret=interpret)
