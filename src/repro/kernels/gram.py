"""Pallas TPU kernel: Gram matrix  G = U^T U  by row-block accumulation.

U is (n, k) with n huge and k small: the natural TPU schedule streams
(bm, k) row slabs of U through VMEM once and accumulates the k x k product
on the MXU — HBM traffic is exactly one read of U (n*k) plus one k*k write,
the roofline minimum.  Used for both ``U^T U`` and ``V^T V`` in every ALS
iteration.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.autotune import resolve_tiles
from repro.kernels.bsr_spmm import mxu_precision


def _gram_kernel(u_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[...]
    out_ref[...] += jnp.dot(u.T, u, precision=mxu_precision(u, u),
                            preferred_element_type=out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def _gram_impl(u: jax.Array, bm: int, interpret: bool) -> jax.Array:
    n, k = u.shape
    n_pad = (-n) % bm
    u_p = jnp.pad(u, ((0, n_pad), (0, 0)))
    out = pl.pallas_call(
        _gram_kernel,
        grid=(u_p.shape[0] // bm,),
        in_specs=[pl.BlockSpec((bm, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((k, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, k), jnp.float32),
        interpret=interpret,
        name="gram",
    )(u_p)
    return out


def gram(u: jax.Array, bm: Optional[int] = None,
         interpret: bool = False) -> jax.Array:
    """U^T @ U for (n, k) U, accumulated over (bm, k) VMEM slabs.

    ``bm=None`` resolves the slab height through the autotune ledger
    (``gram_bm``, default 512)."""
    if bm is None:
        bm = resolve_tiles(u.shape[0], None, u.shape[1]).gram_bm
    return _gram_impl(u, bm=bm, interpret=interpret)
