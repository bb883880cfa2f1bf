"""Pallas TPU kernel: fused BSR spmm + Gram accumulate — one grid sweep.

Both ALS half-steps pair a sparse product with a Gram matrix of the *same*
dense operand:  ``V = solve(reduce(U^T U), A^T U)`` reads U twice — once as
the spmm dense operand, once for the Gram.  Launching ``bsr_spmm`` and
``gram`` separately therefore streams U through HBM twice per half-step.
This kernel computes both in one sweep: while a (bk, k) slab of U sits in
VMEM for the tile product it also contributes its ``slab^T @ slab`` to the
k x k Gram accumulator — the second HBM read of U disappears, which is the
paper's keep-intermediates-near-compute argument applied to the MXU
pipeline (and the limited-internal-memory design of Nguyen & Ho,
arXiv:1506.08938).

Grid: (n_row_blocks, bcap), bcap innermost.  Unlike ``bsr_spmm`` there is
no k tiling — the slab spans the full factor rank k (small by
construction), which Mosaic handles as a single possibly-sub-lane block
exactly like ``gram``'s (bm, k) slabs.  VMEM working set per
step: bm*bk (tile) + bk*k (U slab) + bm*k (acc) operand-dtype elements
plus the f32 k*k Gram accumulator — (128, 128, k=4) uses ~68 KiB, audited
by the ``pallas-tiles`` IR pass against this docstring's
``fused_working_set`` claim.

Gram coverage: the sweep only sees the U row-blocks that occupied tiles
reference, possibly more than once.  A scalar-prefetched first-occurrence
flag per (row-block, slot) marks exactly one visit per *distinct*
referenced block for Gram accumulation (padding slots reference block 0,
so block 0 is covered even in an all-padding operand); row-blocks no tile
references are folded in afterwards by a masked correction term that
``lax.cond`` skips entirely when coverage is complete — the common case
for real corpora, where every document block holds some term.

SMEM: the two scalar-prefetched ``(nrb, bcap)`` tables (``block_cols`` and
the flags) grow with the tile grid, so large grids launch once per
row-block range from :func:`repro.kernels.bsr_spmm.row_block_chunks`; each
launch returns its rows of the product and its part of the Gram, and the
parts are summed.  The flags are computed over the whole grid first, so
every distinct block still lands in exactly one part.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bsr import BSR, BSROperand
from repro.kernels.bsr_spmm import mxu_precision, pad_rows, row_block_chunks


def _spmm_gram_kernel(block_cols_ref, gram_flags_ref, tiles_ref, u_ref,
                      out_ref, gram_ref):
    i = pl.program_id(0)  # row-block
    s = pl.program_id(1)  # slot within the row-block's capacity

    @pl.when(s == 0)
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when((i == 0) & (s == 0))
    def _init_gram():
        gram_ref[...] = jnp.zeros_like(gram_ref)

    tile = tiles_ref[0, 0]
    u = u_ref[...]  # (bk, k) slab, already in VMEM for the tile product
    out_ref[...] += jnp.dot(
        tile, u, precision=mxu_precision(tile, u),
        preferred_element_type=out_ref.dtype,
    )

    @pl.when(gram_flags_ref[i, s] != 0)
    def _accumulate_gram():
        uf = u.astype(jnp.float32)
        gram_ref[...] += jnp.dot(uf.T, uf, precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)


def _coverage(block_cols: jax.Array, ncb: int):
    """First-occurrence flags over the flattened (nrb, bcap) slots plus the
    per-column-block covered mask.  A block referenced from several slots is
    flagged only at its first, so its Gram contribution lands exactly once.
    """
    nrb, bcap = block_cols.shape
    size = nrb * bcap
    flat = block_cols.reshape(-1).astype(jnp.int32)
    pos = jnp.arange(size, dtype=jnp.int32)
    first_pos = jnp.full((ncb,), size, jnp.int32).at[flat].min(pos)
    flags = (first_pos[flat] == pos).astype(jnp.int32).reshape(nrb, bcap)
    return flags, first_pos < size


def _spmm_gram_launch(block_cols, flags, tiles, u_p, r0: int,
                      interpret: bool):
    """One launch over row-blocks ``r0 .. r0 + len(block_cols)`` of the full
    ``tiles`` array: those row-blocks' product rows and their Gram part."""
    nr, bcap = block_cols.shape
    _, _, bm, bk = tiles.shape
    k = u_p.shape[1]
    return pl.pallas_call(
        _spmm_gram_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nr, bcap),
            in_specs=[
                pl.BlockSpec((1, 1, bm, bk),
                             lambda i, s, cols, flags: (r0 + i, s, 0, 0)),
                pl.BlockSpec((bk, k),
                             lambda i, s, cols, flags: (cols[i, s], 0)),
            ],
            out_specs=[
                pl.BlockSpec((bm, k), lambda i, s, cols, flags: (i, 0)),
                pl.BlockSpec((k, k), lambda i, s, cols, flags: (0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((nr * bm, k), u_p.dtype),
            jax.ShapeDtypeStruct((k, k), jnp.float32),
        ],
        interpret=interpret,
        name="bsr_spmm_gram",
    )(block_cols, flags, tiles, u_p)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bsr_spmm_gram(
    a: BSR, u: jax.Array, interpret: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """``(dense(A) @ U, U^T U)`` in one sweep over the tiles: one Pallas
    launch, or one per row-block range where the grid's SMEM tables need
    splitting.

    The product matches :func:`repro.kernels.bsr_spmm.bsr_spmm` bit-for-bit
    (same tile stream, same accumulation order); the Gram is accumulated in
    f32 like :func:`repro.kernels.gram.gram` but in referenced-block order,
    so it agrees to f32 roundoff, not bitwise.  Returns ``(y, gram)`` with
    ``y`` cropped to (n, k) and ``gram`` (k, k) f32.
    """
    nrb, bcap, _bm, bk = a.tiles.shape
    n, _m = a.shape
    u_p = pad_rows(u, bk)
    ncb = u_p.shape[0] // bk
    flags, covered = _coverage(a.block_cols, ncb)

    ys, gs = zip(*[
        _spmm_gram_launch(a.block_cols[r0:r1], flags[r0:r1], a.tiles, u_p,
                          r0, interpret)
        for r0, r1 in row_block_chunks(nrb, bcap, 2)])
    y = jnp.concatenate(ys)
    g = functools.reduce(jnp.add, gs)

    def _add_unreferenced(g):
        # fold in the row-blocks no occupied tile references: mask U down
        # to those rows and add the masked Gram.  Runs only when coverage
        # is incomplete (lax.cond), so fully-covered operands pay nothing.
        row_covered = covered[jnp.arange(u_p.shape[0]) // bk]
        um = jnp.where(row_covered[:, None], 0.0, u_p.astype(jnp.float32))
        return g + jnp.dot(um.T, um, precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)

    g = jax.lax.cond(jnp.all(covered), lambda g: g, _add_unreferenced, g)
    return y[:n], g


def bsr_spmm_gram_t(
    a, u: jax.Array, interpret: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """``(dense(A)^T @ U, U^T U)`` via the transposed-format BSR copy —
    the fused counterpart of :func:`repro.kernels.bsr_spmm.bsr_spmm_t`.
    ``a`` is a :class:`BSROperand` or the transposed-format :class:`BSR`.
    """
    a_t = a.bsr_t if isinstance(a, BSROperand) else a
    return bsr_spmm_gram(a_t, u, interpret=interpret)
