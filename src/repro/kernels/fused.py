"""Pallas TPU kernel: fused BSR spmm + Gram — one launch per half-step.

Both ALS half-steps pair a sparse product with a Gram matrix of the *same*
dense operand:  ``V = solve(reduce(U^T U), A^T U)`` reads U twice — once as
the spmm dense operand, once for the Gram.  This kernel loads U into VMEM
once per launch and takes both from there, which is the paper's
keep-intermediates-near-compute argument applied to the MXU pipeline (and
the limited-internal-memory design of Nguyen & Ho, arXiv:1506.08938).

Resident factor: U enters transposed and lane-dense, ``(k, m_pad)`` with
m_pad a whole number of ``bk`` blocks (held as ``(m_pad, k)`` it would be
padded to 128 lanes, 25.6x the bytes at k=5), single-buffered under a
constant index map, so it is read from HBM once.  Each tile's ``(bk, k)``
slab is a ``pl.ds(block_cols[i, s] * bk, bk)`` lane slice of it,
transposed in VMEM; the Gram is ``U^T U`` over the whole resident factor,
taken once by the first grid step — the Gram of exactly the factor passed
in, whatever the tiles reference.

Grid: (n_row_blocks, ceil(bcap / S)).  A grid step takes S consecutive
slots of one row-block — S (bm, bk) tiles in one DMA — and adds their
``tile @ slab`` products into the row-block's output block in ascending
slot order, the accumulation order of ``bsr_spmm``, so the product matches
it bit-for-bit.  A TPU grid step costs about 0.35 us whatever it holds,
against 0.08 us of HBM time for one 128 x 128 f32 tile, so
:func:`repro.kernels.autotune.fused_slots` sizes S from the shapes to
about 1 MiB of tiles, within ``VMEM_BUDGET`` beside the factor; where
bcap is no multiple of S the last step's block runs past ``bcap`` and its
slots there are skipped.  VMEM per step, as the ``pallas-tiles`` IR pass
counts blocks: S*bm*bk (tiles) + k*m_pad (factor) + bm*k (output) operand
elements plus the f32 k*k Gram — (128, 128, k=4, S=3, m_pad=384) uses
about 200 KiB; :func:`repro.kernels.autotune.fused_working_set` adds the
double buffers and Mosaic's padding.

SMEM: the scalar-prefetched ``(nrb, bcap)`` ``block_cols`` table grows
with the tile grid, so large grids launch once per row-block range from
:func:`repro.kernels.bsr_spmm.row_block_chunks`, one table each, as
``bsr_spmm`` does; each launch returns its rows of the product and the
first also the Gram.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import fused_slots
from repro.kernels.bsr import BSR, BSROperand
from repro.kernels.bsr_spmm import mxu_precision, pad_rows, row_block_chunks


def _spmm_gram_kernel(block_cols_ref, tiles_ref, u_ref, out_ref, *gram_ref,
                      bcap: int, slots: int):
    i = pl.program_id(0)  # row-block
    j = pl.program_id(1)  # group of `slots` slots within the row-block
    bk = tiles_ref.shape[-1]

    if gram_ref:  # the first launch of a half-step also takes the Gram
        @pl.when((i == 0) & (j == 0))
        def _gram():
            uf = u_ref[...].astype(jnp.float32)  # (k, m_pad)
            gram_ref[0][...] = jax.lax.dot_general(
                uf, uf, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)

    def product(t):
        start = pl.multiple_of(block_cols_ref[i, j * slots + t] * bk, bk)
        slab = u_ref[:, pl.ds(start, bk)].T  # (bk, k)
        tile = tiles_ref[0, t]
        return jnp.dot(tile, slab, precision=mxu_precision(tile, slab),
                       preferred_element_type=out_ref.dtype)

    @pl.when(j == 0)
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    n_valid = bcap - j * slots  # slots of this step inside bcap

    @pl.when(n_valid >= slots)
    def _full_step():
        acc = out_ref[...]
        for t in range(slots):
            acc += product(t)
        out_ref[...] = acc

    @pl.when(n_valid < slots)
    def _last_step():  # the block runs past bcap: skip the slots beyond it
        for t in range(slots - 1):
            @pl.when(t < n_valid)
            def _slot(t=t):
                out_ref[...] += product(t)


def _spmm_gram_launch(block_cols, tiles, u_t, r0: int, slots: int,
                      gram: bool, interpret: bool):
    """One launch over row-blocks ``r0 .. r0 + len(block_cols)`` of the full
    ``tiles`` array: those row-blocks' product rows, and the Gram of the
    resident factor ``u_t`` (k, m_pad) where ``gram`` is set."""
    nr, bcap = block_cols.shape
    _, _, bm, bk = tiles.shape
    k, m_pad = u_t.shape
    out_specs = [pl.BlockSpec((bm, k), lambda i, j, cols: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((nr * bm, k), u_t.dtype)]
    if gram:
        out_specs.append(pl.BlockSpec((k, k), lambda i, j, cols: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((k, k), jnp.float32))
    return pl.pallas_call(
        functools.partial(_spmm_gram_kernel, bcap=bcap, slots=slots),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nr, pl.cdiv(bcap, slots)),
            in_specs=[
                pl.BlockSpec((1, slots, bm, bk),
                             lambda i, j, cols: (r0 + i, j, 0, 0)),
                pl.BlockSpec((k, m_pad), lambda i, j, cols: (0, 0),
                             pipeline_mode=pl.Buffered(1)),
            ],
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        interpret=interpret,
        name="bsr_spmm_gram",
    )(block_cols, tiles, u_t)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bsr_spmm_gram(
    a: BSR, u: jax.Array, interpret: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """``(dense(A) @ U, U^T U)`` with U resident in VMEM: one Pallas
    launch, or one per row-block range where the grid's SMEM table needs
    splitting.

    The product matches :func:`repro.kernels.bsr_spmm.bsr_spmm` bit-for-bit
    (same tile products, same accumulation order); the Gram is taken in f32
    like :func:`repro.kernels.gram.gram` but in one dot over the factor, so
    it agrees to f32 roundoff, not bitwise.  Returns ``(y, gram)`` with
    ``y`` cropped to (n, k) and ``gram`` (k, k) f32.  Raises ``ValueError``
    where the resident factor leaves no room in VMEM for a tile (see
    :func:`repro.kernels.autotune.fused_slots`).
    """
    nrb, bcap, bm, bk = a.tiles.shape
    n, m = a.shape
    k = u.shape[1]
    slots = fused_slots(bm, bk, k, m, bcap, u.dtype.itemsize)
    if not slots:
        raise ValueError(
            f"a ({k}, {m}) factor leaves no VMEM for a ({bm}, {bk}) tile: "
            "use bsr_spmm and gram")
    u_t = pad_rows(u, bk).T  # (k, m_pad), lane-dense

    outs = [_spmm_gram_launch(a.block_cols[r0:r1], a.tiles, u_t, r0, slots,
                              r0 == 0, interpret)
            for r0, r1 in row_block_chunks(nrb, bcap, 1)]
    y = jnp.concatenate([o[0] for o in outs])
    return y[:n], outs[0][1]


def bsr_spmm_gram_t(
    a, u: jax.Array, interpret: bool = False
) -> Tuple[jax.Array, jax.Array]:
    """``(dense(A)^T @ U, U^T U)`` via the transposed-format BSR copy —
    the fused counterpart of :func:`repro.kernels.bsr_spmm.bsr_spmm_t`.
    ``a`` is a :class:`BSROperand` or the transposed-format :class:`BSR`.
    """
    a_t = a.bsr_t if isinstance(a, BSROperand) else a
    return bsr_spmm_gram(a_t, u, interpret=interpret)
