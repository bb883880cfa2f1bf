"""Pallas TPU kernel: Block-CSR sparse-matrix x dense-matrix product.

The ALS hot spot is ``A @ V`` / ``A^T @ U`` with A sparse.  On TPU we
execute it as a stream of dense (bm x bk) @ (bk x kb) MXU tile products,
one per *occupied* block, selected with scalar-prefetched block-column
indices: the U operand's BlockSpec index_map reads ``block_cols`` so the
pipeline fetches exactly the needed (bk, kb) slab of U from HBM into VMEM
for each tile — HBM traffic is proportional to the number of occupied
blocks, which is the paper's memory/compute win restated for the MXU.

Grid: (n_row_blocks, k/kb, bcap) with the bcap loop innermost (accumulation
into the same output block, revisited k/kb times).  A factor no wider than
``kb`` is one full-width k block (no zero columns).  VMEM working set per
step: bm*bk (tile) + bk*kb (U slab) + bm*kb (acc) floats, with kb = k for
such a factor; (128, 128, kb=128) uses 192 KiB and (128, 128, k=4) 68 KiB
— comfortably inside the ~16 MiB VMEM budget, leaving room for double
buffering.

``kb=None`` (the default) resolves through the autotune ledger
(:func:`repro.kernels.autotune.resolve_tiles`) — per-(shape-bucket,
device-kind) measured sizes, falling back to the audited 128 default.  The
fused spmm+gram variant of this kernel lives in
:mod:`repro.kernels.fused`; both share the padding/clamping helpers below.

SMEM: scalar prefetch copies the whole ``(nrb, bcap)`` ``block_cols``
table into the core's 1 MiB SMEM, so one launch over a large tile grid
does not fit (Wikipedia's term-major grid, 1121 x 98, needs 0.55 MiB for
its table).  :func:`row_block_chunks` splits the grid
into row-block ranges whose tables fit :data:`SMEM_PREFETCH_BUDGET`; each
range is one launch over the full tile array (the tile index map adds the
range's offset, so no tile is copied).  Output rows depend only on their
own row-block, so the product is the same with or without the split.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import resolve_tiles
from repro.kernels.bsr import BSR, BSROperand


#: SMEM bytes the scalar-prefetched int32 tables of one launch may take —
#: half of a TPU core's 1 MiB, leaving room for Mosaic's own scalars
SMEM_PREFETCH_BUDGET = 512 * 1024


def _smem_table_bytes(rows: int, cols: int) -> int:
    """SMEM bytes of a (rows, cols) int32 table: Mosaic pads 2-D SMEM
    arrays to (8, 128) tiles."""
    return -(-rows // 8) * 8 * (-(-cols // 128) * 128) * 4


def row_block_chunks(nrb: int, bcap: int, n_tables: int):
    """Static ``(start, stop)`` row-block ranges covering ``nrb`` rows such
    that ``n_tables`` scalar-prefetched ``(rows, bcap)`` int32 tables fit
    :data:`SMEM_PREFETCH_BUDGET` per launch (at least 8 rows a launch).
    One range — one launch, the unsplit program — whenever the whole grid
    fits."""
    per8 = n_tables * _smem_table_bytes(8, bcap)
    rows = max(SMEM_PREFETCH_BUDGET // per8, 1) * 8
    return [(r0, min(r0 + rows, nrb)) for r0 in range(0, nrb, rows)]


def pad_rows(u: jax.Array, bk: int) -> jax.Array:
    """Zero-pad the dense operand's rows up to a block-column multiple, so
    every scalar-prefetched block index addresses a full (bk, ...) slab."""
    return jnp.pad(u, ((0, (-u.shape[0]) % bk), (0, 0)))


def pad_operand(u: jax.Array, bk: int, kb: int):
    """The shared pad step of the separate spmm kernels: rows up to a bk
    multiple and the effective k block ``kb_eff``.  A factor no wider than
    ``kb`` is one full-width k block with no column padding — the same
    (bk, k) slab the fused kernel slices from its resident factor, so both
    kernels run identical tile products; wider factors pad their columns up to a kb multiple."""
    u_p = pad_rows(u, bk)
    k = u.shape[1]
    if k <= kb:
        return u_p, k
    return jnp.pad(u_p, ((0, 0), (0, (-k) % kb))), kb


def mxu_precision(x: jax.Array, y: jax.Array):
    """Contract precision of an in-kernel dot: f32 operands contract at
    full f32 (``HIGHEST``) so the kernels agree with an f32 reference to
    roundoff; narrower operands keep Mosaic's default."""
    if x.dtype == jnp.float32 and y.dtype == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return None


def _spmm_kernel(block_cols_ref, tiles_ref, u_ref, out_ref):
    s = pl.program_id(2)  # slot within the row-block's capacity

    @pl.when(s == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tile = tiles_ref[0, 0]  # (bm, bk)
    u = u_ref[...]
    out_ref[...] += jnp.dot(
        tile, u, precision=mxu_precision(tile, u),
        preferred_element_type=out_ref.dtype,
    )


def _spmm_launch(block_cols, tiles, u_p, kb_eff: int, r0: int,
                 interpret: bool) -> jax.Array:
    """One launch over row-blocks ``r0 .. r0 + len(block_cols)`` of the
    full ``tiles`` array; returns those row-blocks' output rows."""
    nr, bcap = block_cols.shape
    _, _, bm, bk = tiles.shape
    grid = (nr, u_p.shape[1] // kb_eff, bcap)
    return pl.pallas_call(
        _spmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bm, bk),
                             lambda i, j, s, cols: (r0 + i, s, 0, 0)),
                pl.BlockSpec((bk, kb_eff), lambda i, j, s, cols: (cols[i, s], j)),
            ],
            out_specs=pl.BlockSpec((bm, kb_eff), lambda i, j, s, cols: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((nr * bm, u_p.shape[1]), u_p.dtype),
        interpret=interpret,
        name="bsr_spmm",
    )(block_cols, tiles, u_p)


@functools.partial(jax.jit, static_argnames=("kb", "interpret"))
def _bsr_spmm_impl(a: BSR, u: jax.Array, kb: int, interpret: bool) -> jax.Array:
    nrb, bcap, _bm, bk = a.tiles.shape
    n, _m = a.shape
    k = u.shape[1]
    u_p, kb_eff = pad_operand(u, bk, kb)
    out = jnp.concatenate([
        _spmm_launch(a.block_cols[r0:r1], a.tiles, u_p, kb_eff, r0, interpret)
        for r0, r1 in row_block_chunks(nrb, bcap, 1)])
    return out[:n, :k]


def bsr_spmm(a: BSR, u: jax.Array, kb: Optional[int] = None,
             interpret: bool = False) -> jax.Array:
    """Compute ``dense(A) @ U`` for BSR ``A`` (n x m) and dense ``U`` (m x k).

    ``U`` is zero-padded up to block multiples; the result is cropped back
    to (n, k).  ``kb=None`` resolves the k-tile through the autotune ledger.
    """
    if kb is None:
        kb = resolve_tiles(a.shape[0], a.shape[1], u.shape[1]).kb
    return _bsr_spmm_impl(a, u, kb=kb, interpret=interpret)


def bsr_spmm_t(a, u: jax.Array, kb: Optional[int] = None,
               interpret: bool = False) -> jax.Array:
    """Compute ``dense(A)^T @ U`` scatter-free via the transposed-format BSR
    copy built tile-wise at ingest (see :func:`repro.kernels.bsr.bsr_transpose`).

    ``a`` is either a :class:`BSROperand` (the two-orientation ingest
    product) or the transposed-format :class:`BSR` itself; the product is
    the same streaming-tile kernel as :func:`bsr_spmm` — padding, clamping
    and all — run on A^T's tiles.
    """
    a_t = a.bsr_t if isinstance(a, BSROperand) else a
    return bsr_spmm(a_t, u, kb=kb, interpret=interpret)
