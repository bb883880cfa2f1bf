"""Pallas TPU kernel: BSR tiles filled from their entries on the device.

Ingest (:func:`repro.kernels.bsr._build`) sends a matrix's non-zeros to the
device sorted by destination tile, with ``tile_start``, where each tile's
entries begin.  This kernel writes them into zero tiles, one tile a grid
step: it copies the 1,024-entry blocks that hold the tile's entries from
HBM into SMEM (a block stays loaded for the steps that follow) and sets
each entry into its row of the VMEM tile by a masked select.  The device
holds the entries and the tiles, nothing of tile volume besides, and
nothing is sorted or scattered on it: XLA's TPU scatter, told its indices
come sorted, lost updates, and unsorted it compiles a sort of the entries
that takes seconds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: entries copied to SMEM at a time: the tiling of a 1-D int32 / f32 array
#: in HBM, which a copy's offset and length must follow
BLOCK = 1024


def _fill_kernel(start_ref, vals_hbm, offs_hbm, out_ref, vals, offs, loaded,
                 sem, *, swap: bool):
    t = pl.program_id(0)
    _, p, q = out_ref.shape
    lanes = lax.broadcasted_iota(jnp.int32, (1, q), 1)

    @pl.when(t == 0)
    def _():
        loaded[0] = -1

    start, end = start_ref[t], start_ref[t + 1]

    def block(k, carry):
        @pl.when(loaded[0] != k)
        def _():
            at = pl.ds(pl.multiple_of(k * BLOCK, BLOCK), BLOCK)
            copies = [pltpu.make_async_copy(src.at[at], dst, sem.at[i])
                      for i, (src, dst) in enumerate(((vals_hbm, vals),
                                                      (offs_hbm, offs)))]
            for c in copies:
                c.start()
            for c in copies:
                c.wait()
            loaded[0] = k

        def entry(i, carry):
            o = offs[i]
            # offs is r * bk + c of the forward tile; the transposed tile
            # (bk, bm) takes the entry at (c, r)
            r, c = (o % p, o // p) if swap else (o // q, o % q)
            row = out_ref[0, pl.ds(r, 1), :]
            out_ref[0, pl.ds(r, 1), :] = jnp.where(lanes == c, vals[i], row)
            return carry

        base = k * BLOCK
        return lax.fori_loop(jnp.maximum(start - base, 0),
                             jnp.minimum(end - base, BLOCK), entry, carry)

    out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
    lax.fori_loop(start // BLOCK, pl.cdiv(end, BLOCK), block, 0)


@functools.partial(jax.jit, static_argnames=("grid", "swap", "interpret"))
def fill_tiles(vals, offs, tile_start, perm=None, *, grid, swap: bool,
               interpret: bool = False):
    """Tiles of shape ``grid = (row_blocks, cap, p, q)``: tile ``t`` holds
    entries ``tile_start[t] .. tile_start[t + 1]`` of ``vals`` (read in the
    order ``perm`` gives, where given), each at its in-tile offset ``offs =
    r * bk + c``, or at ``(c, r)`` where ``swap`` (the transposed
    orientation).  The entries' length is a multiple of :data:`BLOCK`."""
    if perm is not None:
        vals, offs = vals[perm], offs[perm]
    nrb, cap, p, q = grid
    tiles = nrb * cap
    return pl.pallas_call(
        functools.partial(_fill_kernel, swap=swap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, p, q), lambda t, start: (t, 0, 0)),
            scratch_shapes=[pltpu.SMEM((BLOCK,), vals.dtype),
                            pltpu.SMEM((BLOCK,), jnp.int32),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((tiles, p, q), vals.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="bsr_fill",
    )(tile_start, vals, offs).reshape(grid)
