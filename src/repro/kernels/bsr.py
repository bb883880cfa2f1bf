"""Block-CSR (BSR) sparse format and its construction.

TPU adaptation of the paper's sparse storage: the MXU consumes dense
128x128 tiles, so instead of element-wise CSC (MATLAB) we store A as a set
of *dense tiles at sparse block coordinates*:

* ``tiles``:      (n_row_blocks, bcap, bm, bk)  — dense MXU-ready tiles
* ``block_cols``: (n_row_blocks, bcap) int32    — column-block index per tile

Rows of blocks are padded to a fixed per-row-block capacity ``bcap`` (same
static-capacity philosophy as ``repro.sparse``); padded slots have zero
tiles and block_col 0, contributing nothing to the product.

``A^T @ X`` reuses the same kernel on a transposed-format copy built once at
ingest (memory 2x nnz-blocks — the standard trade for scatter-free TPU
execution).  :class:`BSROperand` bundles the two orientations; it is the
operand type the ``pallas-bsr`` matmul backend consumes.

Every constructor goes through one function, :func:`_build`: the host works on
the element COO and the occupied blocks only, and the tiles of each
orientation are written on the device by one Pallas launch from the entries
(:mod:`repro.kernels.fill`), so no tile volume is made, transposed or read
back on the host.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fill import fill_tiles


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BSR:
    tiles: jax.Array        # (nrb, bcap, bm, bk)
    block_cols: jax.Array   # (nrb, bcap) int32
    shape: Tuple[int, int] = dataclasses.field(metadata=dict(static=True))

    @property
    def bm(self) -> int:
        return self.tiles.shape[2]

    @property
    def bk(self) -> int:
        return self.tiles.shape[3]

    @property
    def bcap(self) -> int:
        return self.tiles.shape[1]

    @property
    def nrb(self) -> int:
        return self.tiles.shape[0]

    def nnz(self) -> jax.Array:
        return jnp.sum(self.tiles != 0)

    def sqnorm(self) -> jax.Array:
        return jnp.sum(self.tiles.astype(jnp.float32) ** 2)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BSROperand:
    """A in BSR form plus its transposed-format copy (both built at ingest).

    ``bsr`` is A (n x m); ``bsr_t`` stores A^T (m x n) so the same
    streaming-tile kernel serves both ALS half-steps scatter-free.
    ``shape`` is the logical (n, m) of A.
    """
    bsr: BSR
    bsr_t: BSR
    shape: Tuple[int, int] = dataclasses.field(metadata=dict(static=True))

    @property
    def n(self) -> int:
        return self.shape[0]

    @property
    def m(self) -> int:
        return self.shape[1]

    def nnz(self) -> jax.Array:
        return self.bsr.nnz()

    def sqnorm(self) -> jax.Array:
        return self.bsr.sqnorm()


def bsr_from_dense(a: np.ndarray, bm: int = 128, bk: int = 128, bcap: int | None = None) -> BSR:
    """Dense ``(n, m)`` -> BSR, padding n, m up to block multiples: the
    forward half of :func:`_build`, fed the array's
    non-zeros.  An explicit ``bcap`` below a row-block's occupancy keeps
    its ``bcap`` largest-Frobenius-norm blocks and warns."""
    a = np.asarray(a)
    rows, cols, vals = _dense_coo(a)
    (bsr,) = _build(rows, cols, vals, a.shape, bm, bk, bcap=bcap,
                    transposed=False, who="bsr_from_dense")
    return bsr


def _keep_top_per_group(group_ids, sqnorms, ngroups: int, cap: int):
    """Rank items within each group by descending ``sqnorms``, keep the
    ``cap`` largest per group, and slot the survivors in ascending
    original-index order (the layout invariant of every BSR here:
    ascending block-col / source-row-block within a slot row).

    Returns ``(keep, slots, counts)``: a boolean keep mask over the items,
    the slot index per item (only meaningful where ``keep``), and the
    per-group item counts (for the caller's truncation warning).
    """
    group_ids = group_ids.astype(np.int64)
    counts = np.bincount(group_ids, minlength=ngroups)
    by_norm = np.lexsort((-sqnorms, group_ids))
    starts = np.cumsum(counts) - counts
    norm_rank = np.empty(len(group_ids), dtype=np.int64)
    norm_rank[by_norm] = np.arange(len(group_ids)) - starts[group_ids[by_norm]]
    keep = norm_rank < cap
    pos = np.flatnonzero(keep)  # kept items, ascending original index
    gk = group_ids[pos]
    order = np.argsort(gk, kind="stable")
    kept_counts = np.bincount(gk, minlength=ngroups)
    kept_starts = np.cumsum(kept_counts) - kept_counts
    slots = np.zeros(len(group_ids), dtype=np.int64)
    slots[pos[order]] = np.arange(len(gk)) - kept_starts[gk[order]]
    return keep, slots, counts


def bsr_from_scipy(sp_matrix, bm: int = 128, bk: int = 128,
                   bcap: int | None = None, dtype=None) -> BSR:
    """Direct ``scipy.sparse -> BSR`` ingest, never materializing the dense
    matrix: the forward half of :func:`_build`.  The host
    works on the non-zeros and the occupied blocks; the tiles are filled
    on the device.  This is the ingest path for real vectorizer corpora,
    where the dense (n, m) matrix would not fit on the host.

    ``bcap`` bounds the occupied-block slots per row-block; row-blocks with
    more occupied blocks keep the ``bcap`` largest by Frobenius norm (the
    top-t philosophy applied block-wise) and a warning reports how many
    row-blocks were truncated.
    """
    rows, cols, vals = _scipy_coo(sp_matrix, dtype)
    (bsr,) = _build(rows, cols, vals, sp_matrix.shape, bm, bk, bcap=bcap,
                    transposed=False)
    return bsr


def _scipy_coo(sp_matrix, dtype=None):
    """Row-major element COO of a scipy matrix: duplicates summed,
    explicit zeros dropped, values cast to ``dtype`` after that."""
    coo = sp_matrix.tocoo()
    coo.sum_duplicates()
    coo.eliminate_zeros()
    data = coo.data if dtype is None else coo.data.astype(dtype)
    return coo.row, coo.col, data


def _dense_coo(a: np.ndarray):
    """Row-major element COO of a dense host array's non-zeros."""
    rows, cols = np.nonzero(a)
    return rows, cols, a[rows, cols]


#: the per-entry arrays are padded to a multiple of this many entries (and
#: so of :data:`repro.kernels.fill.BLOCK`), so every operand of one tile
#: grid whose nnz falls in the same bucket (each chunk of a stream, in
#: practice) reuses one compiled fill
NNZ_BUCKET = 1 << 16

_BLOCKS_CUT = ("{who}: {over} row-blocks exceed bcap={cap}; keeping the "
               "{cap} largest-Frobenius-norm blocks per row-block")
_TILES_CUT = ("bsr_transpose: {over} row-blocks of the transpose exceed "
              "bcap={cap}; keeping the {cap} largest-Frobenius-norm tiles "
              "per row-block")


def _slot_table(groups, others, sqnorms, ngroups: int, cap, cut):
    """One orientation's layout over occupied blocks, given each block's
    row-block ``groups`` and block-col ``others``: keeps the ``cap``
    largest-norm blocks of each row-block (``cap=None``: the fullest
    row-block's count; ``cut(over=, cap=)`` words the warning when some
    row-block overflows), slotted in the order the blocks come.  Returns
    ``(cap, keep, tile, block_cols)``, ``tile`` being each block's flat
    tile index ``row_block * cap + slot`` where kept, and ``ngroups * cap``
    (out of bounds) where not."""
    if cap is None:
        cap = max(int(np.bincount(groups, minlength=ngroups).max(initial=1)),
                  1)
    keep, slots, counts = _keep_top_per_group(groups, sqnorms, ngroups, cap)
    over = int((counts > cap).sum())
    if over:
        warnings.warn(cut(over=over, cap=cap), stacklevel=4)
    block_cols = np.zeros((ngroups, cap), dtype=np.int32)
    block_cols[groups[keep], slots[keep]] = others[keep]
    return cap, keep, np.where(keep, groups * cap + slots, ngroups * cap), \
        block_cols


def _build(rows, cols, vals, shape, bm: int, bk: int, *, bcap=None,
           forward: bool = True, transposed: bool = True,
           who: str = "bsr_from_scipy", stats: dict | None = None):
    """Build BSR orientations of the ``(n, m)`` matrix whose distinct
    non-zeros are the element COO ``(rows, cols, vals)``.

    The host works only on the entries and the occupied blocks: each
    block's squared norm and, per orientation, the tile it lands in.
    ``forward`` is A in ``(n/bm, bcap, bm, bk)`` tiles, ``bcap`` truncating
    as :func:`bsr_from_scipy` says; ``transposed`` is A^T in ``(m/bk,
    bcap_t, bk, bm)`` tiles from the kept forward blocks (all blocks when
    ``forward`` is off, ``bcap`` then truncating as :func:`bsr_transpose`
    says), in ascending source-row-block order.  The kept entries go to
    the device in the first orientation's tile order, about 12 bytes an
    entry: the value, the in-tile offset and, for a second orientation,
    its own order of them; and, per orientation, where each tile's
    entries start.  There :func:`~repro.kernels.fill.fill_tiles` writes
    them into zero tiles, once an orientation.  ``stats["ingest_bytes"]`` grows by the bytes
    uploaded.  The build is the host span ``bsr.ingest``."""
    with jax.profiler.TraceAnnotation("bsr.ingest"):
        n, m = shape
        nrb, ncb = -(-n // bm), -(-m // bk)
        block = (rows // bm).astype(np.int64) * ncb + cols // bk
        uniq, inv = np.unique(block, return_inverse=True)
        ubi, ubj = uniq // ncb, uniq % ncb
        sqnorms = np.bincount(inv, weights=np.square(vals, dtype=np.float64),
                              minlength=len(uniq))
        r, c = (rows % bm).astype(np.int64), (cols % bk).astype(np.int64)
        grids, keys, tiles, block_cols = [], [], [], []
        kept = np.ones(len(uniq), dtype=bool)
        if forward:
            cap, kept, tile, bcols = _slot_table(
                ubi, ubj, sqnorms, nrb, bcap,
                functools.partial(_BLOCKS_CUT.format, who=who))
            grids.append((nrb, cap, bm, bk))
            tiles.append(tile)
            keys.append((tile[inv] * bm + r) * bk + c)
            block_cols.append(bcols)
        if transposed:
            # the transpose holds the kept forward blocks that hold a value
            sub = np.flatnonzero(kept & (sqnorms > 0))
            cap, kept_t, tile_t, bcols = _slot_table(
                ubj[sub], ubi[sub], sqnorms[sub], ncb,
                None if forward else bcap, _TILES_CUT.format)
            kept = np.zeros(len(uniq), dtype=bool)
            kept[sub] = kept_t
            tile = np.full(len(uniq), ncb * cap, dtype=np.int64)
            tile[sub] = tile_t
            grids.append((ncb, cap, bk, bm))
            tiles.append(tile)
            keys.append((tile[inv] * bk + c) * bm + r)
            block_cols.append(bcols)
        # the entries every orientation keeps, in the first one's tile order
        # (its tiles, and each tile's (row, col), ascending)
        ent = np.flatnonzero(kept[inv] & (vals != 0))
        ent = ent[np.argsort(keys[0][ent])]
        nnz = len(ent)
        bucket = max(-(-nnz // NNZ_BUCKET), 1) * NNZ_BUCKET
        host = {"vals": np.zeros(bucket,
                                 jax.dtypes.canonicalize_dtype(vals.dtype)),
                "offs": np.zeros(bucket, np.int32), "perm": None,
                "starts": [], "block_cols": block_cols}
        host["vals"][:nnz] = vals[ent]
        host["offs"][:nnz] = r[ent] * bk + c[ent]
        order = np.arange(nnz)
        if len(grids) == 2:
            # the second orientation reads the entries in its own tile order
            order = np.argsort(keys[1][ent])
            host["perm"] = np.arange(bucket, dtype=np.int32)
            host["perm"][:nnz] = order
        for grid, tile, at in zip(grids, tiles, (np.arange(nnz), order)):
            host["starts"].append(np.searchsorted(
                tile[inv[ent[at]]], np.arange(grid[0] * grid[1] + 1)
            ).astype(np.int32))
        if stats is not None:
            stats["ingest_bytes"] = (stats.get("ingest_bytes", 0) + sum(
                x.nbytes for x in jax.tree_util.tree_leaves(host)))
        dev = jax.device_put(host)
        interpret = jax.default_backend() != "tpu"
        out = []
        for i, (grid, starts, bcols) in enumerate(
                zip(grids, dev["starts"], dev["block_cols"])):
            swap = i > 0 or not forward
            out.append(BSR(fill_tiles(dev["vals"], dev["offs"], starts,
                                      dev["perm"] if i else None, grid=grid,
                                      swap=swap, interpret=interpret),
                           bcols, (m, n) if swap else (n, m)))
        return out


def bsr_dot_uv(a: BSR, u: jax.Array, v: jax.Array) -> jax.Array:
    """``<A, U V^T>`` contracted tile-wise: sum over occupied tiles of
    ``sum(tile * (U_blk V_blk^T))``, contracted at full f32 precision
    (``HIGHEST``), like the kernels' f32 dots.  Peak temporary is
    ~tile_volume * k / bk — a bk-fold saving over flattening the tiles to
    COO and gathering (tile_volume, k) slabs of U and V.  This is the
    cross term of the relative error for both the local BSR operand and a
    BSR shard's local contribution under the mesh (the per-shard piece the
    sharded backend psums)."""
    nrb, bcap, bm, bk = a.tiles.shape
    n, m = a.shape
    k = u.shape[1]
    uf = u.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    u_blk = jnp.pad(uf, ((0, nrb * bm - n), (0, 0))).reshape(nrb, bm, k)
    ncb = -(-m // bk)
    v_blk = jnp.pad(vf, ((0, ncb * bk - m), (0, 0))).reshape(ncb, bk, k)
    v_blk = v_blk[a.block_cols]  # (nrb, bcap, bk, k); padded slots see
    # block 0, harmless: their tiles are all-zero
    return jnp.einsum("isrc,ird,iscd->",
                      a.tiles.astype(jnp.float32), u_blk, v_blk,
                      precision=jax.lax.Precision.HIGHEST)


def bsr_to_coo(a: BSR):
    """Host-side element COO ``(rows, cols, vals)`` of the stored nonzeros —
    work and temporaries proportional to the stored-tile volume, never the
    dense (n, m) matrix.  This is how an already-ingested BSR re-enters a
    packing front door (e.g. :func:`repro.core.distributed.distribute_bsr`
    carving it into per-device tile grids)."""
    tiles = np.asarray(a.tiles)
    bcols = np.asarray(a.block_cols)
    nz_i, nz_s, nz_r, nz_c = np.nonzero(tiles)
    rows = nz_i * a.bm + nz_r
    cols = bcols[nz_i, nz_s].astype(np.int64) * a.bk + nz_c
    return rows.astype(np.int64), cols, tiles[nz_i, nz_s, nz_r, nz_c]


def bsr_to_dense(a: BSR) -> jax.Array:
    nrb, bcap, bm, bk = a.tiles.shape
    ncb = -(-a.shape[1] // bk)
    out = jnp.zeros((nrb, ncb, bm, bk), dtype=a.tiles.dtype)
    rows = jnp.broadcast_to(jnp.arange(nrb)[:, None], (nrb, bcap))
    out = out.at[rows, a.block_cols].add(a.tiles)
    dense = out.transpose(0, 2, 1, 3).reshape(nrb * bm, ncb * bk)
    return dense[: a.shape[0], : a.shape[1]]


def bsr_transpose(a: BSR, bcap: int | None = None) -> BSR:
    """The transposed-format copy of an existing BSR: its stored non-zeros
    (:func:`bsr_to_coo`) through :func:`_build`'s transposed half.  Tile
    ``(i, s)`` with block-col j lands at row-block j with block-col i, in
    ascending i.  Work and memory are proportional to the stored tiles,
    never the dense (n, m) matrix.

    An explicit ``bcap`` smaller than a destination row-block's occupancy
    keeps its ``bcap`` largest-Frobenius-norm tiles (the same truncation
    policy as :func:`bsr_from_scipy`) and warns with the truncated count.
    """
    rows, cols, vals = bsr_to_coo(a)
    (bsr_t,) = _build(rows, cols, vals, a.shape, a.bm, a.bk, bcap=bcap,
                      forward=False)
    return bsr_t


def bsr_operand(a, bm: int = 128, bk: int = 128, bcap: int | None = None,
                dtype=None, stats: dict | None = None) -> BSROperand:
    """Build the two-orientation :class:`BSROperand` from a dense array, a
    scipy sparse matrix, or an existing :class:`BSR` (transposed copy added
    by :func:`bsr_transpose`).  Both orientations of a new operand come from
    one host pass over the non-zeros and are filled on the device
    (:func:`_build`); ``stats["ingest_bytes"]`` grows by the bytes that went
    to the device."""
    if isinstance(a, BSROperand):
        return a
    if isinstance(a, BSR):
        return BSROperand(a, bsr_transpose(a), a.shape)
    if hasattr(a, "tocoo"):  # scipy sparse, without a hard scipy import
        rows, cols, vals = _scipy_coo(a, dtype)
        who = "bsr_from_scipy"
    else:
        a = np.asarray(a)
        if dtype is not None:
            a = a.astype(dtype)
        rows, cols, vals = _dense_coo(a)
        who = "bsr_from_dense"
    bsr, bsr_t = _build(rows, cols, vals, a.shape, bm, bk, bcap=bcap,
                        who=who, stats=stats)
    return BSROperand(bsr, bsr_t, bsr.shape)
