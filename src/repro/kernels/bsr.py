"""Block-CSR (BSR) sparse format + host-side converters.

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
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


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
    """Host-side conversion (numpy).  Pads n, m up to block multiples.

    Fully vectorized: occupied blocks scatter into their slots through the
    same :func:`_keep_top_per_group` machinery as :func:`bsr_from_scipy`,
    so large dense fixtures ingest in numpy time rather than a Python
    double loop.  An explicit ``bcap`` below a row-block's occupancy keeps
    its ``bcap`` largest-Frobenius-norm blocks and warns (the scipy-ingest
    truncation policy; the old loop silently kept the first ``bcap``).
    """
    a = np.asarray(a)
    n, m = a.shape
    n_pad = (-n) % bm
    m_pad = (-m) % bk
    ap = np.pad(a, ((0, n_pad), (0, m_pad)))
    nrb, ncb = ap.shape[0] // bm, ap.shape[1] // bk
    blocked = ap.reshape(nrb, bm, ncb, bk).transpose(0, 2, 1, 3)  # (nrb, ncb, bm, bk)
    block_sq = (blocked.astype(np.float64) ** 2).sum(axis=(2, 3))  # (nrb, ncb)
    occ_i, occ_j = np.nonzero(block_sq > 0)  # row-major: ascending j within i
    cap = bcap
    if cap is None:
        cap = max(int(np.bincount(occ_i, minlength=nrb).max(initial=1)), 1)
    keep, slots, counts = _keep_top_per_group(
        occ_i, block_sq[occ_i, occ_j], nrb, cap)
    if (counts > cap).any():
        warnings.warn(
            f"bsr_from_dense: {int((counts > cap).sum())} row-blocks exceed "
            f"bcap={cap}; keeping the {cap} largest-Frobenius-norm "
            "blocks per row-block",
            stacklevel=2,
        )
    tiles = np.zeros((nrb, cap, bm, bk), dtype=a.dtype)
    bcols = np.zeros((nrb, cap), dtype=np.int32)
    i_k, j_k, s_k = occ_i[keep], occ_j[keep], slots[keep]
    tiles[i_k, s_k] = blocked[i_k, j_k]
    bcols[i_k, s_k] = j_k
    return BSR(jnp.asarray(tiles), jnp.asarray(bcols), (n, m))


def _keep_top_per_group(group_ids, sqnorms, ngroups: int, cap: int):
    """Rank items within each group by descending ``sqnorms``, keep the
    ``cap`` largest per group, and slot the survivors in ascending
    original-index order (the layout invariant ``bsr_from_dense``
    establishes: ascending block-col / source-row-block within a slot row).

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
    matrix: memory and work are proportional to nnz plus the stored-tile
    volume.  This is the ingest path for real vectorizer corpora, where the
    dense (n, m) matrix would not fit on the host.

    ``bcap`` bounds the occupied-block slots per row-block; row-blocks with
    more occupied blocks keep the ``bcap`` largest by Frobenius norm (the
    top-t philosophy applied block-wise) and a warning reports how many
    row-blocks were truncated.
    """
    coo = sp_matrix.tocoo()
    coo.sum_duplicates()
    coo.eliminate_zeros()
    n, m = coo.shape
    data = coo.data if dtype is None else coo.data.astype(dtype)
    nrb, ncb = -(-n // bm), -(-m // bk)
    bi = coo.row // bm
    bj = coo.col // bk
    block_id = bi.astype(np.int64) * ncb + bj
    uniq, inv = np.unique(block_id, return_inverse=True)
    ubi = (uniq // ncb).astype(np.int64)
    ubj = (uniq % ncb).astype(np.int32)
    sqnorms = np.zeros(len(uniq), dtype=np.float64)
    np.add.at(sqnorms, inv, data.astype(np.float64) ** 2)
    cap = bcap
    if cap is None:
        counts = np.bincount(ubi, minlength=nrb)
        cap = max(int(counts.max(initial=1)), 1)
    # on overflow keep the largest-norm blocks per row-block, slotted in
    # ascending block-col order (uniq is sorted by (ubi, ubj), so the
    # no-overflow layout matches bsr_from_dense exactly)
    keep_block, slot, counts = _keep_top_per_group(ubi, sqnorms, nrb, cap)
    if (counts > cap).any():
        warnings.warn(
            f"bsr_from_scipy: {int((counts > cap).sum())} row-blocks exceed "
            f"bcap={cap}; keeping the {cap} largest-Frobenius-norm "
            "blocks per row-block",
            stacklevel=2,
        )
    tiles = np.zeros((nrb, cap, bm, bk), dtype=data.dtype)
    bcols = np.zeros((nrb, cap), dtype=np.int32)
    kept_uniq = keep_block[inv]
    e_bi = bi[kept_uniq]
    e_slot = slot[inv[kept_uniq]]
    e_r = (coo.row[kept_uniq] % bm).astype(np.int64)
    e_c = (coo.col[kept_uniq] % bk).astype(np.int64)
    np.add.at(tiles, (e_bi, e_slot, e_r, e_c), data[kept_uniq])
    bcols[ubi[keep_block], slot[keep_block]] = ubj[keep_block]
    return BSR(jnp.asarray(tiles), jnp.asarray(bcols), (n, m))


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
    """Build the transposed-format copy tile-wise (host-side, once at
    ingest): every occupied tile (i, s) with block-col j becomes tile
    ``tiles[i, s].T`` at row-block j with block-col i.  Work and memory are
    proportional to the number of occupied tiles — the dense (n, m)
    round-trip this replaces OOMed on exactly the large-A regime the paper
    targets.

    An explicit ``bcap`` smaller than a destination row-block's occupancy
    keeps its ``bcap`` largest-Frobenius-norm tiles (the same truncation
    policy as :func:`bsr_from_scipy`) and warns with the truncated count.
    """
    tiles = np.asarray(a.tiles)
    bcols = np.asarray(a.block_cols)
    nrb, _, bm, bk = tiles.shape
    n, m = a.shape
    ncb = -(-m // bk)
    tile_sq = (tiles.astype(np.float64) ** 2).sum(axis=(2, 3))  # (nrb, bcap)
    occ_i, occ_s = np.nonzero(tile_sq > 0)
    occ_j = bcols[occ_i, occ_s].astype(np.int64)
    if bcap is None:
        bcap = max(int(np.bincount(occ_j, minlength=ncb).max(initial=1)), 1)
    # keep the bcap largest-norm tiles per destination row-block, slotted
    # in ascending source-row-block order (occupied tiles enumerate in
    # (i, s) row-major order, matching bsr_from_dense's layout)
    keep, slots, counts = _keep_top_per_group(
        occ_j, tile_sq[occ_i, occ_s], ncb, bcap)
    if (counts > bcap).any():
        warnings.warn(
            f"bsr_transpose: {int((counts > bcap).sum())} row-blocks of the "
            f"transpose exceed bcap={bcap}; keeping the {bcap} "
            "largest-Frobenius-norm tiles per row-block",
            stacklevel=2,
        )
    tiles_t = np.zeros((ncb, bcap, bk, bm), dtype=tiles.dtype)
    bcols_t = np.zeros((ncb, bcap), dtype=np.int32)
    i_o, s_o, j_o = occ_i[keep], occ_s[keep], occ_j[keep]
    tiles_t[j_o, slots[keep]] = tiles[i_o, s_o].transpose(0, 2, 1)
    bcols_t[j_o, slots[keep]] = i_o
    return BSR(jnp.asarray(tiles_t), jnp.asarray(bcols_t), (m, n))


def bsr_operand(a, bm: int = 128, bk: int = 128, bcap: int | None = None,
                dtype=None) -> BSROperand:
    """Build the two-orientation :class:`BSROperand` from a dense array, a
    scipy sparse matrix, or an existing :class:`BSR` (transposed copy added
    tile-wise)."""
    if isinstance(a, BSROperand):
        return a
    if isinstance(a, BSR):
        bsr = a
    elif hasattr(a, "tocoo"):  # scipy sparse, without a hard scipy import
        bsr = bsr_from_scipy(a, bm=bm, bk=bk, bcap=bcap, dtype=dtype)
    else:
        a = np.asarray(a)
        if dtype is not None:
            a = a.astype(dtype)
        bsr = bsr_from_dense(a, bm=bm, bk=bk, bcap=bcap)
    return BSROperand(bsr, bsr_transpose(bsr), bsr.shape)
