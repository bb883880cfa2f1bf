"""The Pallas MXU backend: BSR streaming-tile products on ``BSROperand``.

``matmul`` and ``matmul_t`` run :func:`repro.kernels.bsr_spmm.bsr_spmm` on
the two BSR orientations built once at ingest (HBM traffic proportional to
occupied blocks — the paper's memory/compute win restated for the MXU);
``gram`` streams (bm, k) row slabs through VMEM once.  The half-step pair
hooks ``matmul_with_gram`` / ``matmul_t_with_gram`` run the *fused*
spmm+gram kernel (:mod:`repro.kernels.fused`) — one launch computes
the sparse product and the Gram from one copy of the dense factor held in
VMEM, so the half-step reads the factor from HBM once.  Tile sizes
resolve through the autotune ledger
(:func:`repro.kernels.autotune.resolve_tiles`) unless pinned at
construction.

Two registry entries share this class:

* ``pallas-bsr`` — the default, fused half-step;
* ``pallas-bsr-unfused`` — the separate-launch reference
  (``fuse_halfstep=False``), kept registered so benchmarks and parity
  tests can measure the fusion win against the identical tile stream.

Off-TPU the kernels execute in Pallas interpret mode: correct, used for CI
validation, slow — hence opt-in there (see :mod:`repro.backend.base`
selection rules).
"""
from __future__ import annotations

import dataclasses

import jax

from repro.backend.base import LocalExecution, register_backend
from repro.kernels.autotune import fused_slots, resolve_tiles
from repro.kernels.bsr import BSROperand, bsr_operand
from repro.kernels.ops import gram_matrix, spmm, spmm_gram, spmm_t, spmm_t_gram
from repro.sparse.csr import SpCSR, to_scipy


class PallasBsrBackend(LocalExecution):
    """MXU block-sparse products over the two-orientation BSR operand."""

    #: the epilogue (relu + top-t threshold mask) runs as one fused
    #: VMEM-tiled pass (kernels.project_mask) instead of two elementwise
    #: passes with a full-size intermediate
    fuse_epilogue = True

    def __init__(self, bm: int | None = None, bk: int | None = None, *,
                 fuse_halfstep: bool = True, name: str = "pallas-bsr"):
        self.name = name
        #: explicit tile dims pin the ingest blocking; ``None`` resolves
        #: per operand shape through the autotune ledger
        self.bm = bm
        self.bk = bk
        #: False = the separate-launch reference path (spmm then gram)
        self.fuse_halfstep = fuse_halfstep

    def tile_config(self, n: int, m: int, k: int | None = None):
        """Ledger-resolved tile sizes for an (n, m[, k]) call site, with
        construction-time ``bm`` / ``bk`` pins applied on top."""
        tiles = resolve_tiles(n, m, k)
        if self.bm is not None or self.bk is not None:
            tiles = dataclasses.replace(
                tiles,
                bm=self.bm if self.bm is not None else tiles.bm,
                bk=self.bk if self.bk is not None else tiles.bk)
        return tiles

    def accepts(self, a) -> bool:
        return isinstance(a, BSROperand)

    def prepare(self, a, dtype=None, bcap: int | None = None,
                stats: dict | None = None) -> BSROperand:
        """Ingest dense / scipy-sparse / SpCSR / BSR input into the
        two-orientation BSR operand.  Sparse inputs never touch a dense
        (n, m) matrix: the host works on the non-zeros and the occupied
        blocks, and both orientations' tiles are filled on the device
        (:func:`repro.kernels.bsr.bsr_operand`).  ``stats["ingest_bytes"]``
        grows by the bytes that went to the device."""
        if isinstance(a, BSROperand):
            return a
        if isinstance(a, SpCSR):
            a = to_scipy(a)  # nnz-proportional host round-trip
        tiles = self.tile_config(*a.shape)
        return bsr_operand(a, bm=tiles.bm, bk=tiles.bk, bcap=bcap,
                           dtype=dtype, stats=stats)

    def matmul(self, a: BSROperand, v: jax.Array) -> jax.Array:
        return spmm(a.bsr, v)

    def matmul_t(self, a: BSROperand, u: jax.Array) -> jax.Array:
        return spmm_t(a.bsr_t, u)

    def gram(self, x: jax.Array) -> jax.Array:
        # the kernel accumulates in f32; cast back so the solve chain keeps
        # the factor dtype (parity with the jnp backends)
        return gram_matrix(x).astype(x.dtype)

    # -- fused half-step pair -------------------------------------------------

    def _fusable(self, bsr, x: jax.Array) -> bool:
        """The fused kernel holds the whole (k, m) factor in VMEM beside its
        tiles, so its working set grows with k x m: fall back to the
        separate launches where not even one tile fits beside the factor
        (or fusion is disabled)."""
        if not self.fuse_halfstep:
            return False
        return fused_slots(bsr.bm, bsr.bk, x.shape[1], bsr.shape[1],
                           bsr.bcap, x.dtype.itemsize) > 0

    def matmul_with_gram(self, a: BSROperand, v: jax.Array):
        if not self._fusable(a.bsr, v):
            return super().matmul_with_gram(a, v)
        y, g = spmm_gram(a.bsr, v)
        return y, g.astype(v.dtype)

    def matmul_t_with_gram(self, a: BSROperand, u: jax.Array):
        if not self._fusable(a.bsr_t, u):
            return super().matmul_t_with_gram(a, u)
        y, g = spmm_t_gram(a.bsr_t, u)
        return y, g.astype(u.dtype)

    def local_dot(self, a: BSROperand, u: jax.Array, v: jax.Array) -> jax.Array:
        from repro.kernels.bsr import bsr_dot_uv

        return bsr_dot_uv(a.bsr, u, v)


register_backend(PallasBsrBackend())
register_backend(PallasBsrBackend(fuse_halfstep=False,
                                  name="pallas-bsr-unfused"))
