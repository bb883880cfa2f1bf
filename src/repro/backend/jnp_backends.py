"""Pure-jnp matmul backends: the dense baseline and the padded-CSR
gather/scatter reference path (the pre-backend-layer production path).

The jnp-csr products are size-triggered: once the gather/contribution
temporary ``(rows, cap, k)`` would exceed ``SPMM_CHUNK_ELEMS`` elements,
they switch to the capacity-axis chunked accumulation the deleted
distributed fork used (``spmm_chunked`` / ``spmm_t_chunked``), whose peak
temporary is ``(rows, SPMM_CHUNK_WIDTH, k)``.  Because
:class:`repro.backend.sharded.ShardedBackend` runs *both* ALS half-steps
through the inner backend's forward ``matmul`` (on the two stored
orientations), sharded runs inherit the chunking automatically.  Set
``REPRO_SPMM_BF16=1`` to additionally gather in bfloat16 with f32
accumulation (the fork's traffic-halving trick; off by default because it
perturbs results beyond summation order).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend.base import LocalExecution, register_backend
from repro.sparse.csr import (
    SpCSR, from_dense, from_scipy, spmm, spmm_chunked, spmm_t,
    spmm_t_chunked,
)

#: element count of the (rows, cap, k) temporary above which the jnp-csr
#: products accumulate over the capacity axis in chunks (default 32 Mi
#: elements = 128 MB in f32); override with REPRO_SPMM_CHUNK_ELEMS, or
#: monkeypatch the module attribute in tests.
SPMM_CHUNK_ELEMS = int(os.environ.get("REPRO_SPMM_CHUNK_ELEMS",
                                      str(32 * 1024 * 1024)))
#: capacity-axis slice width of the chunked accumulation.
SPMM_CHUNK_WIDTH = int(os.environ.get("REPRO_SPMM_CHUNK_WIDTH", "64"))
#: gather in bfloat16 (f32 accumulation) on the chunked path.
SPMM_BF16 = os.environ.get("REPRO_SPMM_BF16", "0").lower() in ("1", "true")


def _chunked_spmm_config(a: SpCSR, k: int):
    """(use_chunked, compute_dtype) for an (a, k)-shaped product — decided
    at trace time from static shapes."""
    rows, cap = a.values.shape
    if rows * cap * k <= SPMM_CHUNK_ELEMS or cap <= SPMM_CHUNK_WIDTH:
        return False, None
    return True, (jnp.bfloat16 if SPMM_BF16 else None)


class JnpDenseBackend(LocalExecution):
    """XLA dense products — the oracle and the small-matrix baseline."""

    name = "jnp-dense"
    fuse_epilogue = False

    def accepts(self, a) -> bool:
        return isinstance(a, (jax.Array, np.ndarray))

    def prepare(self, a, dtype=None, stats=None):
        if isinstance(a, jax.Array) and dtype is None:
            return a  # pass-through: legacy results stay bit-for-bit
        if isinstance(a, SpCSR):
            from repro.sparse.csr import to_dense

            a = to_dense(a)  # repro: allow[no-densify] this IS the dense reference backend — densifying is its contract
            return a if dtype is None else a.astype(dtype)
        if hasattr(a, "toarray"):  # scipy sparse (an explicitly dense ask)
            a = a.toarray()  # repro: allow[no-densify] dense backend ingest boundary; caller chose jnp-dense
        return jnp.asarray(a, dtype=dtype)

    def matmul(self, a, v):
        return a @ v

    def matmul_t(self, a, u):
        return a.T @ u

    def gram(self, x):
        return x.T @ x

    def local_dot(self, a, u, v):
        return jnp.sum(a * (u @ v.T))


class JnpCsrBackend(LocalExecution):
    """Padded-CSR gather/scatter products on ``SpCSR`` operands."""

    name = "jnp-csr"
    fuse_epilogue = False

    def accepts(self, a) -> bool:
        return isinstance(a, SpCSR)

    def prepare(self, a, dtype=None, stats=None):
        if isinstance(a, SpCSR):
            if dtype is not None and a.values.dtype != jnp.dtype(dtype):
                return SpCSR(a.values.astype(dtype), a.cols, a.shape)
            return a
        if hasattr(a, "tocoo"):  # scipy sparse
            sp = from_scipy(a)
        else:
            sp = from_dense(jnp.asarray(a))
        return self.prepare(sp, dtype=dtype)

    def matmul(self, a, v):
        chunked, cd = _chunked_spmm_config(a, v.shape[1])
        if chunked:
            return spmm_chunked(a, v, chunk=SPMM_CHUNK_WIDTH,
                                compute_dtype=cd)
        return spmm(a, v)

    def matmul_t(self, a, u):
        chunked, cd = _chunked_spmm_config(a, u.shape[1])
        if chunked:
            return spmm_t_chunked(a, u, chunk=SPMM_CHUNK_WIDTH,
                                  compute_dtype=cd)
        return spmm_t(a, u)

    def gram(self, x):
        return x.T @ x

    def local_dot(self, a, u, v):
        """<A, U V^T> over the stored slots: the padded-CSR (row, col)
        pairs index the factors directly (under a shard_map the local ids
        index the local factor shards, so this *is* the per-shard cross
        contribution)."""
        rows = jnp.broadcast_to(
            jnp.arange(a.values.shape[0])[:, None], a.cols.shape)
        dots = jnp.sum(u[rows] * v[a.cols], axis=-1)
        return jnp.sum(a.values * dots)


register_backend(JnpDenseBackend())
register_backend(JnpCsrBackend())
