"""Unified fit history: one result type for every solver.

``FitResult`` subsumes the legacy ``NMFResult`` (per-iteration residual /
error / NNZ traces) and ``SequentialResult`` (per-block residual matrix plus
per-block error) so downstream consumers — benchmarks, the CLI, serving —
read one shape regardless of which solver produced it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.nmf import NMFResult
from repro.core.sequential import SequentialResult

__all__ = ["FitResult"]


@dataclasses.dataclass(frozen=True)
class FitResult:
    """Factors plus convergence history.

    ``residual`` is always a flat per-iteration trace (for the sequential
    solver the per-block traces are concatenated in block order; for the
    streaming solver one entry per document chunk).  ``error`` is
    per-iteration for the ALS-family solvers, per-*block* for the
    sequential solver (the legacy semantics — error is only defined once a
    block has converged), and per-*chunk* for the streaming solver;
    ``error_granularity`` says which.
    """

    u: jax.Array                      # (n, k)
    v: jax.Array                      # (m, k)
    residual: jax.Array               # (n_iter,)
    error: jax.Array                  # (n_iter,) or (blocks,)
    max_nnz: jax.Array                # scalar — max NNZ(U)+NNZ(V) over the run
    solver: str
    n_iter: int
    converged: bool = False           # early-stop tolerance was reached
    nnz_u: Optional[jax.Array] = None  # (n_iter,) where the solver tracks it
    nnz_v: Optional[jax.Array] = None
    error_granularity: str = "iteration"   # "iteration" | "block" | "chunk"
    #: the streaming solver's prefetch counters, summed over its prefetched
    #: passes (the stream and the fold-in): ``packed`` chunks, ``pack_s``
    #: seconds inside the packer, ``stall_s`` seconds the fitting thread
    #: waited for a chunk; and ``ingest_bytes``, the bytes the fit's chunk
    #: ingests sent to the device over all its passes, the seed statistics'
    #: included; ``None`` for the other solvers
    stream_stats: Optional[dict] = None

    @property
    def final_error(self) -> float:
        return float(self.error[-1])

    @property
    def final_residual(self) -> float:
        return float(self.residual[-1])

    @property
    def final_nnz_u(self) -> int:
        if self.nnz_u is not None:
            return int(self.nnz_u[-1])
        return int(jnp.sum(self.u != 0))

    @property
    def final_nnz_v(self) -> int:
        if self.nnz_v is not None:
            return int(self.nnz_v[-1])
        return int(jnp.sum(self.v != 0))

    @classmethod
    def from_nmf_result(cls, res: NMFResult, solver: str,
                        converged: bool = False) -> "FitResult":
        return cls(
            u=res.u, v=res.v, residual=res.residual, error=res.error,
            max_nnz=res.max_nnz, solver=solver,
            n_iter=int(res.residual.shape[0]), converged=converged,
            nnz_u=res.nnz_u, nnz_v=res.nnz_v,
        )

    @classmethod
    def from_sequential_result(cls, res: SequentialResult,
                               solver: str = "sequential") -> "FitResult":
        residual = res.residual.reshape(-1)
        return cls(
            u=res.u, v=res.v, residual=residual, error=res.error,
            max_nnz=res.max_nnz, solver=solver,
            n_iter=int(residual.shape[0]),
            error_granularity="block",
        )

    @classmethod
    def concatenate(cls, parts: list["FitResult"],
                    converged: bool = False) -> "FitResult":
        """Stitch chunked runs (early-stop / ``partial_fit``) into one
        history; factors come from the last chunk."""
        if len(parts) == 1:
            return dataclasses.replace(parts[0], converged=converged)
        last = parts[-1]
        cat = lambda field: jnp.concatenate([getattr(p, field) for p in parts])
        has_nnz = all(p.nnz_u is not None for p in parts)
        return cls(
            u=last.u, v=last.v,
            residual=cat("residual"), error=cat("error"),
            max_nnz=jnp.max(jnp.stack([p.max_nnz for p in parts])),
            solver=last.solver, n_iter=sum(p.n_iter for p in parts),
            converged=converged,
            nnz_u=cat("nnz_u") if has_nnz else None,
            nnz_v=cat("nnz_v") if has_nnz else None,
            error_granularity=last.error_granularity,
        )
