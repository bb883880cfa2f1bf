"""Matmul-backend protocol, registry, and auto-selection.

The ALS hot spot is three products — ``A @ V``, ``A^T @ U``, and the small
Gram matrices ``X^T X`` — and the paper's enforced-sparsity claim is that
all three scale with nnz, not n*m.  A :class:`MatmulBackend` bundles one
implementation strategy for the trio; solvers dispatch through the
registry so the Pallas MXU kernels, the padded-CSR gather/scatter
reference, and the dense baseline are interchangeable behind one
``NMFConfig(backend=...)`` switch.

Backends additionally own the *execution topology*: the ALS engine's
residual / error / nnz bookkeeping runs through the reduction hooks
``reduce_u`` / ``reduce_v`` / ``reduce_all`` plus the metric hooks
``sqnorm`` / ``relative_error``.  For single-device backends
(:class:`LocalExecution`) the reductions are identity, so the engine is
bit-for-bit the legacy single-device loop; under
:class:`repro.backend.sharded.ShardedBackend` they become mesh ``psum``s
and the *same* engine runs SPMD over a device grid.  The online engine
(:mod:`repro.core.online`) reduces its sufficient statistics — ``sum
A_c V_c`` via ``matmul``'s contraction, ``sum V_c^T V_c`` via ``reduce_v``
— through the identical hooks, so streaming inherits every execution mode
for free.

Backends are stateless singletons (hashable, compared by identity) so they
can ride through ``jax.jit`` static arguments; the matrix operand itself is
a pytree (dense array, :class:`~repro.sparse.csr.SpCSR`, or
:class:`~repro.kernels.bsr.BSROperand`) traced as usual.

Selection rules (:func:`select_backend` / :func:`default_backend_name`):

* an operand already in a backend's native format picks that backend
  (``BSROperand`` -> ``pallas-bsr``, ``SpCSR`` -> ``jnp-csr``, dense ->
  ``jnp-dense``);
* scipy-sparse *input* at ingest defaults to ``pallas-bsr`` on TPU (the
  MXU fast path) and ``jnp-csr`` elsewhere (the Pallas kernels run in
  interpret mode off-TPU — correct but slow, so they are opt-in there);
* ``NMFConfig(backend=...)`` overrides everything.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Protocol, runtime_checkable

import jax


@runtime_checkable
class MatmulBackend(Protocol):
    """Strategy for the three ALS products plus operand ingest."""

    #: registry key, e.g. ``"pallas-bsr"``
    name: str
    #: True when the backend's epilogue wants the fused relu+threshold-mask
    #: sparsifier (single VMEM pass) instead of relu-then-mask
    fuse_epilogue: bool

    def accepts(self, a) -> bool:
        """True when ``a`` is already this backend's native operand type."""
        ...

    def prepare(self, a, dtype=None, stats=None):
        """Coerce arbitrary input (dense, scipy sparse, SpCSR, BSROperand)
        to this backend's native operand.  Host-side, called once at ingest;
        never materializes a dense matrix from sparse input unless the
        backend itself is dense.  A backend that builds its operand from
        the input adds the bytes it sent to the device to
        ``stats["ingest_bytes"]`` where ``stats`` is given."""
        ...

    def matmul(self, a, v: jax.Array) -> jax.Array:
        """A @ V -> (n, k)."""
        ...

    def matmul_t(self, a, u: jax.Array) -> jax.Array:
        """A^T @ U -> (m, k)."""
        ...

    def gram(self, x: jax.Array) -> jax.Array:
        """X^T X -> (k, k) — the *local* Gram; the engine applies
        ``reduce_u`` / ``reduce_v`` on top (identity on one device)."""
        ...

    def matmul_with_gram(self, a, v: jax.Array):
        """``(A @ V, V^T V)`` — the batch half-step's product pair.  Both
        read V, so a backend that owns its kernels can compute them in one
        sweep while V is resident (the fused Pallas path); the default is
        the separate ``matmul`` + ``gram`` calls, bit-for-bit.  The Gram is
        the *local* one — the engine still applies ``reduce_v``."""
        ...

    def matmul_t_with_gram(self, a, u: jax.Array):
        """``(A^T @ U, U^T U)`` — the other half-step's pair; same fusion
        contract as :meth:`matmul_with_gram`, local Gram."""
        ...

    def reduce_u(self, x: jax.Array) -> jax.Array:
        """Sum ``x`` over U's shard axes (identity on one device)."""
        ...

    def reduce_v(self, x: jax.Array) -> jax.Array:
        """Sum ``x`` over V's shard axis (identity on one device)."""
        ...

    def reduce_all(self, x: jax.Array) -> jax.Array:
        """Sum ``x`` over every shard axis (identity on one device)."""
        ...

    def sqnorm(self, a) -> jax.Array:
        """Global ``||A||_F^2`` of the operand."""
        ...

    def relative_error(self, a, u: jax.Array, v: jax.Array,
                       a_sqnorm: jax.Array) -> jax.Array:
        """Global ``||A - U V^T||_F / ||A||_F``."""
        ...

    def local_sqnorm(self, a) -> jax.Array:
        """``||A||_F^2`` of one *native* operand, with no reduction applied —
        the per-shard contribution :class:`repro.backend.sharded.ShardedBackend`
        psums (on one device it equals ``sqnorm``)."""
        ...

    def local_dot(self, a, u: jax.Array, v: jax.Array) -> jax.Array:
        """``<A, U V^T>`` over one native operand's stored nonzeros, with no
        reduction applied — the relative-error cross term per shard.  Keeping
        this on the *inner* backend is what lets the sharded execution layer
        carry any local operand (padded CSR, BSR tiles, ...) without
        hard-coding a format."""
        ...


class LocalExecution:
    """Single-device execution hooks shared by the local backends.

    Reductions are identity (there is nothing to reduce over) and the
    metric hooks delegate to the operand-type dispatch in
    :mod:`repro.core.nmf`, so every pre-sharding result stays bit-for-bit.
    """

    def reduce_u(self, x):
        return x

    def reduce_v(self, x):
        return x

    def reduce_all(self, x):
        return x

    def matmul_with_gram(self, a, v):
        # separate-launch reference: backends with fused kernels override
        return self.matmul(a, v), self.gram(v)

    def matmul_t_with_gram(self, a, u):
        return self.matmul_t(a, u), self.gram(u)

    def sqnorm(self, a):
        from repro.core.nmf import _sqnorm

        return _sqnorm(a)

    def relative_error(self, a, u, v, a_sqnorm):
        from repro.core.nmf import _relative_error

        return _relative_error(a, u, v, a_sqnorm)

    def local_sqnorm(self, a):
        from repro.core.nmf import _sqnorm

        return _sqnorm(a)


_REGISTRY: Dict[str, MatmulBackend] = {}


def register_backend(backend: MatmulBackend) -> MatmulBackend:
    """Register a backend singleton under ``backend.name``."""
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> MatmulBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown matmul backend {name!r}; available: {available_backends()}"
        ) from None


def available_backends() -> List[str]:
    return sorted(_REGISTRY)


def select_backend(a) -> MatmulBackend:
    """Auto-select by operand type (see module docstring)."""
    for backend in _REGISTRY.values():
        if backend.accepts(a):
            return backend
    raise TypeError(
        f"no registered matmul backend accepts operand of type "
        f"{type(a).__name__}; available: {available_backends()}")


def resolve_backend(a, name: Optional[str] = None) -> MatmulBackend:
    """Backend for an already-ingested operand: the named one (validated
    against the operand type) or the type-selected default."""
    if name is None:
        return select_backend(a)
    backend = get_backend(name)
    if not backend.accepts(a):
        raise TypeError(
            f"backend {name!r} cannot consume operand of type "
            f"{type(a).__name__}; ingest it first with "
            f"get_backend({name!r}).prepare(...)")
    return backend


def default_backend_name(a) -> str:
    """Ingest-time default for raw *input* (before ``prepare``): scipy
    sparse goes to the kernel path on TPU and the jnp-csr reference
    elsewhere; everything else keeps its native format."""
    if hasattr(a, "tocoo"):  # scipy sparse, without a hard scipy import
        return "pallas-bsr" if jax.default_backend() == "tpu" else "jnp-csr"
    return select_backend(a).name
