"""Mesh-native execution layer: any local matmul backend, sharded.

Sharding is an execution property of the one ALS engine, not a second
algorithm.  :class:`ShardedBackend` wraps a *local* backend (``jnp-csr``
or ``pallas-bsr``) with the mesh collectives of DESIGN.md §4:

* ``matmul`` / ``matmul_t`` run the inner backend on the local shard (both
  orientations are stored, so the transpose product is scatter-free) and
  ``psum`` the partial products over the contracted mesh axis;
* ``gram`` stays local — the engine reduces it with ``reduce_u`` /
  ``reduce_v``, which here are ``psum``s over the factor's shard axes;
* ``sqnorm`` / ``relative_error`` psum the *inner backend's* per-shard
  contributions (``local_sqnorm`` / ``local_dot`` protocol hooks), so the
  engine's per-iteration traces are the global quantities for any local
  operand format.

One iteration of Algorithm 2 then costs exactly four psums of useful data —
  G_U   = psum_R(U_i^T U_i)                (k x k)
  V_j   = relu( psum_R(A_ij^T U_i) G_U^{-1} ) , top-t_v
  G_V   = psum_C(V_j^T V_j)                (k x k)
  U_i   = relu( psum_C(A_ij V_j) G_V^{-1} ) , top-t_u
— plus one fused (nbins,)-vector psum per enforced factor for the
histogram top-t threshold (:class:`repro.core.topk.DistTopK`).

No all-gather of A, U, or V ever occurs; peak per-device memory is the
local shard's stored entries * 2 orientations + (n/R + m/C) * k.

Which local operand a shard carries is a pluggable *shard format*
(:data:`_SHARDABLE_INNER`): ``jnp-csr`` devices hold padded-CSR blocks
(:class:`repro.core.distributed.DistCSR`), ``pallas-bsr`` devices hold
dense MXU tiles at sparse block coordinates
(:class:`repro.core.distributed.DistBSR` via ``distribute_bsr``), so every
shard feeds the Pallas streaming-tile kernels directly.  A format is four
leaf arrays with leading (R, C) grid axes plus a rule for rebuilding the
local two-orientation operand inside the shard_map.

:func:`make_sharded_als` is the lowering shim: it shard_maps the *unified*
:func:`repro.core.nmf.als_nmf` over a mesh, handing it a :class:`ShardView`
of the local shards and a :class:`ShardedBackend` carrying the axis names.
:func:`make_sharded_online` does the same for the streaming engine
(:func:`repro.core.online.online_als_step`): chunk columns sharded on the
cols axis, the ``av`` accumulator row-sharded like U, ``gv`` replicated.

Both lowering shims draw their shard_mapped and jitted callables from
*module-level* caches keyed on ``(mesh, axes, sparsifiers, ..., iters)`` —
so repeated ``make_sharded_*`` calls with the same configuration (one per
``EnforcedNMF.fit`` / ``partial_fit``) reuse the compiled executable
instead of recompiling per engine instance.  The jitted callables donate
the large rotating buffers — ``u0`` for the batch engine, the ``av``/``gv``
accumulators for the online engine — so repeated fits and streaming chunks
update the factors in place instead of double-buffering them.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.backend.base import MatmulBackend, get_backend
from repro.core import distributed as _dist
from repro.core.distributed import DistBSR, DistCSR, make_dist_specs
from repro.kernels.bsr import BSR, BSROperand
from repro.sparse.csr import SpCSR

__all__ = ["ShardView", "ShardedBackend", "make_sharded_als",
           "make_sharded_online"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardView:
    """One device's view of the sharded operand, inside a shard_map.

    ``fwd`` is the local A_ij block as an operand the inner backend's
    ``matmul`` consumes (column ids are *local*); ``tsp`` is the same block
    transposed, stored explicitly so A^T @ U is a scatter-free forward
    product.  The concrete types come from the inner backend's shard
    format — padded-CSR ``SpCSR`` pairs for ``jnp-csr``, two-orientation
    ``BSROperand`` views over the same tile arrays for ``pallas-bsr``.
    ``shape`` is the local logical block shape — the engine sizes V's
    local shard from it.
    """

    fwd: Any
    tsp: Any

    @property
    def shape(self) -> Tuple[int, int]:
        return self.fwd.shape


@dataclasses.dataclass(frozen=True)
class ShardedBackend:
    """Wrap a local :class:`MatmulBackend` with mesh collectives.

    Frozen dataclass over (inner backend singleton, axis names): hashable
    by value, so an instance rides through the engine's jit-static
    ``backend`` argument.  Must execute inside a shard_map over a mesh
    defining ``rows_axes`` (U's shard axes) and ``cols_axis`` (V's).
    """

    inner: MatmulBackend
    rows_axes: Tuple[str, ...]
    cols_axis: str

    fuse_epilogue = False

    @property
    def name(self) -> str:
        return f"sharded[{self.inner.name}]"

    # -- operand ingest ------------------------------------------------------

    def accepts(self, a) -> bool:
        return isinstance(a, ShardView)

    def prepare(self, a, dtype=None, stats=None):
        if not isinstance(a, ShardView):
            raise TypeError(
                "ShardedBackend consumes ShardView shards built inside a "
                "shard_map; distribute the matrix first (the engines from "
                "make_sharded_als / make_sharded_online expose "
                "run.distribute)")
        return a

    # -- the three products (local product + psum over the contracted axis) --

    def matmul(self, a: ShardView, v: jax.Array) -> jax.Array:
        """A @ V: local A_ij @ V_j summed over the column blocks."""
        return jax.lax.psum(self.inner.matmul(a.fwd, v), self.cols_axis)

    def matmul_t(self, a: ShardView, u: jax.Array) -> jax.Array:
        """A^T @ U: forward product on the transposed orientation
        (scatter-free), summed over the row blocks."""
        return jax.lax.psum(self.inner.matmul(a.tsp, u), self.rows_axes)

    def gram(self, x: jax.Array) -> jax.Array:
        return self.inner.gram(x)

    def matmul_with_gram(self, a: ShardView, v: jax.Array):
        """Fused half-step pair on the local shard: the inner backend
        computes (A_ij @ V_j, V_j^T V_j) in one sweep (one Pallas launch
        for ``pallas-bsr``); only the product is psummed over the
        contracted axis — the Gram stays local, exactly like :meth:`gram`,
        and the engine reduces it with ``reduce_v``."""
        y, g = self.inner.matmul_with_gram(a.fwd, v)
        return jax.lax.psum(y, self.cols_axis), g

    def matmul_t_with_gram(self, a: ShardView, u: jax.Array):
        """Fused pair on the transposed orientation: forward fused product
        on ``a.tsp`` (scatter-free), product psummed over the row axes,
        Gram local for the engine's ``reduce_u``."""
        y, g = self.inner.matmul_with_gram(a.tsp, u)
        return jax.lax.psum(y, self.rows_axes), g

    # -- reduction hooks (the engine's bookkeeping becomes global) -----------

    def reduce_u(self, x: jax.Array) -> jax.Array:
        return jax.lax.psum(x, self.rows_axes)

    def reduce_v(self, x: jax.Array) -> jax.Array:
        return jax.lax.psum(x, self.cols_axis)

    def reduce_all(self, x: jax.Array) -> jax.Array:
        return jax.lax.psum(jax.lax.psum(x, self.rows_axes), self.cols_axis)

    # -- metrics (per-shard contributions from the inner backend, psummed) ---

    def local_sqnorm(self, a: ShardView) -> jax.Array:
        return self.inner.local_sqnorm(a.fwd)

    def local_dot(self, a: ShardView, u: jax.Array, v: jax.Array) -> jax.Array:
        return self.inner.local_dot(a.fwd, u, v)

    def sqnorm(self, a: ShardView) -> jax.Array:
        return self.reduce_all(self.local_sqnorm(a))

    def relative_error(self, a: ShardView, u: jax.Array, v: jax.Array,
                       a_sqnorm: jax.Array) -> jax.Array:
        """E = ||A - U V^T||_F / ||A||_F from local contributions: the
        inner backend's ``local_dot`` cross term <A_ij, U_i V_j^T> (local
        ids index the local factor shards directly — gather-dots for CSR
        shards, tile-wise einsum for BSR shards) and the Gram cross term
        from the psummed Grams."""
        cross = self.reduce_all(self.local_dot(a, u, v))
        gu = self.reduce_u(u.T @ u)
        gv = self.reduce_v(v.T @ v)
        err_sq = jnp.maximum(a_sqnorm - 2.0 * cross + jnp.sum(gu * gv), 0.0)
        return jnp.sqrt(err_sq / jnp.maximum(a_sqnorm, 1e-30))


# ---------------------------------------------------------------------------
# Shard formats: which local operand each inner backend carries on the mesh
# ---------------------------------------------------------------------------

class _CsrShardFormat:
    """Padded-CSR shards (``DistCSR``): (R, C, rows, cap) value/col grids in
    both orientations, rebuilt as local ``SpCSR`` pairs per device."""

    #: local block shapes are carried by the leaf arrays themselves
    needs_shape = False

    def ingest(self, a, r: int, c: int) -> DistCSR:
        # calls resolve through the module so the no-densify test guards
        # (which monkeypatch repro.core.distributed) stay meaningful
        if isinstance(a, DistCSR):
            return a
        if isinstance(a, SpCSR):
            return _dist.distribute_csr_from_padded(a, r, c)
        if isinstance(a, (BSR, BSROperand)) or hasattr(a, "tocoo"):
            rows_e, cols_e, vals_e, (n, m) = _dist._coo_of(a)
            return _dist._distribute_coo(rows_e, cols_e, vals_e, n, m, r, c)
        import numpy as np

        return _dist.distribute_csr(np.asarray(a), r, c)

    def leaves(self, dist: DistCSR):
        return dist.values, dist.cols, dist.values_t, dist.cols_t

    def leaf_specs(self, rows_axes, cols_axis):
        return (P(rows_axes, cols_axis, None, None),) * 4

    def rebuild(self, leaves, shape) -> DistCSR:
        return DistCSR(*leaves, shape)

    def local(self, leaves, shape, grid) -> ShardView:
        """The (1, 1, rows, cap)-leading local block arrays inside a
        shard_map, as a ShardView over both orientations."""
        values, cols, values_t, cols_t = leaves
        n_loc, m_loc = values.shape[2], values_t.shape[2]
        return ShardView(
            fwd=SpCSR(values[0, 0], cols[0, 0], (n_loc, m_loc)),
            tsp=SpCSR(values_t[0, 0], cols_t[0, 0], (m_loc, n_loc)),
        )


class _BsrShardFormat:
    """BSR tile-grid shards (``DistBSR``): every device holds its block's
    dense MXU tiles at sparse block coordinates, both orientations, and
    feeds them straight to the Pallas streaming-tile kernels.  The local
    logical block shape cannot be recovered from the padded tile arrays,
    so this format threads the global (n, m) through the jit-static
    ``shape`` argument of the lowering shims.

    ``backend_name`` picks which registered Pallas backend resolves the
    ingest tile sizes (through its autotune-ledger ``tile_config``) — the
    fused default and the separate-launch reference share the format."""

    needs_shape = True

    def __init__(self, backend_name: str = "pallas-bsr"):
        self.backend_name = backend_name

    def ingest(self, a, r: int, c: int) -> DistBSR:
        if isinstance(a, DistBSR):
            return a
        be = get_backend(self.backend_name)
        # per-*shard* shape bucket: each device's kernels see the local
        # (n/r, m/c) block, so that is the shape the ledger keys on
        tiles = be.tile_config(max(a.shape[0] // r, 1),
                               max(a.shape[1] // c, 1))
        return _dist.distribute_bsr(a, r, c, bm=tiles.bm, bk=tiles.bk)

    def leaves(self, dist: DistBSR):
        return dist.tiles, dist.block_cols, dist.tiles_t, dist.block_cols_t

    def leaf_specs(self, rows_axes, cols_axis):
        tile_spec = P(rows_axes, cols_axis, None, None, None, None)
        col_spec = P(rows_axes, cols_axis, None, None)
        return (tile_spec, col_spec, tile_spec, col_spec)

    def rebuild(self, leaves, shape) -> DistBSR:
        return DistBSR(*leaves, shape)

    def local(self, leaves, shape, grid) -> ShardView:
        """Strip the (1, 1) grid axes and assemble the two-orientation
        ``BSROperand`` views over the *same* local tile arrays (pure pytree
        reshuffling, zero copies): ``fwd`` runs A_ij @ V_j as forward tile
        products, ``tsp`` runs A_ij^T @ U_i the same way."""
        tiles, bcols, tiles_t, bcols_t = leaves
        (r, c) = grid
        n, m = shape
        n_loc, m_loc = n // r, m // c
        bsr = BSR(tiles[0, 0], bcols[0, 0], (n_loc, m_loc))
        bsr_t = BSR(tiles_t[0, 0], bcols_t[0, 0], (m_loc, n_loc))
        return ShardView(
            fwd=BSROperand(bsr, bsr_t, (n_loc, m_loc)),
            tsp=BSROperand(bsr_t, bsr, (m_loc, n_loc)),
        )


#: local backends whose operands a ShardView can carry, and the shard
#: format (ingest + leaf layout + local rebuild) each one uses
_SHARDABLE_INNER = {
    "jnp-csr": _CsrShardFormat(),
    "pallas-bsr": _BsrShardFormat(),
    "pallas-bsr-unfused": _BsrShardFormat("pallas-bsr-unfused"),
}


def _check_inner(inner: str):
    try:
        return _SHARDABLE_INNER[inner]
    except KeyError:
        raise ValueError(
            f"ShardedBackend wraps one of {sorted(_SHARDABLE_INNER)}, got "
            f"{inner!r}") from None


def _grid_of(mesh, rows_axes, cols_axis) -> Tuple[int, int]:
    r = 1
    for ax in rows_axes:
        r *= mesh.shape[ax]
    return r, mesh.shape[cols_axis]


def _attach_engine_api(run, fmt, mesh, rows_axes, cols_axis, be,
                       shard_fn, jitted):
    """The shared surface of both lowering shims: cached callables, specs,
    and the format-aware ``distribute`` ingest (shard grid + device_put).

    ``run.leaf_specs`` is the per-leaf PartitionSpec tuple of the engine's
    operand grid — correct for any shard format.  ``run.specs`` keeps the
    legacy ``(a_spec, u_spec, v_spec)`` triple whose first element is the
    padded-CSR leaf spec; use ``leaf_specs`` for the operand on non-CSR
    formats (only ``u_spec`` / ``v_spec`` are format-independent)."""
    r, c = _grid_of(mesh, rows_axes, cols_axis)
    leaf_specs = fmt.leaf_specs(rows_axes, cols_axis)

    def distribute(a, pad_cols_to=None):
        """Shard ``a`` for this engine: ingest into the shard format and
        ``device_put`` each leaf onto the mesh.  Already-distributed
        operands pass through ingest unchanged (and the device_put is a
        no-op on matching shardings), so chunks packed ahead of time — the
        corpus :class:`~repro.data.corpus.Prefetcher`'s worker thread —
        cost nothing to re-distribute at step time.

        ``pad_cols_to`` widens the logical column count with empty
        documents before the shard ingest (streaming chunks whose width
        the mesh grid doesn't divide).  No stored entries change: an
        all-zero column yields an exactly-zero V row and contributes
        nothing to the online statistics."""
        if pad_cols_to is not None:
            n, m = a.shape
            if isinstance(a, (DistCSR, DistBSR)):
                if a.shape[1] != pad_cols_to:
                    raise ValueError(
                        f"operand is already distributed at {a.shape}; pad "
                        f"to {pad_cols_to} columns before distributing")
            elif pad_cols_to < m:
                raise ValueError(
                    f"pad_cols_to={pad_cols_to} is narrower than the "
                    f"operand's {m} columns")
            elif pad_cols_to != m:
                if isinstance(a, (SpCSR, BSROperand)):
                    # widen the logical shape only; the shard ingest reads
                    # elements + the logical shape
                    a = dataclasses.replace(a, shape=(n, pad_cols_to))
                else:
                    a = jnp.pad(jnp.asarray(a),
                                ((0, 0), (0, pad_cols_to - m)))
        dist = fmt.ingest(a, r, c)
        put = tuple(
            jax.device_put(x, NamedSharding(mesh, s))
            for x, s in zip(fmt.leaves(dist), leaf_specs))
        return fmt.rebuild(put, dist.shape)

    run.shard_fn = shard_fn
    run.jitted = jitted
    run.backend = be
    run.specs = make_dist_specs(be.rows_axes, cols_axis)
    run.leaf_specs = leaf_specs
    run.distribute = distribute
    return run


@functools.lru_cache(maxsize=None)
def _sharded_als_shard_fn(mesh, rows_axes, cols_axis, sparsify_u, sparsify_v,
                          track_error, inner, iters, shape=None):
    """Module-level cache of the shard_mapped batch-ALS step, keyed on the
    full configuration — repeated ``solve_distributed`` fits with the same
    config get the same callable (and thus jax's compiled-executable
    reuse) instead of recompiling per ``make_sharded_als`` instance.
    ``shape`` is the global (n, m), needed only by shard formats that
    cannot recover the local block shape from the leaf arrays (BSR)."""
    from repro.core.nmf import NMFResult, als_nmf

    fmt = _SHARDABLE_INNER[inner]
    be = ShardedBackend(get_backend(inner), rows_axes, cols_axis)
    grid = _grid_of(mesh, rows_axes, cols_axis)
    _, u_spec, v_spec = make_dist_specs(rows_axes, cols_axis)
    rep = P()
    out_specs = NMFResult(u=u_spec, v=v_spec, residual=rep, error=rep,
                          max_nnz=rep, nnz_u=rep, nnz_v=rep, health=rep)

    def step_fn(*args):
        *leaves, u0 = args
        local = fmt.local(tuple(leaves), shape, grid)
        return als_nmf(local, u0, iters=iters, sparsify_u=sparsify_u,
                       sparsify_v=sparsify_v, track_error=track_error,
                       backend=be)

    return jax.shard_map(
        step_fn,
        mesh=mesh,
        in_specs=(*fmt.leaf_specs(rows_axes, cols_axis), u_spec),
        out_specs=out_specs,
        check_vma=False,
    )


@functools.lru_cache(maxsize=None)
def _sharded_als_jit(mesh, rows_axes, cols_axis, sparsify_u, sparsify_v,
                     track_error, inner, iters, shape=None):
    # donate u0 (argument 4, after the four operand leaves): its sharding
    # matches the output u's, so XLA updates the factor in place across the
    # tol-chunked calls instead of double-buffering the largest live array
    args = (mesh, rows_axes, cols_axis, sparsify_u, sparsify_v, track_error,
            inner, iters)
    fn = (_sharded_als_shard_fn(*args) if shape is None
          else _sharded_als_shard_fn(*args, shape))
    return jax.jit(fn, donate_argnums=(4,))


def make_sharded_als(
    mesh: jax.sharding.Mesh,
    rows_axes: Tuple[str, ...],
    cols_axis: str,
    *,
    sparsify_u=None,
    sparsify_v=None,
    track_error: bool = True,
    inner: str = "jnp-csr",
):
    """shard_map the unified ALS engine over ``mesh``.

    Returns ``run(a, u0, iters) -> NMFResult`` with ``a`` a shard grid in
    ``inner``'s format (``DistCSR`` for ``jnp-csr``, ``DistBSR`` for
    ``pallas-bsr`` — build either with ``run.distribute(operand)``), u0
    (n, k) sharded ``P(rows_axes, None)`` and outputs (u sharded over rows,
    v over cols, replicated scalar traces).  ``sparsify_u`` / ``sparsify_v``
    should be mesh-aware (:class:`repro.core.topk.DistTopK`) or ``None``.
    ``run.shard_fn(iters)`` exposes the un-jitted shard-mapped callable for
    tracing and AOT lowering (the IR analysis traces it).

    The jitted step donates ``u0`` (in-place factor rotation across
    tol-chunked calls); pass a fresh or mesh-resharded array per call —
    ``run.distribute`` plus a ``device_put`` of u0 is the canonical
    driver sequence (see ``solve_distributed``).

    The underlying shard_mapped / jitted callables come from module-level
    caches keyed on ``(mesh, axes, sparsifiers, track_error, inner,
    iters[, shape])``, so constructing a fresh engine per fit (as the
    solver layer does) costs no recompilation.
    """
    fmt = _check_inner(inner)
    key = (mesh, tuple(rows_axes), cols_axis, sparsify_u, sparsify_v,
           track_error, inner)
    be = ShardedBackend(get_backend(inner), tuple(rows_axes), cols_axis)

    def shard_fn(iters: int, shape=None):
        if shape is None:
            return _sharded_als_shard_fn(*key, iters)
        return _sharded_als_shard_fn(*key, iters, shape)

    def jitted(iters: int, shape=None):
        if shape is None:
            return _sharded_als_jit(*key, iters)
        return _sharded_als_jit(*key, iters, shape)

    def run(a, u0: jax.Array, iters: int):
        shape = a.shape if fmt.needs_shape else None
        return jitted(iters, shape)(*fmt.leaves(a), u0)  # repro: allow[donation-safety] donated u0 rides after the starred leaves by contract; solve_distributed copies it before device_put (see docstring)

    return _attach_engine_api(run, fmt, mesh, tuple(rows_axes), cols_axis,
                              be, shard_fn, jitted)


# ---------------------------------------------------------------------------
# Streaming: the online engine shard_mapped over the same grid
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _sharded_online_shard_fn(mesh, rows_axes, cols_axis, sparsify_u,
                             sparsify_v, inner, iters, shape=None):
    from repro.core.online import (
        OnlineStats, OnlineStepResult, online_als_step,
    )

    fmt = _SHARDABLE_INNER[inner]
    be = ShardedBackend(get_backend(inner), rows_axes, cols_axis)
    grid = _grid_of(mesh, rows_axes, cols_axis)
    _, u_spec, v_spec = make_dist_specs(rows_axes, cols_axis)
    rep = P()
    out_specs = OnlineStepResult(
        u=u_spec, v=v_spec, stats=OnlineStats(av=u_spec, gv=rep), health=rep)

    def step_fn(*args):
        *leaves, u, av, gv, forget = args
        local = fmt.local(tuple(leaves), shape, grid)
        return online_als_step(
            local, u, OnlineStats(av=av, gv=gv), forget, iters=iters,
            sparsify_u=sparsify_u, sparsify_v=sparsify_v, backend=be)

    return jax.shard_map(
        step_fn,
        mesh=mesh,
        in_specs=(*fmt.leaf_specs(rows_axes, cols_axis),
                  u_spec, u_spec, rep, rep),
        out_specs=out_specs,
        check_vma=False,
    )


@functools.lru_cache(maxsize=None)
def _sharded_online_jit(mesh, rows_axes, cols_axis, sparsify_u, sparsify_v,
                        inner, iters, shape=None):
    # donate the sufficient-statistics accumulators av (argument 5) and gv
    # (argument 6): their shardings match the returned stats', so every
    # streaming chunk folds into the accumulators in place instead of
    # double-buffering the (n, k) running sum.  u (argument 4) is NOT
    # donated — callers legitimately hold the pre-chunk factor to measure
    # cross-chunk movement (the streaming solver's residual).
    args = (mesh, rows_axes, cols_axis, sparsify_u, sparsify_v, inner, iters)
    fn = (_sharded_online_shard_fn(*args) if shape is None
          else _sharded_online_shard_fn(*args, shape))
    return jax.jit(fn, donate_argnums=(5, 6))


def make_sharded_online(
    mesh: jax.sharding.Mesh,
    rows_axes: Tuple[str, ...],
    cols_axis: str,
    *,
    sparsify_u=None,
    sparsify_v=None,
    inner: str = "jnp-csr",
):
    """shard_map the online engine (:func:`repro.core.online.online_als_step`)
    over ``mesh``.

    Returns ``run(a_chunk, u, stats, iters, forget=1.0) ->
    OnlineStepResult`` where the chunk is a shard grid in ``inner``'s
    format (``run.distribute(chunk)`` builds it — per-device padded CSR
    for ``jnp-csr``, per-device BSR tiles for ``pallas-bsr``), its columns
    sharded over ``cols_axis`` (rows over ``rows_axes``, like the batch
    layout), ``u`` and ``stats.av`` row-sharded ``P(rows_axes, None)``, and
    ``stats.gv`` replicated.  The chunk's sufficient statistics
    ``A_c V_c`` / ``V_c^T V_c`` are mesh-reduced through the
    ``ShardedBackend`` hooks (``matmul`` psums over ``cols_axis``,
    ``reduce_v`` over ``cols_axis``), so the committed accumulators are the
    global quantities — online NMF on a pod with per-device memory
    ~ stored(chunk)/(R*C) + (n/R + m_c/C) * k.

    The jitted step donates ``stats.av`` / ``stats.gv`` (in-place
    accumulator rotation across chunks; the returned stats replace them) —
    ``u`` is not donated, so the pre-chunk factor stays readable.

    ``sparsify_u`` / ``sparsify_v`` should be mesh-aware
    (:class:`repro.core.topk.DistTopK` — ``sparsify_v`` over
    ``(cols_axis,)`` for the per-chunk V top-t) or ``None``.  Callables are
    drawn from the same module-level keyed caches as
    :func:`make_sharded_als`, so one engine per ``partial_fit`` call costs
    no recompilation.
    """
    fmt = _check_inner(inner)
    key = (mesh, tuple(rows_axes), cols_axis, sparsify_u, sparsify_v, inner)
    be = ShardedBackend(get_backend(inner), tuple(rows_axes), cols_axis)

    def shard_fn(iters: int, shape=None):
        if shape is None:
            return _sharded_online_shard_fn(*key, iters)
        return _sharded_online_shard_fn(*key, iters, shape)

    def jitted(iters: int, shape=None):
        if shape is None:
            return _sharded_online_jit(*key, iters)
        return _sharded_online_jit(*key, iters, shape)

    def run(a_chunk, u: jax.Array, stats, iters: int, forget=1.0):
        forget = jnp.asarray(forget, dtype=u.dtype)
        shape = a_chunk.shape if fmt.needs_shape else None
        return jitted(iters, shape)(*fmt.leaves(a_chunk), u, stats.av,  # repro: allow[donation-safety] donated av/gv are the estimator-internal accumulators the returned stats replace; u is not donated (docstring)
                                    stats.gv, forget)

    return _attach_engine_api(run, fmt, mesh, tuple(rows_axes), cols_axis,
                              be, shard_fn, jitted)
