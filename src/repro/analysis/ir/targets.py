"""The traceable entry-point catalog the IR passes run over.

Every (solver, backend) pair in the registries appears here, either as an
:class:`~repro.analysis.ir.framework.IRTarget` traced with abstract values
(``jax.ShapeDtypeStruct`` leaves inside the real operand pytrees — no data
ever materializes) or as an entry in :data:`UNSUPPORTED_PAIRS` naming why
the registry rejects the combination.  Mesh targets trace the *real*
shard_mapped step functions from :mod:`repro.backend.sharded` over the
2x2 and 4x1 forced-host meshes; kernel targets trace each Pallas kernel
directly so the tile auditor sees its ``pallas_call`` grid mapping.

Shapes are canonical and committed (:data:`CANON`): the planner's peak
bytes go into the budget ledger, so the trace must be byte-for-byte
reproducible across machines.  The shapes are chosen so that on the sparse
backends every legitimate intermediate stays under ``blowup_multiplier``
times the operand footprint while a densified (n, m) intermediate lands
far above it — on every mesh shape (the ratios tighten per shard).
"""
from __future__ import annotations

import functools
import math
from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.ir.framework import IRTarget

__all__ = ["CANON", "UNSUPPORTED_PAIRS", "default_targets", "MESH_SHAPES"]

#: canonical trace shapes — part of the budget ledger's identity: changing
#: any of these is a deliberate re-baseline (--ir --update-budgets)
CANON = dict(
    n=512, m=384, k=4, cap=8, iters=3,
    bm=128, bk=128, bcap=3,
    t_u=1024, t_v=768,
    blowup_multiplier=4.0,
)

MESH_SHAPES: List[Tuple[int, int]] = [(2, 2), (4, 1)]

#: (solver, backend) pairs the registries reject by design — listed so the
#: ledger demonstrably covers the full registry product, not just the
#: pairs that happen to trace
UNSUPPORTED_PAIRS = {
    "sequential[pallas-bsr]":
        "solver registry rejects it: Algorithm 3's rank-k2 block updates "
        "have no BSR operand path",
    "distributed[jnp-dense]":
        "mesh execution requires a sharded operand format; jnp-dense has "
        "no shard format (see backend.sharded._SHARDABLE_INNER)",
    "streaming[mesh,jnp-dense]":
        "same constraint as distributed[jnp-dense]: no dense shard format",
}


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _nbytes(*trees) -> int:
    total = 0
    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            total += math.prod(leaf.shape) * leaf.dtype.itemsize
    return total


def _csr_struct(n, m, cap):
    from repro.sparse.csr import SpCSR

    return SpCSR(_sds((n, cap)), _sds((n, cap), jnp.int32), (n, m))


def _bsr_struct(n, m, bm, bk, bcap):
    from repro.kernels.bsr import BSR, BSROperand

    nrb, nrb_t = -(-n // bm), -(-m // bk)
    bsr = BSR(_sds((nrb, bcap, bm, bk)), _sds((nrb, bcap), jnp.int32),
              (n, m))
    bsr_t = BSR(_sds((nrb_t, bcap, bk, bm)), _sds((nrb_t, bcap), jnp.int32),
                (m, n))
    return BSROperand(bsr, bsr_t, (n, m))


def _operand(backend, n, m):
    c = CANON
    if backend == "jnp-dense":
        return _sds((n, m))
    if backend == "jnp-csr":
        return _csr_struct(n, m, c["cap"])
    return _bsr_struct(n, m, c["bm"], c["bk"], c["bcap"])


def _sparsifiers(backend):
    """The epilogue sparsifiers the local solver layer would build: fused
    relu+top-t for the backend that owns its epilogue, bisection top-t
    otherwise (both hashable, riding the jit-static arguments)."""
    from repro.core import topk

    if backend == "pallas-bsr":
        return topk.FusedReluTopK(CANON["t_u"]), topk.FusedReluTopK(CANON["t_v"])
    return (functools.partial(topk.topk_project_bisect, t=CANON["t_u"]),
            functools.partial(topk.topk_project_bisect, t=CANON["t_v"]))


# ---------------------------------------------------------------------------
# Local engine targets
# ---------------------------------------------------------------------------

def _als_target(backend: str, enforced: bool) -> IRTarget:
    c = CANON
    a = _operand(backend, c["n"], c["m"])
    u0 = _sds((c["n"], c["k"]))
    sp_u, sp_v = _sparsifiers(backend) if enforced else (None, None)

    def trace():
        from repro.core.nmf import als_nmf

        def step(a, u0):
            return als_nmf(a, u0, iters=c["iters"], sparsify_u=sp_u,
                           sparsify_v=sp_v, track_error=True,
                           backend=backend)

        return jax.make_jaxpr(step)(a, u0)

    solver = "enforced" if enforced else "als"
    name = f"{solver}[{backend}]"
    return IRTarget(name=name, kind="engine", trace=trace,
                    operand_bytes=_nbytes(a), budget_key=name)


def _sequential_target(backend: str) -> IRTarget:
    c = CANON
    a = _operand(backend, c["n"], c["m"])
    k2, blocks = 2, 2
    u0 = _sds((c["n"], k2))

    def trace():
        from repro.core.sequential import sequential_als_nmf

        def step(a, u0):
            return sequential_als_nmf(
                a, u0, k2=k2, blocks=blocks, iters=c["iters"],
                t_u=c["t_u"] // blocks, t_v=c["t_v"] // blocks,
                track_error=True, backend=backend)

        return jax.make_jaxpr(step)(a, u0)

    name = f"sequential[{backend}]"
    return IRTarget(name=name, kind="engine", trace=trace,
                    operand_bytes=_nbytes(a), budget_key=name)


def _streaming_local_target(backend: str) -> IRTarget:
    c = CANON
    a = _operand(backend, c["n"], c["m"])
    u = _sds((c["n"], c["k"]))
    av, gv = _sds((c["n"], c["k"])), _sds((c["k"], c["k"]))
    sp_u, sp_v = _sparsifiers(backend)

    def trace():
        from repro.core.online import OnlineStats, online_als_step

        def step(a, u, av, gv, forget):
            return online_als_step(a, u, OnlineStats(av=av, gv=gv), forget,
                                   iters=2, sparsify_u=sp_u, sparsify_v=sp_v,
                                   backend=backend)

        return jax.make_jaxpr(step)(a, u, av, gv, _sds(()))

    name = f"streaming[{backend}]"
    return IRTarget(name=name, kind="engine", trace=trace,
                    operand_bytes=_nbytes(a), budget_key=name)


def _streaming_corpus_target() -> IRTarget:
    """The prefetch-fed per-chunk step: the same online half-step the
    out-of-core stream runs, traced over one corpus chunk exactly as the
    ``Prefetcher`` delivers it — chunk-width operand padded to the shared
    per-chunk row cap, not the O(corpus) cap of the full matrix."""
    c = CANON
    m_chunk, chunk_cap = c["m"] // 8, 4
    a = _csr_struct(c["n"], m_chunk, chunk_cap)
    u = _sds((c["n"], c["k"]))
    av, gv = _sds((c["n"], c["k"])), _sds((c["k"], c["k"]))
    sp_u, sp_v = _sparsifiers("jnp-csr")

    def trace():
        from repro.core.online import OnlineStats, online_als_step

        def step(a, u, av, gv, forget):
            return online_als_step(a, u, OnlineStats(av=av, gv=gv), forget,
                                   iters=2, sparsify_u=sp_u, sparsify_v=sp_v,
                                   backend="jnp-csr")

        return jax.make_jaxpr(step)(a, u, av, gv, _sds(()))

    name = "streaming[corpus,jnp-csr]"
    return IRTarget(name=name, kind="engine", trace=trace,
                    operand_bytes=_nbytes(a), budget_key=name)


# ---------------------------------------------------------------------------
# Mesh targets: the real shard_mapped steps over forced-host meshes
# ---------------------------------------------------------------------------

def _dist_leaves(inner: str, r: int, c: int):
    cn = CANON
    n, m = cn["n"], cn["m"]
    n_loc, m_loc = n // r, m // c
    if inner == "jnp-csr":
        cap = cn["cap"]
        return (_sds((r, c, n_loc, cap)), _sds((r, c, n_loc, cap), jnp.int32),
                _sds((r, c, m_loc, cap)), _sds((r, c, m_loc, cap), jnp.int32))
    bm, bk, bcap = cn["bm"], cn["bk"], 2
    nrb, nrb_t = -(-n_loc // bm), -(-m_loc // bk)
    return (_sds((r, c, nrb, bcap, bm, bk)),
            _sds((r, c, nrb, bcap), jnp.int32),
            _sds((r, c, nrb_t, bcap, bk, bm)),
            _sds((r, c, nrb_t, bcap), jnp.int32))


def _mesh_engine(rc: Tuple[int, int], inner: str):
    """(engine-builder, shard-shape arg) for a mesh ALS target — built lazily
    so no devices are touched until the target actually traces."""
    from repro.backend.sharded import make_sharded_als
    from repro.core.topk import DistTopK
    from repro.launch.mesh import make_nmf_mesh

    mesh = make_nmf_mesh(*rc)
    eng = make_sharded_als(
        mesh, ("data",), "model",
        sparsify_u=DistTopK(CANON["t_u"], ("data",)),
        sparsify_v=DistTopK(CANON["t_v"], ("model",)),
        track_error=True, inner=inner)
    shape = (CANON["n"], CANON["m"]) if inner == "pallas-bsr" else None
    return eng, shape


def _distributed_target(rc: Tuple[int, int], inner: str) -> IRTarget:
    c = CANON
    leaves = _dist_leaves(inner, *rc)
    u0 = _sds((c["n"], c["k"]))

    def trace():
        eng, shape = _mesh_engine(rc, inner)
        return jax.make_jaxpr(eng.shard_fn(c["iters"], shape))(*leaves, u0)

    lower = None
    if inner == "jnp-csr":  # Pallas-bearing steps cannot compile off-TPU
        def lower():
            eng, shape = _mesh_engine(rc, inner)
            return eng.jitted(c["iters"], shape).lower(*leaves, u0).compile()

    name = f"distributed[{rc[0]}x{rc[1]},{inner}]"
    return IRTarget(name=name, kind="mesh", trace=trace, lower=lower,
                    donate_argnums=(4,),  # u0, per _sharded_als_jit
                    operand_bytes=_nbytes(leaves) // (rc[0] * rc[1]),
                    requires_devices=rc[0] * rc[1], budget_key=name)


def _streaming_mesh_target(rc: Tuple[int, int], inner: str) -> IRTarget:
    c = CANON
    leaves = _dist_leaves(inner, *rc)
    u = _sds((c["n"], c["k"]))
    av, gv = _sds((c["n"], c["k"])), _sds((c["k"], c["k"]))

    def make_engine():
        from repro.backend.sharded import make_sharded_online
        from repro.core.topk import DistTopK
        from repro.launch.mesh import make_nmf_mesh

        mesh = make_nmf_mesh(*rc)
        eng = make_sharded_online(
            mesh, ("data",), "model",
            sparsify_u=DistTopK(c["t_u"], ("data",)),
            sparsify_v=DistTopK(c["t_v"], ("model",)),
            inner=inner)
        shape = (c["n"], c["m"]) if inner == "pallas-bsr" else None
        return eng, shape

    def trace():
        eng, shape = make_engine()
        return jax.make_jaxpr(eng.shard_fn(2, shape))(
            *leaves, u, av, gv, _sds(()))

    lower = None
    if inner == "jnp-csr":
        def lower():
            eng, shape = make_engine()
            return eng.jitted(2, shape).lower(
                *leaves, u, av, gv, _sds(())).compile()

    name = f"streaming[{rc[0]}x{rc[1]},{inner}]"
    return IRTarget(name=name, kind="mesh", trace=trace, lower=lower,
                    donate_argnums=(5, 6),  # av, gv, per _sharded_online_jit
                    operand_bytes=_nbytes(leaves) // (rc[0] * rc[1]),
                    requires_devices=rc[0] * rc[1], budget_key=name)


# ---------------------------------------------------------------------------
# Kernel targets: each Pallas kernel, traced so the tile auditor sees its
# grid mapping (lowering them needs a TPU; tracing does not)
# ---------------------------------------------------------------------------

def _kernel_targets() -> List[IRTarget]:
    c = CANON
    out = []

    bsr = _bsr_struct(c["n"], c["m"], c["bm"], c["bk"], c["bcap"]).bsr
    u = _sds((c["m"], c["k"]))

    def trace_spmm():
        from repro.kernels.bsr_spmm import bsr_spmm

        return jax.make_jaxpr(lambda a, u: bsr_spmm(a, u))(bsr, u)

    out.append(IRTarget(
        name="kernel:bsr_spmm", kind="kernel", trace=trace_spmm,
        operand_bytes=_nbytes(bsr, u),
        # the docstring's working-set claim, now checked: bm*bk tile +
        # bk*kb U slab + bm*kb acc, f32, with kb = k for a factor no wider
        # than the k tile ("(128, 128, k=4) uses 68 KiB")
        documented_vmem_bytes=(
            (c["bm"] * c["bk"] + c["bk"] * c["k"] + c["bm"] * c["k"]) * 4),
        budget_key="kernel:bsr_spmm"))

    def trace_spmm_gram():
        from repro.kernels.fused import bsr_spmm_gram

        return jax.make_jaxpr(lambda a, u: bsr_spmm_gram(a, u))(bsr, u)

    out.append(IRTarget(
        name="kernel:bsr_spmm_gram", kind="kernel", trace=trace_spmm_gram,
        operand_bytes=_nbytes(bsr, u),
        # the fused.py docstring's working-set claim, now checked: S*bm*bk
        # tiles + the resident k*m factor + bm*k acc (f32) plus the f32 k*k
        # Gram, with S = bcap here (3 slots, below the 16-tile step target)
        documented_vmem_bytes=(
            (c["bcap"] * c["bm"] * c["bk"] + c["k"] * c["m"]
             + c["bm"] * c["k"]) * 4 + c["k"] * c["k"] * 4),
        budget_key="kernel:bsr_spmm_gram"))

    ug = _sds((c["n"], c["k"]))

    def trace_gram():
        from repro.kernels.gram import gram

        return jax.make_jaxpr(lambda u: gram(u))(ug)

    out.append(IRTarget(
        name="kernel:gram", kind="kernel", trace=trace_gram,
        operand_bytes=_nbytes(ug), budget_key="kernel:gram"))

    x = _sds((c["n"], c["k"]))

    def trace_mask():
        from repro.kernels.project_mask import project_mask

        return jax.make_jaxpr(lambda x, tau: project_mask(x, tau))(x, _sds(()))

    out.append(IRTarget(
        name="kernel:project_mask", kind="kernel", trace=trace_mask,
        operand_bytes=_nbytes(x), budget_key="kernel:project_mask"))

    q = _sds((1, 2, 512, 64))

    def trace_flash():
        from repro.kernels.flash_attention import flash_attention

        return jax.make_jaxpr(
            lambda q, k, v: flash_attention(q, k, v, causal=True))(q, q, q)

    out.append(IRTarget(
        name="kernel:flash_attention", kind="kernel", trace=trace_flash,
        operand_bytes=_nbytes(q) * 3, budget_key="kernel:flash_attention"))
    return out


def default_targets() -> List[IRTarget]:
    targets = []
    for backend in ("jnp-dense", "jnp-csr", "pallas-bsr"):
        targets.append(_als_target(backend, enforced=False))
        targets.append(_als_target(backend, enforced=True))
        targets.append(_streaming_local_target(backend))
    targets.append(_streaming_corpus_target())
    for backend in ("jnp-dense", "jnp-csr"):
        targets.append(_sequential_target(backend))
    for rc in MESH_SHAPES:
        for inner in ("jnp-csr", "pallas-bsr"):
            targets.append(_distributed_target(rc, inner))
            targets.append(_streaming_mesh_target(rc, inner))
    targets.extend(_kernel_targets())
    return targets
