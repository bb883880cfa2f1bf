"""Registered solver strategies over the shared ALS engine.

Every solver maps ``(a, config, u0) -> FitResult`` and accepts both dense
``jax.Array`` and padded-CSR ``SpCSR`` inputs (the engines dispatch on the
type internally).  The ALS family — ``als``, ``enforced``, and
``distributed`` — is *one* engine (:func:`repro.core.nmf.als_nmf`) under
three execution configurations: the distributed solver only swaps in a
:class:`repro.backend.sharded.ShardedBackend` and mesh-aware sparsifiers,
so ``tol`` early-stop chunking, per-iteration ``nnz_u``/``nnz_v``
trajectories, ``track_error``, and ``FitResult.converged`` behave
identically on one device or a pod.  The ``streaming`` solver trades the
batch engine for the online one (:mod:`repro.core.online`): column chunks
through accumulated sufficient statistics, locally or mesh-reduced.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.nmf import (
    Matrix, NMFResult, _matmul_t, _relative_error, als_nmf, factor_gram,
    solve_gram,
)
from repro.core.sequential import SequentialResult, sequential_als_nmf
from repro.kernels.bsr import BSROperand
from repro.nmf.config import NMFConfig
from repro.nmf.registry import register_solver
from repro.nmf.result import FitResult
from repro.robustness import faults
from repro.robustness.snapshot import FitCheckpointer, FitHealthError

__all__ = ["solve_als", "solve_enforced", "solve_sequential",
           "solve_distributed", "solve_streaming", "dist_budget",
           "default_chunk_docs", "mesh_inner_backend"]

#: iteration chunk used when an early-stop tolerance is active — small enough
#: to stop promptly, large enough that at most two distinct scan lengths are
#: compiled per run.
_TOL_CHUNK = 10


def default_chunk_docs(m: int) -> int:
    """Streaming solver's default chunk width (8 chunks over the corpus) —
    shared with the CLI so reported doc counts stay in sync."""
    return max(-(-m // 8), 1)


def dist_budget(sparsity, rows: int, k: int, which: str):
    """Whole-factor nonzero budget for the mesh engines'
    :class:`~repro.core.topk.DistTopK`, which always thresholds the whole
    (rows, k) factor.  ``columnwise`` budgets are per *column*, so they
    scale by ``k`` here — total nnz matches the local path, though the
    histogram threshold does not enforce the per-column distribution."""
    t = sparsity.resolve(rows, k, which)
    if t is not None and sparsity.mode == "columnwise":
        t = min(t * k, rows * k)
    return t


def _reject_bsr_operand(a: Matrix, solver_name: str) -> None:
    """The legacy sequential engine dispatches on dense/SpCSR only; a BSR
    operand reaching it would fail deep inside with cryptic
    shape/attribute errors (the config-level check only sees explicitly
    named backends, not an operand passed in directly)."""
    if isinstance(a, BSROperand):
        raise TypeError(
            f"the {solver_name!r} solver does not support BSR operands "
            "(backend 'pallas-bsr'); use the als/enforced solvers, or "
            "pass the matrix as dense / SpCSR / scipy sparse")


def mesh_inner_backend(config: NMFConfig, a: Matrix) -> str:
    """The *local per-shard* backend the mesh engines wrap: an explicit
    ``config.backend`` wins; a ``BSROperand`` operand auto-selects the
    Pallas tile path (its tiles re-pack per device without densifying), an
    already-distributed ``DistBSR`` (a prefetch-packed chunk) keeps it;
    everything else defaults to the padded-CSR reference shards."""
    from repro.core.distributed import DistBSR

    if config.backend is not None:
        return config.backend
    return ("pallas-bsr" if isinstance(a, (BSROperand, DistBSR))
            else "jnp-csr")


def _history_meta(parts) -> dict:
    """Host-side JSON view of the per-iteration histories accumulated so
    far — what a checkpoint's manifest carries so a resumed fit's
    ``FitResult`` covers the pre-crash iterations too."""
    def cat(field):
        return np.concatenate(
            [np.asarray(jax.device_get(getattr(p, field))) for p in parts]
        ).tolist()

    if not parts:
        return {"residual": [], "error": [], "nnz_u": [], "nnz_v": [],
                "max_nnz": 0}
    return {
        "residual": cat("residual"),
        "error": cat("error"),
        "nnz_u": [int(x) for x in cat("nnz_u")],
        "nnz_v": [int(x) for x in cat("nnz_v")],
        "max_nnz": max(int(p.max_nnz) for p in parts),
    }


def _part_from_saved(hist: dict) -> NMFResult:
    """Rebuild the pre-crash history as a synthetic first part.  Its
    factors are ``None`` — only the *last* part's factors are ever read by
    :meth:`FitResult.concatenate`, matching how the tol-chunk loop already
    treats intermediate parts (their ``u`` buffers are donated into the
    next chunk)."""
    return NMFResult(
        u=None, v=None,
        residual=jnp.asarray(hist["residual"], jnp.float32),
        error=jnp.asarray(hist["error"], jnp.float32),
        max_nnz=jnp.int32(hist["max_nnz"]),
        nnz_u=jnp.asarray(hist["nnz_u"], jnp.int32),
        nnz_v=jnp.asarray(hist["nnz_v"], jnp.int32),
    )


def _reseed_perturb(host_u, seed: int, attempt: int) -> jax.Array:
    """Rollback restart point: the restored (clean) factor with a small
    multiplicative jitter from a reseeded key — zeros stay zero (the
    sparsity structure survives) but the trajectory leaves the basin that
    went unstable.  ``attempt`` folds into the key so every retry explores
    a different perturbation."""
    u = jnp.asarray(host_u)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1 + attempt)
    scale = jax.random.uniform(key, u.shape, dtype=u.dtype,
                               minval=0.9, maxval=1.1)
    return u * scale


def _run_chunked(run, config: NMFConfig, u0: jax.Array, solver_name: str,
                 ckpt: FitCheckpointer = None, place=None,
                 u0_src=None) -> FitResult:
    """Drive ``run(u_init, iters) -> NMFResult`` with the shared early-stop
    + checkpoint/resume + health-rollback protocol.  Every ALS-family
    execution mode (local backends and the sharded mesh engine) goes
    through here, so the semantics are defined once.

    The engine recomputes V from U at the top of every iteration, so
    restarting a chunk from a previous chunk's U — whether for ``tol``
    checking, a checkpoint boundary, or a post-crash resume — is exactly
    equivalent to one long run.

    * ``ckpt`` — optional :class:`FitCheckpointer`; snapshots ``u`` plus
      the host-side histories every ``checkpoint_every`` iterations and
      seeds the resume path.
    * ``place`` — maps a restored host array onto the run's device/sharding
      (mesh runs pass a fresh-copy ``device_put``; default ``jnp.asarray``).
      Restoring through ``place`` is what makes restarts *elastic*: the
      snapshot is saved gathered, and whatever mesh the resumed process has
      receives it resharded.
    * ``u0_src`` — a never-donated reference to the initial guess, the
      rollback target when no checkpoint exists yet (the mesh engine
      donates the ``u0`` actually passed to ``run``).

    Host spans name the driver's steps in a profiler trace: ``nmf.dispatch``
    around each ``run`` (which returns once the engine is enqueued),
    ``nmf.sync`` around each blocking read of a device value, and
    ``nmf.result`` around assembling the :class:`FitResult`.
    """
    place = jnp.asarray if place is None else place
    u0_src = u0 if u0_src is None else u0_src
    total = config.iters

    parts, u, done, converged = [], u0, 0, False
    mark = (0, 0)  # (iterations done, len(parts)) at the last good snapshot
    if ckpt is not None and config.resume:
        saved = ckpt.resume()
        if saved is not None:
            done, arrays, meta = saved
            if done >= total:
                raise ValueError(
                    f"checkpoint at {ckpt.ckpt_dir} already holds {done} "
                    f"iterations but config.iters is {total}; raise iters "
                    "(the fingerprint ignores it) to continue the run")
            u = place(arrays["u"])
            parts = [_part_from_saved(meta["history"])]
            mark = (done, 1)

    if config.tol > 0.0:
        step_base = (_TOL_CHUNK if ckpt is None
                     else min(_TOL_CHUNK, ckpt.every))
    else:
        step_base = total if ckpt is None else ckpt.every

    rollbacks = 0
    while done < total:
        step = min(step_base, total - done)
        u_in = faults.poison("poison-step", done, u)
        with jax.profiler.TraceAnnotation("nmf.dispatch"):
            res = run(u_in, step)
        first_bad = -1
        if config.on_unhealthy != "ignore":
            with jax.profiler.TraceAnnotation("nmf.sync"):
                first_bad = int(res.health)
        if first_bad >= 0:
            bad_at = done + first_bad
            if (config.on_unhealthy == "raise"
                    or rollbacks >= config.max_rollbacks):
                raise FitHealthError(
                    f"{solver_name} fit went unhealthy (non-finite factors "
                    f"or exploding residual) at iteration {bad_at}"
                    + ("" if config.on_unhealthy == "raise" else
                       f"; gave up after {rollbacks} rollback(s)"))
            rollbacks += 1
            done, nparts = mark
            parts = parts[:nparts]
            if ckpt is not None and ckpt.last is not None:
                host_u = ckpt.last[1]["u"]
            else:
                with jax.profiler.TraceAnnotation("nmf.sync"):
                    host_u = jax.device_get(u0_src)
            u = place(_reseed_perturb(host_u, config.seed, rollbacks))
            warnings.warn(
                f"{solver_name} fit went unhealthy at iteration {bad_at}; "
                f"rolling back to iteration {done} with reseeded RNG "
                f"(attempt {rollbacks}/{config.max_rollbacks})",
                RuntimeWarning)
            continue
        parts.append(res)
        u, done = res.u, done + step
        if ckpt is not None and ckpt.due(done, total):
            ckpt.save(done, {"u": u}, history=_history_meta(parts))
            mark = (done, len(parts))
        if config.tol > 0.0:
            with jax.profiler.TraceAnnotation("nmf.sync"):
                last_r = float(res.residual[-1])
            if last_r <= config.tol:
                converged = True
                break
    with jax.profiler.TraceAnnotation("nmf.result"):
        return FitResult.concatenate(
            [FitResult.from_nmf_result(p, solver_name) for p in parts],
            converged=converged)


def _als_family(a: Matrix, config: NMFConfig, u0: jax.Array,
                solver_name: str) -> FitResult:
    from repro.backend import resolve_backend

    n, m = a.shape
    # fuse the relu+threshold epilogue into one Pallas pass when the backend
    # asks for it (the jnp backends keep the legacy two-pass epilogue so
    # legacy results stay bit-for-bit)
    fused = resolve_backend(a, config.backend).fuse_epilogue
    sp_u = config.sparsity.sparsifier(n, config.k, "u", fused=fused)
    sp_v = config.sparsity.sparsifier(m, config.k, "v", fused=fused)

    def run(u_init, iters):
        return als_nmf(a, u_init, iters=iters, sparsify_u=sp_u,
                       sparsify_v=sp_v, track_error=config.track_error,
                       backend=config.backend)

    ckpt = FitCheckpointer.from_config(config, a)
    return _run_chunked(run, config, u0, solver_name, ckpt=ckpt)


@register_solver("als")
def solve_als(a: Matrix, config: NMFConfig, u0: jax.Array) -> FitResult:
    """Projected ALS (paper Alg. 1).  With a non-trivial ``Sparsity`` spec
    this is identical to ``"enforced"`` — Alg. 1 is Alg. 2 with identity
    sparsifiers, and the two share one engine."""
    return _als_family(a, config, u0, "als")


@register_solver("enforced")
def solve_enforced(a: Matrix, config: NMFConfig, u0: jax.Array) -> FitResult:
    """Enforced-sparsity ALS (paper Alg. 2): top-t projection of U and/or V
    inside every iteration, per ``config.sparsity``."""
    return _als_family(a, config, u0, "enforced")


@register_solver("sequential", u0_cols=lambda cfg: cfg.block_size)
def solve_sequential(a: Matrix, config: NMFConfig, u0: jax.Array) -> FitResult:
    """Sequential ALS (paper Alg. 3): topics converge one ``block_size``-wide
    block at a time; ``config.iters`` is the per-block budget.

    ``t_u`` / ``t_v`` budgets apply per block (the Alg. 3 semantics); the
    legacy engine enforces them via bisection regardless of ``sparsity.mode``.
    Early-stop ``tol`` is ignored — blocks run their fixed budget.
    ``config.backend`` is threaded through to the block products.
    """
    _reject_bsr_operand(a, "sequential")
    k2 = config.block_size
    blocks = config.k // k2
    if u0.shape[1] == config.k and k2 != config.k:
        u0 = u0[:, :k2]
    if u0.shape[1] != k2:
        raise ValueError(
            f"sequential solver needs u0 with {k2} (block_size) or "
            f"{config.k} (k) columns, got {u0.shape[1]}")
    n, m = a.shape
    common = dict(
        k2=k2, iters=config.iters,
        t_u=config.sparsity.resolve(n, k2, "u"),
        t_v=config.sparsity.resolve(m, k2, "v"),
        track_error=config.track_error,
        backend=config.backend,
    )
    ckpt = FitCheckpointer.from_config(config, a)
    if ckpt is None:
        res = sequential_als_nmf(a, u0, blocks=blocks, **common)
        return FitResult.from_sequential_result(res)

    # Checkpointing: converge checkpoint_every-block groups per compiled
    # call, snapshotting the zero-padded carried factors between groups.
    # Each block update reads only (a, u0, U1, V1), so a resumed group is
    # exactly the computation the uninterrupted scan would have run.
    done = 0
    u1 = v1 = None
    rs_parts, es_parts, mn_parts = [], [], []
    if config.resume:
        saved = ckpt.resume()
        if saved is not None:
            done, arrays, meta = saved
            if done >= blocks:
                raise ValueError(
                    f"checkpoint at {ckpt.ckpt_dir} already holds all "
                    f"{done} converged blocks; nothing to resume")
            u1, v1 = jnp.asarray(arrays["u"]), jnp.asarray(arrays["v"])
            hist = meta["history"]
            rs_parts = [np.asarray(hist["residual"], np.float32)
                        .reshape(done, config.iters)]
            es_parts = [np.asarray(hist["error"], np.float32)]
            mn_parts = [int(hist["max_nnz"])]
    while done < blocks:
        nb = min(ckpt.every, blocks - done)
        res = sequential_als_nmf(a, u0, blocks=nb, total_blocks=blocks,
                                 carry_u=u1, carry_v=v1, start_block=done,
                                 **common)
        u1, v1 = res.u, res.v
        rs_parts.append(np.asarray(jax.device_get(res.residual)))
        es_parts.append(np.asarray(jax.device_get(res.error)))
        mn_parts.append(int(res.max_nnz))
        done += nb
        if ckpt.due(done, blocks):
            ckpt.save(done, {"u": u1, "v": v1}, history={
                "residual": np.concatenate(
                    [r.reshape(-1) for r in rs_parts]).tolist(),
                "error": np.concatenate(es_parts).tolist(),
                "max_nnz": max(mn_parts),
            })
    seq = SequentialResult(
        u=u1, v=v1,
        residual=jnp.asarray(np.concatenate(
            [np.asarray(r).reshape(-1, config.iters) for r in rs_parts])),
        error=jnp.asarray(np.concatenate(es_parts)),
        max_nnz=jnp.int32(max(mn_parts)),
    )
    return FitResult.from_sequential_result(seq)


def _make_packer(model, stream_stats: dict):
    """The host-side pack function the stream (and its
    :class:`~repro.data.corpus.Prefetcher` worker) runs per chunk.

    Local runs ingest the host chunk for the configured backend and
    ``device_put`` the result, so the conversion and the host→device copy
    of chunk N+1 ride under chunk N's compute, as the fold-in's do, and
    ``partial_fit`` finds the backend's operand already on the device.  A
    padded-CSR chunk never goes to the device on the ``pallas-bsr`` path,
    so nothing there depends on its slot capacity (the fullest row, which
    varies with the data and which the device pads to a multiple of 128).
    Mesh runs do the full ahead-of-time pack: pad to the grid + per-device
    shard distribute (:meth:`EnforcedNMF._pack_mesh_chunk`), returning a
    :class:`~repro.data.corpus.PackedChunk`.  A local ingest's counters
    add to ``stream_stats``."""
    if model._mesh_streaming():
        return model._pack_mesh_chunk
    return lambda chunk: jax.device_put(model._coerce(chunk,
                                                      stats=stream_stats))


#: the :class:`~repro.data.corpus.Prefetcher` counters a streamed fit sums
#: over its prefetched passes into ``FitResult.stream_stats``, beside
#: ``ingest_bytes``: the bytes its chunk ingests sent to the device, over
#: all its passes (the seed statistics' included)
STREAM_STATS = ("packed", "pack_s", "stall_s")


def _add_stream_stats(total: dict, stream) -> None:
    for key in STREAM_STATS:
        total[key] += stream.stats[key]


def _fold_in_streamed(model, source, config: NMFConfig,
                      stream_stats: dict) -> jax.Array:
    """Frozen-U fold-in of the whole corpus, one chunk at a time: each
    chunk contributes its rows of the (m, k) right-hand side ``A^T U``,
    then one shared Gram solve + relu + enforcement — the same normal
    equations :meth:`EnforcedNMF.transform` solves, without ever holding a
    resident corpus operand.  Runs the full schedule even when ``tol``
    early-stopped the factor stream, so ``v`` always covers the corpus.
    The prefetcher's counters add to ``stream_stats``; the pass is the
    host span ``nmf.stream.fold_in``."""
    from repro.data.corpus import Prefetcher

    with jax.profiler.TraceAnnotation("nmf.stream.fold_in"):
        u = model.u_
        gram = factor_gram(u)
        parts = []
        with Prefetcher(range(len(source.schedule)),
                        lambda i: model._coerce(source.load(i),
                                                stats=stream_stats),
                        depth=config.prefetch_depth,
                        enabled=config.prefetch) as stream:
            for chunk in stream:
                parts.append(_matmul_t(chunk, u))
        _add_stream_stats(stream_stats, stream)
        v = solve_gram(gram, jnp.concatenate(parts, axis=0))
        return model._enforce_v(jnp.maximum(v, 0.0))


def _restore_stream_state(model, ckpt, u0, config: NMFConfig, attempt: int):
    """Roll the streaming estimator back to the last good snapshot (or the
    initial guess) with a reseed-perturbed factor; returns the restored
    running ``max_nnz``.  The accumulators restore exactly — they are
    stream statistics, not functions of ``u`` — so replaying the chunks
    since the snapshot is the same computation the uninterrupted stream
    would have run."""
    if ckpt is not None and ckpt.last is not None:
        _, arrays, meta = ckpt.last
        model.u_ = _reseed_perturb(arrays["u"], config.seed, attempt)
        model._av_acc = jnp.asarray(arrays["av"])
        model._gv_acc = jnp.asarray(arrays["gv"])
        model.n_docs_seen_ = int(meta["n_docs_seen"])
        return jnp.int32(meta["history"]["max_nnz"])
    model.u_ = _reseed_perturb(jax.device_get(u0), config.seed, attempt)
    model._av_acc = None
    model._gv_acc = None
    model.n_docs_seen_ = 0
    return jnp.sum(model.u_ != 0).astype(jnp.int32)


@register_solver("streaming")
def solve_streaming(a: Matrix, config: NMFConfig, u0: jax.Array) -> FitResult:
    """Online ALS (:mod:`repro.core.online`) over column chunks of ``a`` —
    the corpus is streamed through ``EnforcedNMF.partial_fit`` in
    ``config.chunk_docs``-document chunks (default: 8 chunks), so peak
    factor-side memory is one chunk's loadings plus the two sufficient-
    statistics accumulators, never the full ``V``.

    ``a`` may be resident (dense / ``SpCSR``) or out of core: a
    :func:`repro.data.corpus.write_corpus` directory path,
    :class:`~repro.data.corpus.MmapCorpus`, or any
    :class:`~repro.data.corpus.ChunkSource` streams chunks off disk with
    host memory O(chunk), never O(corpus).  Either way the host half of
    each step (chunk carve / mmap page-in, operand packing, ``device_put``
    — on a mesh, the per-device shard distribute) runs on a prefetch
    worker double-buffered against the in-flight online step
    (``config.prefetch`` / ``prefetch_depth``; results are bit-identical
    with prefetch off).  Resident and from-disk fits carve identical chunk
    arrays under the same schedule, so their trajectories match
    bit-for-bit.

    ``t_v`` budgets resolve against the full corpus and are rescaled per
    chunk, so per-document sparsity matches a batch fit; each chunk gets
    ``min(config.iters, 10)`` inner passes.  With a non-1x1
    ``config.mesh_shape`` every chunk update runs shard_mapped over the
    device grid with the sufficient statistics mesh-reduced
    (:func:`repro.backend.sharded.make_sharded_online`) — online NMF on a
    pod.  ``tol`` early-stops the stream once the cross-chunk relative
    residual ``||U_c - U_{c-1}||_F / ||U_c||_F`` drops below it.

    The returned history is per *chunk* (``error_granularity="chunk"``):
    ``residual`` is the cross-chunk U movement, ``error`` the relative
    reconstruction error of each chunk, and the final ``v`` is one frozen-U
    fold-in pass over the whole corpus (shape (m, k)), streamed chunk-wise
    over the full schedule.  ``stream_stats`` sums the prefetcher's
    counters over both passes.  Each chunk step of the stream is the host
    span ``nmf.stream.chunk``.
    """
    from repro.data.corpus import PackedChunk, Prefetcher, as_chunk_source
    from repro.nmf.estimator import EnforcedNMF

    if isinstance(a, BSROperand):
        raise TypeError(
            "the 'streaming' solver carves column chunks host-side, which "
            "BSR operands (backend 'pallas-bsr') cannot do; fit with dense "
            "/ SpCSR / scipy input (partial_fit chunks may still use any "
            "backend, pallas-bsr included)")
    source = as_chunk_source(a, chunk_docs=config.chunk_docs)
    n, m = source.shape
    n_chunks = len(source.schedule)
    model = EnforcedNMF(config)
    model.u_ = u0
    model.n_features_ = n
    model._m_ref = m  # t_v budgets are full-corpus; chunks rescale
    stream_stats = dict.fromkeys(STREAM_STATS + ("ingest_bytes",), 0)
    pack = _make_packer(model, stream_stats)
    ckpt = FitCheckpointer.from_config(config, source)

    # per-chunk metrics stay device scalars — only the tol check forces a
    # host sync, so with tol=0 chunk dispatches pipeline freely.  Health
    # is synced only at checkpoint boundaries and stream end (NaNs are
    # sticky through the accumulators, so a later check still catches an
    # earlier poisoning) — and always *before* a snapshot commits, so a
    # checkpoint is never poisoned.
    residuals, errors, nnz_us, nnz_vs = [], [], [], []
    max_nnz = jnp.sum(u0 != 0).astype(jnp.int32)
    converged = False
    start = 0
    mark = (0, 0)  # (chunks done, metrics length) at the last good snapshot
    if ckpt is not None and config.resume:
        saved = ckpt.resume()
        if saved is not None:
            start, arrays, meta = saved
            if start >= n_chunks:
                raise ValueError(
                    f"checkpoint at {ckpt.ckpt_dir} already covers all "
                    f"{start} chunks; nothing to resume")
            hist = meta["history"]
            model.u_ = jnp.asarray(arrays["u"])
            model._av_acc = jnp.asarray(arrays["av"])
            model._gv_acc = jnp.asarray(arrays["gv"])
            model.n_docs_seen_ = int(meta["n_docs_seen"])
            residuals = [np.float32(x) for x in hist["residual"]]
            errors = [np.float32(x) for x in hist["error"]]
            nnz_us = [np.int32(x) for x in hist["nnz_u"]]
            nnz_vs = [np.int32(x) for x in hist["nnz_v"]]
            max_nnz = jnp.int32(hist["max_nnz"])
            mark = (start, len(residuals))

    rollbacks = 0
    replay = True
    while replay:
        replay = False
        with Prefetcher(range(start, n_chunks),
                        lambda i: pack(source.load(i)),
                        depth=config.prefetch_depth,
                        enabled=config.prefetch) as stream:
            for idx, packed in zip(range(start, n_chunks), stream):
                with jax.profiler.TraceAnnotation("nmf.stream.chunk"):
                    chunk = (packed.host if isinstance(packed, PackedChunk)
                             else packed)
                    u_prev = model.u_
                    model.u_ = faults.poison("poison-step", idx, model.u_)
                    model.partial_fit(packed)
                    u, v = model.u_, model.v_
                    num = jnp.linalg.norm(u - u_prev)
                    den = jnp.maximum(jnp.linalg.norm(u), 1e-30)
                    r = num / den
                    residuals.append(r)
                    errors.append(_relative_error(chunk, u, v)
                                  if config.track_error else jnp.float32(0.0))
                    nu = jnp.sum(u != 0).astype(jnp.int32)
                    nv = jnp.sum(v != 0).astype(jnp.int32)
                    nnz_us.append(nu)
                    nnz_vs.append(nv)
                    max_nnz = jnp.maximum(max_nnz, nu + nv)
                    done = idx + 1
                    boundary = ckpt is not None and ckpt.due(done, n_chunks)
                    if ((boundary or done == n_chunks)
                            and config.on_unhealthy != "ignore"
                            and int(model.health_) >= 0):
                        if (config.on_unhealthy == "raise"
                                or rollbacks >= config.max_rollbacks):
                            raise FitHealthError(
                                f"streaming fit went unhealthy by chunk {idx}"
                                + ("" if config.on_unhealthy == "raise" else
                                   f"; gave up after {rollbacks} rollback(s)"))
                        rollbacks += 1
                        start, keep = mark
                        del residuals[keep:], errors[keep:]
                        del nnz_us[keep:], nnz_vs[keep:]
                        max_nnz = _restore_stream_state(
                            model, ckpt, u0, config, rollbacks)
                        warnings.warn(
                            f"streaming fit went unhealthy by chunk {idx}; "
                            f"rolling back to chunk {start} with reseeded RNG "
                            f"(attempt {rollbacks}/{config.max_rollbacks})",
                            RuntimeWarning)
                        replay = True
                        break
                    if boundary:
                        ckpt.save(
                            done,
                            {"u": model.u_, "av": model._av_acc,
                             "gv": model._gv_acc},
                            history={
                                "residual": [float(x) for x in residuals],
                                "error": [float(x) for x in errors],
                                "nnz_u": [int(x) for x in nnz_us],
                                "nnz_v": [int(x) for x in nnz_vs],
                                "max_nnz": int(max_nnz),
                            },
                            n_docs_seen=int(model.n_docs_seen_))
                        mark = (done, len(residuals))
                    if config.tol > 0.0 and float(r) <= config.tol:
                        converged = True
                        break
        _add_stream_stats(stream_stats, stream)

    # frozen-U fold-in: the corpus loadings, streamed chunk-wise
    v_full = _fold_in_streamed(model, source, config, stream_stats)
    return FitResult(
        u=model.u_, v=v_full,
        residual=jnp.stack(residuals).astype(jnp.float32),
        error=jnp.stack(errors).astype(jnp.float32),
        max_nnz=max_nnz,
        solver="streaming", n_iter=len(residuals), converged=converged,
        nnz_u=jnp.stack(nnz_us),
        nnz_v=jnp.stack(nnz_vs),
        error_granularity="chunk",
        stream_stats=stream_stats,
    )


@register_solver("distributed")
def solve_distributed(a: Matrix, config: NMFConfig, u0: jax.Array) -> FitResult:
    """Enforced ALS on a ``config.mesh_shape`` device grid — the *same*
    engine as ``als``/``enforced``, shard_mapped with a
    :class:`~repro.backend.sharded.ShardedBackend` and mesh-aware
    :class:`~repro.core.topk.DistTopK` sparsifiers.  It therefore honors
    ``tol`` early stopping, ``track_error``, and the per-iteration
    ``nnz_u``/``nnz_v`` trajectories (running-max ``max_nnz``, Fig. 6
    semantics) exactly like the single-device solvers.

    The default 1x1 mesh runs anywhere (CPU included) through the same
    shard_map code path the pod dry-run lowers; larger meshes need
    ``rows * cols`` visible devices and shapes divisible by the grid.
    ``SpCSR`` input is sharded directly from the padded-CSR arrays —
    nnz-proportional host work, no dense (n, m) driver allocation; dense
    input goes through the thin dense->COO adapter.

    ``config.backend`` names the *local* per-shard backend wrapped by
    ``ShardedBackend``: ``"jnp-csr"`` shards padded CSR, ``"pallas-bsr"``
    shards per-device BSR tile grids (``distribute_bsr``) so every device
    feeds the MXU streaming-tile kernels; ``None`` selects by operand
    (``BSROperand`` -> ``pallas-bsr``, else ``jnp-csr``).  Sparsity
    enforcement always uses the histogram threshold — one fused vector
    psum — so ``sparsity.mode`` bisection/exact variants map onto it here.
    """
    from jax.sharding import NamedSharding

    from repro.backend.sharded import make_sharded_als
    from repro.core.topk import DistTopK
    from repro.launch.mesh import make_nmf_mesh

    r, c = config.mesh_shape
    n, m = a.shape
    if n % r or m % c:
        raise ValueError(
            f"matrix shape {(n, m)} must be divisible by mesh_shape {(r, c)}")
    mesh = make_nmf_mesh(r, c)

    rows_axes, cols_axis = ("data",), "model"
    t_u = dist_budget(config.sparsity, n, config.k, "u")
    t_v = dist_budget(config.sparsity, m, config.k, "v")
    engine = make_sharded_als(
        mesh, rows_axes, cols_axis,
        sparsify_u=None if t_u is None else DistTopK(t_u, rows_axes),
        sparsify_v=None if t_v is None else DistTopK(t_v, (cols_axis,)),
        track_error=config.track_error,
        inner=mesh_inner_backend(config, a),
    )
    _, u_spec, _ = engine.specs
    dist = engine.distribute(a)

    def place(x):
        # the jitted step donates its u argument (in-place factor
        # rotation); device_put may alias the source buffer, so hand it a
        # real copy — one (n, k) allocation per fit / restore, not per
        # iteration.  Restored checkpoints (saved gathered) land here too,
        # resharded onto whatever mesh this process has — elastic restart.
        return jax.device_put(jnp.array(x, copy=True),
                              NamedSharding(mesh, u_spec))

    def run(u_init, iters):
        with jax.set_mesh(mesh):
            return engine(dist, u_init, iters)

    ckpt = FitCheckpointer.from_config(config, a)
    return _run_chunked(run, config, place(u0), "distributed", ckpt=ckpt,
                        place=place, u0_src=u0)
