"""``EnforcedNMF`` — the single estimator front door.

scikit-learn's ``NMF`` shape (``fit`` / ``fit_transform`` / ``transform``)
plus gensim's streaming ``partial_fit``, over the paper's solver family:

    A (n_terms x m_docs)  ~=  U (n_terms x k) @ V (m_docs x k)^T

``U`` holds the term-topic factors ("components"), ``V`` the document-topic
loadings.  ``fit`` dispatches through the solver registry; ``transform``
folds unseen documents into a fitted topic space with ``U`` frozen (one
enforced-sparsity least-squares pass — topic inference for new documents);
``partial_fit`` streams document mini-batches through the online engine
(:mod:`repro.core.online`) with accumulated sufficient statistics,
gensim-style.  The estimator itself is a thin adapter: the update lives in
:func:`repro.core.online.online_als_step`, runs through the configured
matmul backend, and — with ``solver="streaming"`` and a non-1x1
``mesh_shape`` — executes shard_mapped over a device grid with the
statistics mesh-reduced (:func:`repro.backend.sharded.make_sharded_online`).

Inputs may be dense ``jax.Array`` / numpy arrays, padded-CSR ``SpCSR``, or
scipy sparse matrices (term-document matrices from sklearn/gensim
vectorizers — converted via :func:`repro.sparse.from_scipy`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import BSROperand, default_backend_name, get_backend
from repro.core.distributed import DistBSR, DistCSR
from repro.core.nmf import (
    Matrix, _matmul, _matmul_t, _relative_error, factor_gram, init_u0,
    solve_gram,
)
from repro.core.online import (
    OnlineStats, init_online_stats, online_als_step, seed_online_stats,
)
from repro.nmf.config import NMFConfig, Sparsity
from repro.nmf.registry import get_solver
from repro.nmf.result import FitResult
from repro.sparse.csr import SpCSR

__all__ = ["EnforcedNMF"]

ArrayLike = Union[jax.Array, np.ndarray, SpCSR, BSROperand]


class EnforcedNMF:
    """Estimator over the enforced-sparse NMF solver family.

    >>> model = EnforcedNMF(NMFConfig(k=5, sparsity=Sparsity(t_u=55)))
    >>> model.fit(a)                       # a: (n_terms, m_docs)
    >>> v_new = model.transform(a_held_out)  # fold-in, U frozen

    Keyword overrides are applied on top of the given config, so
    ``EnforcedNMF(k=10, solver="sequential")`` works without building an
    ``NMFConfig`` by hand.

    ``solver="distributed"`` executes the same ALS engine shard_mapped
    over a ``config.mesh_shape`` device grid (``("data", "model")`` axes):
    the fitted ``u_`` comes back sharded over ``"data"``, ``v_`` over
    ``"model"``, and the history traces are replicated scalars — every
    other estimator feature (``tol``, ``track_error``, nnz trajectories)
    is unchanged because the engine is.

    Fitted attributes: ``u_`` (n, k), ``v_`` (m, k), ``result_``
    (:class:`FitResult` history), ``n_iter_``, ``n_features_`` (term count),
    ``n_docs_seen_``.
    """

    def __init__(self, config: Optional[NMFConfig] = None, **overrides):
        if config is None:
            config = NMFConfig()
        if overrides:
            config = dataclasses.replace(config, **overrides)
        self.config = config
        self.u_: Optional[jax.Array] = None
        self.v_: Optional[jax.Array] = None
        self.result_: Optional[FitResult] = None
        self.n_iter_: int = 0
        self.n_features_: Optional[int] = None
        self.n_docs_seen_: int = 0
        # first unhealthy inner pass of the latest online step (-1 = ok);
        # the streaming solver reads this at checkpoint boundaries
        self.health_ = jnp.int32(-1)
        # reference document count for scaling absolute t_v budgets in
        # transform, and online-ALS sufficient statistics for partial_fit
        self._m_ref: Optional[int] = None
        self._av_acc: Optional[jax.Array] = None   # sum A_c V_c   (n, k)
        self._gv_acc: Optional[jax.Array] = None   # sum V_c^T V_c (k, k)

    # -- input coercion ------------------------------------------------------

    def _coerce(self, a: ArrayLike, chunkable: bool = False,
                for_mesh: bool = False, stats: Optional[dict] = None) -> Matrix:
        """Accept jax/numpy dense, SpCSR, BSROperand, or scipy sparse and
        ingest it for ``config.backend``.

        With no explicit backend, jax arrays / SpCSR / BSROperand pass
        through untouched (bit-for-bit with the legacy entry points) and
        scipy sparse takes the device default (Pallas BSR kernels on TPU,
        jnp-csr elsewhere) — never densifying.  An explicit
        ``config.backend`` converts whatever comes in to that backend's
        operand; numpy/scipy input is cast to ``config.dtype``.

        ``chunkable=True`` (the streaming ``fit``) keeps a pallas-bsr
        target in column-sliceable SpCSR form instead — the corpus must be
        carved into document chunks host-side, and each chunk re-ingests
        for the configured backend inside ``partial_fit``.  ``for_mesh``
        (the distributed solver and mesh-streaming chunks) likewise skips
        the single-operand BSR conversion: the sharded ingest re-packs the
        corpus into *per-device* tile grids / CSR blocks
        (``engine.distribute``), so a whole-corpus ``BSROperand`` here
        would be packed twice.  ``stats`` collects the backend's ingest
        counters (``Backend.prepare``)."""
        name = self.config.backend
        if for_mesh and isinstance(a, BSROperand):
            # every shard format re-packs the stored tiles per device
            # (pallas-bsr tile-wise, jnp-csr through the COO front door)
            return a
        if (chunkable or for_mesh) and name and name.startswith("pallas-bsr"):
            name = "jnp-csr"
        if name is None:
            if isinstance(a, (SpCSR, BSROperand, jax.Array)):
                return a
            if hasattr(a, "tocoo"):  # scipy sparse, without a hard import
                name = default_backend_name(a)
                if (name == "pallas-bsr"
                        and (for_mesh
                             or self.config.solver in ("sequential",
                                                       "distributed",
                                                       "streaming"))):
                    # sequential dispatches on dense/SpCSR only; the
                    # streaming fit carves column chunks host-side and the
                    # mesh paths re-pack per device — keep the sliceable
                    # COO-able form (the mesh engines still run the Pallas
                    # kernels per shard when backend="pallas-bsr")
                    name = "jnp-csr"
            else:
                return jnp.asarray(a, dtype=self.config.jnp_dtype)
        native = isinstance(a, (SpCSR, BSROperand, jax.Array))
        counters = {} if stats is None else {"stats": stats}
        return get_backend(name).prepare(
            a, dtype=None if native else self.config.jnp_dtype, **counters)

    def _check_fitted(self):
        if self.u_ is None:
            raise RuntimeError(
                "this EnforcedNMF instance is not fitted yet; "
                "call fit or partial_fit first")

    def _check_features(self, a: Matrix):
        if self.n_features_ is not None and a.shape[0] != self.n_features_:
            raise ValueError(
                f"input has {a.shape[0]} terms, the fitted model has "
                f"{self.n_features_}")

    # -- fitting -------------------------------------------------------------

    def fit(self, a: ArrayLike, u0: Optional[jax.Array] = None,
            resume: Optional[bool] = None) -> "EnforcedNMF":
        """Factorize ``a`` with the configured solver.  ``u0`` overrides the
        seeded default initial guess (shape (n, k); the sequential solver
        also accepts the (n, block_size) block shape).

        With ``solver="streaming"``, ``a`` may also be out of core: a
        :func:`repro.data.corpus.write_corpus` directory path, an
        :class:`~repro.data.corpus.MmapCorpus`, or any
        :class:`~repro.data.corpus.ChunkSource` — chunks stream off disk
        (double-buffered against compute per ``config.prefetch``) and host
        memory stays O(chunk), never O(corpus).

        ``resume`` overrides ``config.resume`` for this call: with a
        ``config.checkpoint_dir`` holding a snapshot of this same run, the
        fit continues from it instead of starting over (see
        :mod:`repro.robustness`).

        Under a profiler trace the call is the host span ``nmf.fit``;
        inside it ``nmf.prepare`` (input coercion and the initial guess),
        the solver's spans and ``nmf.seed_stats`` (the statistics
        ``partial_fit`` continues from).  A streamed fit adds, on this
        thread, ``nmf.stream.chunk`` (each chunk step of the stream pass),
        ``nmf.stream.stall`` (waiting on the prefetcher),
        ``nmf.stream.ingest`` (a chunk's conversion to the backend operand
        here) and ``nmf.stream.fold_in`` (the frozen-U pass), and on the
        prefetch worker ``nmf.stream.pack`` around each pack."""
        from repro.data.corpus import as_chunk_source, is_corpus_input

        with jax.profiler.TraceAnnotation("nmf.fit"):
            cfg = self.config
            if resume is not None:
                cfg = cfg.replace(resume=bool(resume))
            with jax.profiler.TraceAnnotation("nmf.prepare"):
                streamed = is_corpus_input(a)
                if streamed:
                    if cfg.solver != "streaming":
                        raise ValueError(
                            f"out-of-core corpora stream chunk-wise; the "
                            f"{cfg.solver!r} solver needs a resident "
                            "matrix — use solver='streaming' (or load the "
                            "corpus yourself)")
                    a = as_chunk_source(a, chunk_docs=cfg.chunk_docs)
                else:
                    a = self._coerce(a, chunkable=cfg.solver == "streaming",
                                     for_mesh=cfg.solver == "distributed")
                n, m = a.shape
                entry = get_solver(cfg.solver)
                if u0 is None:
                    u0 = init_u0(jax.random.PRNGKey(cfg.seed), n,
                                 entry.u0_cols(cfg)).astype(cfg.jnp_dtype)
            result = entry.fn(a, cfg, u0)
            self.u_, self.v_, self.result_ = result.u, result.v, result
            self.n_iter_ = result.n_iter
            self.n_features_ = n
            # fit is from-scratch; only partial_fit accumulates
            self.n_docs_seen_ = m
            self._m_ref = m
            # seed streaming statistics so partial_fit continues from this
            # fit; one extra backend spmm (~1/(2*iters) of the fit) beats
            # pinning the corpus
            with jax.profiler.TraceAnnotation("nmf.seed_stats"):
                if streamed:
                    stats = self._seed_stats_streamed(a, result.stream_stats)
                else:
                    seed_backend = cfg.backend
                    if (seed_backend is not None
                            and not get_backend(seed_backend).accepts(a)):
                        # the corpus stayed in a sliceable / shardable form
                        # (streaming fit keeps SpCSR for column chunks; the
                        # mesh paths re-pack per device) — seed through the
                        # operand's own backend instead
                        seed_backend = None
                    stats = seed_online_stats(a, self.v_,
                                              backend=seed_backend)
            self._av_acc, self._gv_acc = stats.av, stats.gv
            return self

    def _seed_stats_streamed(self, source,
                             stream_stats: Optional[dict] = None) -> OnlineStats:
        """Full-corpus online statistics ``(A V, V^T V)`` from a chunk
        source, one chunk resident at a time: each chunk contributes
        ``A_c V_c`` with its rows of the fitted loadings.  Its ingest
        counters add to ``stream_stats``, the fit's, where given."""
        v = self.v_
        av = None
        for i, (lo, hi) in enumerate(source.schedule):
            host = source.load(i)
            with jax.profiler.TraceAnnotation("nmf.stream.ingest"):
                chunk = self._coerce(host, stats=stream_stats)
            part = _matmul(chunk, v[lo:hi])
            av = part if av is None else av + part
        return OnlineStats(av=av, gv=factor_gram(v))

    def fit_transform(self, a: ArrayLike,
                      u0: Optional[jax.Array] = None) -> jax.Array:
        """Fit and return the document-topic loadings ``V`` (m, k)."""
        return self.fit(a, u0=u0).v_

    # -- fold-in -------------------------------------------------------------

    def transform(self, a_new: ArrayLike) -> jax.Array:
        """Fold unseen documents into the fitted topic space: one
        enforced-sparsity least-squares pass for ``V_new`` with ``U`` frozen,

            V_new = top-t( relu( A_new^T U (U^T U)^{-1} ) )

        Returns non-negative (m_new, k) loadings.  Absolute whole-factor
        ``t_v`` budgets are rescaled by ``m_new / m_train`` so the per-
        document sparsity matches training; per-column and fractional
        budgets resolve against the batch naturally.
        """
        self._check_fitted()
        a_new = self._coerce(a_new)
        self._check_features(a_new)
        u = self.u_
        v = solve_gram(factor_gram(u), _matmul_t(a_new, u))
        return self._enforce_v(jnp.maximum(v, 0.0))

    def _v_sparsity(self, m_new: int) -> Sparsity:
        """The sparsity spec for an (m_new, k) loadings matrix: absolute
        whole-factor ``t_v`` budgets are rescaled by ``m_new / m_ref`` so
        per-document sparsity matches the reference corpus (``transform``
        fold-ins and ``partial_fit`` chunks share this rule; per-column and
        fractional budgets resolve against the batch naturally)."""
        sp = self.config.sparsity
        if (sp.t_v is not None and sp.mode != "columnwise"
                and self._m_ref):
            t = max(1, round(sp.t_v * m_new / self._m_ref))
            sp = dataclasses.replace(sp, t_v=t)
        return sp

    def _enforce_v(self, v: jax.Array) -> jax.Array:
        return self._v_sparsity(v.shape[0]).apply(v, "v")

    # -- streaming -----------------------------------------------------------

    def _mesh_streaming(self) -> bool:
        return (self.config.solver == "streaming"
                and tuple(self.config.mesh_shape) != (1, 1))

    def partial_fit(self, a_chunk: ArrayLike, iters: Optional[int] = None,
                    forget: float = 1.0) -> "EnforcedNMF":
        """Online ALS over one document mini-batch (n_terms, m_chunk).

        Keeps running sufficient statistics ``sum A_c V_c`` and
        ``sum V_c^T V_c`` over all chunks seen, so the ``U`` update uses the
        whole stream, not just the newest batch (gensim-style online NMF);
        ``forget`` < 1 exponentially decays old chunks.  ``iters`` defaults
        to ``min(config.iters, 10)`` inner passes per batch.  Absolute
        whole-factor ``t_v`` budgets are rescaled by the chunk's share of
        the reference corpus (see :meth:`transform`), so per-document
        sparsity is chunk-size invariant; ``t_u`` applies to the full
        factor.

        The update is one :func:`repro.core.online.online_als_step` through
        ``config.backend``; with ``solver="streaming"`` and a non-1x1
        ``mesh_shape`` it runs shard_mapped over the device grid with the
        chunk's columns sharded and the statistics ``psum``-reduced.  A
        :class:`~repro.data.corpus.PackedChunk` (mesh streaming only) or an
        already-distributed ``DistCSR`` / ``DistBSR`` shard grid skips the
        pad + distribute — the corpus prefetcher packs chunks ahead of
        time, so the step consumes committed per-device buffers.  Any
        other chunk converts to the backend's operand under the host span
        ``nmf.stream.ingest``.
        """
        from repro.data.corpus import PackedChunk

        if not 0.0 < forget <= 1.0:
            raise ValueError(f"forget must be in (0, 1], got {forget}")
        cfg = self.config
        mc_true: Optional[int] = None
        if isinstance(a_chunk, PackedChunk):
            if not self._mesh_streaming():
                raise ValueError(
                    "PackedChunk carries a mesh-distributed operand; it "
                    "needs solver='streaming' with a non-1x1 mesh_shape")
            mc_true = int(a_chunk.m_docs)
            a_chunk = a_chunk.operand
        if isinstance(a_chunk, (DistCSR, DistBSR)):
            if not self._mesh_streaming():
                raise ValueError(
                    "distributed shard grids need solver='streaming' with "
                    "a non-1x1 mesh_shape")
        else:
            with jax.profiler.TraceAnnotation("nmf.stream.ingest"):
                a_chunk = self._coerce(a_chunk,
                                       for_mesh=self._mesh_streaming())
        self._check_features(a_chunk)
        n = a_chunk.shape[0]
        mc = mc_true if mc_true is not None else a_chunk.shape[1]
        if self.u_ is None:
            self.u_ = init_u0(jax.random.PRNGKey(cfg.seed), n,
                              cfg.k).astype(cfg.jnp_dtype)
            self.n_features_ = n
        if self._m_ref is None:
            self._m_ref = mc
        if self._gv_acc is None:
            stats = init_online_stats(n, cfg.k, self.u_.dtype)
        else:
            stats = OnlineStats(av=self._av_acc, gv=self._gv_acc)

        n_inner = max(iters if iters is not None else min(cfg.iters, 10), 1)
        if self._mesh_streaming():
            res = self._partial_fit_sharded(a_chunk, stats, n_inner, forget,
                                            mc=mc)
        else:
            sp_u = cfg.sparsity.sparsifier(n, cfg.k, "u")
            sp_v = self._v_sparsity(mc).sparsifier(mc, cfg.k, "v")
            res = online_als_step(
                a_chunk, self.u_, stats, forget, iters=n_inner,
                sparsify_u=sp_u, sparsify_v=sp_v, backend=cfg.backend)

        self.u_, self.v_ = res.u, res.v
        self._av_acc, self._gv_acc = res.stats.av, res.stats.gv
        self.health_ = res.health
        self.n_docs_seen_ += mc
        return self

    def _partial_fit_sharded(self, a_chunk: Matrix, stats: OnlineStats,
                             n_inner: int, forget: float,
                             mc: Optional[int] = None):
        """One online step shard_mapped over the ``config.mesh_shape`` grid:
        chunk columns sharded on ``"model"``, ``u`` / ``stats.av``
        row-sharded on ``"data"``, ``stats.gv`` replicated; sparsity
        enforcement via the histogram :class:`~repro.core.topk.DistTopK`
        (the mesh counterpart of the local bisection threshold).  The chunk
        re-ingests into the inner backend's per-device shard format —
        padded CSR for ``jnp-csr``, BSR tile grids for ``pallas-bsr`` (the
        MXU streaming-tile kernels inside every shard).  An
        already-distributed ``DistCSR`` / ``DistBSR`` (packed ahead of time
        by the corpus prefetcher via :meth:`_pack_mesh_chunk`) passes
        through the ingest unchanged; ``mc`` then carries the chunk's true
        document count for the ``t_v`` budget and the ``v`` slice.

        Chunk widths need no mesh alignment: ``engine.distribute`` pads the
        column count up to a multiple of the cols axis with empty documents
        — an all-zero column yields an exactly-zero V row and contributes
        nothing to the statistics — and the returned ``v`` is sliced back.
        The *term* axis is a model-lifetime constant and must divide the
        rows axis.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.backend.sharded import make_sharded_online
        from repro.core.topk import DistTopK
        from repro.launch.mesh import make_nmf_mesh
        from repro.nmf.solvers import dist_budget, mesh_inner_backend

        cfg = self.config
        n, mc_stored = a_chunk.shape
        mc = mc_stored if mc is None else int(mc)
        r, c = cfg.mesh_shape
        if n % r:
            raise ValueError(
                f"term count {n} must be divisible by the mesh rows "
                f"axis {r} (mesh_shape {(r, c)})")
        mc_pad = (mc_stored if isinstance(a_chunk, (DistCSR, DistBSR))
                  else -(-mc // c) * c)
        mesh = make_nmf_mesh(r, c)

        rows_axes, cols_axis = ("data",), "model"
        t_u = dist_budget(cfg.sparsity, n, cfg.k, "u")
        t_v = dist_budget(self._v_sparsity(mc), mc, cfg.k, "v")
        engine = make_sharded_online(
            mesh, rows_axes, cols_axis,
            sparsify_u=None if t_u is None else DistTopK(t_u, rows_axes),
            sparsify_v=None if t_v is None else DistTopK(t_v, (cols_axis,)),
            inner=mesh_inner_backend(cfg, a_chunk),
        )
        _, u_spec, _ = engine.specs
        dist = engine.distribute(a_chunk, pad_cols_to=mc_pad)
        u = jax.device_put(self.u_, NamedSharding(mesh, u_spec))
        # the jitted step donates av/gv (in-place accumulator rotation —
        # the committed statistics below replace them on success).  These
        # are estimator-internal buffers with no caller-visible aliases, so
        # no defensive copy; if the step itself fails the model's stream
        # statistics are gone with it and the next partial_fit must follow
        # a fresh fit.
        stats = OnlineStats(
            av=jax.device_put(stats.av, NamedSharding(mesh, u_spec)),
            gv=jax.device_put(stats.gv, NamedSharding(mesh, P())),
        )
        with jax.set_mesh(mesh):
            res = engine(dist, u, stats, n_inner, forget)
        if mc_pad != mc:  # drop the empty padding documents' loadings
            res = res._replace(v=res.v[:mc])
        return res

    def _pack_mesh_chunk(self, a_chunk: ArrayLike):
        """The host half of a mesh streaming step, runnable ahead of time
        (the corpus :class:`~repro.data.corpus.Prefetcher`'s worker):
        coerce + pad the chunk to the mesh grid and distribute it —
        per-device shard ingest plus ``device_put`` — so chunk N+1's
        transfer rides under chunk N's in-flight online step.  Returns a
        :class:`~repro.data.corpus.PackedChunk`; :meth:`partial_fit`
        consumes it with a pass-through ingest and a no-op ``device_put``.

        The engine here carries no sparsifiers — ``distribute`` depends
        only on the mesh and shard format, both of which the step-time
        engine shares, so the packed operand is byte-identical to what the
        synchronous path would build."""
        from repro.backend.sharded import make_sharded_online
        from repro.data.corpus import PackedChunk
        from repro.launch.mesh import make_nmf_mesh
        from repro.nmf.solvers import mesh_inner_backend

        cfg = self.config
        host = a_chunk
        a_chunk = self._coerce(a_chunk, for_mesh=True)
        n, mc = a_chunk.shape
        r, c = cfg.mesh_shape
        if n % r:
            raise ValueError(
                f"term count {n} must be divisible by the mesh rows "
                f"axis {r} (mesh_shape {(r, c)})")
        engine = make_sharded_online(
            make_nmf_mesh(r, c), ("data",), "model",
            inner=mesh_inner_backend(cfg, a_chunk))
        dist = engine.distribute(a_chunk, pad_cols_to=-(-mc // c) * c)
        return PackedChunk(operand=dist, m_docs=mc, host=host)

    # -- evaluation ----------------------------------------------------------

    def score(self, a: ArrayLike, v: Optional[jax.Array] = None) -> float:
        """Relative reconstruction error ``||A - U V^T||_F / ||A||_F`` of the
        fitted factors on ``a`` (lower is better).  ``v`` defaults to a
        fold-in ``transform`` of ``a``."""
        self._check_fitted()
        a = self._coerce(a)
        self._check_features(a)
        if v is None:
            if self.v_ is not None and self.v_.shape[0] == a.shape[1]:
                v = self.v_
            else:
                v = self.transform(a)
        return float(_relative_error(a, self.u_, v))
