"""Streaming execution layer: the online sufficient-statistics engine.

``EnforcedNMF.partial_fit`` is a thin adapter over
:func:`repro.core.online.online_als_step`, so it must match the
pre-refactor hand-rolled estimator loop on one device (default backend)
to float32 rounding, thread every matmul backend, and — with ``solver="streaming"``
and a non-1x1 mesh — match the single-device trajectory through the
mesh-reduced shard_map path.  Multi-device grids run in a subprocess with
``--xla_force_host_platform_device_count=4`` (2x2 and 4x1).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import init_u0
from repro.core.nmf import solve_gram, _matmul, _matmul_t
from repro.data import synthetic_journal_corpus
from repro.nmf import EnforcedNMF, NMFConfig, Sparsity, available_solvers
from repro.sparse import SpCSR, column_block, to_dense

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(n, code):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.fixture(scope="module")
def corpus():
    a_sp, dj = synthetic_journal_corpus(n_terms=192, n_docs=120,
                                        n_journals=4, seed=11)
    return a_sp, jnp.asarray(to_dense(a_sp)), dj


# ---------------------------------------------------------------------------
# Single-device: the engine is the legacy loop, to float32 rounding
# ---------------------------------------------------------------------------

def _legacy_partial_fit_stream(a, chunks, cfg, n_inner):
    """The pre-refactor ``EnforcedNMF.partial_fit`` loop, verbatim (eager,
    whole-factor ``t_v`` per chunk, ``u.T @ u`` grams) — the oracle for the
    acceptance check."""
    sp = cfg.sparsity
    u = gv_acc = av_acc = v = None
    for lo, hi in chunks:
        chunk = a[:, lo:hi]
        n, _ = chunk.shape
        if u is None:
            u = init_u0(jax.random.PRNGKey(cfg.seed), n,
                        cfg.k).astype(cfg.jnp_dtype)
            gv_acc = jnp.zeros((cfg.k, cfg.k), u.dtype)
            av_acc = jnp.zeros((n, cfg.k), u.dtype)
        for _ in range(n_inner):
            v = solve_gram(u.T @ u, _matmul_t(chunk, u))
            v = sp.apply(jnp.maximum(v, 0.0), "v")
            gv = 1.0 * gv_acc + v.T @ v
            av = 1.0 * av_acc + _matmul(chunk, v)
            u = solve_gram(gv, av)
            u = sp.apply(jnp.maximum(u, 0.0), "u")
        gv_acc, av_acc = gv, av
    return u, v, gv_acc, av_acc


#: how far the jitted online engine may sit from the eager legacy loop:
#: XLA fuses the compiled loop's float32 arithmetic (the eager loop ran op
#: by op), so the two round differently; over 30 inner passes of solves
#: the factors part by at most 1.5e-6 relative on this corpus, and 1e-5
#: leaves room for other summation orders while any change to the update
#: itself (a pass more or less, the statistics counted twice) moves them
#: by orders of magnitude more
ENGINE_RTOL = 1e-5


def test_partial_fit_bitexact_with_legacy_loop(corpus):
    """Single-device partial_fit through the jitted online engine is the
    pre-refactor eager estimator loop (default backend, equal chunks from
    scratch): the same non-zeros kept, the same values to float32 rounding
    (:data:`ENGINE_RTOL`)."""
    _, a, _ = corpus
    cfg = NMFConfig(k=4, iters=20, sparsity=Sparsity(t_u=48, t_v=120))
    chunks = [(0, 40), (40, 80), (80, 120)]
    ul, vl, gvl, avl = _legacy_partial_fit_stream(a, chunks, cfg, n_inner=10)

    model = EnforcedNMF(cfg)
    for lo, hi in chunks:
        model.partial_fit(a[:, lo:hi])
    for got, want in [(model.u_, ul), (model.v_, vl), (model._gv_acc, gvl),
                      (model._av_acc, avl)]:
        got, want = np.asarray(got), np.asarray(want)
        np.testing.assert_array_equal(got != 0, want != 0)
        np.testing.assert_allclose(got, want, rtol=ENGINE_RTOL, atol=0)
    assert model.n_docs_seen_ == 120


def test_fit_seeds_streaming_stats_via_backend(corpus):
    """``fit`` seeds the online accumulators with the full-corpus
    statistics (through the backend layer — same values as the legacy
    direct products) so partial_fit continues the fit."""
    a_sp, a, _ = corpus
    model = EnforcedNMF(NMFConfig(k=4, iters=10)).fit(a)
    np.testing.assert_array_equal(
        np.asarray(model._gv_acc), np.asarray(model.v_.T @ model.v_))
    np.testing.assert_array_equal(
        np.asarray(model._av_acc), np.asarray(a @ model.v_))
    # continuing the stream refines, not resets: error stays near the fit
    before = model.score(a)
    model.partial_fit(a[:, :40])
    assert model.score(a) < before + 0.05
    assert model.n_docs_seen_ == 120 + 40


def test_partial_fit_backend_parity(corpus):
    """The online step threads the backend registry: jnp-csr on SpCSR
    chunks tracks jnp-dense on dense chunks."""
    a_sp, a, _ = corpus
    cfg = dict(k=4, iters=16, sparsity=Sparsity(t_u=48, t_v=120))
    dense = EnforcedNMF(NMFConfig(backend="jnp-dense", **cfg))
    csr = EnforcedNMF(NMFConfig(backend="jnp-csr", **cfg))
    for lo, hi in [(0, 60), (60, 120)]:
        dense.partial_fit(a[:, lo:hi])
        csr.partial_fit(column_block(a_sp, lo, hi))
    np.testing.assert_allclose(np.asarray(dense.u_), np.asarray(csr.u_),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(dense._gv_acc),
                               np.asarray(csr._gv_acc), rtol=2e-4, atol=2e-4)


def test_streaming_vs_batch_parity(corpus):
    """partial_fit over column chunks converges to within tolerance of a
    batch ``fit`` on the same corpus."""
    _, a, _ = corpus
    sparsity = Sparsity(t_u=48, t_v=240)
    batch = EnforcedNMF(NMFConfig(k=4, iters=40, sparsity=sparsity)).fit(a)
    stream = EnforcedNMF(NMFConfig(k=4, iters=40, sparsity=sparsity))
    for i in range(4):
        stream.partial_fit(a[:, i * 30:(i + 1) * 30])
    s_stream = stream.score(a, v=stream.transform(a))
    s_batch = batch.score(a)
    assert s_stream < s_batch + 0.05
    assert int(jnp.sum(stream.u_ != 0)) <= 48 + 5


# ---------------------------------------------------------------------------
# Satellite bugfix: per-chunk t_v budgets rescale like transform's
# ---------------------------------------------------------------------------

def test_partial_fit_rescales_t_v_budget(corpus):
    """Absolute whole-factor ``t_v`` budgets shrink with the chunk's share
    of the reference corpus (the ``transform`` rule) — a 30-doc chunk of a
    120-doc model gets 1/4 of the budget, not the whole of it."""
    _, a, _ = corpus
    model = EnforcedNMF(NMFConfig(
        k=4, iters=20, sparsity=Sparsity(t_u=48, t_v=240))).fit(a)
    model.partial_fit(a[:, :30])
    # rescaled budget: 240 * 30/120 = 60 (+ threshold ties); the
    # pre-bugfix behavior kept up to 240
    assert int(jnp.sum(model.v_ != 0)) <= 60 + 5


def test_streaming_solver_matches_batch_per_document_nnz(corpus):
    """The streaming solver resolves ``t_v`` against the full corpus and
    rescales per chunk, so per-document V sparsity matches a batch fit of
    the same budget."""
    _, a, _ = corpus
    sparsity = Sparsity(t_u=48, t_v=240)
    batch = EnforcedNMF(NMFConfig(k=4, iters=30, sparsity=sparsity)).fit(a)
    stream = EnforcedNMF(NMFConfig(k=4, iters=30, solver="streaming",
                                   chunk_docs=30, sparsity=sparsity)).fit(a)
    nnz_b = int(jnp.sum(batch.v_ != 0))
    nnz_s = int(jnp.sum(stream.v_ != 0))
    assert nnz_s <= 240 + 5  # full-corpus budget, not per-chunk copies
    assert abs(nnz_s - nnz_b) <= 0.1 * 240


# ---------------------------------------------------------------------------
# The "streaming" solver registry entry
# ---------------------------------------------------------------------------

def test_streaming_solver_registered():
    assert "streaming" in available_solvers()


def test_streaming_solver_chunk_history(corpus):
    a_sp, a, _ = corpus
    model = EnforcedNMF(NMFConfig(k=4, iters=20, solver="streaming",
                                  chunk_docs=40,
                                  sparsity=Sparsity(t_u=48))).fit(a_sp)
    r = model.result_
    assert r.solver == "streaming"
    assert r.error_granularity == "chunk"
    assert r.n_iter == 3  # 120 docs / 40-doc chunks
    assert r.residual.shape == (3,) and r.error.shape == (3,)
    assert model.v_.shape == (120, 4)  # full-corpus fold-in loadings
    assert model.n_docs_seen_ == 120
    assert float(r.error[-1]) < 1.0
    # the dense initial guess dominates the running max (Fig. 6 semantics)
    assert int(r.max_nnz) >= 192 * 4


def test_streaming_solver_dense_and_sparse_agree(corpus):
    a_sp, a, _ = corpus
    cfg = NMFConfig(k=4, iters=20, solver="streaming", chunk_docs=40)
    dense = EnforcedNMF(cfg).fit(a)
    sparse = EnforcedNMF(cfg).fit(a_sp)
    np.testing.assert_allclose(np.asarray(dense.u_), np.asarray(sparse.u_),
                               rtol=2e-4, atol=2e-5)


def test_streaming_solver_tol_early_stop(corpus):
    _, a, _ = corpus
    model = EnforcedNMF(NMFConfig(k=4, iters=20, solver="streaming",
                                  chunk_docs=10, tol=0.5)).fit(a)
    r = model.result_
    assert r.converged
    assert r.n_iter < 12  # stopped before draining all 12 chunks
    assert float(r.residual[-1]) <= 0.5


def test_streaming_solver_rejects_bsr(corpus):
    from repro.backend import get_backend

    _, a, _ = corpus
    bsr = get_backend("pallas-bsr").prepare(np.asarray(a))
    with pytest.raises(TypeError, match="BSR"):
        EnforcedNMF(NMFConfig(k=4, iters=4, solver="streaming")).fit(bsr)


def test_streaming_scipy_auto_backend_avoids_bsr(monkeypatch):
    """Scipy input whose device default is pallas-bsr (TPU) must downgrade
    to jnp-csr for the streaming solver — its fit carves column chunks
    host-side, which BSR operands cannot do."""
    sps = pytest.importorskip("scipy.sparse")
    from repro.nmf import estimator as est_mod

    monkeypatch.setattr(est_mod, "default_backend_name",
                        lambda a: "pallas-bsr")
    m = sps.random(64, 40, density=0.2, random_state=0, format="csr",
                   dtype=np.float32)
    model = EnforcedNMF(NMFConfig(k=3, iters=4, solver="streaming",
                                  chunk_docs=20))
    assert isinstance(model._coerce(m), SpCSR)
    model.fit(m)  # end-to-end: chunks, no BSR rejection
    assert model.u_.shape == (64, 3)


# ---------------------------------------------------------------------------
# column_block (host-side chunk carving)
# ---------------------------------------------------------------------------

def test_column_block_slices_columns(corpus):
    a_sp, a, _ = corpus
    blk = column_block(a_sp, 30, 75)
    assert blk.shape == (192, 45)
    np.testing.assert_allclose(np.asarray(to_dense(blk)),
                               np.asarray(a[:, 30:75]))
    # pinning cap keeps chunk shapes uniform across the stream
    blk2 = column_block(a_sp, 30, 75, cap=a_sp.cap)
    assert blk2.cap == a_sp.cap
    np.testing.assert_allclose(np.asarray(to_dense(blk2)),
                               np.asarray(a[:, 30:75]))
    with pytest.raises(ValueError, match="column range"):
        column_block(a_sp, 90, 150)


# ---------------------------------------------------------------------------
# Mesh streaming: the same step, shard_mapped with psum-reduced statistics
# ---------------------------------------------------------------------------

_MESH_PARITY_CODE = """
    import jax, jax.numpy as jnp, numpy as np, json
    from repro.data import synthetic_journal_corpus
    from repro.nmf import EnforcedNMF, NMFConfig, Sparsity
    from repro.sparse import to_dense
    a_sp, _ = synthetic_journal_corpus(n_terms=128, n_docs=96, n_journals=4, seed=3)
    a = jnp.asarray(to_dense(a_sp))
    def stream(mesh_shape, sparsity):
        cfg = NMFConfig(k=4, iters=20, solver="streaming",
                        mesh_shape=mesh_shape, sparsity=sparsity,
                        backend="jnp-csr" if mesh_shape != (1, 1) else None)
        m = EnforcedNMF(cfg)
        for i in range(3):
            m.partial_fit(a[:, i * 32:(i + 1) * 32])
        return m
    rec = {}
    dense = Sparsity()
    ref = stream((1, 1), dense)
    rec["ref_u"] = np.asarray(ref.u_).tolist()
    for shape in [(2, 2), (4, 1)]:
        m = stream(shape, dense)
        rec["%dx%d_u" % shape] = np.asarray(m.u_).tolist()
    sp = Sparsity(t_u=48, t_v=96)
    ref_s = stream((1, 1), sp)
    m_s = stream((2, 2), sp)
    rec["sparse"] = {
        "ref_score": float(ref_s.score(a)), "mesh_score": float(m_s.score(a)),
        "mesh_nnz_u": int(jnp.sum(m_s.u_ != 0)),
        "mesh_nnz_v": int(jnp.sum(m_s.v_ != 0)),
    }
    # ragged / mesh-unaligned chunks: padded with empty documents inside
    # _partial_fit_sharded, so odd widths shard fine and match local
    def stream_ragged(mesh_shape):
        cfg = NMFConfig(k=4, iters=20, solver="streaming",
                        mesh_shape=mesh_shape,
                        backend="jnp-csr" if mesh_shape != (1, 1) else None)
        m = EnforcedNMF(cfg)
        for lo, hi in [(0, 31), (31, 64), (64, 96)]:
            m.partial_fit(a[:, lo:hi])
        return m
    ref_r = stream_ragged((1, 1))
    m_r = stream_ragged((2, 2))
    rec["ragged"] = {
        "ref_u": np.asarray(ref_r.u_).tolist(),
        "mesh_u": np.asarray(m_r.u_).tolist(),
        "mesh_v_shape": list(m_r.v_.shape),
    }
    # streaming-solver fit with a chunk width the mesh doesn't divide
    m_fit = EnforcedNMF(NMFConfig(k=4, iters=20, solver="streaming",
                                  chunk_docs=31, mesh_shape=(2, 2),
                                  backend="jnp-csr")).fit(a)
    rec["ragged_fit"] = {"err": float(m_fit.result_.final_error),
                         "n_chunks": int(m_fit.result_.n_iter)}
    print(json.dumps(rec))
"""


def test_mesh_streaming_matches_single_device():
    """2x2 and 4x1 partial_fit trajectories match the single-device online
    engine within 1e-4 relative error (exact modulo psum summation order
    when no sparsifier runs), and the sparse DistTopK variant lands on the
    same solution quality and budgets."""
    out = json.loads(run_with_devices(4, textwrap.dedent(_MESH_PARITY_CODE))
                     .strip().splitlines()[-1])
    ref_u = np.asarray(out["ref_u"])
    for grid in ("2x2", "4x1"):
        u = np.asarray(out[f"{grid}_u"])
        rel = np.linalg.norm(u - ref_u) / np.linalg.norm(ref_u)
        assert rel < 1e-4, (grid, rel)
    sp = out["sparse"]
    assert abs(sp["mesh_score"] - sp["ref_score"]) < 0.02
    assert sp["mesh_nnz_u"] <= 48 + 6  # histogram-bin ties
    assert sp["mesh_nnz_v"] <= 96 + 6
    # mesh-unaligned chunk widths pad with empty documents and still match
    ragged = out["ragged"]
    ref_u = np.asarray(ragged["ref_u"])
    u = np.asarray(ragged["mesh_u"])
    assert np.linalg.norm(u - ref_u) / np.linalg.norm(ref_u) < 1e-4
    assert ragged["mesh_v_shape"] == [32, 4]  # last chunk, padding dropped
    assert out["ragged_fit"]["n_chunks"] == 4  # ceil(96/31)
    assert out["ragged_fit"]["err"] < 1.0


def test_make_sharded_online_uses_keyed_cache():
    """Two engines with identical config share the same shard_mapped and
    jitted callables (module-level keyed cache) — one engine per
    partial_fit call costs no recompilation."""
    from repro.backend.sharded import make_sharded_online
    from repro.core.topk import DistTopK
    from repro.launch.mesh import make_nmf_mesh

    mesh = make_nmf_mesh(1, 1)
    kw = dict(sparsify_u=DistTopK(10, ("data",)),
              sparsify_v=DistTopK(20, ("model",)))
    e1 = make_sharded_online(mesh, ("data",), "model", **kw)
    e2 = make_sharded_online(make_nmf_mesh(1, 1), ("data",), "model", **kw)
    assert e1.shard_fn(3) is e2.shard_fn(3)
    assert e1.jitted(3) is e2.jitted(3)
    assert e1.jitted(3) is not e1.jitted(4)  # distinct iters still distinct


# ---------------------------------------------------------------------------
# TopicServer refresh: serving traffic folds back into the model
# ---------------------------------------------------------------------------

def test_topic_server_refresh_streams_served_docs(corpus):
    from repro.serving import TopicRequest, TopicServer

    a_sp, a, _ = corpus
    model = EnforcedNMF(NMFConfig(
        k=4, iters=25, sparsity=Sparsity(t_u=48, t_v=240))).fit(a_sp)
    server = TopicServer(model, max_batch=4)
    a_np = np.asarray(a)
    for rid in range(8):
        col = a_np[:, rid]
        terms = [(int(i), float(col[i])) for i in np.nonzero(col)[0]]
        server.submit(TopicRequest(rid=rid, terms=terms, top=2))
    server.run_until_drained()
    seen_before = model.n_docs_seen_
    folded = server.refresh()
    assert folded == 8 and server.refreshed == 8
    assert model.n_docs_seen_ == seen_before + 8
    assert bool(jnp.all(model.u_ >= 0))
    assert server.refresh() == 0  # buffer drained
    # the refreshed model still serves
    server.submit(TopicRequest(rid=99, terms=[(5, 1.0), (40, 2.0)], top=2))
    done = server.run_until_drained()
    assert done[0].topics is not None


def test_topic_server_auto_refresh(corpus):
    from repro.serving import TopicRequest, TopicServer

    a_sp, a, _ = corpus
    model = EnforcedNMF(NMFConfig(k=4, iters=20)).fit(a_sp)
    server = TopicServer(model, max_batch=4, refresh_every=6)
    a_np = np.asarray(a)
    for rid in range(12):
        col = a_np[:, rid]
        terms = [(int(i), float(col[i])) for i in np.nonzero(col)[0]]
        server.submit(TopicRequest(rid=rid, terms=terms))
    server.run_until_drained()
    assert server.refreshed >= 6  # triggered from inside step()


def test_topic_server_refresh_buffer_is_bounded(corpus):
    """A server that never refreshes holds at most refresh_buffer served
    documents (oldest age out) — no unbounded growth in long-running
    serving loops."""
    from repro.serving import TopicRequest, TopicServer

    a_sp, a, _ = corpus
    model = EnforcedNMF(NMFConfig(k=4, iters=10)).fit(a_sp)
    server = TopicServer(model, max_batch=4, refresh_buffer=5)
    a_np = np.asarray(a)
    for rid in range(12):
        col = a_np[:, rid]
        terms = [(int(i), float(col[i])) for i in np.nonzero(col)[0]]
        server.submit(TopicRequest(rid=rid, terms=terms))
    server.run_until_drained()
    assert len(server._refresh_buf) == 5
    assert server.refresh() == 5  # folds the newest five, then empty
    assert len(server._refresh_buf) == 0


def test_streaming_fit_with_explicit_pallas_backend():
    """fit() with solver="streaming" and backend="pallas-bsr" works end to
    end: the corpus stays column-sliceable SpCSR, and every chunk
    re-ingests into the BSR operand for the MXU (interpret-mode) path."""
    a_sp, _ = synthetic_journal_corpus(n_terms=96, n_docs=48, n_journals=3,
                                       seed=2)
    model = EnforcedNMF(NMFConfig(k=3, iters=6, solver="streaming",
                                  chunk_docs=24, backend="pallas-bsr"))
    model.fit(a_sp)
    assert model.u_.shape == (96, 3)
    assert model.result_.n_iter == 2
    ref = EnforcedNMF(NMFConfig(k=3, iters=6, solver="streaming",
                                chunk_docs=24)).fit(a_sp)
    np.testing.assert_allclose(np.asarray(model.u_), np.asarray(ref.u_),
                               rtol=2e-4, atol=2e-4)


def test_topic_server_refresh_every_implies_buffer(corpus):
    """refresh_every larger than refresh_buffer grows the buffer — the
    auto-refresh trigger must be reachable."""
    from repro.serving import TopicServer

    a_sp, _, _ = corpus
    model = EnforcedNMF(NMFConfig(k=4, iters=10)).fit(a_sp)
    server = TopicServer(model, refresh_every=64, refresh_buffer=5)
    assert server._refresh_buf.maxlen == 64
