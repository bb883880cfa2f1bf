"""The fit's own names in a profiler trace: the host spans of
``EnforcedNMF.fit`` and the named scopes of the ALS engine's loop body.

Host spans (``jax.profiler.TraceAnnotation``) land on the profiler's host
clock; named scopes (``jax.named_scope``) land in each device op's
``op_name`` metadata, which the compiled HLO shows."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from repro.backend import get_backend
from repro.core.nmf import als_nmf
from repro.nmf import EnforcedNMF, NMFConfig, Sparsity

from _hlo import (ENGINE_SCOPES, ONLINE_SCOPE, ONLINE_SCOPES, SCOPE,
                  loop_body, op_name)

#: every host span a one-chunk fit writes, once each
FIT_SPANS = ("nmf.fit", "nmf.prepare", "nmf.dispatch", "nmf.sync",
             "nmf.result", "nmf.seed_stats")
N, M, K = 256, 192, 4


@pytest.fixture(scope="module")
def operand():
    a = sp.random(N, M, density=0.05, random_state=0, format="csr",
                  dtype=np.float32)
    return get_backend("pallas-bsr").prepare(a, dtype=np.float32)


def _config(**kw):
    kw = {"iters": 3, "tol": 0.0, "solver": "enforced", **kw}
    return NMFConfig(k=K, sparsity=Sparsity(t_u=100, t_v=80),
                     backend="pallas-bsr", **kw)


def _u0():
    return jnp.asarray(np.random.default_rng(0).random((N, K), np.float32))


def _host_lines(trace_dir):
    """``[(name, start_ns, end_ns), ...]`` of each host thread's events,
    one list a thread."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    assert path, f"no trace written under {trace_dir}"
    return [[(e.name, e.start_ns, e.end_ns) for e in line.events]
            for plane in ProfileData.from_file(path[-1]).planes
            if plane.name.startswith("/host:") for line in plane.lines]


def _host_events(trace_dir):
    """``(name, start_ns, end_ns)`` of every event on the host's threads."""
    return [e for line in _host_lines(trace_dir) for e in line]


def test_a_traced_fit_writes_each_span_once_inside_nmf_fit(operand,
                                                           tmp_path):
    model = EnforcedNMF(_config())
    u0 = _u0()
    model.fit(operand, u0=u0)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        model.fit(operand, u0=u0)
        jax.block_until_ready(model.u_)
    spans = [e for e in _host_events(tmp_path) if e[0].startswith("nmf.")]
    names = [name for name, _, _ in spans]
    assert sorted(names) == sorted(FIT_SPANS), names
    (_, fit_a, fit_b), = [e for e in spans if e[0] == "nmf.fit"]
    for name, a, b in spans:
        assert fit_a <= a <= b <= fit_b, name
    start = {name: a for name, a, _ in spans}
    order = sorted(FIT_SPANS[1:], key=start.get)
    assert order == ["nmf.prepare", "nmf.dispatch", "nmf.sync",
                     "nmf.result", "nmf.seed_stats"]


def test_the_driver_names_every_device_read(operand, tmp_path):
    """With ``tol`` the residual read is a blocking read too: one
    ``nmf.sync`` for the health and one for the residual of each chunk."""
    model = EnforcedNMF(_config(tol=1e-30, iters=12))
    u0 = _u0()
    model.fit(operand, u0=u0)
    with jax.profiler.trace(str(tmp_path)):
        model.fit(operand, u0=u0)
    names = [e[0] for e in _host_events(tmp_path)
             if e[0].startswith("nmf.")]
    chunks = 2  # 12 iterations in chunks of 10
    assert names.count("nmf.dispatch") == chunks
    assert names.count("nmf.sync") == 2 * chunks
    assert names.count("nmf.result") == names.count("nmf.fit") == 1


def test_the_compiled_engine_carries_every_scope(operand):
    cfg = _config()
    sp_u = cfg.sparsity.sparsifier(N, K, "u", fused=True)
    sp_v = cfg.sparsity.sparsifier(M, K, "v", fused=True)
    text = als_nmf.lower(operand, _u0(), iters=3,
                         sparsify_u=sp_u, sparsify_v=sp_v, track_error=True,
                         backend="pallas-bsr").compile().as_text()
    found = {m.group(1) for name in re.findall(r'op_name="([^"]*)"', text)
             for m in [SCOPE.search(name)] if m}
    assert found == set(ENGINE_SCOPES)
    # every fusion or custom call the body function made sits in a scope;
    # the scan's own counter and output stacking lie outside the body call
    # (``.../while/body/<op>``), and ops the compiler makes carry no
    # ``op_name`` or only the call's own
    unscoped = []
    for line in loop_body(text):
        if not re.search(r"\s(fusion|custom-call)\(", line):
            continue
        name = op_name(line)
        if "/while/body/closed_call/" in name and not SCOPE.search(name):
            unscoped.append(line.strip()[:160])
    assert not unscoped, unscoped


#: the stream's spans on the thread that calls ``fit``, and how many a
#: fit of :data:`CHUNKS` chunks writes: a chunk step each; a wait on the
#: queue for each chunk of the stream, and for each chunk and the end of
#: the fold-in; a conversion in ``partial_fit`` and in the seed
#: statistics for each chunk; one fold-in
CHUNKS = 8
STREAM_SPANS = {"nmf.stream.chunk": CHUNKS,
                "nmf.stream.stall": 2 * CHUNKS + 1,
                "nmf.stream.ingest": 2 * CHUNKS,
                "nmf.stream.fold_in": 1}


@pytest.fixture(scope="module")
def corpus_dir(tmp_path_factory):
    from repro.data.corpus import write_corpus

    a = sp.random(N, M, density=0.05, random_state=1, format="csr",
                  dtype=np.float32)
    return write_corpus(a, tmp_path_factory.mktemp("corpus"),
                        chunk_docs=M // CHUNKS)


def test_a_traced_streamed_fit_names_its_chunks_packs_and_stalls(
        corpus_dir, tmp_path):
    from repro.data.corpus import MmapCorpus

    model = EnforcedNMF(_config(solver="streaming", chunk_docs=M // CHUNKS))
    u0 = _u0()
    model.fit(MmapCorpus(corpus_dir), u0=u0)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        model.fit(MmapCorpus(corpus_dir), u0=u0)
        jax.block_until_ready(model.u_)
    lines = [[e for e in line if e[0].startswith("nmf.")]
             for line in _host_lines(tmp_path)]
    (main,) = [line for line in lines
               if any(e[0] == "nmf.fit" for e in line)]
    names = [e[0] for e in main]
    for name, count in STREAM_SPANS.items():
        assert names.count(name) == count, (name, names.count(name))
    assert "nmf.stream.pack" not in names
    (_, fit_a, fit_b), = [e for e in main if e[0] == "nmf.fit"]
    assert all(fit_a <= a <= b <= fit_b for _, a, b in main)
    # the prefetch worker packs each chunk of both prefetched passes on a
    # thread of its own; the seed statistics pack on the main thread
    packs = [e for line in lines if line is not main for e in line
             if e[0] == "nmf.stream.pack"]
    assert len(packs) == 2 * CHUNKS
    assert model.result_.stream_stats["packed"] == 2 * CHUNKS
    assert model.result_.stream_stats["pack_s"] > 0


def test_other_solvers_report_no_stream_counters(operand):
    model = EnforcedNMF(_config()).fit(operand, u0=_u0())
    assert model.result_.stream_stats is None


def test_the_compiled_online_step_carries_every_scope(operand):
    from repro.core.online import init_online_stats, online_als_step

    cfg = _config()
    text = online_als_step.lower(
        operand, _u0(), init_online_stats(N, K), 1.0, iters=3,
        sparsify_u=cfg.sparsity.sparsifier(N, K, "u"),
        sparsify_v=cfg.sparsity.sparsifier(M, K, "v"),
        backend="pallas-bsr").compile().as_text()
    found = {m.group(1) for name in re.findall(r'op_name="([^"]*)"', text)
             for m in [ONLINE_SCOPE.search(name)] if m}
    assert found == set(ONLINE_SCOPES)
