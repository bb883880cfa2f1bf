"""The zero-recompile contract, asserted with the compiler's own counter.

``recompile_guard`` counts jax's ``backend_compile`` monitoring event —
emitted once per real XLA compilation, never on an executable-cache hit —
so these tests pin the repo's caching claims dynamically: a second
identical ``EnforcedNMF.fit`` and a second same-shaped
``TopicServer.refresh`` must compile *nothing*.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import RecompilationError, recompile_guard
from repro.data import synthetic_journal_corpus
from repro.nmf import EnforcedNMF, NMFConfig
from repro.serving.topics import TopicRequest, TopicServer


@pytest.fixture(scope="module")
def corpus():
    a_sp, _ = synthetic_journal_corpus(n_terms=120, n_docs=80,
                                       n_journals=4, seed=7)
    return a_sp


# ---------------------------------------------------------------------------
# the guard itself
# ---------------------------------------------------------------------------

def test_positive_control_fresh_jit_is_counted():
    with recompile_guard(max_compiles=50) as counter:
        jax.jit(lambda x: x * 3.5)(jnp.ones(16)).block_until_ready()
    assert counter.count >= 1


def test_guard_raises_on_unexpected_compilation():
    with pytest.raises(RecompilationError, match="XLA compilation"):
        with recompile_guard():
            jax.jit(lambda x: x - 7.25)(jnp.ones(16)).block_until_ready()


def test_guard_reusing_cached_executable_is_free():
    f = jax.jit(lambda x: x + 0.5)
    f(jnp.ones(16)).block_until_ready()
    with recompile_guard() as counter:
        f(jnp.ones(16)).block_until_ready()
    assert counter.count == 0


# ---------------------------------------------------------------------------
# the repo's caching claims
# ---------------------------------------------------------------------------

def test_second_identical_fit_compiles_nothing(corpus):
    """Engines are drawn from module-level keyed caches, so a fresh
    estimator with an identical config fitting the same-shaped operand
    reuses every executable of the first fit."""
    cfg = NMFConfig(k=4, iters=6, solver="als")
    EnforcedNMF(cfg).fit(corpus)  # warm every executable
    with recompile_guard() as counter:
        model = EnforcedNMF(cfg).fit(corpus)
    assert counter.count == 0
    assert model.u_ is not None


def test_second_refresh_compiles_nothing(corpus):
    """TopicServer.refresh streams served docs through partial_fit; the
    second refresh over a same-shaped batch must hit the cached online
    step end to end."""
    docs = [
        TopicRequest(rid=i, terms=[(3 * i % 120, 2.0), ((7 * i + 1) % 120, 1.0)])
        for i in range(8)
    ]

    def serve_and_refresh(server):
        for req in docs:
            server.submit(TopicRequest(rid=req.rid, terms=req.terms,
                                       top=req.top))
        server.run_until_drained()
        assert server.refresh() == len(docs)

    model = EnforcedNMF(NMFConfig(k=4, iters=6, solver="als")).fit(corpus)
    server = TopicServer(model, max_batch=len(docs))
    serve_and_refresh(server)  # warm: transform + online step executables
    with recompile_guard() as counter:
        serve_and_refresh(server)
    assert counter.count == 0
