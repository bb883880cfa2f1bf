"""The streamed cell at a tiny size on the CPU: the program's streamed fit
follows the plain online reference; a sound run is correct; with the
timed path broken underneath, ``correct`` comes out false, once for each
fault of the stream (a fold-in Gram in bfloat16, a chunk left out, the
statistics dropped at every chunk, a budget broken); the control at
``high`` reads above the program."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import corpus as C
from bench import harness, program
from bench.reference import als as ref_als
from bench.reference import online as ref_online

from conftest import TINY_CORPUS, tiny

CELL = "pubmed-journals.stream"
#: eight chunks of the tiny corpus, as the cell has eight of the full one
TINY_CHUNK = TINY_CORPUS["n_docs"] // 8
#: the fewest fits a tiny window holds: enough for the check to draw from
TINY_WINDOW_FITS = 2
SHAPE = dict(n_terms=TINY_CORPUS["n_terms"], n_docs=TINY_CORPUS["n_docs"],
             n_journals=5, terms_per_doc=60, topic_strength=0.7,
             zipf_exponent=1.1)
#: how far the program's streamed fit may sit from the reference on the
#: CPU, where both contract in float32: sound fits read 1e-7 to 1e-6
CPU_AGREEMENT = 1e-4


def _tiny_stream_cell():
    cell = tiny(harness.load_cell(CELL))
    cell.config["stream"]["chunk_docs"] = TINY_CHUNK
    cell.traffic = {**cell.traffic, "window_fits": TINY_WINDOW_FITS}
    return cell


def _run(cell, seed=2**31 + 7):
    return harness.execute(cell, seed, 0.5, False, time.perf_counter())


@pytest.mark.parametrize("source", ["disk", "resident"])
@pytest.mark.parametrize("backend", ["jnp-csr", "pallas-bsr"])
def test_the_program_follows_the_reference(tmp_path, backend, source):
    from repro.data.corpus import MmapCorpus, write_corpus
    from repro.nmf import EnforcedNMF, NMFConfig, Sparsity

    t_u, t_v, iters = 600, 250, 4
    corpus = C.journal_corpus(11, **SHAPE)
    u0 = program.initial_factor(11, 0, SHAPE["n_terms"], 5)
    a = corpus.a
    if source == "disk":
        a = MmapCorpus(write_corpus(corpus.a, tmp_path, chunk_docs=TINY_CHUNK))
    model = EnforcedNMF(NMFConfig(
        k=5, iters=iters, solver="streaming", backend=backend, tol=0.0,
        chunk_docs=TINY_CHUNK,
        sparsity=Sparsity(t_u=t_u, t_v=t_v, mode="global"))).fit(a, u0=u0)
    ref = ref_online.stream_host(ref_als.dense(corpus.a), u0, TINY_CHUNK,
                                 iters, t_u, t_v)
    assert program.rel_fro(model.u_, ref.u) < CPU_AGREEMENT
    assert program.rel_fro(model.v_, ref.v) < CPU_AGREEMENT
    assert np.count_nonzero(ref.u) == t_u
    assert np.count_nonzero(ref.v) == t_v


def test_a_sound_run_is_correct():
    result = _run(_tiny_stream_cell())
    assert result["correct"], result["compared"]
    assert result["attempted"] >= TINY_WINDOW_FITS
    assert result["failed"] == 0
    assert list(result)[-1] == "compared"


def _fold_in_gram_in_bfloat16(monkeypatch):
    """The fold-in's Gram ``U^T U`` contracted from bfloat16 operands, as
    XLA's default precision does on a TPU."""
    import repro.nmf.solvers as solvers

    def gram(x):
        xb = x.astype(jnp.bfloat16)
        return jnp.dot(xb.T, xb, preferred_element_type=jnp.float32)

    monkeypatch.setattr(solvers, "factor_gram", gram)


def _one_chunk_left_out(monkeypatch):
    """The stream leaves out its third chunk: ``partial_fit`` returns at
    once on it."""
    from repro.nmf import EnforcedNMF

    real, calls = EnforcedNMF.partial_fit, []

    def fake(self, *args, **kw):
        calls.append(None)
        return self if len(calls) % 8 == 3 else real(self, *args, **kw)

    monkeypatch.setattr(EnforcedNMF, "partial_fit", fake)


def _statistics_reset_each_chunk(monkeypatch):
    """Every chunk starts from empty statistics, as if the stream held
    nothing of the chunks before it."""
    from repro.nmf import EnforcedNMF

    real = EnforcedNMF.partial_fit

    def fake(self, *args, **kw):
        self._av_acc = self._gv_acc = None
        return real(self, *args, **kw)

    monkeypatch.setattr(EnforcedNMF, "partial_fit", fake)


def _u_over_budget(monkeypatch):
    """The fitted U holds one non-zero above its budget, too small to move
    the factors' gap."""
    from repro.nmf import EnforcedNMF

    real = EnforcedNMF.partial_fit

    def fake(self, *args, **kw):
        real(self, *args, **kw)
        u = self.u_.ravel()
        zero = jnp.argmin(jnp.where(u == 0, 0, 1))
        self.u_ = u.at[zero].set(1e-9 * jnp.max(u)).reshape(self.u_.shape)
        return self

    monkeypatch.setattr(EnforcedNMF, "partial_fit", fake)


STREAM_FAULTS = [_fold_in_gram_in_bfloat16, _one_chunk_left_out,
                 _statistics_reset_each_chunk, _u_over_budget]


@pytest.mark.parametrize("fault", STREAM_FAULTS, ids=lambda f: f.__name__)
def test_a_broken_stream_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    result = _run(_tiny_stream_cell())
    assert not result["correct"], result["compared"]


#: the least the control reads above the program on the CPU, where XLA's
#: k x k solves stay float32 and the control's lower precision shows less
#: than on the chip, whose limits it fails there
CPU_CONTROL_FLOOR = 1e-5


def test_the_control_reads_above_the_program():
    import jax

    cell = _tiny_stream_cell()
    corpus = C.journal_corpus(2**31 + 99, **SHAPE)
    driver = harness.driver_of(cell)
    st = driver.State(cell.config, cell.traffic, 2**31 + 99, corpus)
    readings = driver.control(st, "high")
    limits = dict(cell.limits)
    if jax.devices()[0].platform != "tpu":
        limits.update(v_gap=CPU_CONTROL_FLOOR, u_gap=CPU_CONTROL_FLOOR)
    failed = [name for name, limit in limits.items()
              if readings[name] > limit]
    assert failed, (readings, cell.limits)
