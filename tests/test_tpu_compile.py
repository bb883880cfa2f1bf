"""Compile the Pallas kernels of the main path for a described TPU v5e.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for a
chip that is described, not attached, at the widths of the paper's three
corpora (``configs/nmf_paper.py``) with a fully-filled tile grid and k=5.
Mosaic refuses here what interpret mode accepts — unaligned blocks, more
SMEM or VMEM than a core has — so these tests guard the chip path without
a chip.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU compiler library, and every test
worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.nmf_paper import NMF_CONFIGS
from repro.kernels.bsr import BSR
from repro.kernels.bsr_spmm import bsr_spmm, row_block_chunks
from repro.kernels.fused import bsr_spmm_gram
from repro.kernels.gram import gram
from repro.kernels.project_mask import project_mask

from _hlo import SCOPE, at_highest, dots, loop_body, op_name

CORPORA = ("reuters", "pubmed", "wikipedia")
BM = BK = 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _full_bsr(n, m, sharding):
    """A fully-filled (n, m) tile grid: every row-block holds every
    column-block, the largest operand the corpus shape can produce."""
    nrb, ncb = -(-n // BM), -(-m // BK)
    return BSR(_sds((nrb, ncb, BM, BK), sharding),
               _sds((nrb, ncb), sharding, jnp.int32), (n, m))


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _launches(text):
    return text.count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("corpus", CORPORA)
@pytest.mark.parametrize("transposed", [False, True],
                         ids=["term_major", "doc_major"])
def test_bsr_spmm_compiles(one_chip, corpus, transposed):
    cfg = NMF_CONFIGS[corpus]
    n, m, k = cfg["n_terms"], cfg["n_docs"], cfg["k"]
    if transposed:
        n, m = m, n
    a = _full_bsr(n, m, one_chip)
    text = _compiled_text(lambda a, u: bsr_spmm(a, u), a,
                          _sds((m, k), one_chip))
    assert _launches(text) == len(row_block_chunks(a.nrb, a.bcap, 1))


@pytest.mark.parametrize("corpus", CORPORA)
@pytest.mark.parametrize("transposed", [False, True],
                         ids=["term_major", "doc_major"])
def test_fused_spmm_gram_compiles(one_chip, corpus, transposed):
    """Both orientations of the fused kernel fit SMEM and VMEM at every
    width: one scalar-prefetched table a launch, so the Wikipedia term-major
    grid (1121 x 98 tiles) splits into row-block launches, and the resident
    factor fits beside the tiles at the widest factor (143,462 terms)."""
    cfg = NMF_CONFIGS[corpus]
    n, m, k = cfg["n_terms"], cfg["n_docs"], cfg["k"]
    if transposed:
        n, m = m, n
    a = _full_bsr(n, m, one_chip)
    text = _compiled_text(lambda a, u: bsr_spmm_gram(a, u), a,
                          _sds((m, k), one_chip))
    chunks = row_block_chunks(a.nrb, a.bcap, 1)
    assert _launches(text) == len(chunks)
    if corpus == "wikipedia" and not transposed:
        assert len(chunks) > 1


@pytest.mark.parametrize("corpus", CORPORA)
@pytest.mark.parametrize("transposed", [False, True],
                         ids=["term_major", "doc_major"])
def test_paper_widths_take_the_fused_path(corpus, transposed):
    """At the paper's k=5 the pallas-bsr backend runs both half-steps of
    every corpus through the fused kernel, not the bsr_spmm + gram
    fallback."""
    from repro.backend.pallas_bsr import PallasBsrBackend

    cfg = NMF_CONFIGS[corpus]
    n, m, k = cfg["n_terms"], cfg["n_docs"], cfg["k"]
    if transposed:
        n, m = m, n
    a = BSR(jax.ShapeDtypeStruct((-(-n // BM), -(-m // BK), BM, BK),
                                 jnp.float32),
            jax.ShapeDtypeStruct((-(-n // BM), -(-m // BK)), jnp.int32),
            (n, m))
    assert PallasBsrBackend()._fusable(a, jax.ShapeDtypeStruct((m, k),
                                                               jnp.float32))


@pytest.mark.parametrize("corpus", CORPORA)
@pytest.mark.parametrize("transposed", [False, True],
                         ids=["term_major", "doc_major"])
def test_tile_fill_compiles_in_place(one_chip, corpus, transposed):
    """Ingest's device-side fill of a full tile grid from 262,144 entries
    (the ``bsr_fill`` kernel, after the transpose's gather of the entries)
    needs no temporary of tile volume: the chip holds the tiles, the
    entries and a few words an entry, nothing more."""
    from repro.kernels.bsr import NNZ_BUCKET
    from repro.kernels.fill import fill_tiles

    cfg = NMF_CONFIGS[corpus]
    nrb, ncb = -(-cfg["n_terms"] // BM), -(-cfg["n_docs"] // BK)
    grid = (ncb, nrb, BK, BM) if transposed else (nrb, ncb, BM, BK)
    entries = 4 * NNZ_BUCKET
    order = _sds((entries,), one_chip, jnp.int32) if transposed else None
    compiled = fill_tiles.lower(
        _sds((entries,), one_chip), _sds((entries,), one_chip, jnp.int32),
        _sds((nrb * ncb + 1,), one_chip, jnp.int32), order, grid=grid,
        swap=transposed, interpret=False).compile()
    assert _launches(compiled.as_text()) == 1
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == 4 * nrb * ncb * BM * BK
    assert memory.temp_size_in_bytes <= 32 * entries


@pytest.mark.parametrize("corpus", CORPORA)
@pytest.mark.parametrize("side", ["terms", "docs"])
def test_gram_compiles(one_chip, corpus, side):
    cfg = NMF_CONFIGS[corpus]
    rows = cfg["n_terms"] if side == "terms" else cfg["n_docs"]
    text = _compiled_text(lambda u: gram(u), _sds((rows, cfg["k"]), one_chip))
    assert _launches(text) == 1


@pytest.mark.parametrize("corpus", CORPORA)
@pytest.mark.parametrize("side", ["terms", "docs"])
def test_project_mask_compiles(one_chip, corpus, side):
    cfg = NMF_CONFIGS[corpus]
    rows = cfg["n_terms"] if side == "terms" else cfg["n_docs"]
    text = _compiled_text(lambda x, tau: project_mask(x, tau),
                          _sds((rows, cfg["k"]), one_chip),
                          _sds((), one_chip))
    assert _launches(text) == 1


def test_every_op_of_the_fit_loop_carries_a_scope(one_chip, monkeypatch):
    """The whole enforced fit at Reuters width: on the chip every fusion,
    kernel launch and nested loop of the ALS loop body sits in one of the
    engine's named scopes, which a profiler trace reports per op."""
    import repro.kernels.ops as ops
    from repro.core.nmf import als_nmf
    from repro.kernels.bsr import BSROperand
    from repro.nmf.config import Sparsity

    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = NMF_CONFIGS["reuters"]
    n, m, k = cfg["n_terms"], cfg["n_docs"], cfg["k"]
    a = BSROperand(_full_bsr(n, m, one_chip), _full_bsr(m, n, one_chip),
                   (n, m))
    sparsity = Sparsity(t_u=55, mode="global")
    text = _compiled_text(
        lambda a, u0: als_nmf(
            a, u0, iters=75, backend="pallas-bsr",
            sparsify_u=sparsity.sparsifier(n, k, "u", fused=True)),
        a, _sds((n, k), one_chip))
    ops_of_body = [line for line in loop_body(text)
                   if re.search(r"\s(fusion|custom-call|while)\(", line)]
    assert sum('tpu_custom_call' in line for line in ops_of_body) == 3
    unscoped = [line.strip()[:160] for line in ops_of_body
                if not SCOPE.search(op_name(line))]
    assert not unscoped, unscoped


def _gram_spy(monkeypatch):
    """Record the argument of every ``factor_gram`` call the estimator and
    the solvers make."""
    import repro.nmf.estimator as estimator
    import repro.nmf.solvers as solvers
    from repro.core.nmf import factor_gram

    seen = []

    def spy(x):
        seen.append(jax.ShapeDtypeStruct(x.shape, x.dtype))
        return factor_gram(x)

    for module in (estimator, solvers):
        monkeypatch.setattr(module, "factor_gram", spy)
    return seen


def _tiny_streamed_model():
    import numpy as np
    import scipy.sparse as sp

    from repro.data.corpus import as_chunk_source
    from repro.nmf import EnforcedNMF, NMFConfig, Sparsity
    from repro.sparse import from_scipy

    n, m, k = 256, 96, 5
    a = sp.random(n, m, density=0.1, random_state=0, format="csr",
                  dtype=np.float32)
    model = EnforcedNMF(NMFConfig(k=k, solver="streaming", backend="jnp-csr",
                                  sparsity=Sparsity(t_u=300, t_v=100)))
    rng = np.random.default_rng(0)
    model.u_ = jnp.asarray(rng.random((n, k), np.float32))
    model.v_ = jnp.asarray(rng.random((m, k), np.float32))
    model.n_features_, model._m_ref = n, m
    return model, a, as_chunk_source(from_scipy(a), chunk_docs=m // 4)


@pytest.mark.parametrize("site", ["transform", "fold_in", "seed_stats"])
def test_stream_grams_contract_at_highest(one_chip, monkeypatch, site):
    """The Grams of ``transform``, the streamed fit's fold-in and its seed
    statistics are one ``factor_gram`` each, and the chip's compiler keeps
    every product of it at full float32 precision (at XLA's default a TPU
    product is one bfloat16 pass)."""
    from repro.nmf.solvers import STREAM_STATS, _fold_in_streamed

    model, a, source = _tiny_streamed_model()
    seen = _gram_spy(monkeypatch)
    if site == "transform":
        model.transform(a)
    elif site == "fold_in":
        _fold_in_streamed(model, source, model.config,
                          dict.fromkeys(STREAM_STATS, 0))
    else:
        model._seed_stats_streamed(source)
    assert len(seen) == 1, seen
    from repro.core.nmf import factor_gram

    products = dots(_compiled_text(factor_gram, _sds(seen[0].shape,
                                                     one_chip)))
    assert products and all(at_highest(line) for line in products), products


def test_the_batch_fit_takes_none_of_the_stream_grams(monkeypatch):
    """A resident fit, the batch cells' path, seeds its statistics through
    the fused kernel and never reaches ``factor_gram``: its program is the
    one it was before the stream's Grams moved to full precision."""
    import numpy as np
    import scipy.sparse as sp

    from repro.backend import get_backend
    from repro.nmf import EnforcedNMF, NMFConfig, Sparsity

    a = sp.random(256, 192, density=0.05, random_state=0, format="csr",
                  dtype=np.float32)
    op = get_backend("pallas-bsr").prepare(a, dtype=np.float32)
    seen = _gram_spy(monkeypatch)
    EnforcedNMF(NMFConfig(k=4, iters=2, tol=0.0, backend="pallas-bsr",
                          sparsity=Sparsity(t_u=100, t_v=80))).fit(op)
    assert seen == []
