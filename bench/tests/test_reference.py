"""The plain references against the program at a tiny size, their
independence from it, and the control's lower precision."""
import ast
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import corpus as C
from bench import program
from bench.reference import als as ref_als
from bench.reference.precision import dot, np_dot

REFERENCE = Path(ref_als.__file__).resolve().parent
SHAPE = dict(n_terms=640, n_docs=384, n_journals=5, terms_per_doc=60,
             topic_strength=0.7, zipf_exponent=1.1)


def _imports(path: Path):
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield node.module or ""


def test_references_import_nothing_of_the_program():
    for path in REFERENCE.glob("*.py"):
        for name in _imports(path):
            assert not name.startswith(("repro", "benchmarks")), (path, name)


def test_nothing_in_bench_imports_the_old_benchmarks():
    for path in REFERENCE.parent.rglob("*.py"):
        for name in _imports(path):
            assert not name.startswith("benchmarks"), (path, name)


def _program_fit(a, u0, iters, t_u, t_v):
    from repro.nmf import EnforcedNMF, NMFConfig, Sparsity

    model = EnforcedNMF(NMFConfig(
        k=u0.shape[1], iters=iters, backend="pallas-bsr", tol=0.0,
        sparsity=Sparsity(t_u=t_u, t_v=t_v, mode="global")))
    model.fit(a, u0=u0)
    return model


@pytest.mark.parametrize("t_u,t_v", [(600, 250), (55, None)],
                         ids=["both_budgets", "u_budget_only"])
def test_reference_als_follows_the_program(t_u, t_v):
    corpus = C.journal_corpus(7, **SHAPE)
    u0 = program.initial_factor(7, 0, 640, 5)
    model = _program_fit(corpus.a, u0, 6, t_u, t_v)
    ref = ref_als.fit_host(ref_als.dense(corpus.a), u0, 6, t_u, t_v)
    assert np.max(np.abs(np.asarray(model.result_.error) - ref.error)) < 1e-5
    assert program.rel_fro(model.u_, ref.u) < 1e-4
    assert program.rel_fro(model.v_, ref.v) < 1e-4
    assert np.count_nonzero(ref.u) == t_u
    if t_v is not None:
        assert np.count_nonzero(ref.v) == t_v


@pytest.mark.parametrize("precision,lo,hi", [("highest", 0, 1e-6),
                                             ("high", 1e-7, 1e-4)])
def test_named_precisions_sit_one_step_apart(precision, lo, hi):
    rng = np.random.default_rng(0)
    a = rng.random((64, 512), np.float32)
    b = rng.random((512, 8), np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    for got in (np.asarray(dot(jnp.asarray(a), jnp.asarray(b), precision)),
                np_dot(a, b, precision)):
        rel = np.max(np.abs(got - exact)) / np.max(np.abs(exact))
        assert lo <= rel < hi, (precision, rel)
