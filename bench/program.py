"""The benchmark's calls into the program under test, shared by the
drivers: corpus, ingest, the estimator a configuration describes, the
initial factors, and a fit timed to its end.

The program is imported only here and in the drivers, and only through the
entry points that the window drives: ``get_backend(...).prepare`` and
``EnforcedNMF``.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import corpus as corpus_mod

#: initial-factor index of the fit that set-up runs; window fits count up
#: from 0
WARMUP = 2**31 - 2


def build_corpus(config: Dict[str, Any], seed: int,
                 rec) -> corpus_mod.Corpus:
    c = config["corpus"]
    t0 = time.perf_counter()
    out = corpus_mod.journal_corpus(
        seed, c["n_terms"], c["n_docs"], c["n_journals"], c["terms_per_doc"],
        c["topic_strength"], c["zipf_exponent"])
    rec.setup["corpus_s"] = time.perf_counter() - t0
    return out


def ingest(config: Dict[str, Any], corpus: corpus_mod.Corpus, rec):
    """The corpus as the backend's operand, timed to its arrival on the
    device (``rec.setup["ingest_s"]``)."""
    from repro.backend import get_backend

    t0 = time.perf_counter()
    op = get_backend(config["fit"]["backend"]).prepare(corpus.a,
                                                       dtype=np.float32)
    jax.block_until_ready(op)
    rec.setup["ingest_s"] = time.perf_counter() - t0
    return op


def estimator(config: Dict[str, Any]):
    """A fresh ``EnforcedNMF`` as the configuration states it."""
    from repro.nmf import EnforcedNMF, NMFConfig, Sparsity

    f = config["fit"]
    return EnforcedNMF(NMFConfig(
        k=config["k"], iters=config["iters"],
        sparsity=Sparsity(t_u=f["t_u"], t_v=f["t_v"], mode=f["mode"]),
        solver=f["solver"], backend=f["backend"], dtype=f["dtype"],
        tol=f["tol"]))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _uniform(seed32, index, n, k):
    key = jax.random.fold_in(jax.random.key(seed32), index)
    return jax.random.uniform(key, (n, k), jnp.float32)


def initial_factor(seed: int, index: int, n: int, k: int) -> jax.Array:
    """The uniform [0, 1) initial ``U`` (n, k) of fit ``index`` of
    ``seed``, made on the device."""
    return _uniform(corpus_mod.int32_seed(seed, corpus_mod.FACTORS),
                    np.uint32(index), n, k)


def run_fit(model, op, u0) -> np.ndarray:
    """One complete fit: the factors on the device and the error history
    on the host.  Returns the history."""
    with jax.profiler.TraceAnnotation("bench.fit"):
        model.fit(op, u0=u0)
        jax.block_until_ready((model.u_, model.v_))
    with jax.profiler.TraceAnnotation("bench.fetch"):
        return np.asarray(model.result_.error)


def warmup_fit(config, seed: int, model, op, rec) -> np.ndarray:
    """The set-up fit, which traces, lowers and compiles (or loads from the
    cache) every program a fit runs (``rec.setup["warmup_fit_s"]``)."""
    n, _ = op.shape
    t0 = time.perf_counter()
    err = run_fit(model, op, initial_factor(seed, WARMUP, n, config["k"]))
    rec.setup["warmup_fit_s"] = time.perf_counter() - t0
    return err


def rel_fro(x, ref) -> float:
    """``||x - ref||_F / ||ref||_F`` in float64."""
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(x - ref) / max(np.linalg.norm(ref), 1e-300))
