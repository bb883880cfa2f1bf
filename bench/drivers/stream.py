"""Traffic of streamed fits off disk, back to back.

Set-up builds the corpus from the seed, writes it in the program's on-disk
corpus layout (``write_corpus``: one shard of padded CSR a chunk of
``stream.chunk_docs`` documents, crc32-checked) to a temporary directory,
opens it memory-mapped (``MmapCorpus``), and runs one streamed fit that
compiles (or loads from the cache) every program a fit runs.  The window
then runs streamed fits of that directory until ``--seconds`` have passed,
each from its own initial ``U`` (drawn from the seed and the fit's index),
each ending with ``U`` and ``V`` on the device and the history on the
host.  The directory is removed once the check no longer needs it, and at
the latest when the process exits.

The check follows ``checked_fits`` of the window's fits, drawn from the
seed by reservoir sampling, with the plain online reference
(:mod:`bench.reference.online`, on the corpus held in memory) and compares
the worst of them:

* ``v_gap``: the program's ``V`` against the reference's fold-in from the
  program's own final ``U``, ``||V - V_ref||_F / ||V_ref||_F``.  No
  iteration lies between them, so two sound float32 computations agree to
  rounding; what moves it is the fold-in's arithmetic, such as a Gram
  contracted in bfloat16.  Top-t's selection is discontinuous at its
  threshold, so entries whose reference value lies within
  :data:`THRESHOLD_BAND` of the threshold, where rounding alone decides
  whether an entry is kept, are left out on both sides;
* ``u_gap``: the program's ``U`` against the reference's after the same
  stream from the same initial ``U``, ``||U - U_ref||_F / ||U_ref||_F``;
* ``over_budget``: the most non-zeros that a followed fit's ``U`` or ``V``
  holds above ``t_u`` or ``t_v``, entries tied with the smallest kept value
  excepted (as in the fit cells).

Traffic parameters: ``checked_fits``, how many window fits the check
follows; ``window_fits``, the fewest fits a window holds.  A window ends at
the first fit that ends once ``--seconds`` have passed and ``window_fits``
fits are done, so ``fit_s`` averages several fits of the host-bound stream,
whichever ``--seconds`` the run is given.  Configuration:
``stream.chunk_docs``, ``stream.prefetch`` and ``stream.prefetch_depth``,
handed to the estimator.  The reference repeats what the program does per
chunk: :func:`inner_passes` passes, every chunk's statistics kept (the
estimator's ``forget`` of 1).
"""
from __future__ import annotations

import atexit
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Any, List

import numpy as np

from bench import corpus as corpus_mod
from bench import program
from bench.harness import BENCH, load_module
from bench.reference import als as ref_als
from bench.reference import online as ref_online

#: the width, relative to the fold-in's threshold, of the band of
#: reference values that ``v_gap`` leaves out: a hundred times the float32
#: rounding between two sound computations of a loading (1e-6 of it),
#: and a tenth of what a bfloat16 Gram moves every loading by
THRESHOLD_BAND = 1e-4

_over_budget = load_module(BENCH / "drivers" / "fit.py")._over_budget


@dataclasses.dataclass
class State:
    config: dict
    traffic: dict
    seed: int
    corpus: corpus_mod.Corpus
    path: Any = None
    source: Any = None
    model: Any = None
    #: the reservoir: ``(fit index, u, v)`` of the fits the check follows
    kept: List[tuple] = dataclasses.field(default_factory=list)


def inner_passes(config: dict) -> int:
    """The passes the streaming solver makes over each chunk: its rule
    ``min(iters, 10)`` (``EnforcedNMF.partial_fit``)."""
    return min(int(config["iters"]), 10)


def _estimator(config: dict):
    """The streaming estimator the configuration states."""
    from repro.nmf import EnforcedNMF

    s = config["stream"]
    return EnforcedNMF(program.estimator(config).config,
                       chunk_docs=s["chunk_docs"], prefetch=s["prefetch"],
                       prefetch_depth=s["prefetch_depth"])


def _remove(st: State) -> None:
    if st.path is not None:
        shutil.rmtree(st.path, ignore_errors=True)
        st.path = None


def setup(cell, seed: int, rec) -> State:
    from repro.data.corpus import MmapCorpus, write_corpus

    cfg = cell.config
    st = State(cfg, cell.traffic, seed, program.build_corpus(cfg, seed, rec))
    st.path = tempfile.mkdtemp(prefix="bench-stream-")
    atexit.register(shutil.rmtree, st.path, True)
    t0 = time.perf_counter()
    write_corpus(st.corpus.a, st.path, chunk_docs=cfg["stream"]["chunk_docs"],
                 dtype=np.float32)
    rec.setup["write_s"] = time.perf_counter() - t0
    st.source = MmapCorpus(st.path)
    st.model = _estimator(cfg)
    program.warmup_fit(cfg, seed, st.model, st.source, rec)
    a = st.corpus.a.tocsc()
    rec.setup["nnz"] = a.nnz
    #: ``[documents, nnz]`` of each chunk, in order
    rec.setup["chunks"] = [[hi - lo, int(a.indptr[hi] - a.indptr[lo])]
                           for lo, hi in st.source.schedule]
    rec.setup["corpus_bytes"] = st.source.nbytes
    rec.setup["passes"] = inner_passes(cfg)
    return st


def window(st: State, seconds: float, rec) -> None:
    n, _ = st.source.shape
    k, want = st.config["k"], int(st.traffic["checked_fits"])
    least = int(st.traffic["window_fits"])
    rng = corpus_mod.rng_for(st.seed, corpus_mod.SAMPLE)
    fits = failed = 0
    durations = []
    counters: dict = {}
    t0 = time.perf_counter()
    while True:
        t_fit = time.perf_counter()
        err = program.run_fit(st.model, st.source,
                              program.initial_factor(st.seed, fits, n, k))
        durations.append(time.perf_counter() - t_fit)
        failed += not np.all(np.isfinite(err))
        for key, value in (getattr(st.model.result_, "stream_stats", None)
                           or {}).items():
            counters[key] = counters.get(key, 0) + value
        slot = fits if fits < want else int(rng.integers(0, fits + 1))
        if slot < want:
            entry = (fits, st.model.u_, st.model.v_)
            if slot < len(st.kept):
                st.kept[slot] = entry
            else:
                st.kept.append(entry)
        fits += 1
        if fits >= least and time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    rec.window.update(
        window_s=window_s, fits=fits, attempted=fits, failed=failed,
        stream_stats=counters or None,
        summary={"fits": fits, "window_s": window_s,
                 "fit_s": window_s / fits, "durations": durations,
                 "last_error": float(err[-1]),
                 "stream_stats": counters or None})


def _v_gap(a, u, v, t_v) -> float:
    """``v`` against the reference fold-in from ``u``, the entries within
    :data:`THRESHOLD_BAND` of the reference's threshold left out."""
    loads = ref_online.fold_in_host(a, u, None)
    keep = np.ones(loads.shape, bool)
    if t_v is not None and t_v < loads.size:
        tau = np.sort(loads.ravel())[loads.size - int(t_v)]
        keep = np.abs(loads - tau) > THRESHOLD_BAND * tau
        loads = np.where(loads >= tau, loads, 0.0)
    return program.rel_fro(np.where(keep, v, 0.0), np.where(keep, loads, 0.0))


def _follow(st: State, candidates, precision: str) -> dict:
    """Run the reference from each candidate's initial U and compare.
    ``candidates`` maps a fit index to the program's ``(u, v)``, or to
    ``None`` for the control (the reference at ``precision`` then stands
    in for the program)."""
    cfg, f = st.config, st.config["fit"]
    a = ref_als.dense(st.corpus.a)
    n, k = st.corpus.a.shape[0], cfg["k"]
    args = (cfg["stream"]["chunk_docs"], inner_passes(cfg), f["t_u"],
            f["t_v"])
    rows = []
    for i, fit in candidates.items():
        u0 = program.initial_factor(st.seed, i, n, k)
        ref = ref_online.stream_host(a, u0, *args)
        if fit is None:
            fit = ref_online.stream_host(a, u0, *args, precision=precision)
        rows.append({"fit": i, "v_gap": _v_gap(a, fit[0], fit[1], f["t_v"]),
                     "u_gap": program.rel_fro(fit[0], ref.u),
                     "over_budget": max(_over_budget(fit[0], f["t_u"]),
                                        _over_budget(fit[1], f["t_v"]))})
    print(f"fits followed by the check: {json.dumps(rows)}", file=sys.stderr)
    return {key: max(r[key] for r in rows)
            for key in ("v_gap", "u_gap", "over_budget")}


def check(st: State, rec) -> dict:
    """Frees the program's state and the corpus directory, then compares
    window fits, drawn from the seed, with the reference's."""
    fits = {i: (np.asarray(u), np.asarray(v)) for i, u, v in st.kept}
    st.kept, st.model, st.source = [], None, None
    _remove(st)
    gc.collect()
    t0 = time.perf_counter()
    out = _follow(st, fits, "highest")
    rec.window["check_s"] = time.perf_counter() - t0
    return out


def control(st: State, precision: str) -> dict:
    """The check's numbers with the reference at ``precision`` in the
    program's place (the control that the check must refuse)."""
    _remove(st)
    return _follow(st, dict.fromkeys(range(int(st.traffic["checked_fits"]))),
                   precision)
