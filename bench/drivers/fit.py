"""Traffic of complete fits back to back on one estimator.

Set-up builds the corpus from the seed, ingests it once, and runs one fit
that compiles (or loads from the cache) every program a fit runs.  The
window then runs fits on that operand and estimator until ``--seconds``
have passed, each from its own initial ``U`` (drawn from the seed and the
fit's index), each ending with its factors ready on the device and its
error history on the host.

The check follows ``checked_fits`` fits that the window ran, drawn from
the seed by reservoir sampling as the window runs (so the window keeps the
factors of that many fits on the device, however many it completes, and
the peak memory it reports does not grow with the rate), with the plain
dense reference ALS (:mod:`bench.reference.als`) from the same initial
``U``.  It compares:

* ``u_gap_3rd`` and ``v_gap_3rd``: of ``||X - X_ref||_F / ||X_ref||_F``
  over the fits followed, the :data:`AGREEING_FITS`-th smallest: at least
  that many fits must agree with the reference.  Not every fit,
  because enforced-sparsity ALS does not contract: from the same start, a
  sound float32 run and the reference part on some fits (up to six in
  fifteen on Reuters, where U keeps 55 entries), as two sound float32 runs
  of different summation order do, and end at different fixed points; the
  fits that do not part agree to rounding.  A fault of the timed path
  that reaches all but two of the fits followed moves it;
* ``over_budget``: the most non-zeros that a followed fit's U or V holds
  above its budget (``t_u``, ``t_v``), entries tied with the smallest kept
  value excepted.  The budgets are the configuration's guarantee, so the
  limit is 0; its ``"global"`` mode keeps every entry equal to the
  threshold, as the reference does, and terms whose rows of A are equal
  give equal entries, so a fit may keep a few more than ``t`` that way.

Traffic parameters: ``checked_fits``, how many window fits the check
follows.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
from typing import Any, List

import numpy as np

from bench import corpus as corpus_mod
from bench import program
from bench.reference import als as ref_als

#: how many of the fits followed must agree with the reference
AGREEING_FITS = 3

@dataclasses.dataclass
class State:
    config: dict
    traffic: dict
    seed: int
    corpus: corpus_mod.Corpus
    op: Any = None
    model: Any = None
    #: the reservoir: ``(fit index, u, v)`` of the fits the check follows
    kept: List[tuple] = dataclasses.field(default_factory=list)


def setup(cell, seed: int, rec) -> State:
    st = State(cell.config, cell.traffic, seed,
               program.build_corpus(cell.config, seed, rec))
    st.op = program.ingest(cell.config, st.corpus, rec)
    st.model = program.estimator(cell.config)
    program.warmup_fit(cell.config, seed, st.model, st.op, rec)
    rec.setup["nnz"] = st.corpus.a.nnz
    return st


def window(st: State, seconds: float, rec) -> None:
    n, _ = st.op.shape
    k, want = st.config["k"], int(st.traffic["checked_fits"])
    rng = corpus_mod.rng_for(st.seed, corpus_mod.SAMPLE)
    fits = failed = 0
    durations = []
    t0 = time.perf_counter()
    while True:
        t_fit = time.perf_counter()
        err = program.run_fit(st.model, st.op,
                              program.initial_factor(st.seed, fits, n, k))
        durations.append(time.perf_counter() - t_fit)
        failed += not np.all(np.isfinite(err))
        slot = fits if fits < want else int(rng.integers(0, fits + 1))
        if slot < want:
            entry = (fits, st.model.u_, st.model.v_)
            if slot < len(st.kept):
                st.kept[slot] = entry
            else:
                st.kept.append(entry)
        fits += 1
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    rec.window.update(
        window_s=window_s, fits=fits, iters=st.config["iters"],
        attempted=fits, failed=failed,
        summary={"fits": fits, "window_s": window_s,
                 "fit_s": window_s / fits, "fit_s_min": min(durations),
                 "fit_s_max": max(durations),
                 "last_error": float(err[-1])})


def _over_budget(x: np.ndarray, t) -> int:
    """Non-zeros of ``x`` kept beyond its ``t`` largest and the entries
    tied with the t-th."""
    kept = np.sort(x[x != 0])[::-1]
    if t is None or kept.size <= t:
        return 0
    return int(np.count_nonzero(kept < kept[int(t) - 1]))


def _follow(st: State, candidates, precision: str) -> dict:
    """Run the reference from each candidate's initial U and compare.
    ``candidates`` maps a fit index to the program's ``(u, v)``, or to
    ``None`` for the control (the reference at ``precision`` then stands
    in for the program)."""
    cfg, f = st.config, st.config["fit"]
    a = ref_als.dense(st.corpus.a)
    n, k = st.corpus.a.shape[0], cfg["k"]
    rows = []
    for i, fit in candidates.items():
        u0 = program.initial_factor(st.seed, i, n, k)
        ref = ref_als.fit_host(a, u0, cfg["iters"], f["t_u"], f["t_v"])
        if fit is None:
            low = ref_als.fit_host(a, u0, cfg["iters"], f["t_u"], f["t_v"],
                                   precision)
            fit = (low.u, low.v)
        rows.append({"fit": i, "u_gap": program.rel_fro(fit[0], ref.u),
                     "v_gap": program.rel_fro(fit[1], ref.v),
                     "over_budget": max(_over_budget(fit[0], f["t_u"]),
                                        _over_budget(fit[1], f["t_v"]))})
    print(f"fits followed by the check: {json.dumps(rows)}", file=sys.stderr)
    agree = min(AGREEING_FITS, len(rows))
    out = {f"{key}_3rd": sorted(r[key] for r in rows)[agree - 1]
           for key in ("u_gap", "v_gap")}
    out["over_budget"] = max(r["over_budget"] for r in rows)
    return out


def check(st: State, rec) -> dict:
    """Frees the program's state, then compares window fits, drawn from
    the seed, with the reference's."""
    fits = {i: (np.asarray(u), np.asarray(v)) for i, u, v in st.kept}
    st.kept, st.model, st.op = [], None, None
    gc.collect()
    t0 = time.perf_counter()
    out = _follow(st, fits, "highest")
    rec.window["check_s"] = time.perf_counter() - t0
    return out


def control(st: State, precision: str) -> dict:
    """The check's numbers with the reference at ``precision`` in the
    program's place (the control that the check must refuse)."""
    return _follow(st, dict.fromkeys(range(int(st.traffic["checked_fits"]))),
                   precision)
