"""The readers of the program's own spans (``bench/scope.py`` and
``host_idle_ms_per_fit``), on a synthetic reduction and on a trace
recorded on the CPU; and ``halfstep_roofline`` on the kernel names the
program gives its launches."""
import time

import numpy as np
import pytest

from bench import harness, scope
from bench import trace as tm


def _metric(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def _records(red, fits=2, iters=75, cell="reuters-21578.fit"):
    return harness.Records(cell=harness.load_cell(cell), seed=0,
                           setup={"nnz": 1000},
                           window={"fits": fits, "iters": iters},
                           peaks={"flops_per_s": 1e9,
                                  "hbm_bytes_per_s": 1e9},
                           trace=red)


def _reduced():
    """Two fits; device 0 idles 1.0-1.2 (nmf.sync of fit 1), 1.5-2.5
    (fit 1's result and seed-stats tail, the host between fits, fit 2's
    prepare) and 3.0-3.1 (nmf.sync of fit 2)."""
    ops = [tm.Op("fusion.1", 0.0, 1.0, 1.0, 0, {}),
           tm.Op("fusion.2", 1.2, 1.5, 0.3, 0, {}),
           tm.Op("fusion.1", 2.5, 3.0, 0.5, 0, {}),
           tm.Op("fusion.2", 3.1, 4.0, 0.9, 0, {})]
    spans = [tm.Span("bench.window", 0.0, 4.0, 0),
             tm.Span("bench.fit", 0.0, 1.9, 1),
             tm.Span("bench.fit", 2.1, 4.0, 1)]
    host = [tm.Span("nmf.fit", 0.0, 1.8, 2),
            tm.Span("nmf.dispatch", 0.0, 0.1, 3),
            tm.Span("nmf.sync", 0.1, 1.3, 3),
            tm.Span("np.asarray(jax.Array)", 0.1, 1.3, 4),
            tm.Span("nmf.result", 1.3, 1.6, 3),
            tm.Span("nmf.seed_stats", 1.6, 1.7, 3),
            tm.Span("nmf.fit", 2.2, 3.9, 2),
            tm.Span("nmf.prepare", 2.2, 2.6, 3),
            tm.Span("nmf.dispatch", 2.6, 2.7, 3),
            tm.Span("nmf.sync", 2.7, 3.2, 3),
            tm.Span("nmf.result", 3.2, 3.3, 3)]
    return tm.Reduced(window=(0.0, 4.0), n_devices=1, ops=ops, spans=spans,
                      host=host)


def test_fit_spans_and_their_idle_time():
    red = _reduced()
    fits = scope.fit_spans(red)
    assert [(s.start, s.end) for s in fits] == [(0.0, 1.8), (2.2, 3.9)]
    gaps = red.gaps(0)
    assert gaps == [(1.0, 1.2), (1.5, 2.5), (3.0, 3.1)]
    # the middle gap is split across both fits and the host between them
    assert scope.idle_in(gaps, fits[0]) == pytest.approx(0.2 + 0.3)
    assert scope.idle_in(gaps, fits[1]) == pytest.approx(0.3 + 0.1)


def test_idle_split_by_innermost_span():
    red = _reduced()
    split = scope.idle_by_span(red)
    assert split == pytest.approx({
        "nmf.sync": 0.2 + 0.1, "nmf.result": 0.1, "nmf.seed_stats": 0.1,
        "nmf.fit": 0.1, scope.OUTSIDE: 0.4, "nmf.prepare": 0.3})
    assert sum(split.values()) == pytest.approx(tm.length(red.gaps(0)))
    name, seconds = scope.longest_gap(red)
    assert (name, seconds) == (scope.OUTSIDE, pytest.approx(1.0))


def test_segments_label_every_instant_of_the_window():
    red = _reduced()
    pieces = scope.segments(scope.program_spans(red), red.window)
    assert pieces[0][0] == 0.0 and pieces[-1][1] == 4.0
    assert all(a[1] == b[0] for a, b in zip(pieces, pieces[1:]))
    at = {round(0.5 * (a + b), 6): name for a, b, name in pieces}
    assert at[0.05] == "nmf.dispatch" and at[0.7] == "nmf.sync"
    assert at[1.75] == "nmf.fit" and at[2.0] == scope.OUTSIDE


def test_host_idle_reads_the_median_fit_in_ms(capsys):
    read = _metric("host_idle_ms_per_fit").read
    assert read(_records(_reduced())) == pytest.approx(1e3 * 0.45)
    err = capsys.readouterr().err
    assert "outside nmf.fit 200" in err and "longest gap 1000 ms" in err


def test_without_the_programs_spans_nothing_is_read():
    """The parent program writes no ``nmf.*`` span: no reading, no
    error."""
    red = _reduced()
    red.host = [s for s in red.host if not s.name.startswith("nmf.")]
    assert scope.fit_spans(red) == []
    assert _metric("host_idle_ms_per_fit").read(_records(red)) is None
    assert _metric("host_idle_ms_per_fit").read(_records(None)) is None


def test_host_idle_on_a_fit_traced_on_the_cpu(tmp_path, tiny_cell):
    import jax

    from bench import program

    cell = tiny_cell("reuters-21578.fit")
    rec = harness.Records(cell=cell, seed=11)
    corpus = program.build_corpus(cell.config, 11, rec)
    op = program.ingest(cell.config, corpus, rec)
    model = program.estimator(cell.config)
    n, k = op.shape[0], cell.config["k"]
    program.warmup_fit(cell.config, 11, model, op, rec)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tm.WINDOW_SPAN):
            for i in range(3):
                program.run_fit(model, op,
                                program.initial_factor(11, i, n, k))
                time.sleep(0.05)
    red = tm.load(str(tmp_path))
    fits = scope.fit_spans(red)
    assert len(fits) == 3
    inner = {s.name for s in scope.program_spans(red)} - {scope.FIT}
    assert inner == {"nmf.prepare", "nmf.dispatch", "nmf.sync",
                     "nmf.result", "nmf.seed_stats"}
    split = scope.idle_by_span(red)
    assert set(split) <= inner | {scope.FIT, scope.OUTSIDE}
    assert sum(split.values()) == pytest.approx(tm.length(red.gaps(0)))
    # the sleeps between fits (0.15 s) are idle outside every nmf.fit, but
    # for the seed statistics, which the fit leaves running as it returns
    assert split[scope.OUTSIDE] > 0.075
    value = _metric("host_idle_ms_per_fit").read(
        _records(red, fits=3, iters=cell.config["iters"]))
    longest_fit = max(s.end - s.start for s in fits)
    assert 0 <= value <= 1e3 * longest_fit
    assert np.isfinite(value)


def _kernel(name, start, end):
    text = (f"%{name} = f32[6656,256]{{1,0:T(8,128)}} custom-call(%a), "
            f'custom_call_target="tpu_custom_call"')
    return tm.Op(name, start, end, end - start, 0, {}, text)


def test_halfstep_roofline_times_the_product_launches_only():
    """The launches carry the names the program gives its kernels
    (``bsr_spmm_gram``, ``bsr_spmm``, ``project_mask``, ``gram``); only
    the products count as the half-step's time."""
    from bench import work

    ops = [_kernel("bsr_spmm_gram.23", 0.0, 2.0),
           _kernel("bsr_spmm.4", 2.0, 2.5),
           _kernel("project_mask.11", 2.5, 3.5),
           _kernel("gram.2", 3.5, 3.75),
           tm.Op("fusion.1", 3.75, 4.0, 0.25, 0, {})]
    red = tm.Reduced(window=(0.0, 4.0), n_devices=1, ops=ops,
                     spans=[tm.Span("bench.window", 0.0, 4.0, 0)], host=[])
    rec = _records(red)
    per_fit = (work.iteration_halfsteps(6424, 1985, 1000, 5) * 75
               + work.halfstep(1000, 6424, 1985, 5))
    want = 100 * (per_fit * 2).roofline_s(rec.peaks) / 2.5
    assert _metric("halfstep_roofline").read(rec) == pytest.approx(want)
