"""The reduction from a profiler trace to busy time, idle gaps, time by
operation name and host-span labels."""
import time

import pytest

from bench import trace as tm


def test_union_merges_overlaps_and_clips():
    got = tm.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)], clip=(0.5, 10))
    assert got == [(0.5, 3), (5, 9)]


def test_complement_is_the_gaps_inside_the_window():
    assert tm.complement([(1, 2), (4, 5)], (0, 6)) == [(0, 1), (2, 4), (5, 6)]
    assert tm.complement([], (0, 1)) == [(0, 1)]
    assert tm.complement([(0, 1)], (0, 1)) == []


def test_self_time_subtracts_nested_events():
    # a while loop (0..10) holding two ops, then a lone op
    assert tm.self_times([(0, 10), (1, 3), (4, 8), (12, 13)]) == [4, 2, 4, 1]


def _reduced():
    ops = [tm.Op("fusion.1", 1.0, 2.0, 1.0, 0, {}),
           tm.Op("custom-call.7", 2.5, 4.0, 1.5, 0, {"hlo_category":
                                                        "custom-call"}),
           tm.Op("fusion.1", 4.0, 4.5, 0.5, 0, {}),
           tm.Op("fusion.1", 1.0, 3.0, 2.0, 1, {})]
    spans = [tm.Span("bench.window", 0.0, 6.0, 0),
             tm.Span("bench.fit", 0.5, 4.6, 1),
             tm.Span("bench.fetch", 4.6, 5.5, 1)]
    host = [tm.Span("PjitFunction(als_nmf)", 0.6, 0.9, 2),
            tm.Span("transfer", 4.7, 5.4, 2)]
    return tm.Reduced(window=(0.0, 6.0), n_devices=2, ops=ops, spans=spans,
                      host=host)


def test_busy_union_is_averaged_over_devices():
    red = _reduced()
    # device 0 busy 1 + 1.5 + 0.5 = 3 s, device 1 busy 2 s
    assert red.busy_s == pytest.approx(2.5)
    assert tm.idle_pct(red) == pytest.approx(100 * (1 - 2.5 / 6))


def test_gaps_and_their_labels():
    red = _reduced()
    assert red.gaps(0) == [(0.0, 1.0), (2.0, 2.5), (4.5, 6.0)]
    assert red.label((0.0, 1.0)) == "bench.fit > PjitFunction(als_nmf)"
    assert red.label((4.5, 6.0)) == "bench.fetch > transfer"
    assert red.label((5.8, 5.9)) == "bench.window"
    assert red.label((0.7, 0.8)) == "bench.fit > PjitFunction(als_nmf)"


def test_time_by_name_and_breakdown():
    red = _reduced()
    assert red.time_by_name() == {"fusion.1": 3.5, "custom-call.7": 1.5}
    assert [tm.is_kernel(op) for op in red.ops] == [False, True,
                                                          False, False]
    b = tm.breakdown(red)
    assert b["device_ops"][0] == ["fusion.1", 3.5]
    text = '%bsr_spmm_gram.2 = (f32[64,5]{1,0:T(8,128)}) custom-call(...)'
    assert tm.hlo_name(text) == "bsr_spmm_gram.2"
    assert tm.short_text(text) == "bsr_spmm_gram.2 = (f32[64,5]) custom-call(...)"
    assert b["idle_gaps"][0] == ["bench.fetch > transfer", 1.5]
    assert len(b["idle_gaps"]) == 3


def test_reduces_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tm.WINDOW_SPAN):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.fit"):
                    f(x).block_until_ready()
                time.sleep(0.02)
    red = tm.load(str(tmp_path))
    assert sum(s.name == "bench.fit" for s in red.spans) == 3
    assert red.window_s > 0.06
    names = red.time_by_name()
    assert any(n.startswith("dot") for n in names), names
    assert 0 < red.busy_s < red.window_s
    # the sleeps leave gaps of about 20 ms, outside every bench.fit
    longest = max(red.gaps(0), key=lambda g: g[1] - g[0])
    assert longest[1] - longest[0] > 0.015
    assert red.label(longest).startswith("bench.window")
    assert 0 < tm.idle_pct(red) < 100


def test_mfu_reads_the_device_span_of_the_traced_window():
    from bench import harness, work

    cell = harness.load_cell("reuters-21578.fit")
    rec = harness.Records(cell=cell, seed=0, setup={"nnz": 1000},
                          window={"fits": 2, "iters": 75},
                          peaks={"flops_per_s": 1e9})
    mfu = harness.load_module(harness.BENCH / "metrics" / "mfu.fit.py")
    assert mfu.read(rec) is None
    rec.trace = _reduced()
    flops = work.iteration_flops(6424, 1985, 1000, 5) * 2 * 75
    # the device ops run from 1.0 to 4.5 s of the 6 s window
    assert mfu.read(rec) == pytest.approx(100 * flops / (3.5 * 1e9))
