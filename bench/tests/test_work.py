"""The operations and bytes the rooflines read, and the peak table."""
import json

import pytest

from bench import work


def test_halfstep_counts_products_grams_and_single_reads():
    w = work.halfstep(nnz=1000, rows_out=30, cols_in=50, k=4)
    assert w.flops == 2 * 1000 * 4 + 2 * 50 * 4 * 4
    assert w.bytes == 1000 * 8 + (50 + 30) * 4 * 4 + 4 * 4 * 4


def test_iteration_is_both_orientations():
    it = work.iteration_halfsteps(n=50, m=30, nnz=1000, k=4)
    assert it == work.halfstep(1000, 30, 50, 4) + work.halfstep(1000, 50, 30, 4)
    assert it * 3 == work.Work(3 * it.flops, 3 * it.bytes)
    full = work.iteration_flops(50, 30, 1000, 4)
    assert full == it.flops + 2 * 80 * 16 + 2 * 1000 * 4 + 2 * 50 * 16


def test_roofline_is_the_larger_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.Work(1000, 50).roofline_s(peak) == 10.0   # compute bound
    assert work.Work(100, 50).roofline_s(peak) == 5.0     # bandwidth bound


def test_pubmed_iteration_is_megabytes_not_the_tile_grid():
    # PubMed journals at seed 0: 329,475 stored entries, k = 5
    it = work.iteration_halfsteps(20112, 7510, 329475, 5)
    assert 5e6 < it.bytes < 8e6
    v5e = work.load_peaks("TPU v5 lite")
    assert it.roofline_s(v5e) == pytest.approx(it.bytes / 819e9)


def test_peaks_are_keyed_by_device_kind(tmp_path):
    v5e = work.load_peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in json.loads(work.PEAKS_FILE.read_text())["source"]
    with pytest.raises(KeyError, match="no peaks"):
        work.load_peaks("cpu")
