"""Autotune ledger: resolution order, legality pre-filter, fallback."""
import json

import numpy as np
import pytest

from repro.kernels import autotune
from repro.kernels.autotune import (
    DEFAULT_TILES, VMEM_BUDGET, TileConfig, autotune as run_autotune,
    fused_working_set, legal_candidates, load_ledger, resolve_tiles,
    shape_bucket, spmm_working_set, update_ledger,
)


@pytest.fixture
def ledger(tmp_path, monkeypatch):
    """Point the module at a throwaway ledger file and return its path."""
    path = tmp_path / "ledger.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_LEDGER", str(path))
    autotune._LEDGER_CACHE.clear()
    yield path
    autotune._LEDGER_CACHE.clear()


def _write(path, entries):
    path.write_text(json.dumps({"entries": entries}))
    autotune._LEDGER_CACHE.clear()


def test_shape_bucket_pow2_and_wildcards():
    assert shape_bucket(4096, 2048, 8) == "n4096-m2048-k8"
    assert shape_bucket(3000, 2048, None) == "n4096-m2048-k*"
    assert shape_bucket(129) == "n256-m*-k*"
    assert shape_bucket(1, 1, 1) == "n1-m1-k1"


def test_resolve_exact_bucket_hit(ledger):
    _write(ledger, {"testdev/n4096-m2048-k8":
                    {"bm": 256, "bk": 128, "kb": 256}})
    tiles = resolve_tiles(4096, 2048, 8, device="testdev")
    assert (tiles.bm, tiles.bk, tiles.kb) == (256, 128, 256)
    # unmeasured fields inherit the defaults
    assert tiles.gram_bm == DEFAULT_TILES.gram_bm


def test_resolve_bucket_fallback_order(ledger):
    _write(ledger, {
        "testdev/n4096-m2048-k*": {"bm": 256},
        "testdev/n4096-m*-k*": {"bm": 512},
    })
    # no exact (n,m,k) entry: the k* bucket wins over the m*-k* bucket
    assert resolve_tiles(4096, 2048, 8, device="testdev").bm == 256
    # no (n,m,*) entry either: fall through to (n,*,*)
    assert resolve_tiles(4096, 999, 8, device="testdev").bm == 512


def test_resolve_missing_falls_back_to_defaults(ledger):
    assert resolve_tiles(64, 64, 4, device="testdev") == DEFAULT_TILES
    # absent file entirely
    assert load_ledger() == {"entries": {}}


def test_resolve_ignores_other_devices(ledger):
    _write(ledger, {"othertpu/n4096-m2048-k8": {"bm": 512}})
    assert resolve_tiles(4096, 2048, 8, device="testdev") == DEFAULT_TILES


def test_ledger_cache_invalidated_on_update(ledger):
    assert resolve_tiles(4096, 2048, 8, device="d") == DEFAULT_TILES
    update_ledger("d/n4096-m2048-k8", {"bm": 256}, ledger)
    assert resolve_tiles(4096, 2048, 8, device="d").bm == 256


def test_legal_candidates_minor_dim_rule():
    # bk / kb must be 128-lane multiples: 64s are filtered out
    cands = [(128, 64, 128), (128, 128, 64), (128, 128, 128)]
    assert legal_candidates(4096, 2048, 8, candidates=cands) == [
        (128, 128, 128)]


def test_legal_candidates_vmem_budget():
    # a (4096, 4096, 4096) f32 triple double-buffers to 384 MiB >> 16 MiB
    big = (4096, 4096, 4096)
    assert legal_candidates(8192, 8192, 8, candidates=[big]) == []
    ok = (128, 128, 128)
    assert legal_candidates(8192, 8192, 8, candidates=[big, ok]) == [ok]


def test_legal_candidates_oversized_blocks_dropped():
    # block dims more than 2x the operand are pure padding
    assert (512, 128, 128) not in legal_candidates(128, 2048, 8)
    assert (128, 512, 128) not in legal_candidates(4096, 128, 8)


def test_legal_candidates_default_grid_all_legal():
    cands = legal_candidates(4096, 2048, 8)
    assert cands  # the committed defaults must be sweepable
    for bm, bk, kb in cands:
        assert bk % 128 == 0 and kb % 128 == 0
        assert 2 * spmm_working_set(bm, bk, kb) <= VMEM_BUDGET
        assert fused_working_set(bm, bk, 8, 2048) <= VMEM_BUDGET


def test_working_set_formulas():
    assert spmm_working_set(128, 128, 128) == 3 * 128 * 128 * 4
    # the resident (k, m) factor once, rows to 8 sublanes and columns to
    # whole bk blocks; S tiles and the lane-padded (bm, k) output block
    # double-buffered; the (k, k) f32 Gram padded to an (8, 128) tile
    assert fused_working_set(128, 128, 4, 300, slots=16) == (
        8 * 384 * 4 + 2 * 16 * 128 * 128 * 4 + 2 * 128 * 128 * 4
        + 2 * 8 * 128 * 4)


def test_autotune_off_tpu_returns_default_fallback():
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("sweeps for real on TPU")
    entry = run_autotune(256, 256, 8)
    assert entry["source"] == "default-fallback"
    assert entry["bm"] == DEFAULT_TILES.bm
    assert "fused_us" not in entry  # nothing was timed


def test_autotune_forced_sweep_records_winner(ledger):
    """force=True exercises the sweep plumbing off-TPU (interpret-mode
    wall time, not a tuning fact — but the entry shape is the contract)."""
    entry = run_autotune(128, 128, 4, density=0.3, repeats=1, force=True,
                         seed=0)
    assert entry["source"] == "autotune"
    assert entry["fused_us"] > 0 and entry["spmm_us"] > 0
    assert (entry["bm"], entry["bk"], entry["kb"]) in legal_candidates(
        128, 128, 4)
    path = update_ledger("testdev/" + shape_bucket(128, 128, 4), entry,
                         ledger)
    tiles = resolve_tiles(128, 128, 4, device="testdev")
    assert tiles.bm == entry["bm"]
    assert path == ledger


def test_kernel_entry_points_accept_none_tiles(ledger):
    """kb=None / bm=None resolve through the ledger, not hard-coded ints."""
    import jax.numpy as jnp
    from repro.kernels.bsr import bsr_from_dense
    from repro.kernels.bsr_spmm import bsr_spmm
    from repro.kernels.gram import gram

    rng = np.random.default_rng(0)
    a = rng.random((128, 256)).astype(np.float32)
    a[a < 0.7] = 0
    bsr = bsr_from_dense(jnp.asarray(a), bm=64, bk=64)
    u = jnp.asarray(rng.standard_normal((256, 4)).astype(np.float32))
    y = bsr_spmm(bsr, u, kb=None, interpret=True)
    np.testing.assert_allclose(np.asarray(y), a @ np.asarray(u),
                               rtol=1e-5, atol=1e-5)
    g = gram(u, bm=None, interpret=True)
    np.testing.assert_allclose(np.asarray(g), np.asarray(u.T @ u),
                               rtol=1e-5, atol=1e-5)


def test_committed_ledger_parses():
    """The package ledger (the committed file) must load and resolve."""
    from pathlib import Path
    path = Path(autotune.__file__).with_name("autotune_ledger.json")
    assert path.exists()
    data = json.loads(path.read_text())
    assert isinstance(data["entries"], dict)
    for key, entry in data["entries"].items():
        assert "/" in key
        assert entry.get("source") in ("autotune", "default-fallback")
        tiles = autotune._entry_to_tiles(entry)
        assert isinstance(tiles, TileConfig)
