"""Fault tolerance: the deterministic chaos suite.

Every failure mode the robustness layer claims to survive is injected here
through :mod:`repro.robustness.faults` and proven survivable — and, for
checkpoint/resume, proven *exact*: a fit killed mid-run and resumed must
converge to the same factors as the uninterrupted fit, locally and across
a mesh-shape change (elastic restart).  Process-kill realism (``os._exit``
after a checkpoint commits) runs in subprocesses; everything else injects
in-process for speed.
"""
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.data import synthetic_journal_corpus
from repro.data.corpus import (
    ChunkPackError, CorpusIntegrityError, Prefetcher, open_corpus,
    write_corpus,
)
from repro.nmf import EnforcedNMF, NMFConfig
from repro.robustness import (
    KILL_EXIT, CheckpointMismatchError, FitHealthError, faults,
)
from repro.robustness.snapshot import config_fingerprint

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class Boom(Exception):
    """In-process stand-in for a hard kill."""


def run_subprocess(code, devices=None, expect=0):
    env = dict(os.environ, PYTHONPATH=SRC)
    if devices is not None:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == expect, (out.returncode, out.stderr[-3000:])
    return out.stdout


@pytest.fixture(scope="module")
def docs():
    rng = np.random.default_rng(0)
    return np.abs(rng.normal(size=(16, 48))).astype(np.float32)


# ---------------------------------------------------------------------------
# the fault registry itself
# ---------------------------------------------------------------------------

def test_fault_fires_exactly_times_then_disarms():
    hits = 0
    with faults.inject("chunk-load", key=2, times=2):
        for _ in range(5):
            try:
                faults.fire("chunk-load", 2)
            except OSError:
                hits += 1
    assert hits == 2
    faults.fire("chunk-load", 2)  # uninstalled: no-op


def test_fault_wildcard_key_matches_everything():
    with faults.inject("chunk-load", times=3):
        for key in ("a", 1, None):
            with pytest.raises(OSError):
                faults.fire("chunk-load", key)
    assert not faults.active()


def test_poison_sets_nans_only_when_armed():
    x = np.ones((8, 4), np.float32)
    assert faults.poison("poison-step", 0, x) is x
    with faults.inject("poison-step", key=0):
        y = faults.poison("poison-step", 0, x)
    assert np.isnan(np.asarray(y)).any()
    assert not np.isnan(x).any()


def test_injected_exception_type_is_customizable():
    with faults.inject("kill", key=1, exc=Boom):
        with pytest.raises(Boom):
            faults.maybe_kill("kill", 1)


def test_pallas_kernel_failure_raises(monkeypatch):
    """A kernel that fails on the pallas-bsr path fails the fit: nothing
    re-runs it on another backend."""
    from repro.backend.pallas_bsr import PallasBsrBackend

    def refuse(self, a, v):
        raise Boom("kernel refused")

    monkeypatch.setattr(PallasBsrBackend, "matmul_with_gram", refuse)
    a = np.abs(np.random.default_rng(3).normal(size=(23, 41))).astype(
        np.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with pytest.raises(Boom, match="kernel refused"):
            EnforcedNMF(NMFConfig(k=3, iters=4, seed=1,
                                  backend="pallas-bsr")).fit(a)


# ---------------------------------------------------------------------------
# fingerprints: what a resume accepts and what it refuses
# ---------------------------------------------------------------------------

def test_config_fingerprint_pins_math_not_schedule():
    base = NMFConfig(k=4, iters=10, seed=1)
    assert config_fingerprint(base) == config_fingerprint(
        base.replace(iters=50, mesh_shape=(2, 2)))
    assert config_fingerprint(base) != config_fingerprint(base.replace(k=5))
    assert config_fingerprint(base) != config_fingerprint(base.replace(seed=2))


def test_resume_refuses_mismatched_config(docs, tmp_path):
    cfg = NMFConfig(k=3, iters=12, seed=1,
                    checkpoint_dir=str(tmp_path), checkpoint_every=4)
    EnforcedNMF(cfg).fit(docs)
    with pytest.raises(CheckpointMismatchError):
        EnforcedNMF(cfg.replace(seed=9)).fit(docs, resume=True)


def test_resume_refuses_different_data(docs, tmp_path):
    cfg = NMFConfig(k=3, iters=12, seed=1,
                    checkpoint_dir=str(tmp_path), checkpoint_every=4)
    EnforcedNMF(cfg).fit(docs)
    other = docs + 1.0
    with pytest.raises(CheckpointMismatchError):
        EnforcedNMF(cfg).fit(other, resume=True)


# ---------------------------------------------------------------------------
# kill-then-resume parity, engine by engine
# ---------------------------------------------------------------------------

def _kill_resume_parity(a, cfg, kill_key):
    """Fit uninterrupted; fit again with a kill injected mid-run; resume;
    the resumed factors must match the uninterrupted ones."""
    ref = EnforcedNMF(cfg.replace(checkpoint_dir=None, resume=False)).fit(a)
    with faults.inject("kill", key=kill_key, exc=Boom):
        with pytest.raises(Boom):
            EnforcedNMF(cfg).fit(a)
    res = EnforcedNMF(cfg).fit(a, resume=True)
    np.testing.assert_allclose(np.asarray(ref.u_), np.asarray(res.u_),
                               atol=1e-5)
    assert res.result_.n_iter == ref.result_.n_iter
    return ref, res


def test_batch_kill_resume_parity(docs, tmp_path):
    cfg = NMFConfig(k=3, iters=20, seed=1,
                    checkpoint_dir=str(tmp_path), checkpoint_every=5)
    _kill_resume_parity(docs, cfg, kill_key=10)


def test_sequential_kill_resume_parity(docs, tmp_path):
    cfg = NMFConfig(k=6, iters=8, seed=1, solver="sequential",
                    checkpoint_dir=str(tmp_path), checkpoint_every=2)
    ref, res = _kill_resume_parity(docs, cfg, kill_key=4)
    assert np.asarray(res.result_.residual).shape == \
        np.asarray(ref.result_.residual).shape


def test_streaming_resident_kill_resume_parity(docs, tmp_path):
    cfg = NMFConfig(k=3, iters=6, seed=1, solver="streaming", chunk_docs=8,
                    checkpoint_dir=str(tmp_path), checkpoint_every=2)
    _kill_resume_parity(docs, cfg, kill_key=4)


def test_streaming_corpus_kill_resume_parity(tmp_path):
    a_sp, _ = synthetic_journal_corpus(n_terms=48, n_docs=40,
                                       n_journals=3, seed=5)
    corpus = write_corpus(a_sp, tmp_path / "corpus", chunk_docs=8)
    cfg = NMFConfig(k=3, iters=6, seed=1, solver="streaming", chunk_docs=8,
                    checkpoint_dir=str(tmp_path / "ckpt"),
                    checkpoint_every=2)
    _kill_resume_parity(str(corpus), cfg, kill_key=2)


def test_resume_with_exhausted_checkpoint_raises(docs, tmp_path):
    cfg = NMFConfig(k=3, iters=10, seed=1,
                    checkpoint_dir=str(tmp_path), checkpoint_every=5)
    EnforcedNMF(cfg).fit(docs)
    with pytest.raises(ValueError, match="raise iters"):
        EnforcedNMF(cfg.replace(iters=5)).fit(docs, resume=True)


# ---------------------------------------------------------------------------
# fit health: NaN injection -> rollback (or raise)
# ---------------------------------------------------------------------------

def test_batch_nan_rollback_recovers(docs, tmp_path):
    cfg = NMFConfig(k=3, iters=20, seed=1,
                    checkpoint_dir=str(tmp_path), checkpoint_every=5)
    with faults.inject("poison-step", key=10):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            model = EnforcedNMF(cfg).fit(docs)
    assert np.isfinite(np.asarray(model.u_)).all()
    assert any("rolling back" in str(x.message) for x in w)
    assert model.result_.n_iter == 20


def test_streaming_nan_rollback_recovers(docs, tmp_path):
    cfg = NMFConfig(k=3, iters=6, seed=1, solver="streaming", chunk_docs=8,
                    checkpoint_dir=str(tmp_path), checkpoint_every=2)
    with faults.inject("poison-step", key=3):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            model = EnforcedNMF(cfg).fit(docs)
    assert np.isfinite(np.asarray(model.u_)).all()
    assert any("rolling back" in str(x.message) for x in w)


def test_on_unhealthy_raise_surfaces_the_failure(docs, tmp_path):
    cfg = NMFConfig(k=3, iters=20, seed=1, on_unhealthy="raise",
                    checkpoint_dir=str(tmp_path), checkpoint_every=5)
    with faults.inject("poison-step", key=10):
        with pytest.raises(FitHealthError):
            EnforcedNMF(cfg).fit(docs)


def test_rollback_budget_exhaustion_raises(docs, tmp_path):
    # the poison re-fires on every replay, so rollbacks can never win
    cfg = NMFConfig(k=3, iters=20, seed=1, max_rollbacks=2,
                    checkpoint_dir=str(tmp_path), checkpoint_every=5)
    with faults.inject("poison-step", key=10, times=10):
        with pytest.raises(FitHealthError, match="gave up"):
            EnforcedNMF(cfg).fit(docs)


def test_health_monitor_reports_without_checkpointing(docs):
    # no checkpoint_dir: on_unhealthy="raise" still guards the fit
    cfg = NMFConfig(k=3, iters=20, seed=1, on_unhealthy="raise")
    with faults.inject("poison-step", key=0):
        with pytest.raises(FitHealthError):
            EnforcedNMF(cfg).fit(docs)


# ---------------------------------------------------------------------------
# corpus integrity + the data-path retry/skip ladder
# ---------------------------------------------------------------------------

def test_corrupted_shard_detected_on_load(tmp_path):
    a_sp, _ = synthetic_journal_corpus(n_terms=48, n_docs=40,
                                       n_journals=3, seed=5)
    out = write_corpus(a_sp, tmp_path / "c", chunk_docs=8)
    shard = out / "shard-00001.values.npy"
    raw = bytearray(shard.read_bytes())
    raw[-1] ^= 0xFF
    shard.write_bytes(bytes(raw))
    corpus = open_corpus(out)
    corpus.load(0)  # intact shard loads fine
    with pytest.raises(CorpusIntegrityError, match="shard 1"):
        corpus.load(1)


def test_injected_shard_corruption_fails_the_fit(tmp_path):
    a_sp, _ = synthetic_journal_corpus(n_terms=48, n_docs=40,
                                       n_journals=3, seed=5)
    out = write_corpus(a_sp, tmp_path / "c", chunk_docs=8)
    cfg = NMFConfig(k=3, iters=4, seed=1, solver="streaming", chunk_docs=8)
    with faults.inject("corrupt-shard", key=1):
        with pytest.raises(ChunkPackError) as ei:
            EnforcedNMF(cfg).fit(str(out))
    assert isinstance(ei.value.__cause__, CorpusIntegrityError)


def test_skip_hatch_survives_a_corrupt_shard(tmp_path, monkeypatch):
    a_sp, _ = synthetic_journal_corpus(n_terms=48, n_docs=40,
                                       n_journals=3, seed=5)
    out = write_corpus(a_sp, tmp_path / "c", chunk_docs=8)
    monkeypatch.setenv("REPRO_STREAM_SKIP_BAD_CHUNKS", "1")
    cfg = NMFConfig(k=3, iters=4, seed=1, solver="streaming", chunk_docs=8)
    with faults.inject("corrupt-shard", key=1):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            model = EnforcedNMF(cfg).fit(str(out))
    assert np.isfinite(np.asarray(model.u_)).all()
    assert any("skipping" in str(x.message) for x in w)


def test_transient_io_error_is_retried_to_success(tmp_path):
    a_sp, _ = synthetic_journal_corpus(n_terms=48, n_docs=40,
                                       n_journals=3, seed=5)
    out = write_corpus(a_sp, tmp_path / "c", chunk_docs=8)
    cfg = NMFConfig(k=3, iters=4, seed=1, solver="streaming", chunk_docs=8)
    ref = EnforcedNMF(cfg).fit(str(out))
    # chunk 2 fails twice (within the default retry budget), then succeeds
    with faults.inject("chunk-load", key=2, times=2):
        model = EnforcedNMF(cfg).fit(str(out))
    np.testing.assert_allclose(np.asarray(ref.u_), np.asarray(model.u_))


def test_chunk_pack_error_carries_context():
    def pack(i):
        raise OSError("mount gone")
    pf = Prefetcher([7, 8], pack, retries=1, retry_backoff=0.001)
    with pytest.raises(ChunkPackError) as ei:
        list(pf)
    assert ei.value.item == 7 and ei.value.index == 0
    assert isinstance(ei.value.__cause__, OSError)
    assert pf.stats["retries"] == 1


def test_prefetch_worker_silent_death_watchdog():
    with faults.inject("prefetch-worker", key=1):
        pf = Prefetcher([0, 1, 2], lambda i: i, depth=2)
        it = iter(pf)
        assert next(it) == 0
        with pytest.raises(RuntimeError, match="died without reporting"):
            list(it)


def test_consumer_raise_stops_the_worker():
    def pack(i):
        if i == 1:
            raise ValueError("bad chunk")
        return i
    pf = Prefetcher(range(10), pack, retries=0)
    with pytest.raises(ChunkPackError):
        list(pf)
    assert pf._stop.is_set()
    pf._thread.join(timeout=5.0)
    assert not pf._thread.is_alive()


# ---------------------------------------------------------------------------
# process-kill realism: os._exit after a checkpoint commit, then resume
# ---------------------------------------------------------------------------

_KILL_FIT = """
import numpy as np
from repro.nmf import EnforcedNMF, NMFConfig
from repro.robustness import faults

rng = np.random.default_rng(0)
a = np.abs(rng.normal(size=(16, 48))).astype(np.float32)
cfg = NMFConfig(k=3, iters=20, seed=1, checkpoint_dir={d!r},
                checkpoint_every=5{extra})
with faults.inject("kill", key=10):
    EnforcedNMF(cfg).fit(a)
raise SystemExit("kill fault never fired")
"""

_RESUME_FIT = """
import numpy as np
from repro.nmf import EnforcedNMF, NMFConfig

rng = np.random.default_rng(0)
a = np.abs(rng.normal(size=(16, 48))).astype(np.float32)
cfg = NMFConfig(k=3, iters=20, seed=1, checkpoint_dir={d!r},
                checkpoint_every=5{extra})
model = EnforcedNMF(cfg).fit(a, resume=True)
ref = EnforcedNMF(NMFConfig(k=3, iters=20, seed=1)).fit(a)
assert np.allclose(np.asarray(ref.u_), np.asarray(model.u_), atol=1e-5), \\
    "resumed factors diverged from the uninterrupted fit"
print("PARITY-OK")
"""


def test_subprocess_kill_exits_with_kill_code_and_resumes(tmp_path):
    d = str(tmp_path)
    run_subprocess(_KILL_FIT.format(d=d, extra=""), expect=KILL_EXIT)
    out = run_subprocess(_RESUME_FIT.format(d=d, extra=""))
    assert "PARITY-OK" in out


def test_subprocess_mesh_kill_then_elastic_resume(tmp_path):
    """Killed on a 2x2 mesh, resumed on 4x1: checkpoints are saved gathered
    and restored against the live mesh, so the shape may change."""
    d = str(tmp_path)
    run_subprocess(_KILL_FIT.format(d=d, extra=", mesh_shape=(2, 2)"),
                   devices=4, expect=KILL_EXIT)
    out = run_subprocess(_RESUME_FIT.format(d=d, extra=", mesh_shape=(4, 1)"),
                         devices=4)
    assert "PARITY-OK" in out


# ---------------------------------------------------------------------------
# serving: malformed requests 400, refresh is transactional
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def topic_model(docs):
    return EnforcedNMF(NMFConfig(k=4, iters=10, seed=1)).fit(docs)


def test_topic_server_rejects_malformed_docs_not_the_tick(topic_model):
    from repro.serving.topics import TopicRequest, TopicServer
    srv = TopicServer(topic_model, max_batch=8)
    srv.submit(TopicRequest(rid=0, terms=[(2, 1.0), (5, 2.0)]))
    srv.submit(TopicRequest(rid=1, terms=[(3, float("nan"))]))
    srv.submit(TopicRequest(rid=2, terms="not-pairs"))
    srv.submit(TopicRequest(rid=3, terms=[(999, 1.0)]))   # all out of vocab
    srv.submit(TopicRequest(rid=4, terms=[(7, 1.5)]))
    done = {r.rid: r for r in srv.run_until_drained()}
    assert sorted(done) == [0, 1, 2, 3, 4]
    assert done[0].error is None and done[0].topics
    assert done[4].error is None and done[4].topics
    for rid in (1, 2, 3):
        assert done[rid].error is not None and done[rid].topics == []
    assert srv.rejected == 3
    # rejected documents must not leak into the fold-in buffer
    assert len(srv._refresh_buf) == 2


def test_topic_refresh_rolls_back_on_unhealthy_update(topic_model):
    from repro.serving.topics import TopicRequest, TopicServer
    srv = TopicServer(topic_model, max_batch=8)
    srv.submit(TopicRequest(rid=0, terms=[(2, 1.0)]))
    srv.run_until_drained()
    u_before = np.asarray(topic_model.u_)
    orig = topic_model.partial_fit

    def poisoned_fit(*args, **kwargs):
        orig(*args, **kwargs)
        topic_model.u_ = topic_model.u_ * jnp.nan
        topic_model.health_ = jnp.int32(0)

    topic_model.partial_fit = poisoned_fit
    try:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert srv.refresh() == 0
    finally:
        topic_model.partial_fit = orig
    assert srv.refresh_failures == 1
    assert any("rolled back" in str(x.message) for x in w)
    np.testing.assert_allclose(np.asarray(topic_model.u_), u_before)
    assert len(srv._refresh_buf) == 1   # re-buffered for the next attempt
    assert srv.refresh() == 1           # and the retry lands
    assert int(topic_model.health_) < 0
