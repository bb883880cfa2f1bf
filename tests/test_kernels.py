"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.bsr import bsr_from_dense, bsr_to_dense, bsr_transpose
from repro.kernels.bsr_spmm import bsr_spmm
from repro.kernels.project_mask import project_mask
from repro.kernels.gram import gram


def _rand_sparse(rng, n, m, density=0.05, dtype=np.float32):
    a = rng.random((n, m)).astype(dtype)
    a[rng.random((n, m)) > density] = 0
    return a


@pytest.mark.parametrize("n,m,k", [(128, 128, 8), (300, 200, 40),
                                   (64, 512, 128), (257, 129, 33)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_bsr_spmm_shapes(n, m, k, dtype):
    rng = np.random.default_rng(n + m + k)
    a = _rand_sparse(rng, n, m, dtype=dtype)
    bsr = bsr_from_dense(a, bm=64, bk=64)
    u = rng.standard_normal((m, k)).astype(dtype)
    out = bsr_spmm(bsr, jnp.asarray(u), interpret=True)
    expect = ref.bsr_spmm_ref(bsr, jnp.asarray(u))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_bsr_roundtrip_and_transpose():
    rng = np.random.default_rng(0)
    a = _rand_sparse(rng, 200, 150)
    bsr = bsr_from_dense(a, bm=32, bk=32)
    np.testing.assert_allclose(np.asarray(bsr_to_dense(bsr)), a)
    at = bsr_transpose(bsr)
    np.testing.assert_allclose(np.asarray(bsr_to_dense(at)), a.T)


@pytest.mark.parametrize("shape", [(100, 37), (256, 256), (17, 512), (1, 1)])
@pytest.mark.parametrize("tau", [0.0, 0.5, 2.0])
def test_project_mask(shape, tau):
    x = jax.random.normal(jax.random.PRNGKey(7), shape)
    out = project_mask(x, jnp.float32(tau), interpret=True)
    expect = ref.project_mask_ref(x, jnp.float32(tau))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("n,k", [(1000, 16), (513, 40), (64, 5), (2048, 128)])
def test_gram(n, k):
    u = jax.random.normal(jax.random.PRNGKey(n), (n, k))
    out = gram(u, interpret=True)
    expect = ref.gram_ref(u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-3)


def test_spmm_bf16():
    rng = np.random.default_rng(3)
    a = _rand_sparse(rng, 128, 128)
    bsr = bsr_from_dense(a.astype(np.float32), bm=64, bk=64)
    bsr = type(bsr)(bsr.tiles.astype(jnp.bfloat16), bsr.block_cols, bsr.shape)
    u = jnp.asarray(rng.standard_normal((128, 16)), dtype=jnp.bfloat16)
    out = bsr_spmm(bsr, u, interpret=True)
    expect = bsr_to_dense(bsr).astype(jnp.float32) @ u.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(expect), rtol=5e-2, atol=1e-1)


# ---------------------------------------------------------------------------
# Fused spmm + gram kernel: vs the separate launches it replaces
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,k", [(128, 128, 8), (300, 200, 40),
                                   (64, 512, 128), (257, 129, 33)])
def test_fused_spmm_gram_vs_separate(n, m, k):
    """Product bit-identical to bsr_spmm (same tile stream, same
    accumulation order); Gram agrees with the oracle to f32 roundoff."""
    from repro.kernels.fused import bsr_spmm_gram
    rng = np.random.default_rng(n + m + k)
    a = _rand_sparse(rng, n, m)
    bsr = bsr_from_dense(a, bm=64, bk=64)
    u = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    y_sep = bsr_spmm(bsr, u, interpret=True)
    y_f, g_f = bsr_spmm_gram(bsr, u, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_sep))
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(u.T @ u),
                               rtol=1e-5, atol=1e-4)


def test_fused_spmm_gram_t_orientation():
    from repro.kernels.bsr import bsr_operand
    from repro.kernels.bsr_spmm import bsr_spmm_t
    from repro.kernels.fused import bsr_spmm_gram_t
    rng = np.random.default_rng(11)
    a = _rand_sparse(rng, 257, 129)
    op = bsr_operand(jnp.asarray(a), bm=64, bk=64)
    u = jnp.asarray(rng.standard_normal((257, 5)).astype(np.float32))
    y_sep = bsr_spmm_t(op, u, interpret=True)
    y_f, g_f = bsr_spmm_gram_t(op, u, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_sep))
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(u.T @ u),
                               rtol=1e-5, atol=1e-4)


def test_fused_spmm_gram_unreferenced_blocks():
    """Column blocks no occupied tile references must still contribute to
    the Gram: it is taken over the whole resident factor, not over the
    slabs the tiles reference."""
    from repro.kernels.fused import bsr_spmm_gram
    rng = np.random.default_rng(4)
    a = np.zeros((128, 256), np.float32)
    a[:64, :64] = rng.random((64, 64))  # only column-block 0 is referenced
    bsr = bsr_from_dense(a, bm=64, bk=64)
    u = jnp.asarray(rng.standard_normal((256, 7)).astype(np.float32))
    y_f, g_f = bsr_spmm_gram(bsr, u, interpret=True)
    np.testing.assert_allclose(np.asarray(y_f), a @ np.asarray(u),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(u.T @ u),
                               rtol=1e-5, atol=1e-4)


def test_fused_spmm_gram_all_zero_operand():
    """Degenerate all-padding operand: product is zero, Gram is still the
    full U^T U of the resident factor."""
    from repro.kernels.fused import bsr_spmm_gram
    rng = np.random.default_rng(5)
    a = np.zeros((100, 180), np.float32)
    bsr = bsr_from_dense(a, bm=64, bk=64)
    u = jnp.asarray(rng.standard_normal((180, 4)).astype(np.float32))
    y_f, g_f = bsr_spmm_gram(bsr, u, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_f), np.zeros((100, 4)))
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(u.T @ u),
                               rtol=1e-5, atol=1e-4)


def _check_fused_against_separate(bsr, u):
    """Fused product bitwise equal to bsr_spmm, Gram to f32 roundoff of
    U^T U."""
    from repro.kernels.fused import bsr_spmm_gram
    y_sep = bsr_spmm(bsr, u, interpret=True)
    y_f, g_f = bsr_spmm_gram(bsr, u, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_sep))
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(u.T @ u),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("bcap,slots", [(59, 15), (3, 3)],
                         ids=["prime_bcap", "bcap_below_step_target"])
def test_fused_spmm_gram_slots_per_step(bcap, slots):
    """128 x 128 f32 tiles aim at 16 a grid step.  PubMed's prime bcap of
    59 takes 4 steps of 15 slots, the last running one slot past bcap,
    which the kernel skips; a bcap below the target is one step of bcap
    slots."""
    from repro.kernels.autotune import FUSED_STEP_BYTES, fused_slots
    rng = np.random.default_rng(bcap)
    n, m, k = 200, bcap * 128, 5
    a = _rand_sparse(rng, n, m)
    bsr = bsr_from_dense(a, bm=128, bk=128)
    assert bsr.bcap == bcap
    assert fused_slots(128, 128, k, m, bcap) == slots
    if bcap % slots:
        assert bcap > slots  # some step runs past bcap
    else:
        assert slots == bcap < FUSED_STEP_BYTES // (128 * 128 * 4)
    u = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    _check_fused_against_separate(bsr, u)


def test_fused_spmm_gram_row_block_launches(monkeypatch):
    """A grid whose block_cols table overflows the SMEM budget runs as
    several row-block launches; their rows concatenate to the same
    product, and the Gram comes from the first launch alone."""
    from repro.kernels import bsr_spmm as bsr_spmm_mod
    from repro.kernels.fused import bsr_spmm_gram
    monkeypatch.setattr(bsr_spmm_mod, "SMEM_PREFETCH_BUDGET", 1)
    rng = np.random.default_rng(12)
    n, m, k = 20 * 64, 7 * 64, 5
    a = _rand_sparse(rng, n, m)
    bsr = bsr_from_dense(a, bm=64, bk=64)
    chunks = bsr_spmm_mod.row_block_chunks(bsr.nrb, bsr.bcap, 1)
    assert chunks == [(0, 8), (8, 16), (16, 20)]
    u = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    jaxpr = jax.make_jaxpr(
        lambda b, x: bsr_spmm_gram(b, x, interpret=True))(bsr, u)
    assert str(jaxpr).count("pallas_call") == len(chunks)
    _check_fused_against_separate(bsr, u)


def test_fused_spmm_gram_bf16():
    from repro.kernels.fused import bsr_spmm_gram
    rng = np.random.default_rng(6)
    a = _rand_sparse(rng, 128, 128)
    bsr = bsr_from_dense(a.astype(np.float32), bm=64, bk=64)
    bsr = type(bsr)(bsr.tiles.astype(jnp.bfloat16), bsr.block_cols, bsr.shape)
    u = jnp.asarray(rng.standard_normal((128, 16)), dtype=jnp.bfloat16)
    y_sep = bsr_spmm(bsr, u, interpret=True)
    y_f, g_f = bsr_spmm_gram(bsr, u, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_f, dtype=np.float32),
                                  np.asarray(y_sep, dtype=np.float32))
    uf = np.asarray(u, dtype=np.float32)
    assert g_f.dtype == jnp.float32  # gram accumulates in f32 regardless
    np.testing.assert_allclose(np.asarray(g_f), uf.T @ uf,
                               rtol=5e-2, atol=1e-1)


def test_fused_backend_matches_unfused_end_to_end():
    """pallas-bsr (fused half-steps) vs pallas-bsr-unfused (separate
    launches) through the full ALS engine: factors within 1e-4."""
    from repro.backend import get_backend
    from repro.core.nmf import als_nmf, init_u0
    rng = np.random.default_rng(7)
    a = _rand_sparse(rng, 192, 160, density=0.1)
    u0 = init_u0(jax.random.PRNGKey(0), 192, 4)
    results = {}
    for name in ("pallas-bsr", "pallas-bsr-unfused"):
        be = get_backend(name)
        op = be.prepare(jnp.asarray(a))
        results[name] = als_nmf(op, u0, iters=5, backend=name)
    np.testing.assert_allclose(np.asarray(results["pallas-bsr"].u),
                               np.asarray(results["pallas-bsr-unfused"].u),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(results["pallas-bsr"].v),
                               np.asarray(results["pallas-bsr-unfused"].v),
                               rtol=1e-4, atol=1e-4)
