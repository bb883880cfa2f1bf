"""Parity suite for the mesh-native execution layer.

The ``distributed`` solver is the shared ALS engine shard_mapped with a
``ShardedBackend`` — so its residual / error / nnz trajectories must track
the single-device ``enforced`` solver on identical data, and it must
honor ``tol`` / ``track_error`` / ``FitResult.converged`` exactly like the
local solvers.  Multi-device grids run in a subprocess with
``--xla_force_host_platform_device_count=4`` (2x2 and 4x1); the DistTopK
exactness check runs in-process on a 1x1 mesh.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(n, code):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


_PARITY_CODE = """
    import jax, jax.numpy as jnp, numpy as np, json
    from repro.nmf import EnforcedNMF, NMFConfig, Sparsity
    from repro.core import init_u0
    from repro.data import synthetic_journal_corpus
    from repro.sparse import to_dense
    a_sp, _ = synthetic_journal_corpus(n_terms=256, n_docs=128, n_journals=5, seed=7)
    a = jnp.asarray(to_dense(a_sp))
    u0 = init_u0(jax.random.PRNGKey(3), 256, 5)
    sparsity = Sparsity(t_u=55, t_v=300)
    ref = EnforcedNMF(NMFConfig(k=5, iters=15, solver="enforced",
                                sparsity=sparsity)).fit(a, u0=u0).result_
    rec = {"ref_err": np.asarray(ref.error).tolist(),
           "ref_res": np.asarray(ref.residual).tolist(),
           "ref_max_nnz": int(ref.max_nnz), "grids": {}}
    for shape in [(2, 2), (4, 1)]:
        r = EnforcedNMF(NMFConfig(k=5, iters=15, solver="distributed",
                                  mesh_shape=shape,
                                  sparsity=sparsity)).fit(a, u0=u0).result_
        rec["grids"]["%dx%d" % shape] = {
            "err": np.asarray(r.error).tolist(),
            "res": np.asarray(r.residual).tolist(),
            "nnz_u": np.asarray(r.nnz_u).tolist(),
            "nnz_v": np.asarray(r.nnz_v).tolist(),
            "max_nnz": int(r.max_nnz),
        }
    print(json.dumps(rec))
"""


def test_sharded_vs_single_device_trajectories():
    """2x2 and 4x1 grids track the single-device enforced solver within
    histogram-threshold tolerance, per iteration."""
    out = json.loads(
        run_with_devices(4, textwrap.dedent(_PARITY_CODE))
        .strip().splitlines()[-1])
    ref_err = np.asarray(out["ref_err"])
    ref_res = np.asarray(out["ref_res"])
    for grid, rec in out["grids"].items():
        err = np.asarray(rec["err"])
        res = np.asarray(rec["res"])
        assert err.shape == ref_err.shape, grid
        # error is a smooth global quantity: tight per-iteration agreement
        assert np.max(np.abs(err - ref_err)) < 0.02, grid
        # the residual is support-sensitive (one histogram-bin threshold tie
        # flips which entries enter ||U_i - U_{i-1}||), so compare loosely
        # per-iteration and require the same converged scale at the end
        assert np.max(np.abs(res - ref_res)) < 0.15, grid
        assert res[-1] < max(2 * ref_res[-1], 0.15), grid
        # nnz trajectories: global counts within histogram-bin ties of t
        assert all(n <= 55 + 6 for n in rec["nnz_u"]), grid
        assert all(n <= 300 + 6 for n in rec["nnz_v"]), grid
        # running max includes the dense initial guess (Fig. 6 semantics)
        assert rec["max_nnz"] == out["ref_max_nnz"] == 256 * 5, grid


def test_sharded_honors_tol_and_track_error():
    """Early stop and track_error=False ride through the shared engine on a
    real 2x2 mesh — the legacy fork silently ignored both."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.nmf import EnforcedNMF, NMFConfig, Sparsity
        from repro.core import init_u0
        from repro.data import synthetic_journal_corpus
        from repro.sparse import to_dense
        a_sp, _ = synthetic_journal_corpus(n_terms=128, n_docs=64, n_journals=4, seed=4)
        a = jnp.asarray(to_dense(a_sp))
        u0 = init_u0(jax.random.PRNGKey(1), 128, 4)
        m = EnforcedNMF(NMFConfig(k=4, iters=75, solver="distributed",
                                  mesh_shape=(2, 2), tol=1e-2,
                                  sparsity=Sparsity(t_u=40))).fit(a, u0=u0)
        r = m.result_
        m2 = EnforcedNMF(NMFConfig(k=4, iters=5, solver="distributed",
                                   mesh_shape=(2, 2), track_error=False,
                                   sparsity=Sparsity(t_u=40))).fit(a, u0=u0)
        print(json.dumps({
            "converged": bool(r.converged), "n_iter": int(r.n_iter),
            "final_res": float(r.final_residual),
            "hist_len": int(r.residual.shape[0]),
            "no_track_error": np.asarray(m2.result_.error).tolist(),
        }))
    """)
    out = json.loads(run_with_devices(4, code).strip().splitlines()[-1])
    assert out["converged"]
    assert out["n_iter"] < 75
    assert out["final_res"] <= 1e-2
    assert out["hist_len"] == out["n_iter"]
    assert out["no_track_error"] == [0.0] * 5


def test_sharded_max_nnz_is_running_max():
    """Regression (Fig. 6 semantics): the distributed solver used to report
    the *final* nnz(U)+nnz(V) as ``max_nnz``; through the shared engine it
    is the running max over the run, matching the single-device solver."""
    from repro.core import enforced_sparsity_nmf, init_u0
    from repro.data import synthetic_journal_corpus
    from repro.nmf import EnforcedNMF, NMFConfig, Sparsity
    from repro.sparse import to_dense

    a_sp, _ = synthetic_journal_corpus(n_terms=96, n_docs=48, n_journals=4,
                                       seed=5)
    a = jnp.asarray(to_dense(a_sp))
    u0 = init_u0(jax.random.PRNGKey(0), 96, 4)  # dense: nnz = 96*4
    model = EnforcedNMF(NMFConfig(k=4, iters=8, solver="distributed",
                                  sparsity=Sparsity(t_u=30, t_v=60))
                        ).fit(a, u0=u0)
    r = model.result_
    ref = enforced_sparsity_nmf(a, u0, t_u=30, t_v=60, iters=8)
    final_nnz = int(r.nnz_u[-1]) + int(r.nnz_v[-1])
    # the old bug: max_nnz == final nnz.  The dense initial guess dominates.
    assert int(r.max_nnz) == 96 * 4
    assert int(r.max_nnz) > final_nnz
    assert int(r.max_nnz) == int(ref.max_nnz)


def test_dist_topk_matches_exact_on_1x1_mesh():
    """DistTopK's histogram threshold on a 1x1 mesh keeps a superset of the
    exact top-t whose size is within histogram-bin resolution of t."""
    from jax.sharding import PartitionSpec as P

    from repro.core.topk import DistTopK, topk_project_exact

    x = jax.random.uniform(jax.random.PRNGKey(42), (64, 8))
    t = 100
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    fn = jax.shard_map(DistTopK(t, ("data",)), mesh=mesh,
                       in_specs=P(), out_specs=P(), check_vma=False)
    kept = fn(x)
    exact = topk_project_exact(x, t)
    kept_mask = np.asarray(kept != 0)
    exact_mask = np.asarray(exact != 0)
    # everything the exact oracle keeps survives the histogram threshold
    assert np.all(kept_mask[exact_mask])
    # and the overshoot is bounded by one-bin resolution ties
    n_kept = int(kept_mask.sum())
    assert t <= n_kept <= t + 5
    # kept values pass through unchanged
    np.testing.assert_array_equal(np.asarray(kept)[exact_mask],
                                  np.asarray(x)[exact_mask])


def test_dist_topk_is_engine_sparsifier():
    """DistTopK is hashable and rides the jit-static sparsify arguments of
    the shared engine (the whole point of making it first-class)."""
    from repro.core.topk import DistTopK

    a = DistTopK(10, ("data",))
    assert hash(a) == hash(DistTopK(10, ("data",)))
    assert a == DistTopK(10, ("data",))
    assert a != DistTopK(11, ("data",))


def test_make_sharded_als_uses_keyed_cache():
    """Engines built twice with the same (mesh, axes, sparsifiers, ...)
    config hand back the *same* shard_mapped and jitted callables from the
    module-level keyed cache — fresh ``make_sharded_als`` instances no
    longer recompile."""
    from repro.backend.sharded import make_sharded_als
    from repro.core.topk import DistTopK
    from repro.launch.mesh import make_nmf_mesh

    kw = dict(sparsify_u=DistTopK(30, ("data",)),
              sparsify_v=DistTopK(60, ("model",)), track_error=True)
    e1 = make_sharded_als(make_nmf_mesh(1, 1), ("data",), "model", **kw)
    e2 = make_sharded_als(make_nmf_mesh(1, 1), ("data",), "model", **kw)
    assert e1.shard_fn(5) is e2.shard_fn(5)
    assert e1.jitted(5) is e2.jitted(5)
    assert e1.jitted(5) is not e1.jitted(6)
    e3 = make_sharded_als(make_nmf_mesh(1, 1), ("data",), "model",
                          sparsify_u=DistTopK(31, ("data",)),
                          sparsify_v=DistTopK(60, ("model",)),
                          track_error=True)
    assert e3.jitted(5) is not e1.jitted(5)  # different config, new entry


def test_second_solve_distributed_fit_zero_recompiles():
    """Regression (ROADMAP "Per-fit shard_map recompile"): a second
    ``solve_distributed`` fit with an identical config adds no entry to the
    module-level jit cache and traces nothing new — the compiled executable
    is reused."""
    from repro.backend import sharded
    from repro.core import init_u0
    from repro.data import synthetic_journal_corpus
    from repro.nmf import EnforcedNMF, NMFConfig, Sparsity
    from repro.sparse import to_dense

    a_sp, _ = synthetic_journal_corpus(n_terms=64, n_docs=32, n_journals=3,
                                       seed=8)
    a = jnp.asarray(to_dense(a_sp))
    u0 = init_u0(jax.random.PRNGKey(6), 64, 3)
    cfg = NMFConfig(k=3, iters=4, solver="distributed",
                    sparsity=Sparsity(t_u=30, t_v=40))

    m1 = EnforcedNMF(cfg).fit(a, u0=u0)
    info_first = sharded._sharded_als_jit.cache_info()
    m2 = EnforcedNMF(cfg).fit(a, u0=u0)
    info_second = sharded._sharded_als_jit.cache_info()
    # no new jit wrapper was built (the keyed cache hit) ...
    assert info_second.misses == info_first.misses
    assert info_second.hits > info_first.hits
    # ... and that one wrapper holds a single compiled trace for the shapes
    # both fits used (jax counts traced executables per jit wrapper)
    from repro.core.topk import DistTopK
    from repro.launch.mesh import make_nmf_mesh

    jitted = sharded._sharded_als_jit(
        make_nmf_mesh(1, 1), ("data",), "model",
        DistTopK(30, ("data",)), DistTopK(40, ("model",)),
        True, "jnp-csr", 4)
    if hasattr(jitted, "_cache_size"):
        assert jitted._cache_size() == 1
    np.testing.assert_array_equal(np.asarray(m1.u_), np.asarray(m2.u_))


def test_columnwise_budget_scales_to_whole_factor_on_mesh():
    """Columnwise budgets are per *column*; the mesh engines' DistTopK
    thresholds the whole factor, so the budget must scale by k — a 1x1-mesh
    distributed fit with t_u=20/columnwise keeps ~20*k entries like the
    local path, not 20."""
    from repro.nmf.solvers import dist_budget
    from repro.data import synthetic_journal_corpus
    from repro.nmf import EnforcedNMF, NMFConfig, Sparsity
    from repro.sparse import to_dense

    sp = Sparsity(t_u=20, mode="columnwise")
    assert dist_budget(sp, 96, 4, "u") == 80
    assert dist_budget(Sparsity(t_u=30), 96, 4, "u") == 30  # global: as-is
    assert dist_budget(Sparsity(), 96, 4, "u") is None

    a_sp, _ = synthetic_journal_corpus(n_terms=96, n_docs=48, n_journals=4,
                                       seed=5)
    a = jnp.asarray(to_dense(a_sp))
    m = EnforcedNMF(NMFConfig(k=4, iters=6, solver="distributed",
                              sparsity=sp)).fit(a)
    nnz_u = int(jnp.sum(m.u_ != 0))
    assert 20 < nnz_u <= 20 * 4 + 6  # whole-factor total, not per-column t
