"""Distributed NMF tests.  Multi-device cases run in a
subprocess with --xla_force_host_platform_device_count (the main process
keeps 1 device so other tests see the default config).

The distributed path is the *unified* ALS engine shard_mapped via
``make_sharded_als`` — there is no separate distributed solver loop; the
deeper parity suite lives in tests/test_sharded_engine.py."""
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


def test_dist_als_matches_single_device():
    """Sharded unified engine on a 4x2 mesh ~= single-device oracle."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.backend.sharded import make_sharded_als
        from repro.core.distributed import distribute_csr
        from repro.core.topk import DistTopK
        from repro.core import init_u0, enforced_sparsity_nmf
        from repro.data import synthetic_journal_corpus
        from repro.sparse import to_dense
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        a_sp, _ = synthetic_journal_corpus(n_terms=256, n_docs=128, n_journals=5, seed=1)
        a = np.asarray(to_dense(a_sp))
        dist = distribute_csr(a, 4, 2)
        u0 = np.asarray(init_u0(jax.random.PRNGKey(2), 256, 5))
        with jax.set_mesh(mesh):
            run = make_sharded_als(mesh, ("data",), "model",
                                   sparsify_u=DistTopK(55, ("data",)),
                                   sparsify_v=DistTopK(300, ("model",)))
            a_sh = NamedSharding(mesh, P(("data",), "model", None, None))
            dist = jax.tree_util.tree_map(lambda x: jax.device_put(x, a_sh), dist)
            u0d = jax.device_put(u0, NamedSharding(mesh, P(("data",), None)))
            res = run(dist, u0d, 20)
        ref = enforced_sparsity_nmf(jnp.asarray(a), jnp.asarray(u0),
                                    t_u=55, t_v=300, iters=20, exact=True)
        print(json.dumps({
            "dist_err": float(res.error[-1]), "ref_err": float(ref.error[-1]),
            "nnz_u": int(jnp.sum(res.u != 0)),
            "nnz_u_trace": int(res.nnz_u[-1]),
            "max_nnz": int(res.max_nnz), "ref_max_nnz": int(ref.max_nnz),
        }))
    """)
    out = json.loads(run_with_devices(8, code).strip().splitlines()[-1])
    assert abs(out["dist_err"] - out["ref_err"]) < 0.02
    assert out["nnz_u"] <= 60
    # the per-iteration nnz trace is the same global count
    assert out["nnz_u_trace"] == out["nnz_u"]
    # running max over iterations (Fig. 6), not the final count
    assert out["max_nnz"] == out["ref_max_nnz"]


def test_dist_als_multipod_axes():
    """The same engine accepts a (pod, data, model) mesh — rows over
    ('pod','data') — proving the pod axis shards."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.backend.sharded import make_sharded_als
        from repro.core.distributed import distribute_csr
        from repro.core.topk import DistTopK
        from repro.core import init_u0
        from repro.data import synthetic_journal_corpus
        from repro.sparse import to_dense
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        a_sp, _ = synthetic_journal_corpus(n_terms=128, n_docs=64, n_journals=4, seed=2)
        a = np.asarray(to_dense(a_sp))
        dist = distribute_csr(a, 4, 2)
        u0 = np.asarray(init_u0(jax.random.PRNGKey(2), 128, 4))
        with jax.set_mesh(mesh):
            run = make_sharded_als(mesh, ("pod", "data"), "model",
                                   sparsify_u=DistTopK(40, ("pod", "data")),
                                   sparsify_v=DistTopK(100, ("model",)))
            a_sh = NamedSharding(mesh, P(("pod", "data"), "model", None, None))
            dist = jax.tree_util.tree_map(lambda x: jax.device_put(x, a_sh), dist)
            u0d = jax.device_put(u0, NamedSharding(mesh, P(("pod", "data"), None)))
            res = run(dist, u0d, 10)
        print(json.dumps({"err": float(res.error[-1]),
                          "finite": bool(jnp.isfinite(res.error[-1]))}))
    """)
    out = json.loads(run_with_devices(8, code).strip().splitlines()[-1])
    assert out["finite"] and out["err"] < 1.0


def test_single_device_shard_map_paths():
    """The sharded engine code path also runs on a 1x1 mesh in-process."""
    from repro.backend.sharded import make_sharded_als
    from repro.core import init_u0
    from repro.core.distributed import distribute_csr
    from repro.core.topk import DistTopK
    from repro.data import synthetic_journal_corpus
    from repro.sparse import to_dense
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    a_sp, _ = synthetic_journal_corpus(n_terms=64, n_docs=32, n_journals=4, seed=3)
    a = np.asarray(to_dense(a_sp))
    dist = distribute_csr(a, 1, 1)
    u0 = init_u0(jax.random.PRNGKey(0), 64, 4)
    with jax.set_mesh(mesh):
        run = make_sharded_als(mesh, ("data",), "model",
                               sparsify_u=DistTopK(30, ("data",)))
        res = run(dist, u0, 8)
    assert jnp.isfinite(res.error[-1])
    assert res.residual.shape == (8,)
    assert int(res.nnz_u[-1]) <= 30 + 4  # histogram-bin tie tolerance
