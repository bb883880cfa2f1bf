"""Sharded-engine benchmark: the unified ALS engine on 1x1 vs 2x2 meshes,
swept over the inner per-shard backends (jnp-csr CSR shards vs pallas-bsr
per-device MXU tile grids).

Measures what the mesh-native execution layer costs and buys — shard
ingest (``engine.distribute``: ``distribute_csr_from_padded`` or
``distribute_bsr``), compile, and the warm solve loop — on forced host
devices, plus the single-device ``enforced`` solver as the no-shard_map
reference.  Writes ``BENCH_sharded.json`` so the collective-overhead and
per-inner-backend trajectories have data on every push.

On CPU the forced host devices share the same cores, so 2x2 is *not*
expected to be faster, and the Pallas kernels execute in interpret mode
(numerics validation, not a speed signal) — the numbers that matter here
are the shard_map / psum overhead over the 1x1 run and the per-backend
ingest cost (on a real pod the same code paths scale the paper's Fig. 10
workload with the MXU kernels compiled).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python benchmarks/bench_sharded.py --smoke
"""
from __future__ import annotations

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import argparse
import json
import platform
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _timed(fn, repeats=3):
    t0 = time.perf_counter()
    for _ in range(repeats):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / repeats


def bench(n: int, m: int, k: int, iters: int, grids, inners, seed: int = 0):
    from jax.sharding import NamedSharding

    from repro.backend.sharded import make_sharded_als
    from repro.core import init_u0
    from repro.core.topk import DistTopK
    from repro.data import synthetic_journal_corpus
    from repro.launch.mesh import make_nmf_mesh
    from repro.nmf import EnforcedNMF, NMFConfig, Sparsity

    a_sp, _ = synthetic_journal_corpus(n_terms=n, n_docs=m, n_journals=5,
                                       seed=seed)
    u0 = init_u0(jax.random.PRNGKey(2), n, k)
    t_u = max(n * k // 50, k)
    t_v = max(m * k // 50, k)

    results = {}
    # single-device reference: same engine, identity reductions
    cfg = NMFConfig(k=k, iters=iters, solver="enforced",
                    sparsity=Sparsity(t_u=t_u, t_v=t_v), track_error=False)
    model = EnforcedNMF(cfg)
    t0 = time.perf_counter()
    model.fit(a_sp, u0=u0)
    jax.block_until_ready(model.u_)
    results["enforced-1dev"] = {
        "fit_s": time.perf_counter() - t0,
        "final_error": float(model.score(a_sp)),
    }

    for r, c in grids:
        if len(jax.devices()) < r * c or n % r or m % c:
            for inner in inners:
                results[f"{r}x{c}[{inner}]"] = {"status": "skipped"}
            continue
        mesh = make_nmf_mesh(r, c)
        for inner in inners:
            run = make_sharded_als(
                mesh, ("data",), "model",
                sparsify_u=DistTopK(t_u, ("data",)),
                sparsify_v=DistTopK(t_v, ("model",)),
                track_error=False,
                inner=inner,
            )
            _, u_spec, _ = run.specs
            t0 = time.perf_counter()
            dist = run.distribute(a_sp)
            jax.block_until_ready(jax.tree_util.tree_leaves(dist))
            ingest_s = time.perf_counter() - t0
            u_sh = NamedSharding(mesh, u_spec)

            def u_fresh():
                # the jitted step donates its u argument — hand every call
                # a real copy so the timing loop can repeat
                return jax.device_put(jnp.array(u0, copy=True), u_sh)

            with jax.set_mesh(mesh):
                t0 = time.perf_counter()
                res = run(dist, u_fresh(), iters)
                jax.block_until_ready(res.u)
                first_s = time.perf_counter() - t0
                solve_s = _timed(lambda: run(dist, u_fresh(), iters).u)
            results[f"{r}x{c}[{inner}]"] = {
                "ingest_s": ingest_s,
                "compile_plus_first_run_s": first_s,
                "solve_s": solve_s,
                "per_iter_ms": solve_s / iters * 1e3,
                "final_residual": float(res.residual[-1]),
                "max_nnz": int(res.max_nnz),
            }
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small corpus so the shard_map path runs on every "
                         "CI push with 4 forced host devices (pallas-bsr "
                         "shards execute in interpret mode)")
    ap.add_argument("--full", action="store_true",
                    help="large-synthetic corpus (paper Fig. 10 scale)")
    ap.add_argument("--inners", default="jnp-csr,pallas-bsr,pallas-bsr-unfused",
                    help="comma-separated inner per-shard backends to sweep "
                         "(pallas-bsr-unfused is the separate-launch "
                         "reference the fused half-step is gated against)")
    ap.add_argument("--out", default="BENCH_sharded.json")
    args = ap.parse_args(argv)

    if args.full:
        n, m, k, iters = 25_000, 12_000, 16, 10
    elif args.smoke:
        n, m, k, iters = 256, 128, 4, 4
    else:
        n, m, k, iters = 2048, 1024, 8, 8
    grids = [(1, 1), (2, 2)]
    inners = [s.strip() for s in args.inners.split(",") if s.strip()]
    results = bench(n, m, k, iters, grids, inners)

    payload = {
        "shape": {"n": n, "m": m, "k": k, "iters": iters},
        "grids": ["%dx%d" % g for g in grids],
        "inner_backends": inners,
        "devices": len(jax.devices()),
        "device_kind": jax.default_backend(),
        "platform": platform.platform(),
        "jax_version": jax.__version__,
        "results": results,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(json.dumps(payload, indent=2))

    ok = all("final_residual" in r or r.get("status") == "skipped"
             for name, r in results.items() if name != "enforced-1dev")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
