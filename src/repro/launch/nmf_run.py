"""NMF drivers: real runs (paper-scale synthetic corpora) and the
production-mesh dry-run of the distributed enforced-sparsity ALS.

Dry-run (the paper's "large" workload on 256/512 chips):
    PYTHONPATH=src python -m repro.launch.dryrun --nmf [--multi-pod]
(launch/dryrun.py imports nmf_dryrun_cell from here)

Real run (any size that fits one host), through the unified estimator:
    PYTHONPATH=src python -m repro.launch.nmf_run --config pubmed --t-u 5000
    PYTHONPATH=src python -m repro.launch.nmf_run --config reuters \
        --solver sequential --sparsity "t_u=55,t_v=2000,mode=global"

Streaming (the online sufficient-statistics engine; add --mesh 2x2 on a
multi-device host for the mesh-reduced variant):
    PYTHONPATH=src python -m repro.launch.nmf_run --config reuters --small \
        --solver streaming --stream --chunk-docs 256
"""
from __future__ import annotations

import argparse
import time
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import NMF_CONFIGS


def nmf_input_specs(n: int, m: int, k: int, cap: int, cap_t: int,
                    r: int, c: int):
    """ShapeDtypeStruct stand-ins for the distributed factorization."""
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    n_loc, m_loc = n // r, m // c
    return (
        sds((r, c, n_loc, cap), f32),      # values
        sds((r, c, n_loc, cap), i32),      # cols
        sds((r, c, m_loc, cap_t), f32),    # values_t
        sds((r, c, m_loc, cap_t), i32),    # cols_t
        sds((n, k), f32),                  # u0
    )


def nmf_dryrun_cell(mesh: jax.sharding.Mesh, *,
                    n: int = 4_000_000, m: int = 1_000_000, k: int = 256,
                    nnz_per_row: int = 256, iters: int = 20,
                    t_frac: float = 0.02) -> Dict:
    """Lower + compile the paper's Alg. 2 at production scale on ``mesh`` —
    the *unified* ALS engine shard_mapped via ``make_sharded_als`` (the
    exact code path ``solver="distributed"`` executes), not a separate
    distributed loop.

    Capacity sizing: row nonzeros spread over C column blocks with 2x skew
    margin; transpose orientation likewise (col nnz = n*nnz/m).
    """
    from repro.backend.sharded import make_sharded_als
    from repro.core.nmf import NMFResult
    from repro.core.topk import DistTopK

    axes = mesh.axis_names
    rows_axes = tuple(a for a in ("pod", "data") if a in axes)
    r = 1
    for a in rows_axes:
        r *= mesh.shape[a]
    c = mesh.shape["model"]
    cap = max(2 * nnz_per_row // c, 4)
    col_nnz = n * nnz_per_row // m
    cap_t = max(2 * col_nnz // r, 4)
    t_u = int(n * k * t_frac)
    t_v = int(m * k * t_frac)

    run = make_sharded_als(
        mesh, rows_axes, "model",
        sparsify_u=DistTopK(t_u, rows_axes),
        sparsify_v=DistTopK(t_v, ("model",)),
        track_error=False,
    )
    _, u_spec, v_spec = run.specs
    specs = nmf_input_specs(n, m, k, cap, cap_t, r, c)
    shardings = tuple(
        NamedSharding(mesh, s) for s in (*run.leaf_specs, u_spec)
    )
    rep = NamedSharding(mesh, P())
    out_shardings = NMFResult(
        u=NamedSharding(mesh, u_spec), v=NamedSharding(mesh, v_spec),
        residual=rep, error=rep, max_nnz=rep, nnz_u=rep, nnz_v=rep,
        health=rep,
    )
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = jax.jit(  # repro: allow[jit-cache] one-shot benchmark harness; jitted once then AOT-lowered for the memory analysis
            run.shard_fn(iters),
            in_shardings=shardings,
            out_shardings=out_shardings,
            # u0 rotates in place like the production engine's jit — the
            # memory analysis below then reports the aliased bytes
            donate_argnums=(4,),
        )
        lowered = jitted.lower(*specs)
        compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    rec = {
        "arch": "nmf-large-synthetic",
        "shape": f"n{n}_m{m}_k{k}_iters{iters}",
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "status": "ok",
        "flops": ca.get("flops", 0.0),
        "bytes_accessed": ca.get("bytes accessed", 0.0),
        "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
        "output_bytes": getattr(ma, "output_size_in_bytes", 0),
        "alias_bytes": getattr(ma, "alias_size_in_bytes", 0),
        "compile_s": round(time.time() - t0, 1),
    }
    rec["bytes_per_device"] = (rec["argument_bytes"] + rec["output_bytes"]
                               + rec["temp_bytes"] - rec["alias_bytes"])
    return rec, lowered, compiled


def main(argv=None):
    from repro.launch.compile_cache import enable_compile_cache
    from repro.nmf import available_solvers

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="reuters",
                    choices=list(NMF_CONFIGS.keys()))
    ap.add_argument("--solver", default="enforced",
                    choices=available_solvers())
    ap.add_argument("--sparsity", default=None,
                    help="Sparsity spec, e.g. 't_u=5000,t_v=2000,mode=exact' "
                         "or 'frac_u=0.02' (overrides --t-u/--t-v)")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--t-u", type=int, default=None)
    ap.add_argument("--t-v", type=int, default=None)
    ap.add_argument("--tol", type=float, default=0.0,
                    help="early-stop tolerance on the relative residual")
    ap.add_argument("--backend", default=None,
                    help="matmul backend for the ALS hot path "
                         "(jnp-dense / jnp-csr / pallas-bsr; default: auto). "
                         "Composes with --mesh: --backend pallas-bsr "
                         "--mesh RxC runs the Pallas MXU kernels inside "
                         "every mesh shard (per-device BSR tile grids)")
    ap.add_argument("--stream", action="store_true",
                    help="stream the corpus through the online engine in "
                         "document chunks (implies --solver streaming)")
    ap.add_argument("--chunk-docs", type=int, default=None,
                    help="documents per streaming chunk (default: 8 chunks)")
    ap.add_argument("--corpus-dir", default=None, metavar="PATH",
                    help="stream an out-of-core corpus from this "
                         "repro.data.corpus directory (implies --solver "
                         "streaming).  If PATH has no corpus yet, the "
                         "synthetic corpus is spilled there first "
                         "(write_corpus) and then streamed memory-mapped")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable the double-buffered host->device chunk "
                         "prefetcher (synchronous carving; results are "
                         "bit-identical either way)")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="chunks the prefetcher queues ahead of the online "
                         "step (host memory is O(depth) chunks)")
    ap.add_argument("--mesh", default=None, metavar="RxC",
                    help="device grid for the distributed/streaming solvers, "
                         "e.g. 2x2 (default 1x1); the inner per-shard "
                         "backend comes from --backend (jnp-csr / "
                         "pallas-bsr)")
    ap.add_argument("--checkpoint-dir", default=None, metavar="PATH",
                    help="periodic atomic fit snapshots land here "
                         "(repro.robustness); a killed run restarted with "
                         "--resume continues from the newest one")
    ap.add_argument("--checkpoint-every", type=int, default=10,
                    help="snapshot cadence: iterations (ALS family), "
                         "chunks (streaming), or blocks (sequential)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest checkpoint in "
                         "--checkpoint-dir (fingerprint-checked; refuses a "
                         "mismatched config/corpus)")
    ap.add_argument("--small", action="store_true", help="1/8 scale")
    args = ap.parse_args(argv)
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume needs --checkpoint-dir")
    enable_compile_cache()

    solver = ("streaming" if args.stream or args.corpus_dir
              else args.solver)
    mesh_shape = (1, 1)
    if args.mesh:
        r, _, c = args.mesh.lower().partition("x")
        mesh_shape = (int(r), int(c))

    cfg = dict(NMF_CONFIGS[args.config])
    n, m, k = cfg["n_terms"], cfg["n_docs"], cfg["k"]
    iters = args.iters or cfg.get("iters", 50)
    if args.small:
        n, m = n // 8, m // 8
    chunk_docs = args.chunk_docs
    if mesh_shape != (1, 1):
        # the mesh engines shard whole row/column blocks: trim the
        # synthetic corpus to divisible sizes (streaming chunks need no
        # alignment — ragged widths pad with empty documents internally)
        r, c = mesh_shape
        n = max(n - n % r, r)
        m = max(m - m % c, c)
    from repro.data import synthetic_journal_corpus
    from repro.nmf import EnforcedNMF, NMFConfig, Sparsity

    if args.sparsity is not None:
        sparsity = Sparsity.parse(args.sparsity)
    else:
        sparsity = Sparsity(t_u=args.t_u, t_v=args.t_v)

    if args.corpus_dir is not None:
        from pathlib import Path

        from repro.data.corpus import open_corpus, write_corpus

        if not (Path(args.corpus_dir) / "meta.json").exists():
            print(f"spilling {n}x{m} synthetic corpus to "
                  f"{args.corpus_dir} ...", flush=True)
            a_res, _ = synthetic_journal_corpus(
                n_terms=n, n_docs=m, n_journals=cfg.get("n_journals", 5))
            write_corpus(a_res, args.corpus_dir, chunk_docs=chunk_docs)
            del a_res  # the fit below streams it back memory-mapped
        a = open_corpus(args.corpus_dir)
        n, m = a.shape
        chunk_docs = a.chunk_docs
        print(f"streaming {n}x{m} corpus from {args.corpus_dir} "
              f"({len(a)} mmap shards, chunk_docs={chunk_docs}, "
              f"prefetch={'off' if args.no_prefetch else 'on'})",
              flush=True)
    else:
        print(f"building {n}x{m} synthetic corpus ...", flush=True)
        a, dj = synthetic_journal_corpus(
            n_terms=n, n_docs=m, n_journals=cfg.get("n_journals", 5))
    model = EnforcedNMF(NMFConfig(
        k=k, iters=iters, sparsity=sparsity, solver=solver,
        tol=args.tol, backend=args.backend, mesh_shape=mesh_shape,
        chunk_docs=chunk_docs, prefetch=not args.no_prefetch,
        prefetch_depth=args.prefetch_depth,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        resume=args.resume))
    t0 = time.time()
    model.fit(a)
    jax.block_until_ready(model.u_)
    dt = time.time() - t0
    res = model.result_
    stop = " (early stop)" if res.converged else ""
    unit = "chunks" if res.error_granularity == "chunk" else "iterations"
    print(f"solver={solver}: {model.n_iter_} {unit}{stop} in "
          f"{dt:.1f}s; "
          f"final error {res.final_error:.4f}, "
          f"residual {res.final_residual:.2e}, "
          f"NNZ(U)={res.final_nnz_u}, NNZ(V)={res.final_nnz_v}, "
          f"max stored NNZ={int(res.max_nnz)}")
    if solver == "streaming":
        from repro.nmf.solvers import default_chunk_docs

        # docs actually processed: tol can stop the stream mid-corpus
        w = chunk_docs or default_chunk_docs(m)
        streamed = min(res.n_iter * w, m)
        print(f"streamed {streamed} docs in {res.n_iter} chunks "
              f"({streamed / max(dt, 1e-9):.0f} docs/s, "
              f"mesh {mesh_shape[0]}x{mesh_shape[1]})")


if __name__ == "__main__":
    main()
