#!/usr/bin/env python3
"""Run the enforced-sparse fit once on a TPU and check what comes out.

    python3 chip_smoke.py                # one chip: fit, check, serve
    python3 chip_smoke.py --four-chips   # four chips: 2x2 mesh fit vs 1x1

Phase 1 (fit) generates the PubMed-journals-shaped corpus from ``--seed`` at
the paper's full width (20,112 terms x 7,510 documents, k=5) and fits it
through ``EnforcedNMF`` with the enforced solver on the ``pallas-bsr``
backend (the backend TPU input defaults to).  Phase 1 (check) runs the same
fit with the plain float32 reference (``jnp-dense`` under
``jax.default_matmul_precision("highest")``) on the same chip, compares the
per-iteration relative error and the fitted factors, and compares the first
half-step's products (``A^T U``, ``U^T U``, ``A V``, ``V^T V``) kernel
against reference.
Phase 2 (serve) folds a few of the corpus's documents into the fitted model
through ``TopicServer`` and checks the answers against a float64 fold-in.

``--four-chips`` runs only the mesh path: the distributed solver on a 2x2
mesh with the Pallas kernels in every shard, and the same solver on a 1x1
mesh on one chip, and compares the two trajectories.

The script needs a TPU: anywhere else it exits non-zero without printing a
result.  Any failed check raises, so the last line,
``{"ok": true, "device": {...}}``, is printed only when every phase passed.
All work runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: the paper's PubMed journals corpus (configs/nmf_paper.py)
CORPUS = "pubmed"
T_U, T_V = 5000, 2000
ITERS = 10
#: |E_kernel - E_reference| allowed at every iteration of the fit: above the
#: 1.8e-7 seen on a TPU v5e, below the 1.5e-5 that E moves in the last of the
#: ten iterations, so a fit that stalls one iteration early fails
ERROR_TOL = 2e-6
#: ||X_kernel - X_reference||_F / ||X_reference||_F of each fitted factor
FACTOR_TOL = 1e-3
#: max |kernel - reference| / max |reference| of each first half-step product
PRODUCT_TOL = 1e-4
#: max |E| and |R| difference between the 2x2 and the 1x1 mesh trajectories
MESH_TOL = 1e-4
#: the mesh shapes --four-chips fits; the first is the baseline
MESH_SHAPES = ((1, 1), (2, 2))
#: documents folded in through TopicServer, and its micro-batch: the batch
#: keeps round(t_v * 64 / 7510) = 17 loadings, so the check compares ~34
SERVE_DOCS, SERVE_BATCH = 128, 64
#: relative tolerance of a served loading against the float64 fold-in
SERVE_TOL = 1e-2


class SmokeError(AssertionError):
    """A phase produced a wrong or missing result."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeError(msg)


def make_corpus(seed: int, n_terms: int, n_docs: int, n_journals: int = 5):
    """The planted-journal synthetic corpus, term-major ``SpCSR``."""
    from repro.data import synthetic_journal_corpus

    a, _ = synthetic_journal_corpus(n_terms=n_terms, n_docs=n_docs,
                                    n_journals=n_journals, seed=seed)
    return a


def _config(k, iters, seed, backend, **kw):
    from repro.nmf import NMFConfig, Sparsity

    return NMFConfig(k=k, iters=iters, seed=seed, backend=backend,
                     sparsity=Sparsity(t_u=T_U, t_v=T_V, mode="global"), **kw)


def _fit(model, a):
    """Fit and return the wall seconds, history synced to the host."""
    import jax

    t0 = time.perf_counter()
    model.fit(a)
    jax.block_until_ready((model.u_, model.v_))
    np.asarray(model.result_.error)
    return time.perf_counter() - t0


def phase_fit(a, k: int, iters: int, seed: int):
    """The enforced fit on ``pallas-bsr``: a cold fit (compile + run), then
    a warm fit of the same shapes.  Returns the fitted model, the ingested
    BSR operand and the timings."""
    from repro.analysis.runtime import recompile_guard
    from repro.backend import get_backend
    from repro.nmf import EnforcedNMF

    t0 = time.perf_counter()
    op = get_backend("pallas-bsr").prepare(a)
    ingest_s = time.perf_counter() - t0
    model = EnforcedNMF(_config(k, iters, seed, "pallas-bsr"))
    cold_s = _fit(model, op)
    with recompile_guard(max_compiles=1 << 30) as warm_compiles:
        warm_s = _fit(model, op)
    err = np.asarray(model.result_.error)
    _check(bool(np.all(np.isfinite(err))), f"non-finite error trace {err}")
    return model, op, {
        "ingest_s": ingest_s, "cold_fit_s": cold_s, "warm_fit_s": warm_s,
        "compile_s": cold_s - warm_s, "step_s": warm_s / iters,
        "warm_compiles": warm_compiles.count,
        "bsr_tiles": [list(op.bsr.tiles.shape), list(op.bsr_t.tiles.shape)],
    }


def _rel_dev(x, ref) -> float:
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(x - ref).max() / max(np.abs(ref).max(), 1e-30))


def _rel_fro(x, ref) -> float:
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(x - ref) / max(np.linalg.norm(ref), 1e-30))


def kernels_compiled(op, v) -> bool:
    """True when the fused half-step lowers to a TPU kernel launch (a
    ``tpu_custom_call``), i.e. the kernels do not run in interpret mode."""
    import jax

    from repro.backend import get_backend

    be = get_backend("pallas-bsr")
    text = jax.jit(be.matmul_with_gram).lower(op, v).as_text()
    return "tpu_custom_call" in text


def phase_check(a, op, fitted, k: int, iters: int, seed: int):
    """The reference fit and the first half-step's products, kernel
    against the float32 ``jnp-dense`` reference at highest precision."""
    import jax
    import jax.numpy as jnp

    from repro.backend import get_backend
    from repro.core.nmf import init_u0, solve_gram
    from repro.nmf import EnforcedNMF

    dense = get_backend("jnp-dense").prepare(a)
    be = get_backend("pallas-bsr")
    u0 = init_u0(jax.random.PRNGKey(seed), a.shape[0], k)
    with jax.default_matmul_precision("highest"):
        ref = EnforcedNMF(_config(k, iters, seed, "jnp-dense"))
        ref_s = _fit(ref, dense)
        atu_ref, gu_ref = dense.T @ u0, u0.T @ u0
        v1 = jnp.maximum(solve_gram(gu_ref, atu_ref), 0.0)
        av_ref, gv_ref = dense @ v1, v1.T @ v1
    atu, gu = be.matmul_t_with_gram(op, u0)
    av, gv = be.matmul_with_gram(op, v1)
    products = {"AtU": _rel_dev(atu, atu_ref), "UtU": _rel_dev(gu, gu_ref),
                "AV": _rel_dev(av, av_ref), "VtV": _rel_dev(gv, gv_ref)}
    err = np.asarray(fitted.result_.error)
    err_ref = np.asarray(ref.result_.error)
    error_dev = float(np.abs(err - err_ref).max())
    factors = {"U": (fitted.u_, ref.u_), "V": (fitted.v_, ref.v_)}
    factor_dev = {name: _rel_fro(x, r) for name, (x, r) in factors.items()}
    out = {"reference_fit_s": ref_s, "error": err.tolist(),
           "error_reference": err_ref.tolist(),
           "max_error_deviation": error_dev, "product_deviation": products,
           "factor_deviation": factor_dev,
           "nnz": {name: [int(np.count_nonzero(np.asarray(x)))
                          for x in pair] for name, pair in factors.items()}}
    for name, dev in products.items():
        _check(dev <= PRODUCT_TOL,
               f"{name}: kernel deviates {dev:.3g} > {PRODUCT_TOL} from the "
               "reference")
    _check(error_dev <= ERROR_TOL,
           f"error trajectory deviates {error_dev:.3g} > {ERROR_TOL} from "
           f"the reference: {err.tolist()} vs {err_ref.tolist()}")
    for name, dev in factor_dev.items():
        _check(dev <= FACTOR_TOL,
               f"fitted {name} deviates {dev:.3g} > {FACTOR_TOL} from the "
               "reference fit's")
    return out


def _doc_terms(a, docs: int):
    """The first ``docs`` documents as (term_id, weight) lists."""
    from repro.sparse.csr import to_scipy

    csc = to_scipy(a).tocsc()
    return [list(zip(csc[:, j].indices.tolist(), csc[:, j].data.tolist()))
            for j in range(docs)]


def reference_fold_in(model, term_lists):
    """Float64 fold-in of a batch with the model's factor frozen:
    ``top-t(relu(A^T U (U^T U)^-1))`` under the batch-rescaled budget."""
    u = np.asarray(model.u_, np.float64)
    a_new = np.zeros((len(term_lists), u.shape[0]))
    for d, terms in enumerate(term_lists):
        for t, w in terms:
            a_new[d, t] += w
    v = np.maximum(np.linalg.solve(u.T @ u, (a_new @ u).T).T, 0.0)
    t = model._v_sparsity(len(term_lists)).resolve(*v.shape, "v")
    if t is not None and t < v.size:
        tau = np.sort(v.ravel())[-t]
        v = np.where(v >= tau, v, 0.0)
    return v


def phase_serve(model, a, docs: int, batch: int):
    """Fold ``docs`` corpus documents in through ``TopicServer`` and check
    every answer against :func:`reference_fold_in`."""
    from repro.serving.topics import TopicRequest, TopicServer

    term_lists = _doc_terms(a, docs)
    server = TopicServer(model, max_batch=batch)
    for rid, terms in enumerate(term_lists):
        server.submit(TopicRequest(rid=rid, terms=terms, top=model.config.k))
    t0 = time.perf_counter()
    done = server.run_until_drained()
    serve_s = time.perf_counter() - t0
    _check(len(done) == docs, f"{len(done)} of {docs} requests answered")
    n_topics, loading_dev = 0, 0.0
    for lo in range(0, docs, batch):
        v_ref = reference_fold_in(model, term_lists[lo:lo + batch])
        for d, req in enumerate(done[lo:lo + batch]):
            _check(req.error is None, f"request {req.rid}: {req.error}")
            want = [(int(t), v_ref[d, t]) for t in np.argsort(-v_ref[d])
                    if v_ref[d, t] > 0]
            got = req.topics
            _check([t for t, _ in got] == [t for t, _ in want],
                   f"request {req.rid}: topics {got} != reference {want}")
            for (_, x), (_, y) in zip(got, want):
                loading_dev = max(loading_dev, abs(x - y) / abs(y))
                _check(abs(x - y) <= SERVE_TOL * abs(y),
                       f"request {req.rid}: loading {x} != reference {y}")
            n_topics += len(got)
    _check(n_topics > 0, "no served document has a topic to compare")
    return {"served": server.served, "ticks": -(-docs // batch),
            "serve_s": serve_s, "topics_compared": n_topics,
            "max_loading_deviation": loading_dev,
            "answers": {r.rid: r.topics for r in done[:4]}}


def phase_mesh(a, k: int, iters: int, seed: int):
    """The distributed solver with ``pallas-bsr`` shards on each of
    :data:`MESH_SHAPES`; every trajectory must match the first within
    :data:`MESH_TOL`."""
    from repro.nmf import EnforcedNMF

    runs = {}
    for shape in MESH_SHAPES:
        model = EnforcedNMF(_config(k, iters, seed, "pallas-bsr",
                                    solver="distributed", mesh_shape=shape))
        cold_s = _fit(model, a)
        warm_s = _fit(model, a)
        runs["x".join(map(str, shape))] = {
            "cold_fit_s": cold_s, "warm_fit_s": warm_s,
            "error": np.asarray(model.result_.error).tolist(),
            "residual": np.asarray(model.result_.residual).tolist(),
            "devices": len(model.u_.sharding.device_set),
        }
    base = runs["x".join(map(str, MESH_SHAPES[0]))]
    for name, run in runs.items():
        _check(run["devices"] == int(np.prod([int(s) for s in name.split("x")])),
               f"mesh {name} ran on {run['devices']} device(s)")
        for key in ("error", "residual"):
            dev = float(np.abs(np.subtract(run[key], base[key])).max())
            run[f"max_{key}_deviation"] = dev
            _check(dev <= MESH_TOL,
                   f"mesh {name}: {key} deviates {dev:.3g} > {MESH_TOL}")
    return runs


def _report(name: str, value) -> None:
    print(f"{name}: {json.dumps(value)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh fit and its 1x1 comparison")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.configs.nmf_paper import NMF_CONFIGS
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    _check(not ops._default_interpret(), "kernels would run in interpret mode")
    _report("device", {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devices)})
    _report("compile_cache", enable_compile_cache())
    cfg = NMF_CONFIGS[CORPUS]
    n, m, k = cfg["n_terms"], cfg["n_docs"], cfg["k"]
    t0 = time.perf_counter()
    a = make_corpus(args.seed, n, m, cfg["n_journals"])
    _report("corpus", {"name": CORPUS, "shape": [n, m], "k": k,
                       "nnz": int(a.nnz()),
                       "seed": args.seed,
                       "build_s": time.perf_counter() - t0})

    if args.four_chips:
        _report("mesh", phase_mesh(a, k, ITERS, args.seed))
    else:
        model, op, fit = phase_fit(a, k, ITERS, args.seed)
        _check(kernels_compiled(op, model.v_),
               "the fused half-step did not lower to a TPU kernel")
        _report("fit", fit)
        _report("check", phase_check(a, op, model, k, ITERS, args.seed))
        _report("serve", phase_serve(model, a, SERVE_DOCS, SERVE_BATCH))
    stats = dev.memory_stats() or {}
    _report("peak_bytes_in_use", stats.get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
