import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape x
mesh) cell against 512 placeholder host devices, print memory/cost
analysis, and dump the artifacts the roofline harness consumes.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out out.json]
    PYTHONPATH=src python -m repro.launch.dryrun --nmf [--multi-pod]

``--nmf`` lowers the paper's large factorization through the *unified*
sharded ALS engine (``make_sharded_als`` + ``ShardedBackend`` — the exact
code path ``solver="distributed"`` executes), so the pod-scale memory /
cost numbers describe the production engine, not a stand-in.

The XLA_FLAGS line above MUST run before any other import (jax locks the
device count at first init); smoke tests and benchmarks do NOT import this
module and keep seeing 1 device.
"""
import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, ShapeSpec, cell_supported
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.models.common import ArchConfig
from repro.training.optimizer import AdamW, AdamState


def _shardings(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def param_struct(cfg: ArchConfig, dtype=jnp.float32):
    """ShapeDtypeStructs for params without allocating (eval_shape)."""
    return jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0), dtype))


def lower_cell(
    cfg: ArchConfig,
    shape: ShapeSpec,
    mesh: jax.sharding.Mesh,
    rules: Optional[Dict[str, str]] = None,
    donate: bool = True,
    microbatches: int = 4,
):
    """Lower (not yet compile) one cell.  Returns (lowered, meta)."""
    rules = rules or {"fsdp": "data", "tp": "model", "ep": "model"}
    params_sd = param_struct(cfg)
    pspecs = api.param_pspecs(cfg, params_sd, rules, mesh=mesh)
    psh = _shardings(mesh, pspecs)
    inputs_sd = api.input_specs(cfg, shape)

    if shape.kind == "train":
        opt = AdamW()
        opt_sd = jax.eval_shape(lambda: opt.init(params_sd))
        opt_specs = AdamState(P(), pspecs, pspecs)
        osh = _shardings(mesh, opt_specs)
        bspecs = api.batch_pspecs(cfg, shape, mesh)
        bsh = _shardings(mesh, bspecs)
        step = api.make_train_step(cfg, opt, microbatches=microbatches)
        jitted = jax.jit(  # repro: allow[jit-cache] AOT dry-run: only .lower()ed once, never called repeatedly
            step,
            in_shardings=(psh, osh, bsh),
            out_shardings=(psh, osh, NamedSharding(mesh, P())),
            donate_argnums=(0, 1) if donate else (),
        )
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_sd, opt_sd, inputs_sd)

    elif shape.kind == "prefill":
        bspecs = api.batch_pspecs(cfg, shape, mesh)
        bsh = _shardings(mesh, bspecs)
        step = api.make_prefill_step(cfg)
        jitted = jax.jit(step, in_shardings=(psh, bsh))  # repro: allow[jit-cache] AOT dry-run: only .lower()ed once, never called repeatedly
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_sd, inputs_sd)

    else:  # decode
        cache_sd = api.init_decode_cache(cfg, shape, as_specs=True)
        cspecs = api.cache_pspecs(cfg, shape, mesh, cache_sd)
        csh = _shardings(mesh, cspecs)
        dp = api.batch_axes_for(shape.global_batch, mesh, ("pod", "data"))
        tok_sh = NamedSharding(mesh, P(dp if dp else None))
        step = api.make_decode_step(cfg)
        jitted = jax.jit(  # repro: allow[jit-cache] AOT dry-run: only .lower()ed once, never called repeatedly
            step,
            in_shardings=(psh, csh, tok_sh, NamedSharding(mesh, P())),
            out_shardings=(NamedSharding(mesh, P(dp if dp else None, None)), csh),
            donate_argnums=(1,) if donate else (),
        )
        tok_sd = inputs_sd["token"]
        pos_sd = jax.ShapeDtypeStruct((), jnp.int32)
        with jax.set_mesh(mesh):
            lowered = jitted.lower(params_sd, cache_sd, tok_sd, pos_sd)

    meta = {"arch": cfg.name, "shape": shape.name,
            "mesh": dict(zip(mesh.axis_names, mesh.devices.shape))}
    return lowered, meta


def run_cell(cfg, shape, mesh, verbose=True, save_hlo: Optional[str] = None,
             rules=None) -> Dict[str, Any]:
    t0 = time.time()
    rec: Dict[str, Any] = {"arch": cfg.name, "shape": shape.name,
                           "mesh": "x".join(map(str, mesh.devices.shape))}
    ok, why = cell_supported(cfg, shape)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec
    try:
        lowered, meta = lower_cell(cfg, shape, mesh, rules=rules)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        ca = compiled.cost_analysis() or {}
        ma = compiled.memory_analysis()
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            flops=ca.get("flops", 0.0),
            bytes_accessed=ca.get("bytes accessed", 0.0),
            argument_bytes=getattr(ma, "argument_size_in_bytes", 0),
            output_bytes=getattr(ma, "output_size_in_bytes", 0),
            temp_bytes=getattr(ma, "temp_size_in_bytes", 0),
            alias_bytes=getattr(ma, "alias_size_in_bytes", 0),
        )
        # memory_analysis reports PER-DEVICE sizes for the SPMD module
        # (verified against known sharded argument sizes — see EXPERIMENTS.md)
        live = rec["argument_bytes"] + rec["output_bytes"] + rec["temp_bytes"] - rec["alias_bytes"]
        rec["bytes_per_device"] = live
        if save_hlo:
            with open(save_hlo, "w") as f:
                f.write(compiled.as_text())
            rec["hlo_path"] = save_hlo
        if verbose:
            print(f"  memory_analysis: {ma}")
            print(f"  cost_analysis flops={rec['flops']:.3e} "
                  f"bytes={rec['bytes_accessed']:.3e}")
            print(f"  ~{rec['bytes_per_device']/2**30:.2f} GiB/device "
                  f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        if verbose:
            traceback.print_exc()
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--nmf", action="store_true",
                    help="dry-run the paper's large NMF workload instead")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--hlo-dir", default=None, help="save compiled HLO text per cell")
    args = ap.parse_args(argv)

    mesh = make_production_mesh(multi_pod=args.multi_pod)

    if args.nmf:
        from repro.launch.nmf_run import nmf_dryrun_cell
        rec, lowered, compiled = nmf_dryrun_cell(mesh)
        if args.hlo_dir:
            os.makedirs(args.hlo_dir, exist_ok=True)
            path = os.path.join(
                args.hlo_dir, f"nmf_large_{'mp' if args.multi_pod else 'sp'}.hlo")
            with open(path, "w") as f:
                f.write(compiled.as_text())
            rec["hlo_path"] = path
        print(json.dumps(rec, indent=1))
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
        return 0
    cells = []
    if args.all:
        for cfg in ARCHS.values():
            for shape in SHAPES.values():
                cells.append((cfg, shape))
    else:
        cfg = ARCHS[args.arch]
        shapes = [SHAPES[args.shape]] if args.shape else list(SHAPES.values())
        cells = [(cfg, s) for s in shapes]

    records = []
    for cfg, shape in cells:
        print(f"== {cfg.name} x {shape.name} x mesh{mesh.devices.shape} ==", flush=True)
        hlo = None
        if args.hlo_dir:
            os.makedirs(args.hlo_dir, exist_ok=True)
            tag = f"{cfg.name}_{shape.name}_{'mp' if args.multi_pod else 'sp'}".replace("/", "_")
            hlo = os.path.join(args.hlo_dir, tag + ".hlo")
        rec = run_cell(cfg, shape, mesh, save_hlo=hlo)
        records.append(rec)
        print(f"  -> {rec['status']}" + (f" ({rec.get('reason','')})" if rec["status"] == "skipped" else ""), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")

    n_err = sum(r["status"] == "error" for r in records)
    print(f"\n{len(records)} cells: "
          f"{sum(r['status']=='ok' for r in records)} ok, "
          f"{sum(r['status']=='skipped' for r in records)} skipped, {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
