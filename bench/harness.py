"""The benchmark's harness: finds a cell's parts by name, runs its set-up,
its measured window and its check, and prints the result line.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json``: the configuration (sizes, budgets, schedule);
* ``traffic/<traffic>.json``: the traffic mix, data that names the driver
  that runs it (``"driver"``) and its parameters;
* ``drivers/<driver>.py``: set-up, window, check and control of one kind
  of traffic (``setup``, ``window``, ``check``, ``control``);
* ``limits/<workload>.json``: the limit of each number the cell's check
  compares;
* ``metrics/<metric>.py``: ``read(rec)``, one metric's value from the
  run's :class:`Records`, or ``None`` where the run has nothing to read.

So a new cell, configuration, traffic mix or metric is new files and new
``BENCHMARK.json`` entries, and no existing file changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
#: JAX's persistent compilation cache: one fixed directory in the checkout
CACHE_DIR = CHECKOUT / ".jax_cache"

sys.path.insert(0, str(CHECKOUT / "src"))


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


@dataclasses.dataclass
class Records:
    """What one run measured, for the metric readers."""

    cell: Cell
    seed: int
    setup: Dict[str, float] = dataclasses.field(default_factory=dict)
    window: Dict[str, Any] = dataclasses.field(default_factory=dict)
    setup_s: Optional[float] = None
    compiles: Optional[int] = None
    peak_bytes: Optional[int] = None
    peaks: Optional[Dict[str, float]] = None
    trace: Any = None


def _read_json(path: Path):
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from None


def load_cell(name: str, root: Path = CHECKOUT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its parts."""
    spec = _read_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SpecError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _read_json(root / configs[w["config"]]["file"])
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=_read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                limits=_read_json(BENCH / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


def load_module(path: Path):
    """Import the Python file at ``path`` under a name of its own."""
    mod_name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("")
                                   .parts).replace(".", "_").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not path.is_file():
        raise SpecError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def driver_of(cell: Cell):
    return load_module(BENCH / "drivers" / f"{cell.traffic['driver']}.py")


def evaluate(metrics: List[Dict[str, Any]], rec: Records) -> Dict[str, dict]:
    """Each metric's reading: ``{name: {"value", "unit"}}``; a metric whose
    reader finds nothing to read is left out."""
    out = {}
    for m in metrics:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class CompileCounter:
    """XLA compilations while registered: JAX emits the monitoring event
    below once per backend compilation and never on a cache hit."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def _listener(self, event: str, duration_secs: float, **kwargs) -> None:
        if event == self.EVENT:
            self.count += 1

    def __enter__(self):
        from jax._src import monitoring

        monitoring.register_event_duration_secs_listener(self._listener)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(self._listener)


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache in :data:`CACHE_DIR`, for every
    program however small or quick to compile."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def peak_bytes() -> Optional[int]:
    """``peak_bytes_in_use`` of the fullest device, where reported."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            t_start: float) -> dict:
    """Set up, measure and check one run of ``cell``; the result object.
    ``t_start`` is when the process started, where set-up begins."""
    import jax

    from bench import trace as trace_mod
    from bench.work import load_peaks

    devices = jax.devices()
    driver = driver_of(cell)
    rec = Records(cell=cell, seed=seed)
    if devices[0].platform != "cpu":
        rec.peaks = load_peaks(devices[0].device_kind)
    tracedir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        with CompileCounter() as setup_compiles:
            state = driver.setup(cell, seed, rec)
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tracedir, profiler_options=opts)
        rec.setup_s = time.perf_counter() - t_start
        try:
            with CompileCounter() as compiles, \
                    jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                driver.window(state, seconds, rec)
        finally:
            if trace:
                jax.profiler.stop_trace()
        rec.compiles = compiles.count
        rec.peak_bytes = peak_bytes()
        if trace:
            rec.trace = trace_mod.load(tracedir)
    finally:
        if tracedir:
            shutil.rmtree(tracedir, ignore_errors=True)
    print(f"window: {json.dumps(rec.window.get('summary', {}))}",
          file=sys.stderr)
    print(f"set-up: {json.dumps(rec.setup)}; XLA compilations in set-up: "
          f"{setup_compiles.count}", file=sys.stderr)
    print(f"compilations inside the window: {rec.compiles}", file=sys.stderr)
    readings = driver.check(state, rec)
    del state
    print(f"check: {rec.window.get('check_s')} s", file=sys.stderr)

    compared = {name: {"value": readings.get(name),
                       "limit": limit} for name, limit in cell.limits.items()}
    correct = (rec.window["attempted"] > 0 and rec.window["failed"] == 0
               and all(c["value"] is not None and math.isfinite(c["value"])
                       and c["value"] <= c["limit"]
                       for c in compared.values()))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": rec.peak_bytes}
    result = {"correct": correct, "attempted": rec.window["attempted"],
              "failed": rec.window["failed"]}
    if trace:
        red = rec.trace
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["metrics"] = evaluate(cell.per_layer, rec)
        result["device"] = device
        result["breakdown"] = trace_mod.breakdown(red)
    else:
        result["metrics"] = evaluate(cell.end_to_end, rec)
        missing = {m["name"] for m in cell.end_to_end} - set(result["metrics"])
        if missing and devices[0].platform != "cpu":
            raise RuntimeError(f"end-to-end metrics with no reading: "
                               f"{sorted(missing)}")
        result["device"] = device
    result["compared"] = compared
    return result


def report(result: dict) -> None:
    """The result line last on standard output, and each compared number
    beside its limit last on standard error."""
    sys.stdout.flush()
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once and print its result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    report(execute(cell, args.seed, args.seconds, bool(args.trace), t_start))
    return 0
