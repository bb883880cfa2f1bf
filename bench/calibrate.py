#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from.

    python3 bench/calibrate.py --workload pubmed-journals.fit \\
        --seeds 11,12,13 --control-seeds 3 --seconds 2

For each seed, in one process: the cell's set-up, a short window and the
check, printing the numbers the check compares (the program's readings).
Then, for the first ``--control-seeds`` seeds, the same numbers with the
plain reference at ``high`` (three bfloat16 passes, one step below the
float32 the configurations state) in the program's place: the control's
readings.  One JSON line per reading on standard output, and the whole
list in ``chiprun_out/calibrate-<workload>.json``.  The benchmark's own
runs never run this.  It needs a TPU.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402

CONTROL = "high"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds of the program's readings")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    driver = harness.driver_of(cell)
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = []
    for i, seed in enumerate(seeds):
        rec = harness.Records(cell=cell, seed=seed)
        t0 = time.perf_counter()
        st = driver.setup(cell, seed, rec)
        driver.window(st, args.seconds, rec)
        row = {"seed": seed, "side": "program",
               "readings": driver.check(st, rec),
               "setup": rec.setup, "summary": rec.window.get("summary"),
               "seconds": time.perf_counter() - t0}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if i < args.control_seeds:
            t0 = time.perf_counter()
            row = {"seed": seed, "side": f"control-{CONTROL}",
                   "readings": driver.control(st, CONTROL),
                   "seconds": time.perf_counter() - t0}
            rows.append(row)
            print(json.dumps(row), flush=True)
        del st
    out = harness.CHECKOUT / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"calibrate-{args.workload}.json").write_text(json.dumps(rows))
    for side in sorted({r["side"] for r in rows}):
        mine = [r["readings"] for r in rows if r["side"] == side]
        for name in mine[0]:
            vals = [m[name] for m in mine]
            print(f"{side} {name}: min {min(vals)!r} max {max(vals)!r}",
                  file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
