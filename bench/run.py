#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload pubmed-journals.fit --seed 7 \\
        --seconds 10 --trace 0

From the root of a checkout.  The cell's set-up, its measured window of
``--seconds`` and the check of what the window produced all run in this one
process; the last line of standard output is the result object, and the
last lines of standard error give each compared number beside its limit.
``--trace 1`` traces the window and reports the cell's per-layer metrics in
place of its end-to-end ones.  Off a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
