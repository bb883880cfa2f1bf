"""host_idle_ms_per_fit: milliseconds a fit leaves the device idle while the
host is inside ``EnforcedNMF.fit``, the median over the traced window's
``nmf.fit`` spans of device 0's idle time inside each.

It also prints the window's idle time in ms per fit split by the innermost
program span (:mod:`bench.scope`), "outside nmf.fit" included, and the span
that holds the longest gap.  A program without the ``nmf.*`` spans gives
no reading."""

import statistics
import sys

from bench import scope


def read(rec):
    red = rec.trace
    if red is None or not rec.window.get("fits"):
        return None
    fits = scope.fit_spans(red)
    if not fits:
        return None
    gaps = red.gaps(0)
    per_fit = [scope.idle_in(gaps, s) for s in fits]
    split = sorted(scope.idle_by_span(red).items(), key=lambda kv: -kv[1])
    name, seconds = scope.longest_gap(red) or ("(none)", 0.0)
    print("host_idle_ms_per_fit: idle ms per fit by span: "
          + ", ".join(f"{n} {1e3 * s / len(fits):.6g}" for n, s in split)
          + f"; longest gap {1e3 * seconds:.6g} ms in {name}; "
          f"{len(fits)} fits", file=sys.stderr)
    return 1e3 * statistics.median(per_fit)
