"""stream_host_ms_per_chunk: milliseconds a chunk keeps the fitting thread
on host work that the prefetcher leaves exposed: its time inside the spans
``nmf.stream.stall`` (waiting on the prefetch worker's queue) and
``nmf.stream.ingest`` (converting a chunk to the backend's operand on that
thread), over the traced window, per chunk of every pass the window's fits
made (the stream, the fold-in and the seed statistics: :data:`PASSES`
passes over the chunks a fit).

It also prints the time by span and the window's prefetch counters
(``stream_stats``: chunks packed, seconds packing on the worker, seconds
stalled).  A program without the spans gives no reading."""

import sys
from collections import defaultdict

from bench import scope

SPANS = ("nmf.stream.stall", "nmf.stream.ingest")
PASSES = 3


def read(rec):
    red, w = rec.trace, rec.window
    if red is None or not w.get("fits") or "chunks" not in rec.setup:
        return None
    lo, hi = red.window
    held = defaultdict(float)
    for s in scope.program_spans(red):
        if s.name in SPANS:
            held[s.name] += min(s.end, hi) - max(s.start, lo)
    if not held:
        return None
    chunks = w["fits"] * len(rec.setup["chunks"]) * PASSES
    print("stream_host_ms_per_chunk: ms per chunk by span: "
          + ", ".join(f"{n} {1e3 * s / chunks:.6g}" for n, s in held.items())
          + f"; {chunks} chunks; stream_stats "
          f"{w.get('stream_stats')}", file=sys.stderr)
    return 1e3 * sum(held.values()) / chunks
