"""The program's own names in a reduced trace (:mod:`bench.trace`): the host
spans that ``EnforcedNMF.fit`` writes.

``fit`` marks its whole call ``nmf.fit`` and its steps inside it:
``nmf.prepare`` (input coercion, initial guess), ``nmf.dispatch`` (the
engine's jit call, which returns once enqueued), ``nmf.sync`` (each
blocking read of a device value), ``nmf.result`` (the ``FitResult``) and
``nmf.seed_stats`` (the streaming statistics).  They are host events on the
thread that holds the benchmark's ``bench.*`` spans, so the reduction files
them under :attr:`Reduced.host`.  A program without them (before they
existed) gives no ``nmf.fit`` span, and every reader here then finds
nothing.

The engine's named scopes (``als.v/product`` ... ``als.error``) live in each
device op's ``op_name``, which a TPU trace keeps only in its event metadata
(``tf_op``) and the reduction does not carry; nothing here reads them.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from bench.trace import Interval, Reduced, Span

PREFIX = "nmf."
FIT = "nmf.fit"
#: the label of idle time outside every ``nmf.fit`` span
OUTSIDE = "outside nmf.fit"


def program_spans(red: Reduced) -> List[Span]:
    """The program's spans that overlap the window, by start."""
    lo, hi = red.window
    return sorted((s for s in red.host if s.name.startswith(PREFIX)
                   and s.start < hi and s.end > lo),
                  key=lambda s: (s.start, -s.end))


def fit_spans(red: Reduced) -> List[Span]:
    """The window's ``nmf.fit`` spans: one a fit."""
    return [s for s in program_spans(red) if s.name == FIT]


def idle_in(gaps: Sequence[Interval], span: Span) -> float:
    """Seconds of the disjoint, sorted ``gaps`` inside ``span``."""
    i = bisect.bisect_right([b for _, b in gaps], span.start)
    total = 0.0
    for a, b in gaps[i:]:
        if a >= span.end:
            break
        total += min(b, span.end) - max(a, span.start)
    return total


def segments(spans: Sequence[Span],
             window: Interval) -> List[Tuple[float, float, str]]:
    """The window cut into pieces, each labelled with the innermost span
    that holds it (:data:`OUTSIDE` where none does).  ``spans`` nest, as
    one thread's spans do, and are sorted by start."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Span] = []
    t = window[0]

    def emit(upto: float) -> None:
        nonlocal t
        upto = min(upto, window[1])
        if upto > t:
            out.append((t, upto, stack[-1].name if stack else OUTSIDE))
            t = upto

    for s in spans:
        while stack and stack[-1].end <= s.start:
            emit(stack[-1].end)
            stack.pop()
        emit(s.start)
        stack.append(s)
    while stack:
        emit(stack[-1].end)
        stack.pop()
    emit(window[1])
    return out


def idle_by_span(red: Reduced, device: int = 0) -> Dict[str, float]:
    """Seconds of ``device``'s idle time in the window, by the innermost
    program span the host was in."""
    out: Dict[str, float] = defaultdict(float)
    pieces = segments(program_spans(red), red.window)
    j = 0
    for a, b in red.gaps(device):
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            lo, hi, name = pieces[k]
            out[name] += min(hi, b) - max(lo, a)
            k += 1
    return dict(out)


def longest_gap(red: Reduced,
                device: int = 0) -> Optional[Tuple[str, float]]:
    """The longest idle gap of ``device``: the innermost program span
    that holds most of it, and its seconds."""
    gaps = red.gaps(device)
    if not gaps:
        return None
    a, b = max(gaps, key=lambda g: g[1] - g[0])
    held: Dict[str, float] = defaultdict(float)
    for lo, hi, name in segments(program_spans(red), red.window):
        if hi > a and lo < b:
            held[name] += min(hi, b) - max(lo, a)
    return max(held, key=held.get), b - a
