"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read.

The benchmark traces its window with ``jax.profiler`` and marks it, and its
own calls into the program, with ``TraceAnnotation`` host spans named
``bench.*`` (``bench.window`` around the whole window).  From the trace
this module takes:

* the device's operations: on a TPU the ``XLA Ops`` line of each
  ``/device:TPU:N`` plane; where the trace has no device plane (the CPU
  backend, in the tests) the host events that carry an ``hlo_op`` stat;
* each operation's self time (its duration less that of operations nested
  in it on the same line), summed by name;
* the union of the operations' intervals inside the window (busy time),
  averaged over the devices, and the idle gaps between them;
* each gap's label: the innermost ``bench.*`` span at the middle of the
  gap and the other host event of the same thread that overlaps it most.

All times are seconds.  Host and device events share the profiler's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
DEVICE_LINE = "XLA Ops"

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Op:
    name: str      # the HLO instruction's name, e.g. "fusion.137"
    start: float
    end: float
    self_s: float
    device: int
    stats: Dict[str, str]
    text: str = ""  # the event's full HLO text, where the trace gives it


def hlo_name(text: str) -> str:
    """``"%fusion.137 = f32[] fusion(...)"`` -> ``"fusion.137"``."""
    return text.split(" = ", 1)[0].lstrip("%") if " = " in text else text


def short_text(text: str, width: int = 160) -> str:
    """The HLO text without layouts, cut to ``width``: what a breakdown
    shows of an operation."""
    return re.sub(r"\{[^{}]*\}", "", text).lstrip("%")[:width]


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float
    depth: int


@dataclasses.dataclass
class Reduced:
    window: Interval
    n_devices: int
    ops: List[Op]               # device operations inside the window
    spans: List[Span]           # bench.* spans
    host: List[Span]            # other events of the threads holding spans

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        per_dev = defaultdict(list)
        for op in self.ops:
            per_dev[op.device].append((op.start, op.end))
        total = sum(length(union(iv, self.window)) for iv in per_dev.values())
        return total / max(self.n_devices, 1)

    def gaps(self, device: int = 0) -> List[Interval]:
        """Idle gaps of one device inside the window."""
        return complement(union([(o.start, o.end) for o in self.ops
                                 if o.device == device], self.window),
                          self.window)

    def time_by_name(self, ops: Optional[Iterable[Op]] = None,
                     key=lambda op: op.name) -> Dict[str, float]:
        """Self seconds per operation name (or other ``key``), summed over
        the devices."""
        out: Dict[str, float] = defaultdict(float)
        for op in self.ops if ops is None else ops:
            out[key(op)] += op.self_s
        return dict(out)

    def self_s(self, ops: Iterable[Op]) -> float:
        return sum(op.self_s for op in ops)

    def label(self, gap: Interval) -> str:
        """What the host was doing in ``gap``: the innermost ``bench.*``
        span at its middle, and the host event that overlaps it most."""
        span = innermost(self.spans, 0.5 * (gap[0] + gap[1]))
        best, most = None, 0.0
        for s in self.host:
            got = min(s.end, gap[1]) - max(s.start, gap[0])
            if got > most or (got == most and best is not None
                              and got > 0 and s.depth > best.depth):
                best, most = s, got
        parts = [span, best.name if best is not None else None]
        return " > ".join(p for p in parts if p) or "(no host event)"


def innermost(spans: Sequence[Span], t: float) -> Optional[str]:
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or s.depth > best.depth
                                      or (s.depth == best.depth
                                          and s.end - s.start
                                          < best.end - best.start)):
            best = s
    return None if best is None else best.name


def union(intervals: Iterable[Interval],
          clip: Optional[Interval] = None) -> List[Interval]:
    """Sorted, disjoint union of ``intervals``, clipped to ``clip``."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if clip is not None:
            a, b = max(a, clip[0]), min(b, clip[1])
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def complement(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The gaps of the disjoint sorted ``busy`` inside ``window``."""
    gaps, t = [], window[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < window[1]:
        gaps.append((t, window[1]))
    return gaps


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def self_times(events: Sequence[Tuple[float, float]]) -> List[float]:
    """Self time of each event on one line: its duration less the time of
    the events nested inside it.  ``events`` sorted by start."""
    self_s = [b - a for a, b in events]
    stack: List[int] = []
    for i, (a, b) in enumerate(events):
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            self_s[stack[-1]] -= min(b, events[stack[-1]][1]) - a
        stack.append(i)
    return self_s


def _stats(event) -> Dict[str, str]:
    try:
        return {str(k): str(v) for k, v in event.stats}
    except (TypeError, ValueError):
        return {}


def _line_events(line) -> List[Tuple[float, float, object]]:
    evs = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e)
           for e in line.events]
    evs.sort(key=lambda x: (x[0], -x[1]))
    return evs


def load(path: str) -> Reduced:
    """Read the ``.xplane.pb`` at ``path`` (or the one under the directory
    ``path``) and reduce it to the window marked by ``bench.window``."""
    from jax.profiler import ProfileData

    if not path.endswith(".xplane.pb"):
        found = sorted(glob.glob(f"{path}/**/*.xplane.pb", recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(path)

    spans: List[Span] = []
    host: List[Span] = []
    raw_ops: List[Tuple[str, float, float, float, int, Dict[str, str]]] = []
    planes = list(data.planes)
    device_planes = [p for p in planes if DEVICE_PLANE.match(p.name)]
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = _line_events(line)
            mine = [x for x in evs if x[2].name.startswith(SPAN_PREFIX)]
            if not device_planes:
                hlo = [x for x in evs if "hlo_op" in _stats(x[2])]
                for (a, b, e), s in zip(hlo, self_times([x[:2] for x in hlo])):
                    raw_ops.append((e.name, a, b, s, 0, _stats(e)))
            if not mine:
                continue
            depth, stack = [], []
            for a, b, e in evs:
                while stack and stack[-1] <= a:
                    stack.pop()
                depth.append(len(stack))
                stack.append(b)
            for (a, b, e), d in zip(evs, depth):
                target = spans if e.name.startswith(SPAN_PREFIX) else host
                target.append(Span(e.name, a, b, d))
    for dev, plane in enumerate(sorted(device_planes, key=lambda p: p.name)):
        for line in plane.lines:
            if line.name != DEVICE_LINE:
                continue
            evs = _line_events(line)
            for (a, b, e), s in zip(evs, self_times([x[:2] for x in evs])):
                raw_ops.append((e.name, a, b, s, dev, _stats(e)))

    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace {path} holds no {WINDOW_SPAN!r} span")
    window = (windows[0].start, windows[0].end)
    ops = [Op(hlo_name(n), a, b, s, d, st, n) for n, a, b, s, d, st in raw_ops
           if a < window[1] and b > window[0]]
    return Reduced(window=window,
                   n_devices=max(len(device_planes), 1),
                   ops=ops, spans=spans, host=host)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device operations that took the most self time, and the
    longest idle gaps of device 0 labelled by what the host was doing."""
    by_name = sorted(red.time_by_name(key=lambda op: short_text(
        op.text or op.name)).items(), key=lambda kv: -kv[1])
    gaps = sorted(red.gaps(0), key=lambda g: g[0] - g[1])
    return {"device_ops": [[n, s] for n, s in by_name[:top]],
            "idle_gaps": [[red.label(g), g[1] - g[0]] for g in gaps[:top]]}


def idle_pct(red: Optional[Reduced]) -> Optional[float]:
    """Share of the window in which no operation ran on the device, in %."""
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)


#: how a Pallas (Mosaic) kernel launch shows in a TPU trace: an HLO
#: custom call to the TPU's kernel target
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def is_kernel(op: Op) -> bool:
    """True for a Pallas kernel launch rather than an operation XLA
    generated itself."""
    return KERNEL_TARGET in op.text or op.stats.get("hlo_category") == \
        "custom-call"


def names_seen(red: Reduced, top: int = 40) -> str:
    """The device operations with the most self time, for a reader that
    matched nothing."""
    by_name = sorted(red.time_by_name(key=lambda op: short_text(
        op.text or op.name, 100)).items(), key=lambda kv: -kv[1])[:top]
    return "; ".join(f"{n} {s:.6g}s" for n, s in by_name)
