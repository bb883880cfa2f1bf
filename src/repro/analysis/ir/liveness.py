"""Liveness analysis over jaxprs: live-interval peak bytes and intermediate
aval inventory.

The AST rules prove things about *spellings*; this module proves things
about the lowered computation itself.  Given a closed jaxpr it computes

* :func:`peak_live_bytes` — the per-step peak of live abstract-value bytes
  under sequential execution of the eqns, with call-like eqns (``pjit``,
  ``scan``, ``while``, ``cond``, ``shard_map``, custom-derivative calls)
  contributing their own recursive internal peak as a transient, and
  ``pallas_call`` contributing its VMEM block working set.  Inputs and
  outputs of the jaxpr are counted live for the whole duration (the caller
  holds them; donation is deliberately ignored, so the number is an upper
  bound the budget ledger can hold steady across donation changes).
* :func:`iter_eqns` / :func:`intermediate_avals` — a recursive walk of
  every eqn (through all sub-jaxprs) yielding the produced avals, for the
  dense-blowup detector.

The planner is an *estimate*, not XLA's allocator: XLA fuses elementwise
chains (intermediates never materialize) and reuses buffers more
aggressively than last-use freeing.  It is deliberately conservative and
— crucially for a CI ledger — deterministic: same jaxpr, same number, on
any machine.  ``benchmarks/fig6_memory.py`` cross-checks it against
``compiled.memory_analysis()`` at runtime (the ``memory_guard`` satellite).
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional, Tuple

__all__ = ["aval_bytes", "peak_live_bytes", "iter_eqns",
           "intermediate_avals", "eqn_source", "PeakReport"]


def aval_bytes(aval) -> int:
    """Bytes of one abstract value; 0 for tokens / unshaped avals."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    size = 1
    for d in shape:
        try:
            size *= int(d)
        except (TypeError, ValueError):  # symbolic dim: count as 1
            pass
    return size * dtype.itemsize


def _unclose(jaxpr):
    """The raw Jaxpr of a ClosedJaxpr (or the jaxpr itself)."""
    return getattr(jaxpr, "jaxpr", jaxpr)


def _sub_jaxprs(eqn) -> List:
    """Every (Closed)Jaxpr reachable from an eqn's params — generic, so new
    higher-order primitives are walked without registration."""
    out = []
    for val in eqn.params.values():
        for item in (val if isinstance(val, (list, tuple)) else (val,)):
            if hasattr(item, "eqns") or hasattr(item, "jaxpr") and \
                    hasattr(getattr(item, "jaxpr", None), "eqns"):
                out.append(item)
    return out


def eqn_source(eqn) -> Optional[str]:
    """``file:line`` of the user frame that built the eqn, when jax kept
    source info around (best effort — None otherwise)."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is None:
            return None
        return f"{frame.file_name}:{frame.start_line}"
    except Exception:
        return None


def iter_eqns(jaxpr, _depth: int = 0) -> Iterator[Tuple[object, int]]:
    """Yield ``(eqn, depth)`` for every eqn, recursing through sub-jaxprs."""
    for eqn in _unclose(jaxpr).eqns:
        yield eqn, _depth
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, _depth + 1)


def intermediate_avals(jaxpr) -> Iterator[Tuple[object, object, int]]:
    """Yield ``(aval, eqn, depth)`` for every eqn output in the jaxpr and
    all sub-jaxprs — the candidate set for the dense-blowup detector."""
    for eqn, depth in iter_eqns(jaxpr):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None:
                yield aval, eqn, depth


def block_dims(bm) -> Tuple[int, ...]:
    """Int block dims of one ``pallas_call`` BlockMapping.  Each entry is a
    ``Blocked``/``Element`` dim carrying ``block_size`` or a ``Squeezed``
    dim (no size, counts as 1).  A whole-array block (no ``grid``, no
    BlockSpec) is a full-extent ``Blocked`` dim per axis."""
    return tuple(int(getattr(d, "block_size", 1) or 1)
                 for d in bm.block_shape)


def _pallas_working_set(eqn) -> int:
    """Per-step VMEM block working set of a ``pallas_call`` eqn: one block
    per operand/output BlockSpec (the tile auditor separately checks the
    double-buffered figure against the VMEM budget).  Scalar-prefetch
    operands live in SMEM and have no block mapping."""
    gm = eqn.params.get("grid_mapping")
    if gm is None:
        return 0
    total = 0
    for bm in gm.block_mappings:  # inputs and outputs
        block = 1
        for d in block_dims(bm):
            block *= d
        total += block * bm.array_aval.dtype.itemsize
    return total


def _eqn_extra_bytes(eqn) -> int:
    """Transient bytes an eqn needs *beyond* its operands and outputs (both
    already counted live at the outer level): the recursive internal peak
    of call-like eqns, or the VMEM working set of a ``pallas_call``."""
    if eqn.primitive.name == "pallas_call":
        return _pallas_working_set(eqn)
    subs = _sub_jaxprs(eqn)
    if not subs:
        return 0
    extra = 0
    for sub in subs:
        inner = peak_live_bytes(sub).peak_bytes
        io = sum(aval_bytes(v.aval) for v in _unclose(sub).invars)
        io += sum(aval_bytes(getattr(v, "aval", None) or v)
                  for v in _unclose(sub).outvars
                  if hasattr(v, "aval"))
        extra = max(extra, max(inner - io, 0))
    return extra


@dataclasses.dataclass(frozen=True)
class PeakReport:
    """Planner output for one jaxpr."""

    peak_bytes: int          # max live bytes at any step
    input_bytes: int         # jaxpr invars + constvars (live throughout)
    output_bytes: int        # jaxpr outvars
    peak_eqn: Optional[str]  # primitive name at the peak step
    peak_source: Optional[str]  # file:line of the peak eqn (best effort)


def peak_live_bytes(jaxpr) -> PeakReport:
    """Peak live bytes under sequential eqn execution with last-use freeing.

    Inputs/consts are held by the caller for the whole call, outputs live
    from their defining eqn to the end; every other var lives from its
    defining eqn to its last use.  Call-like eqns add their recursive
    internal transient at their step.
    """
    raw = _unclose(jaxpr)
    eqns = raw.eqns
    n_eqns = len(eqns)

    invars = list(raw.invars) + list(raw.constvars)
    held = set(id(v) for v in invars)
    out_ids = set()
    for v in raw.outvars:
        if hasattr(v, "aval"):  # Literal outvars have no liveness
            out_ids.add(id(v))

    last_use = {}
    var_bytes = {}
    for v in invars:
        var_bytes[id(v)] = aval_bytes(v.aval)
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if hasattr(v, "aval") and not isinstance(
                    getattr(v, "val", None), (int, float)):
                last_use[id(v)] = i
        for v in eqn.outvars:
            if hasattr(v, "aval"):
                var_bytes[id(v)] = aval_bytes(v.aval)

    input_bytes = sum(var_bytes[id(v)] for v in invars)
    output_bytes = sum(var_bytes.get(i, 0) for i in out_ids)

    cur = input_bytes
    peak = cur + output_bytes if n_eqns == 0 else cur
    peak_eqn = None
    peak_source = None
    live = set(held)
    for i, eqn in enumerate(eqns):
        born = []
        for v in eqn.outvars:
            if hasattr(v, "aval") and id(v) not in live:
                live.add(id(v))
                born.append(id(v))
                cur += var_bytes[id(v)]
        candidate = cur + _eqn_extra_bytes(eqn)
        if candidate > peak:
            peak = candidate
            peak_eqn = eqn.primitive.name
            peak_source = eqn_source(eqn)
        # free everything whose last use was this eqn (not caller-held,
        # not an output of the whole jaxpr)
        for v in list(eqn.invars) + list(eqn.outvars):
            vid = id(v)
            if (vid in live and vid not in held and vid not in out_ids
                    and last_use.get(vid, -1) <= i):
                live.discard(vid)
                cur -= var_bytes.get(vid, 0)
    return PeakReport(peak_bytes=int(peak), input_bytes=int(input_bytes),
                      output_bytes=int(output_bytes), peak_eqn=peak_eqn,
                      peak_source=peak_source)
