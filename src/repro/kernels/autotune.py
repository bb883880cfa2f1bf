"""Shape/device-keyed tile autotuner for the Pallas kernel path.

Tile sizes used to be hand-picked constants (``kb=128`` in ``bsr_spmm``,
``bm=512`` in ``gram``).  This module makes them a measured fact:

* :func:`resolve_tiles` — the lookup every kernel entry point calls when a
  tile argument is ``None``: per-(shape-bucket, device-kind) entries from a
  committed JSON ledger, falling back to the audited defaults
  (:data:`DEFAULT_TILES`) when no entry matches.  Resolution is pure host
  work on static shapes, cached per process, so it is free at trace time
  and never perturbs jit cache keys beyond the resolved integers.
* :func:`legal_candidates` — the sweep pre-filter.  Mirrors the
  ``pallas-tiles`` IR pass legality rules
  (:mod:`repro.analysis.ir.passes.pallas_tiles`): minor block dims are
  128-lane multiples (or full extents), second-minor dims are
  sublane multiples for the dtype, and the double-buffered working set of
  both the separate-spmm and the fused spmm+gram kernels fits the 16 MiB
  VMEM budget.  Illegal candidates are never timed.
* :func:`autotune` — the sweep itself: builds a synthetic BSR operand per
  candidate, wall-clock times the fused and separate kernels (the same
  block-until-ready protocol as ``benchmarks/bench_backends.py``), scores
  each candidate against the analytic roofline bound (the
  ``benchmarks/roofline.py`` constants), and returns the winner as a
  ledger entry.  Off-TPU this is interpret-mode-safe: without ``force``
  the sweep is skipped and the defaults are recorded as a fallback entry,
  so CI never commits interpret-mode timings as tuning facts.

Ledger format (``autotune_ledger.json``, committed next to this module;
override the path with ``$REPRO_AUTOTUNE_LEDGER``)::

    {"entries": {"<device-kind>/<shape-bucket>": {
        "bm": 128, "bk": 128, "kb": 128,
        "gram_bm": 512, "mask_bm": 256, "mask_bk": 256,
        "source": "autotune" | "default-fallback",
        "fused_us": ..., "spmm_us": ..., "roofline_us": ...}}}

Shape buckets are power-of-two rounded (``n4096-m2048-k8``) so nearby
problem sizes share an entry; ``k*`` buckets serve call sites that tune
before the factor rank is known (operand ingest).  Missing fields in an
entry inherit the defaults, so a ledger may record only what it measured.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = [
    "TileConfig", "DEFAULT_TILES", "VMEM_BUDGET",
    "shape_bucket", "device_kind", "ledger_path", "load_ledger",
    "resolve_tiles", "legal_candidates", "spmm_working_set",
    "fused_working_set", "fused_slots", "autotune", "update_ledger",
]

#: per-core VMEM budget the legality pre-filter enforces — keep in sync
#: with repro.analysis.ir.passes.pallas_tiles.VMEM_BUDGET
VMEM_BUDGET = 16 * 1024 * 1024

#: analytic roofline constants, mirroring benchmarks/roofline.py (imported
#: lazily there; duplicated here so library code never imports the
#: benchmark harness)
PEAK_FLOPS = 197e12
HBM_BW = 819e9

_LEDGER_ENV = "REPRO_AUTOTUNE_LEDGER"


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """Resolved tile sizes for one (shape-bucket, device) cell.

    ``bm`` / ``bk`` are the BSR tile dims (baked into the operand at
    ingest); ``kb`` tiles the dense operand's k axis in the separate
    ``bsr_spmm`` kernel (the fused kernel streams full-k slabs); the
    ``gram_bm`` / ``mask_*`` fields size the standalone gram and
    project_mask kernels."""

    bm: int = 128
    bk: int = 128
    kb: int = 128
    gram_bm: int = 512
    mask_bm: int = 256
    mask_bk: int = 256


DEFAULT_TILES = TileConfig()

_FIELDS = tuple(f.name for f in dataclasses.fields(TileConfig))


def _sublane(itemsize: int) -> int:
    return {1: 32, 2: 16}.get(itemsize, 8)


def _pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def shape_bucket(n: int, m: Optional[int] = None,
                 k: Optional[int] = None) -> str:
    """Power-of-two shape bucket, ``*`` for dims unknown at the call site
    (e.g. the factor rank during operand ingest)."""
    parts = [f"n{_pow2(n)}"]
    parts.append(f"m{_pow2(m)}" if m is not None else "m*")
    parts.append(f"k{_pow2(k)}" if k is not None else "k*")
    return "-".join(parts)


def device_kind() -> str:
    """Normalized accelerator identity for the ledger key: the first
    device's ``device_kind`` lower-cased with spaces joined by ``_`` (a
    TPU v5e reports ``TPU v5 lite``, so its key is ``tpu_v5_lite``; the
    CPU reports ``cpu``).  A failed probe raises."""
    import jax

    return "_".join(jax.devices()[0].device_kind.lower().split())


def ledger_path() -> Path:
    env = os.environ.get(_LEDGER_ENV)
    if env:
        return Path(env)
    return Path(__file__).with_name("autotune_ledger.json")


_LEDGER_CACHE: Dict[Tuple[str, float], dict] = {}


def load_ledger(path: Optional[Path] = None) -> dict:
    """Parsed ledger (``{}`` entries when the file is absent/invalid),
    cached per (path, mtime) so trace-time resolution costs no I/O."""
    path = Path(path) if path is not None else ledger_path()
    try:
        mtime = path.stat().st_mtime
    except OSError:
        return {"entries": {}}
    key = (str(path), mtime)
    if key not in _LEDGER_CACHE:
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}
        if not isinstance(data.get("entries"), dict):
            data = {"entries": {}}
        _LEDGER_CACHE.clear()  # one live ledger per process is plenty
        _LEDGER_CACHE[key] = data
    return _LEDGER_CACHE[key]


def _entry_to_tiles(entry: dict) -> TileConfig:
    kw = {f: int(entry[f]) for f in _FIELDS if f in entry}
    return dataclasses.replace(DEFAULT_TILES, **kw)


def resolve_tiles(n: int, m: Optional[int] = None, k: Optional[int] = None,
                  device: Optional[str] = None) -> TileConfig:
    """Ledger lookup for the call-site shape: the most specific matching
    bucket wins (``n-m-k``, then ``n-m-k*``, then ``n-m*-k*``); no match
    falls back to :data:`DEFAULT_TILES` — the interpret-mode-safe default.
    """
    ledger = load_ledger()
    entries = ledger["entries"]
    dev = device if device is not None else device_kind()
    for bucket in (shape_bucket(n, m, k),
                   shape_bucket(n, m, None),
                   shape_bucket(n, None, None)):
        entry = entries.get(f"{dev}/{bucket}")
        if entry:
            return _entry_to_tiles(entry)
    return DEFAULT_TILES


# ---------------------------------------------------------------------------
# Legality pre-filter (the pallas-tiles IR pass rules, applied up front)
# ---------------------------------------------------------------------------

def spmm_working_set(bm: int, bk: int, kb: int, itemsize: int = 4) -> int:
    """Per-step VMEM bytes of the separate ``bsr_spmm`` kernel: one (bm,
    bk) tile + one (bk, kb) dense slab + one (bm, kb) accumulator."""
    return (bm * bk + bk * kb + bm * kb) * itemsize


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


#: bytes of tiles a fused grid step aims to hold: a step has a fixed cost
#: of about 0.35 us on a TPU v5e, so one 64 KiB tile a step (0.08 us of
#: HBM time) leaves most of the bandwidth idle, and ~1 MiB hides it
FUSED_STEP_BYTES = 1 << 20


def fused_working_set(bm: int, bk: int, k: int, m: int, slots: int = 1,
                      itemsize: int = 4) -> int:
    """VMEM bytes of the fused spmm+gram kernel over an operand with ``m``
    columns, as Mosaic lays them out: the whole (k, m) factor resident once
    (lane-dense, rows padded to the sublane tile, columns to whole
    ``bk`` blocks and 128 lanes), ``slots`` (bm, bk) tiles and the (bm, k)
    output block double-buffered (the block lane-padded to 128), and the
    f32 (k, k) Gram."""
    lanes = _round_up(_round_up(m, bk), 128)
    factor = _round_up(k, _sublane(itemsize)) * lanes * itemsize
    tiles = 2 * slots * bm * bk * itemsize
    out = 2 * bm * _round_up(k, 128) * itemsize
    gram = 2 * _round_up(k, 8) * _round_up(k, 128) * 4
    return factor + tiles + out + gram


def fused_slots(bm: int, bk: int, k: int, m: int, bcap: int,
                itemsize: int = 4) -> int:
    """Tiles the fused kernel takes a grid step: about
    :data:`FUSED_STEP_BYTES` of them, no more than ``bcap``, spread evenly
    over the steps a row-block needs, and fewer where the resident factor
    leaves too little of :data:`VMEM_BUDGET`.  0 when not even one tile
    fits beside the factor: the fused kernel cannot run."""
    bcap = max(bcap, 1)
    slots = min(bcap, max(1, FUSED_STEP_BYTES // (bm * bk * itemsize)))
    while slots and fused_working_set(bm, bk, k, m, slots,
                                      itemsize) > VMEM_BUDGET:
        slots //= 2
    if slots:
        slots = -(-bcap // -(-bcap // slots))  # even out the last step
    return slots


#: default sweep grid — every value is a 128-lane multiple so the minor-dim
#: rule holds by construction
_CANDIDATE_DIMS = (128, 256, 512)


def legal_candidates(
    n: int, m: int, k: int, itemsize: int = 4,
    candidates: Optional[Iterable[Tuple[int, int, int]]] = None,
) -> List[Tuple[int, int, int]]:
    """(bm, bk, kb) triples passing the ``pallas-tiles`` legality rules:

    * minor block dims (bk for the tile, kb for the dense slab) must be
      128-lane multiples — full-extent exemptions are the *kernel's* doing
      (a factor no wider than kb is one full-width k block), so the
      pre-filter stays conservative;
    * second-minor dims (bm, bk) must be sublane multiples for the dtype;
    * the double-buffered working set of both the separate kernel and the
      fused spmm+gram kernel must fit :data:`VMEM_BUDGET`.
    """
    if candidates is None:
        candidates = [(bm, bk, kb)
                      for bm in _CANDIDATE_DIMS
                      for bk in _CANDIDATE_DIMS
                      for kb in _CANDIDATE_DIMS]
    sub = _sublane(itemsize)
    out = []
    for bm, bk, kb in candidates:
        if bm <= 0 or bk <= 0 or kb <= 0:
            continue
        if bk % 128 or kb % 128:
            continue  # minor-dim 128-lane rule
        if bm % sub or bk % sub:
            continue  # second-minor sublane rule
        if bm > 2 * max(n, 1) or bk > 2 * max(m, 1):
            continue  # block larger than the (padded) operand is all padding
        if 2 * spmm_working_set(bm, bk, kb, itemsize) > VMEM_BUDGET:
            continue
        if fused_working_set(bm, bk, k, m, 1, itemsize) > VMEM_BUDGET:
            continue
        out.append((bm, bk, kb))
    return out


# ---------------------------------------------------------------------------
# The sweep
# ---------------------------------------------------------------------------

def _timed_us(fn, *args, repeats: int = 3) -> float:
    import jax

    out = fn(*args)
    jax.block_until_ready(out)  # compile / warm
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e6


def _roofline_us(n: int, m: int, k: int, bm: int, bk: int, bcap: int,
                 itemsize: int = 4) -> float:
    """Analytic lower bound for the fused half-step product on this device
    class: max(compute, memory) time from the benchmarks/roofline.py
    constants.  The sweep records it next to the measured numbers so a
    ledger entry documents how far off the roof it sits."""
    nrb = -(-n // bm)
    flops = 2.0 * nrb * bcap * bm * bk * k       # spmm MXU work
    flops += 2.0 * nrb * bcap * bk * k * k       # gram accumulate
    bytes_moved = (nrb * bcap * bm * bk + m * k + n * k) * itemsize
    return max(flops / PEAK_FLOPS, bytes_moved / HBM_BW) * 1e6


def autotune(
    n: int, m: int, k: int, *,
    density: float = 0.05,
    bcap: Optional[int] = None,
    repeats: int = 3,
    seed: int = 0,
    force: bool = False,
) -> dict:
    """Sweep the legal (bm, bk, kb) candidates on a synthetic operand and
    return the winning ledger entry.

    Off-TPU (interpret mode) the sweep would time the Python interpreter,
    not the MXU, so unless ``force`` is set it returns the defaults tagged
    ``source: default-fallback`` without timing anything.
    """
    import jax
    import numpy as np

    base = {f: getattr(DEFAULT_TILES, f) for f in _FIELDS}
    if jax.default_backend() != "tpu" and not force:
        return dict(base, source="default-fallback",
                    note="non-TPU backend: interpret-mode timings are not "
                         "tuning facts; pass force=True to sweep anyway")

    from repro.kernels.bsr import bsr_from_dense
    from repro.kernels.bsr_spmm import bsr_spmm
    from repro.kernels.fused import bsr_spmm_gram

    rng = np.random.default_rng(seed)
    a = rng.random((n, m)).astype(np.float32)
    a[rng.random((n, m)) > density] = 0
    u = jax.numpy.asarray(rng.standard_normal((m, k)).astype(np.float32))
    interpret = jax.default_backend() != "tpu"

    records, best = [], None
    for bm, bk, kb in legal_candidates(n, m, k):
        bsr = bsr_from_dense(a, bm=bm, bk=bk, bcap=bcap)
        fused_us = _timed_us(
            lambda b, x: bsr_spmm_gram(b, x, interpret=interpret),
            bsr, u, repeats=repeats)
        spmm_us = _timed_us(
            lambda b, x: bsr_spmm(b, x, kb=kb, interpret=interpret),
            bsr, u, repeats=repeats)
        rec = {"bm": bm, "bk": bk, "kb": kb,
               "fused_us": fused_us, "spmm_us": spmm_us,
               "roofline_us": _roofline_us(n, m, k, bm, bk, bsr.bcap)}
        records.append(rec)
        if best is None or rec["fused_us"] < best["fused_us"]:
            best = rec
    if best is None:  # no legal candidate (degenerate shape)
        return dict(base, source="default-fallback",
                    note="no legal candidate for this shape")
    return dict(base, **{f: best[f] for f in ("bm", "bk", "kb")},
                source="autotune", fused_us=best["fused_us"],
                spmm_us=best["spmm_us"], roofline_us=best["roofline_us"],
                swept=len(records))


def update_ledger(key: str, entry: dict, path: Optional[Path] = None) -> Path:
    """Merge one entry into the ledger file (created if absent)."""
    path = Path(path) if path is not None else ledger_path()
    data = {"_comment": "Autotuned Pallas tile sizes per "
                        "(device-kind, shape-bucket).  Regenerate on new "
                        "hardware with: python -m repro.kernels.autotune",
            "entries": {}}
    if path.exists():
        loaded = load_ledger(path)
        data["entries"] = dict(loaded.get("entries", {}))
        if "_comment" in loaded:
            data["_comment"] = loaded["_comment"]
    data["entries"][key] = entry
    data["entries"] = dict(sorted(data["entries"].items()))
    with open(path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=False)
        f.write("\n")
    _LEDGER_CACHE.clear()
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="sweep Pallas tile candidates and record the winner in "
                    "the autotune ledger")
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--m", type=int, default=2048)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--density", type=float, default=0.05)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--force", action="store_true",
                    help="sweep even off-TPU (interpret-mode wall time — "
                         "not a tuning fact; for plumbing tests only)")
    ap.add_argument("--out", default=None,
                    help="ledger path (default: the committed package "
                         "ledger, or $REPRO_AUTOTUNE_LEDGER)")
    args = ap.parse_args(argv)

    entry = autotune(args.n, args.m, args.k, density=args.density,
                     repeats=args.repeats, force=args.force)
    dev = device_kind()
    keys = [f"{dev}/{shape_bucket(args.n, args.m, args.k)}",
            f"{dev}/{shape_bucket(args.n, args.m, None)}"]
    path = Path(args.out) if args.out else None
    for key in keys:
        path = update_ledger(key, entry, path)
    print(json.dumps({"ledger": str(path), "keys": keys, "entry": entry},
                     indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
