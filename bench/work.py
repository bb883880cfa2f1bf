"""Operations and bytes that enforced-sparsity ALS needs, from nnz and
shapes.

These count the work of the algorithm, not of the program's operand
format: the sparse matrix ``A`` (n terms x m documents, ``nnz`` stored
entries) is read as one float32 value and one int32 index per entry, each
dense factor is read once per half-step and each product written once.  So
a later change of the operand format (tiles, padding, a second
orientation) changes the time a kernel takes but not the work it is held
to, and a share of the roofline cannot pass 100% unless the time leaves out
part of that work.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

VALUE_BYTES = 4    # float32
INDEX_BYTES = 4    # int32

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, times: float) -> "Work":
        return Work(self.flops * times, self.bytes * times)

    def roofline_s(self, peak: dict) -> float:
        """The least time the chip could take: the larger of operations
        over peak FLOP/s and bytes over peak bandwidth."""
        return max(self.flops / peak["flops_per_s"],
                   self.bytes / peak["hbm_bytes_per_s"])


def halfstep(nnz: int, rows_out: int, cols_in: int, k: int) -> Work:
    """One half-step's sparse product and Gram: ``Y = A' X`` with ``A'``
    (rows_out x cols_in, ``nnz`` entries) and the Gram ``X^T X`` of the
    same (cols_in, k) factor, which needs no second read of ``X``."""
    flops = 2 * nnz * k + 2 * cols_in * k * k
    nbytes = (nnz * (VALUE_BYTES + INDEX_BYTES)
              + (cols_in + rows_out) * k * VALUE_BYTES
              + k * k * VALUE_BYTES)
    return Work(float(flops), float(nbytes))


def iteration_halfsteps(n: int, m: int, nnz: int, k: int) -> Work:
    """Both half-steps of one iteration: ``A^T U`` with ``U^T U``, then
    ``A V`` with ``V^T V``."""
    return halfstep(nnz, m, n, k) + halfstep(nnz, n, m, k)


def iteration_flops(n: int, m: int, nnz: int, k: int) -> float:
    """Operations of one whole iteration: the two half-steps, applying the
    two ``k x k`` inverses to the (rows, k) right-hand sides, and the cross
    term ``<A, U V^T>`` and new ``U^T U`` that the tracked error
    ``||A - U V^T||_F`` needs.  The top-t selects compare and count and are
    not counted as operations."""
    return float(iteration_halfsteps(n, m, nnz, k).flops
                 + 2 * (n + m) * k * k + 2 * nnz * k + 2 * n * k * k)


def load_peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, not a default."""
    table = json.loads(path.read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table['devices'])}") from None
