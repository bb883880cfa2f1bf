"""Fit checkpointing: periodic atomic snapshots + fingerprinted resume.

:class:`FitCheckpointer` is the solver-facing wrapper over
:mod:`repro.checkpoint.store`.  A fit configured with
``NMFConfig(checkpoint_dir=...)`` saves an atomic snapshot every
``checkpoint_every`` iterations (or streaming chunks): the factor state,
the host-side progress histories, and a *fingerprint* of the config and
input operand.  ``resume=True`` restores the newest complete snapshot —
but only after the fingerprint matches, so a checkpoint directory left
over from a different corpus, rank, or sparsity budget refuses to resume
instead of silently continuing the wrong run.

What the fingerprint pins vs. what it deliberately ignores:

* **Pinned** — rank ``k``, sparsity spec, solver, dtype, seed, block size,
  chunk width, and the input operand (shape + a sampled content digest; for
  on-disk corpora the manifest identity incl. per-shard checksums).
  Changing any of these makes the saved trajectory meaningless.
* **Ignored** — ``iters`` (resuming with a larger budget is the point),
  ``tol``, ``mesh_shape`` (snapshots are saved gathered and restored with
  ``device_put(x, sharding)`` against the *current* mesh, so a 2x2 fit may
  resume on 4x1 — elastic restart), ``backend`` (every backend computes
  the same factorization, so a run may resume on another), prefetch knobs,
  and the checkpoint settings themselves.

Array state rides in the store's npz payload; host-side scalars, histories
and the fingerprint ride in the manifest's ``meta`` dict (strings cannot
survive the array path).
"""
from __future__ import annotations

import dataclasses
import json
import os
import zlib
from typing import Any, Dict, Optional, Tuple

import numpy as np

from repro.checkpoint import store
from repro.robustness import faults

__all__ = [
    "CheckpointMismatchError", "FitHealthError", "FitCheckpointer",
    "config_fingerprint", "data_fingerprint",
]


class CheckpointMismatchError(RuntimeError):
    """``resume=True`` found a checkpoint whose fingerprint disagrees with
    the current config/input — refusing to continue the wrong run."""


class FitHealthError(RuntimeError):
    """A fit went unhealthy (non-finite factors / exploding residual) and
    could not be recovered within the rollback budget."""


def _crc(x) -> int:
    """Sampled content digest: crc32 over up to ~1 MiB of the raw bytes,
    strided so both ends of the buffer participate.  Cheap enough to run
    on every fit, strong enough to catch "same shape, different corpus"."""
    a = np.ascontiguousarray(x)
    raw = a.view(np.uint8).ravel()
    if raw.nbytes > (1 << 20):
        stride = raw.nbytes // (1 << 20) + 1
        raw = np.ascontiguousarray(raw[::stride])
    return zlib.crc32(raw.tobytes())


def config_fingerprint(config) -> Dict[str, Any]:
    """The run-identity slice of an ``NMFConfig`` (see module docstring for
    the pinned/ignored split)."""
    return {
        "k": int(config.k),
        "sparsity": dataclasses.asdict(config.sparsity),
        "solver": config.solver,
        "dtype": str(config.dtype),
        "seed": int(config.seed),
        "block_size": int(config.block_size),
        "chunk_docs": (None if config.chunk_docs is None
                       else int(config.chunk_docs)),
    }


def data_fingerprint(a) -> Dict[str, Any]:
    """Identity of the input operand: shape plus a content digest.

    * on-disk corpora (``MmapCorpus``) — the manifest identity: shape,
      chunk width, slot cap, shard count, and a digest of the manifest
      itself (which, in the v2 layout, carries every shard's checksum —
      so the corpus *content* is transitively pinned without re-reading
      the shards);
    * other ``ChunkSource``s — shape + schedule (resident chunk sources
      are rebuilt from the live matrix each run; the matrix itself was
      already in-process, so a digest of the first chunk suffices);
    * ``SpCSR`` — shape + sampled digests of the values/cols grids;
    * dense (numpy / jax) — shape, dtype, sampled digest.
    """
    from repro.data.corpus import ChunkSource, MmapCorpus
    from repro.sparse.csr import SpCSR

    if isinstance(a, MmapCorpus):
        manifest = json.dumps(
            {"shape": list(a.shape), "chunk_docs": a.chunk_docs,
             "cap": a.cap, "chunks": getattr(a, "checksums", None)
             or len(a.schedule)},
            sort_keys=True)
        return {"kind": "corpus", "shape": list(a.shape),
                "chunk_docs": int(a.chunk_docs), "cap": int(a.cap),
                "n_chunks": len(a.schedule),
                "digest": zlib.crc32(manifest.encode())}
    if isinstance(a, ChunkSource):
        first = a.load(0)
        if isinstance(first, SpCSR):
            digest = _crc(np.asarray(first.values)) ^ _crc(
                np.asarray(first.cols))
        else:
            digest = _crc(np.asarray(first))
        return {"kind": "chunks", "shape": list(a.shape),
                "chunk_docs": int(a.chunk_docs),
                "n_chunks": len(a.schedule), "digest": int(digest)}
    if isinstance(a, SpCSR):
        return {"kind": "spcsr", "shape": list(a.shape),
                "digest": int(_crc(np.asarray(a.values))
                              ^ _crc(np.asarray(a.cols)))}
    arr = np.asarray(a)
    return {"kind": "dense", "shape": list(arr.shape),
            "dtype": str(arr.dtype), "digest": int(_crc(arr))}


class FitCheckpointer:
    """Solver-side checkpoint driver for one fit.

    * ``save(done, arrays, **meta)`` — atomic snapshot after ``done``
      completed iterations/chunks.  ``arrays`` is a flat name->array dict
      (saved gathered via the store); ``meta`` holds host-side scalars and
      history lists.  The snapshot is also cached in memory as
      :attr:`last`, so health-guard rollback needs no disk round trip.
      After the commit the ``"kill"`` fault site fires — the chaos tests'
      precise guillotine.
    * ``resume()`` — ``(done, arrays, meta)`` of the newest complete
      snapshot, fingerprint-checked; ``None`` when the directory holds no
      checkpoint yet (a fresh run with ``resume=True`` just starts over).
    """

    def __init__(self, ckpt_dir: str, every: int, fingerprint: Dict[str, Any]):
        self.ckpt_dir = str(ckpt_dir)
        self.every = int(every)
        self.fingerprint = fingerprint
        #: (done, arrays, meta) of the most recent save/resume, in memory
        self.last: Optional[Tuple[int, Dict[str, np.ndarray], dict]] = None

    @classmethod
    def from_config(cls, config, a) -> Optional["FitCheckpointer"]:
        """``None`` when the config requests no checkpointing."""
        if config.checkpoint_dir is None:
            return None
        fp = {"config": config_fingerprint(config), "data": data_fingerprint(a)}
        return cls(config.checkpoint_dir, config.checkpoint_every, fp)

    def due(self, done: int, total: int) -> bool:
        """Snapshot boundary: every ``every`` steps, skipping the final one
        (the fit result itself supersedes a last-step snapshot)."""
        return done % self.every == 0 and 0 < done < total

    def save(self, done: int, arrays: Dict[str, Any], **meta) -> None:
        import jax

        host = {k: np.asarray(jax.device_get(v)) for k, v in arrays.items()}
        full_meta = dict(meta)
        full_meta["fingerprint"] = self.fingerprint
        store.save_checkpoint(self.ckpt_dir, done, host, meta=full_meta)
        self.last = (done, host, full_meta)
        faults.maybe_kill("kill", done)

    def resume(self) -> Optional[Tuple[int, Dict[str, np.ndarray], dict]]:
        step = store.latest_step(self.ckpt_dir)
        if step is None:
            return None
        arrays, meta = store.load_checkpoint_arrays(self.ckpt_dir, step)
        saved = (meta or {}).get("fingerprint")
        if saved != self.fingerprint:
            raise CheckpointMismatchError(
                f"checkpoint at {self.ckpt_dir} (step {step}) was written by "
                f"a different run.\n  saved:   {saved}\n  current: "
                f"{self.fingerprint}\nDelete the checkpoint directory to "
                "start fresh, or fix the config/input to match.")
        self.last = (step, arrays, meta)
        return self.last
