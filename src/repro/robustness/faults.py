"""Deterministic fault injection: the chaos harness behind the robustness
test suite.

A *fault* is a (site, key, times) triple armed in a process-wide registry.
Library code consults the registry at a handful of instrumented sites and,
when a matching armed fault is found, simulates the failure *at that exact
point* — so "chunk 3's mmap read fails once", "iteration 20 goes NaN", or
"the prefetch worker dies mid-stream" are reproducible statements a test
can make, not races it hopes to win.  With no faults armed every hook is a
dict lookup returning immediately, so production paths pay nothing.

Instrumented sites (each names the ``key`` it is consulted with):

* ``"chunk-load"`` — corpus chunk loads (key = chunk index).
  :meth:`~repro.data.corpus.MmapCorpus.load` and
  :class:`~repro.data.corpus.ResidentChunks` fire an :class:`InjectedIOError`
  (an ``OSError``), which the :class:`~repro.data.corpus.Prefetcher` retry
  policy treats as transient I/O.
* ``"corrupt-shard"`` — :meth:`MmapCorpus.load` flips the loaded shard's
  bytes (key = shard index), so checksum validation must catch it.
* ``"poison-step"`` — the solver drivers NaN-poison the factor entering
  iteration/chunk ``key``, so the in-engine health monitor must flag it
  and the driver must roll back.
* ``"prefetch-worker"`` — the prefetch worker thread exits *silently*
  before packing item ``key`` (no error, no done sentinel), so the
  consumer-side dead-worker watchdog must notice.
* ``"kill"`` — :meth:`~repro.robustness.snapshot.FitCheckpointer.save`
  hard-exits the process (``os._exit``) right after committing checkpoint
  ``key`` — the kill-mid-fit resume tests' guillotine.  Arm it with
  ``exc=SomeError`` to raise instead of exiting (in-process interruption).

Faults are deterministic: a fault fires exactly ``times`` times at its
site/key and is then exhausted.  The registry is thread-safe (the prefetch
worker consults it off-thread) and test-scoped via the :func:`injected`
context manager or ``clear()``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
from typing import List, Optional

import numpy as np

__all__ = [
    "Fault", "InjectedFault", "InjectedIOError", "KILL_EXIT", "active",
    "clear", "fire", "inject", "injected", "install", "maybe_kill",
    "poison", "should_fire", "uninstall",
]

#: exit status of a ``"kill"``-site hard exit — subprocess tests assert on
#: it to distinguish the injected kill from an ordinary crash
KILL_EXIT = 73


class InjectedFault(RuntimeError):
    """Raised by a fired fault with no specific exception class."""


class InjectedIOError(OSError):
    """The ``"chunk-load"`` site's transient-I/O stand-in (an ``OSError``,
    so retry policies classify it exactly like a real flaky read)."""


@dataclasses.dataclass
class Fault:
    """One armed fault.  ``key=None`` matches any key at the site;
    ``times`` is how many firings remain before it is exhausted."""

    site: str
    key: Optional[int] = None
    times: int = 1
    #: exception instance/class to raise when fired; ``None`` picks the
    #: site default (``InjectedIOError`` for "chunk-load", else
    #: ``InjectedFault``).  For the "kill" site a non-None ``exc`` raises
    #: instead of hard-exiting.
    exc: Optional[object] = None
    fired: int = 0

    def matches(self, site: str, key) -> bool:
        return (self.site == site and self.times > self.fired
                and (self.key is None or key is None or self.key == key))

    def make_exc(self) -> BaseException:
        if self.exc is None:
            cls = InjectedIOError if self.site == "chunk-load" else InjectedFault
            return cls(f"injected fault at site {self.site!r} "
                       f"(key={self.key}, firing {self.fired}/{self.times})")
        if isinstance(self.exc, BaseException):
            return self.exc
        return self.exc(f"injected fault at site {self.site!r}")


_LOCK = threading.Lock()
_FAULTS: List[Fault] = []


def install(site: str, key: Optional[int] = None, times: int = 1,
            exc: Optional[object] = None) -> Fault:
    """Arm a fault; returns it (pass to :func:`uninstall`)."""
    fault = Fault(site=site, key=key, times=int(times), exc=exc)
    with _LOCK:
        _FAULTS.append(fault)
    return fault


def uninstall(fault: Fault) -> None:
    with _LOCK:
        if fault in _FAULTS:
            _FAULTS.remove(fault)


def clear() -> None:
    """Disarm every fault (test teardown)."""
    with _LOCK:
        _FAULTS.clear()


def active() -> List[Fault]:
    with _LOCK:
        return list(_FAULTS)


@contextlib.contextmanager
def injected(*faults: Fault):
    """Scope already-built :class:`Fault` objects to a ``with`` block."""
    with _LOCK:
        _FAULTS.extend(faults)
    try:
        yield list(faults)
    finally:
        with _LOCK:
            for f in faults:
                if f in _FAULTS:
                    _FAULTS.remove(f)


@contextlib.contextmanager
def inject(site: str, key: Optional[int] = None, times: int = 1,
           exc: Optional[object] = None):
    """Arm one fault for the duration of a ``with`` block."""
    fault = install(site, key=key, times=times, exc=exc)
    try:
        yield fault
    finally:
        uninstall(fault)


def _claim(site: str, key) -> Optional[Fault]:
    with _LOCK:
        for fault in _FAULTS:
            if fault.matches(site, key):
                fault.fired += 1
                return fault
    return None


def should_fire(site: str, key=None) -> bool:
    """Consume one firing of a matching armed fault, if any.  The hook for
    sites that simulate the failure themselves (silent worker death, byte
    corruption) rather than raising."""
    return _claim(site, key) is not None


def fire(site: str, key=None) -> None:
    """Raise the matching armed fault's exception, if any; no-op otherwise."""
    fault = _claim(site, key)
    if fault is not None:
        raise fault.make_exc()


def poison(site: str, key, x):
    """Return ``x`` with NaN injected when a matching fault is armed;
    ``x`` unchanged (same object, zero overhead) otherwise."""
    if _claim(site, key) is None:
        return x
    import jax.numpy as jnp

    flat = jnp.ravel(jnp.asarray(x))
    flat = flat.at[: max(1, flat.shape[0] // 97)].set(jnp.nan)
    return flat.reshape(np.shape(x))


def maybe_kill(site: str, key=None) -> None:
    """Hard-exit the process (status :data:`KILL_EXIT`) when a matching
    fault is armed — or raise, if the fault carries an ``exc``.  Placed
    after checkpoint commits so kill-mid-fit tests die at a precise,
    resumable point."""
    fault = _claim(site, key)
    if fault is None:
        return
    if fault.exc is not None:
        raise fault.make_exc()
    os._exit(KILL_EXIT)
