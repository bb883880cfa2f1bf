"""Runtime contract layer: compiled-executable ground truth.

The static rules prove call *shapes* can't thrash the executable cache and
the IR planner *estimates* peak memory; this module asserts the compiler's
own counters — the dynamic complement of both.

``recompile_guard``: jax emits the monitoring event
``/jax/core/compile/backend_compile_duration`` exactly once per real
backend (XLA) compilation and never on an executable-cache hit, so
counting it is ground truth — no probing of private cache sizes, no
heuristics over trace counts::

    with recompile_guard():                # 0 compiles allowed
        model.fit(a)                       # second identical fit: free

    with recompile_guard(max_compiles=2) as counter:
        cold_path()
    assert counter.count <= 2

``memory_guard``: reads ``compiled.memory_analysis()`` — XLA's own
temp/argument/output byte accounting for an executable — and optionally
gates the temp bytes against a budget.  ``benchmarks/fig6_memory.py``
records these numbers next to the IR planner's, closing the loop between
the static ledger and what the allocator actually reserves::

    report = memory_guard(jitted_fn, *args, max_temp_bytes=1 << 30)
    print(report.temp_bytes, report.argument_bytes)

On a backend whose executables expose no memory stats, ``memory_guard``
raises unless ``allow_unsupported=True``, and its degraded report then has
``supported=False`` (callers should skip, not pass).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Iterator, List, Optional

__all__ = ["recompile_guard", "CompilationCounter", "RecompilationError",
           "COMPILE_EVENT", "memory_guard", "MemoryReport",
           "MemoryBudgetError"]

#: fired once per backend_compile; cache hits never emit it
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class RecompilationError(AssertionError):
    """More XLA compilations happened inside a guard than allowed."""


@dataclasses.dataclass
class CompilationCounter:
    """Live tally of backend compilations observed inside a guard."""

    count: int = 0
    events: List[str] = dataclasses.field(default_factory=list)

    def _observe(self, event: str) -> None:
        self.count += 1
        self.events.append(event)


@contextlib.contextmanager
def recompile_guard(max_compiles: int = 0) -> Iterator[CompilationCounter]:
    """Fail if the block triggers more than ``max_compiles`` XLA
    compilations.

    Yields the :class:`CompilationCounter` so callers can also assert
    exact counts (positive controls) or inspect the observed events.  The
    check runs at block exit; an exception already propagating wins over
    the guard's own error.
    """
    from jax._src import monitoring

    counter = CompilationCounter()

    def _listener(event: str, duration_secs: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            counter._observe(event)

    monitoring.register_event_duration_secs_listener(_listener)
    try:
        yield counter
    finally:
        monitoring.unregister_event_duration_listener(_listener)
    if counter.count > max_compiles:
        raise RecompilationError(
            f"{counter.count} XLA compilation(s) inside a "
            f"recompile_guard(max_compiles={max_compiles}) block — "
            "something is thrashing the executable cache (fresh "
            "lambda/partial into jit, unstable static args, or changing "
            "avals)")


# ---------------------------------------------------------------------------
# memory_guard: XLA's own byte accounting for a compiled executable
# ---------------------------------------------------------------------------

class MemoryBudgetError(AssertionError):
    """A compiled executable's temp allocation exceeds the stated budget."""


@dataclasses.dataclass(frozen=True)
class MemoryReport:
    """``compiled.memory_analysis()`` distilled: what the allocator
    reserves for one executable, in bytes."""

    supported: bool
    temp_bytes: int = 0        # scratch the executable allocates itself
    argument_bytes: int = 0    # inputs held live across the call
    output_bytes: int = 0
    alias_bytes: int = 0       # donated/aliased bytes (in-place updates)
    generated_code_bytes: int = 0
    reason: Optional[str] = None  # why unsupported, when it is

    @property
    def peak_bytes(self) -> int:
        """Upper bound comparable to the IR planner's peak: everything the
        call holds at once, minus what donation lets it reuse."""
        return (self.temp_bytes + self.argument_bytes + self.output_bytes
                - self.alias_bytes)


def memory_guard(fn, *args, max_temp_bytes: Optional[int] = None,
                 allow_unsupported: bool = False, **kwargs) -> MemoryReport:
    """Compile ``fn(*args, **kwargs)`` (AOT — nothing executes) and return
    XLA's memory accounting, optionally failing if the executable's temp
    allocation exceeds ``max_temp_bytes``.

    ``fn`` may be an already-jitted callable (anything with ``.lower``) or
    a plain function, which is wrapped in ``jax.jit`` first.  Compilation
    hits jax's executable cache, so guarding a function that later runs
    costs one compile total, not two.
    """
    import jax

    target = fn if hasattr(fn, "lower") else jax.jit(fn)
    try:
        compiled = target.lower(*args, **kwargs).compile()
        stats = compiled.memory_analysis()
    except Exception as e:  # Pallas off-TPU, backends without stats, ...
        if allow_unsupported:
            return MemoryReport(supported=False,
                                reason=f"{type(e).__name__}: {e}")
        raise
    if stats is None:
        if allow_unsupported:
            return MemoryReport(supported=False,
                                reason="memory_analysis() returned None")
        raise RuntimeError(
            "this backend's executables expose no memory_analysis(); pass "
            "allow_unsupported=True to degrade (and skip the assertion "
            "yourself)")
    report = MemoryReport(
        supported=True,
        temp_bytes=int(getattr(stats, "temp_size_in_bytes", 0)),
        argument_bytes=int(getattr(stats, "argument_size_in_bytes", 0)),
        output_bytes=int(getattr(stats, "output_size_in_bytes", 0)),
        alias_bytes=int(getattr(stats, "alias_size_in_bytes", 0)),
        generated_code_bytes=int(
            getattr(stats, "generated_code_size_in_bytes", 0)),
    )
    if max_temp_bytes is not None and report.temp_bytes > max_temp_bytes:
        raise MemoryBudgetError(
            f"compiled executable allocates {report.temp_bytes} temp bytes, "
            f"over the {max_temp_bytes}-byte budget — a densified "
            "intermediate or a dropped donation, most likely")
    return report
