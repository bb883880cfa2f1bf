"""Reading the ALS engine's named scopes out of compiled HLO text, for the
tracing tests (on the CPU) and the chip-compile tests (for a described
TPU)."""
import re

#: the engine's named scopes, as ``op_name`` path components
ENGINE_SCOPES = ("als.v/product", "als.v/solve", "als.v/topk",
                 "als.u/product", "als.u/solve", "als.u/topk",
                 "als.error", "als.health")
SCOPE = re.compile(r"/(als\.[uv]/(?:product|solve|topk)|als\.error"
                   r"|als\.health)(?:/|$)")
#: the online engine's named scopes (``online_als_step``)
ONLINE_SCOPES = ("online.v/product", "online.v/solve", "online.v/topk",
                 "online.u/product", "online.u/solve", "online.u/topk",
                 "online.health")
ONLINE_SCOPE = re.compile(r"/(online\.[uv]/(?:product|solve|topk)"
                          r"|online\.health)(?:/|$)")


def op_name(line: str) -> str:
    """The ``op_name`` metadata of one HLO instruction line, or ``""``."""
    found = re.search(r'op_name="([^"]*)"', line)
    return found.group(1) if found else ""


def computations(text: str) -> dict:
    """HLO text -> {computation name: its instruction lines}."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def loop_body(text: str) -> list:
    """The instruction lines of the ALS scan's loop body: the body of the
    one ``while`` whose ``op_name`` ends in ``jit(als_nmf)/while``."""
    comps = computations(text)
    scan = [line for lines in comps.values() for line in lines
            if re.search(r"\swhile\(", line)
            and re.search(r"jit\(als_nmf\)/while$", op_name(line))]
    assert len(scan) == 1, scan
    return comps[re.search(r"body=%([\w.\-]+)", scan[0]).group(1)]


def dots(text: str) -> list:
    """The matrix products of compiled HLO text: on a TPU a ``dot`` becomes
    a ``convolution`` (or stays a ``dot``)."""
    return [line for line in text.splitlines()
            if re.search(r"\s(convolution|dot)\(", line)]


def at_highest(line: str) -> bool:
    """True where a product's operands contract at full float32 precision
    (``Precision.HIGHEST``); at the default the compiler prints no
    ``operand_precision``."""
    return "operand_precision={highest,highest}" in line
