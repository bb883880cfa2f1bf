"""device_idle_pct.fit: the share of the traced fit window in which no
operation ran on the device, in %."""

from bench.trace import idle_pct


def read(rec):
    return idle_pct(rec.trace) if "fits" in rec.window else None
