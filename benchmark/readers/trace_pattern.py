"""Share (%) of the device's busy time in operations whose names match any
of ``patterns`` (regular expressions, kept as data in the metric's file)."""

from __future__ import annotations


def read(params, ev):
    r = ev.trace
    if r is None:
        return None
    busy = sum(r.busy_s_by_chip.values())
    if busy <= 0:
        return None
    return 100.0 * r.pattern_seconds(params["patterns"]) / busy
