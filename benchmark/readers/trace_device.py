"""Whole-device numbers of the trace: ``{"what": "idle_share"}`` (%),
``"collective_in_flight_share"`` / ``"collective_exposed_share"`` (% of the
traced window, averaged over chips)."""

from __future__ import annotations


def read(params, ev):
    r = ev.trace
    if r is None:
        return None
    what = params["what"]
    if what == "idle_share":
        return 100.0 * r.idle_share
    in_flight, exposed = r.collective_seconds()
    if what == "collective_in_flight_share":
        return 100.0 * in_flight / r.window_s
    if what == "collective_exposed_share":
        return 100.0 * exposed / r.window_s
    raise ValueError(f"unknown trace_device reading {what!r}")
