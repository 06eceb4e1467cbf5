"""The device's own time per program, from the trace's ``XLA Modules`` line
(one event per executed program, named ``jit_<function>(<fingerprint>)``):
``{"module": "<regular expression>", "what": ..., "reduce": "p50"}``.
``what`` ``"duration_ms"`` (the default) reduces the durations of the
matching programs that ran wholly inside the traced window, each divided by
``"per_number"`` where given (``"context.chunk_steps"``: a decode chunk is
that many steps); ``"window_share"`` is the % of the window (times chips) in
which a matching program ran, events cut at the window's edges. The pattern
is data of the metric's file, with a ``patterns_why``. No matching program,
or no trace, reads as nothing."""

from __future__ import annotations

import re

from benchmark.evidence import reduce_samples
from benchmark.trace_reduce import clip, total, union


def read(params, ev):
    r = ev.trace
    if r is None:
        return None
    what = params.get("what", "duration_ms")
    if what == "window_share":
        rx = re.compile(params["module"])
        lo, hi = r.window
        inside = [
            total(clip(union((e.start, e.end) for e in events if rx.search(e.name)), lo, hi))
            for events in r.modules_by_chip.values()
        ]
        if not any(inside):
            return None
        return 100.0 * sum(inside) / (r.window_s * r.chips)
    if what != "duration_ms":
        raise ValueError(f"unknown trace_module reading {what!r}")
    div = 1.0
    if "per_number" in params:
        if params["per_number"] not in ev.numbers:
            return None
        div = ev.numbers[params["per_number"]]
    values = [
        1e3 * e.dur / div
        for events in r.module_events(params["module"]).values() for e in events
    ]
    return reduce_samples(values, params.get("reduce", "p50"))
