"""Durations (ms) of the program's spans of one name, reduced over the
traced requests: ``{"span": "prefill", "reduce": "p50"}``. Optional
``"per_number"`` divides each by a number of the run
(``"context.chunk_steps"``)."""

from __future__ import annotations

from benchmark.evidence import reduce_samples


def read(params, ev):
    div = 1.0
    if "per_number" in params:
        if params["per_number"] not in ev.numbers:
            return None
        div = ev.numbers[params["per_number"]]
    values = [
        (s["end_ms"] - s["start_ms"]) / div
        for trace in ev.traces for s in trace.get("spans", [])
        if s["name"] == params["span"] and s.get("status", "ok") == "ok"
    ]
    return reduce_samples(values, params.get("reduce", "p50"))
