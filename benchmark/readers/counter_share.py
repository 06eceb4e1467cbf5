"""Sums of named numbers over a sum of named numbers, times ``scale`` —
what ``ratio`` (a product over a product) cannot say: a share of several
counters, or ``1 - x``. ``{"plus": [...], "minus": [...], "over": [...],
"scale": 100.0}`` reads ``scale * (sum(plus) - sum(minus)) / sum(over)``
from ``Evidence.numbers`` (``engine.<key>`` is the difference of
``LMEngine.stats[<key>]`` over the window). A missing name — a program
without that counter — or a zero denominator reads as nothing."""

from __future__ import annotations


def read(params, ev):
    def add(names):
        if any(name not in ev.numbers for name in names):
            return None
        return sum(ev.numbers[name] for name in names)

    plus, minus, over = (add(params.get(k, [])) for k in ("plus", "minus", "over"))
    if plus is None or minus is None or not over:
        return None
    return params.get("scale", 1.0) * (plus - minus) / over
