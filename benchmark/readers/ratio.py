"""A product of named numbers over a product of named numbers, times
``scale`` — counters, constants of the cell and this run's end-to-end
values (``Evidence.numbers``). ``{"num": [...], "den": [...], "scale": 1}``.
Missing names or a zero denominator read as nothing."""

from __future__ import annotations


def read(params, ev):
    def product(names):
        out = 1.0
        for name in names:
            if name not in ev.numbers:
                return None
            out *= ev.numbers[name]
        return out

    num, den = product(params.get("num", [])), product(params.get("den", []))
    if num is None or den is None or den == 0:
        return None
    return params.get("scale", 1.0) * num / den
