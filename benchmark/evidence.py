"""What one run hands to the metric readers: the runner fills an
``Evidence``; each per-layer metric's reader takes its number from it, or
returns ``None`` when what it reads is not there (the harness then leaves
the metric out of the line)."""

from __future__ import annotations

import dataclasses
from typing import Any

from benchmark.stats import RequestSample, percentile


@dataclasses.dataclass
class Evidence:
    cell: dict[str, Any]
    #: numbers by dotted name: ``context.*`` (sizes and constants of the
    #: cell), ``e2e.*`` (this run's end-to-end values), ``engine.*`` /
    #: ``xla.*`` / ``client.*`` (counters, as differences over the window),
    #: ``device.*``
    numbers: dict[str, float] = dataclasses.field(default_factory=dict)
    #: per-step metrics the trainer handed to ``fit(hooks=)`` in the window
    hook_steps: list[dict[str, float]] = dataclasses.field(default_factory=list)
    #: finished traces of the program's tracer (``Tracer.snapshot`` docs)
    traces: list[dict[str, Any]] = dataclasses.field(default_factory=list)
    #: what the client saw, one per request counted in the window
    samples: list[RequestSample] = dataclasses.field(default_factory=list)
    #: the reduced device trace (``trace_reduce.Reduction``) of a traced run
    trace: Any = None
    attempted: int = 0
    failed: int = 0
    correct: bool = False
    #: every number ``correct`` was decided on, each beside its limit
    #: (``<name>_limit``): the result line's last key
    check: dict[str, float] = dataclasses.field(default_factory=dict)
    notes: dict[str, Any] = dataclasses.field(default_factory=dict)


def reduce_samples(values: list[float], how: str) -> float | None:
    if not values:
        return None
    if how == "mean":
        return sum(values) / len(values)
    if how == "max":
        return max(values)
    if how.startswith("p"):
        return percentile(values, int(how[1:]) / 100.0)
    raise ValueError(f"unknown reduction {how!r}")
