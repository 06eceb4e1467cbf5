"""Arithmetic on per-request samples: the percentile rule and the
latencies a client of a token stream sees.

Every latency is measured on the client's clock. In an open loop a request
is timed from when it was *due*, so the wait a stall imposes on later
requests is counted; how late the generator actually sent it is reported
beside it (``lateness``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q`` of
    the samples at or below it. No interpolation — a tail is one of the
    measured values."""
    if not samples:
        raise ValueError("percentile of no samples")
    if not 0.0 < q <= 1.0:
        raise ValueError(f"q must be in (0, 1], got {q}")
    ordered = sorted(samples)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def highest_reportable_percentile(n: int, beyond: int = 10) -> float:
    """The highest percentile with ``beyond`` samples above it (the
    choosing-metrics rule): 0.9 needs 100 samples, 0.99 needs 1000."""
    return max(0.0, 1.0 - beyond / n) if n else 0.0


@dataclasses.dataclass
class RequestSample:
    """What the client saw of one request. Times are seconds on the
    client's monotonic clock; ``None`` where the event never happened."""

    index: int
    prompt_tokens: int
    max_new_tokens: int
    t_due: float
    t_sent: float | None = None
    t_first: float | None = None
    t_last: float | None = None
    t_done: float | None = None
    n_out: int = 0
    ok: bool = False
    error: str | None = None
    trace_id: str | None = None
    token_ids: list[int] = dataclasses.field(default_factory=list)

    @property
    def lateness_s(self) -> float | None:
        return None if self.t_sent is None else self.t_sent - self.t_due

    @property
    def ttft_s(self) -> float | None:
        """Due time → first token at the client."""
        return None if self.t_first is None else self.t_first - self.t_due

    @property
    def ttft_from_send_s(self) -> float | None:
        if self.t_first is None or self.t_sent is None:
            return None
        return self.t_first - self.t_sent

    @property
    def tpot_s(self) -> float | None:
        """Mean gap between output tokens: ``(t_last - t_first) / (n - 1)``.
        Robust to tokens arriving several to a frame; undefined for a
        one-token reply."""
        if self.n_out < 2 or self.t_first is None or self.t_last is None:
            return None
        return (self.t_last - self.t_first) / (self.n_out - 1)


def ms(values: Sequence[float | None]) -> list[float]:
    return [v * 1e3 for v in values if v is not None]
