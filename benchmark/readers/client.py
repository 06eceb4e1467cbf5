"""A field of the client's per-request samples (seconds → ms), reduced
over the requests counted in the window:
``{"field": "ttft_from_send_s", "reduce": "p50"}``."""

from __future__ import annotations

from benchmark.evidence import reduce_samples
from benchmark.stats import ms


def read(params, ev):
    values = ms([getattr(s, params["field"]) for s in ev.samples if s.ok])
    return reduce_samples(values, params.get("reduce", "p50"))
