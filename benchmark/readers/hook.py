"""A per-step metric the trainer hands to ``fit(hooks=)``, reduced over the
steps of the window: ``{"key": "device_step_ms", "reduce": "p50"}``."""

from __future__ import annotations

from benchmark.evidence import reduce_samples


def read(params, ev):
    values = [s[params["key"]] for s in ev.hook_steps if params["key"] in s]
    return reduce_samples(values, params.get("reduce", "p50"))
