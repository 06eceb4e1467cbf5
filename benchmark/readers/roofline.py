"""A kernel family's share (%) of its roofline: the least time the chip
could take for what one step requires of it — the larger of operations
over peak FLOP/s and bytes over peak bytes/s, from ``context.<cost>_flops``
and ``context.<cost>_bytes`` (shape functions in ``benchmark/opcount.py``)
— over the time the matching operations took per step in the trace. Steps
are counted as executions of the program matching ``step_module``."""

from __future__ import annotations

from benchmark.opcount import roofline_seconds


def read(params, ev):
    r = ev.trace
    n = ev.numbers
    cost = params["cost"]
    if r is None or f"context.{cost}_flops" not in n:
        return None
    kernel_s = r.pattern_seconds(params["patterns"])
    steps = sum(len(v) for v in r.module_events(params["step_module"]).values())
    if kernel_s <= 0 or steps == 0:
        return None
    peaks = {
        "bf16_flops": n["context.peak_flops_per_chip"],
        "hbm_bytes_per_s": n["context.peak_hbm_bytes_per_s"],
    }
    # costs are per global step; each chip runs its share of it
    least, bound = roofline_seconds(
        n[f"context.{cost}_flops"] / r.chips, n[f"context.{cost}_bytes"] / r.chips, peaks
    )
    ev.notes[f"{cost}_roofline_bound"] = bound
    return 100.0 * least / (kernel_s / steps)
