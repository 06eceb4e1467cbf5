"""The decode step's grouped product as a share (%) of its roofline, from
the work the engine counted and the kernel's traced time.

The work of one decode layer-step is what its *live* rows were routed to:
``engine.moe_assignments_decode`` (token, expert) assignments, each
``context.moe_flops_per_assignment`` operations and
``context.moe_bytes_per_assignment`` bytes of activations, and
``engine.moe_experts_touched_decode`` distinct experts, each
``context.moe_bytes_per_expert`` bytes read once — both divided by
``engine.moe_layer_steps_decode``, the layer-steps they were summed over.
The least time is the larger of operations over peak FLOP/s and bytes over
peak bytes/s. The kernel's time per layer-step is the traced self time of
the calls whose name matches ``pattern`` (``{rows}`` = ``context.max_batch
x context.moe_top_k``: the decode call; a prefill piece's has other rows)
over their number divided by ``calls_per_layer_step``. Counted work, not
nominal occupancy: rows that idle are not in the numerator, so the share
cannot pass 100 % however empty the batch. Which peak bounds it goes into
the notes (``moe_gmm_roofline_bound``). A program without the counters or a
trace without the kernel reads as nothing."""

from __future__ import annotations

import re

from benchmark.opcount import roofline_seconds

COUNTERS = (
    "engine.moe_assignments_decode", "engine.moe_experts_touched_decode",
    "engine.moe_layer_steps_decode",
)
SHAPES = (
    "context.moe_flops_per_assignment", "context.moe_bytes_per_assignment",
    "context.moe_bytes_per_expert", "context.moe_top_k", "context.max_batch",
)


def layer_step_cost(n) -> tuple[float, float]:
    """(operations, bytes) of the mean decode layer-step in the window."""
    steps = n["engine.moe_layer_steps_decode"]
    assignments = n["engine.moe_assignments_decode"] / steps
    touched = n["engine.moe_experts_touched_decode"] / steps
    return (
        assignments * n["context.moe_flops_per_assignment"],
        touched * n["context.moe_bytes_per_expert"]
        + assignments * n["context.moe_bytes_per_assignment"],
    )


def read(params, ev):
    r, n = ev.trace, ev.numbers
    if r is None or any(k not in n for k in COUNTERS + SHAPES):
        return None
    if n["engine.moe_layer_steps_decode"] <= 0:
        return None
    rows = int(n["context.max_batch"] * n["context.moe_top_k"])
    pattern = params["pattern"].format(rows=rows)
    rx = re.compile(pattern)
    lo, hi = r.window
    calls = sum(
        1 for events in r.ops_by_chip.values() for e in events
        if e.start >= lo and e.end <= hi and rx.search(e.detail or e.name)
    )
    kernel_s = r.pattern_seconds([pattern])
    if calls == 0 or kernel_s <= 0:
        return None
    flops, nbytes = layer_step_cost(n)
    least, bound = roofline_seconds(flops, nbytes, {
        "bf16_flops": n["context.peak_flops_per_chip"],
        "hbm_bytes_per_s": n["context.peak_hbm_bytes_per_s"],
    })
    ev.notes["moe_gmm_roofline_bound"] = bound
    return 100.0 * least / (kernel_s / (calls / params["calls_per_layer_step"]))
