"""The control of a serving cell's ``correct``: the plain reference put in
the program's place and computed in the nearest precision below the one
the configuration states — int8 weights (symmetric, one scale per output
channel, the step that halves the weight reads decode is bound by) where
the configuration serves bfloat16, bfloat16 weights where it serves
float32. It need not decode: at each position of the same prompts and
served tokens, the token the lower precision puts first is rated by the
float32 reference like a served one. A cell's limits must fail it.

    python3 -m benchmark.control --workload <cell> --seeds <n> <n> <n> --seconds <s>

runs the cell's own load for ``--seconds`` per seed (long enough to finish
the mix's longest request), all seeds in one process, and prints one line
per seed with the program's numbers beside the control's. It is how the
readings in a traffic file's ``check_why`` are taken; the benchmark's own
runs never run it. ``tests/benchmark/test_benchmark_runners.py`` keeps it
at a size a test run can hold."""

from __future__ import annotations

import argparse
import collections.abc
import gc
import json
import sys
import time

from benchmark import harness
from benchmark.manifest import Manifest
from benchmark.runners import RunContext, serve_common


def lower(path, leaf, weight_dtype: str):
    """One leaf in the precision below ``weight_dtype``, returned as
    float32 (which holds either exactly). Vectors — norm scales, biases —
    stay as they are."""
    import jax.numpy as jnp

    if leaf.ndim < 2:
        return leaf
    w = leaf.astype(jnp.float32)
    if weight_dtype == "float32":
        return w.astype(jnp.bfloat16).astype(jnp.float32)
    if weight_dtype != "bfloat16":
        raise ValueError(f"no precision below {weight_dtype!r} is defined here")
    # one scale per output channel: a kernel's last axis, an embedding's row
    rows = getattr(path[-1], "key", None) == "embedding"
    over = tuple(range(1, w.ndim)) if rows else tuple(range(w.ndim - 1))
    scale = jnp.max(jnp.abs(w), axis=over, keepdims=True) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


class Lowered(collections.abc.Mapping):
    """The parameter tree in the lower precision, one top-level entry (a
    layer) at a time: the reference reads a layer, uses it and lets it go,
    so no second copy of the model is ever alive beside the served one."""

    def __init__(self, params, weight_dtype: str):
        import jax

        self.params = params
        # one function for every entry: layers of one shape compile once
        self._lower = jax.jit(lambda tree: jax.tree_util.tree_map_with_path(
            lambda path, x: lower(path, x, weight_dtype), tree
        ))

    def __getitem__(self, key):
        return self._lower(self.params[key])

    def __iter__(self):
        return iter(self.params)

    def __len__(self):
        return len(self.params)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    manifest = Manifest()
    cell = manifest.cell(args.workload)
    device = harness.require_tpu(cell["chips"])
    from kubeflow_tpu.core import compcache

    compcache.enable_compilation_cache()
    config, traffic = manifest.config(cell["config"]), manifest.traffic(cell["traffic"])
    mode = traffic["runner"].removeprefix("serve_")
    for seed in args.seeds:
        gc.collect()     # the last seed's engine and weights, before the next are made
        ctx = RunContext(
            cell=cell, config=config, traffic=traffic, seed=seed, seconds=args.seconds,
            trace=False, device=device, t_process=time.perf_counter(),
        )
        ev = serve_common.run(
            ctx, mode, control=lambda params: Lowered(params, config["weight_dtype"])
        )
        check = ev.notes["check"]
        print(json.dumps({
            "cell": cell["name"], "seed": seed, "correct": ev.correct,
            "attempted": ev.attempted, "lengths": check.get("lengths"),
            "program": check.get("numbers"), "program_failed": check.get("failed"),
            "control": check.get("control", {}).get("numbers"),
            "control_failed": check.get("control", {}).get("failed"),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
