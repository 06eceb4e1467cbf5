"""Run one cell of ``BENCHMARK.json``:

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads, warms, measures for ``--seconds``, checks the outputs, and prints as
the last line of standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, traced ``breakdown``,
and last ``check``: every number ``correct`` was decided on beside its
limit (``<name>_limit``), which are also the last lines on standard error.
With ``--trace 0`` the metrics are the cell's end-to-end
metrics, taken with the profiler off and no request traced; with
``--trace 1`` they are its per-layer metrics, from a run in which every
request carries a trace context and a few seconds of the window are
profiled. Without the cell's TPU chips it exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from benchmark import check as check_rules, harness  # noqa: E402
from benchmark.manifest import Manifest, plugin  # noqa: E402
from benchmark.runners import RunContext  # noqa: E402


def collect_metrics(manifest: Manifest, ev, traced: bool) -> dict[str, dict]:
    """The line's ``metrics``: name → {value, unit}. A reader that finds
    nothing to read returns ``None`` and its metric is left out."""
    cell = ev.cell["name"]
    out = {}
    if not traced:
        for m in manifest.metrics_of(cell, "end_to_end"):
            value = ev.numbers.get(f"e2e.{m['name']}")
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out
    for m in manifest.metrics_of(cell, "per_layer"):
        spec = manifest.layer_metric(m["name"])
        value = plugin("readers", spec["reader"]).read(spec, ev)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(manifest: Manifest, ev, device: dict, traced: bool) -> dict:
    device = dict(device, memory_peak_bytes=int(ev.numbers["device.memory_peak_bytes"]))
    line = {
        "correct": ev.correct, "attempted": ev.attempted, "failed": ev.failed,
        "metrics": collect_metrics(manifest, ev, traced), "device": device,
    }
    if traced and ev.trace is not None:
        device["busy_s"] = ev.trace.busy_s
        device["window_s"] = ev.trace.window_s
        line["breakdown"] = {
            "device_ops": ev.trace.top_op_groups(10),
            "idle_gaps": ev.trace.idle_gaps_by_host_activity(10),
        }
    # last, so that the end of a failed run's line says by how much
    line["check"] = ev.check
    return line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    manifest = Manifest()
    cell = manifest.cell(args.workload)
    try:
        device = harness.require_tpu(cell["chips"])
    except harness.NoAccelerator as e:
        print(e.message, file=sys.stderr)
        raise
    from kubeflow_tpu.core import compcache

    # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache
    cache_dir = compcache.enable_compilation_cache()
    traffic = manifest.traffic(cell["traffic"])
    ctx = RunContext(
        cell=cell, config=manifest.config(cell["config"]),
        traffic=traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), device=device, t_process=T_PROCESS,
    )
    ev = plugin("runners", traffic["runner"]).run(ctx)
    line = result_line(manifest, ev, device, ctx.trace)
    # everything else a reader of the log may want, on an earlier line
    print(json.dumps({
        "cell": cell["name"], "seed": args.seed, "trace": args.trace,
        "cache_dir": cache_dir, "notes": ev.notes,
        "numbers": {k: v for k, v in sorted(ev.numbers.items())},
    }, default=str), flush=True)
    print(json.dumps(line), flush=True)
    print("\n".join(check_rules.stderr_lines(line["check"])), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
