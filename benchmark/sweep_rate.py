"""Find the highest rate an open-loop cell sustains, once, on the chip:

    python3 -m benchmark.sweep_rate --config mistral-7b-l16 --traffic docqa-open \\
        --rates 0.5,1.0,1.5,2.0 --seconds 30 --seed 0

One deployment on one chip, one segment per rate with the mix's own lengths
(the rate in the traffic file is replaced; the mix need not be in a cell
yet). Per rate it prints what was offered and
finished, the requests in flight at half time and at the end (a backlog
that grows through the segment means the rate is above capacity), and the
TTFT of the requests due in the segment. The knee is read from the table by
whoever fixes the cell's rate (at about four fifths of it); nothing reads
this program's output automatically.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from benchmark import harness  # noqa: E402
from benchmark.evidence import reduce_samples  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.runners import RunContext, serve_common  # noqa: E402
from benchmark.stats import ms  # noqa: E402


def in_flight(samples, t: float) -> int:
    return sum(
        s.t_sent is not None and s.t_sent <= t and (s.t_done is None or s.t_done > t)
        for s in samples
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    manifest = Manifest()
    cell = {"name": f"{args.config}_{args.traffic}", "config": args.config,
            "traffic": args.traffic, "chips": 1}
    device = harness.require_tpu(cell["chips"])
    from kubeflow_tpu.core import compcache

    compcache.enable_compilation_cache()
    ctx = RunContext(
        cell=cell, config=manifest.config(cell["config"]),
        traffic=manifest.traffic(cell["traffic"]), seed=args.seed,
        seconds=args.seconds, trace=False, device=device, t_process=T_PROCESS,
    )
    rows = []
    with serve_common.Deployment(ctx) as dep:
        for rate in (float(r) for r in args.rates.split(",")):
            ctx.traffic = dict(ctx.traffic, arrivals=dict(ctx.traffic["arrivals"], rate_rps=rate))
            requests = serve_common.mix_requests(ctx, "open", args.seconds)
            client = serve_common.Client(
                dep.url, requests, mode="open", clients=0, traced=False, seed=args.seed
            )
            chunks0 = dep.engine.stats["chunks"]
            client.start()
            time.sleep(args.seconds)
            t_half, t_end = client.t0 + args.seconds / 2, client.t0 + args.seconds
            client.stop(drain_s=0.0)
            s = client.samples
            done = [x for x in s if x.ok and x.t_done <= t_end]
            row = {
                "rate_rps": rate, "offered": len(s), "finished": len(done),
                "failed": sum(x.error is not None and not x.ok and x.t_done is not None
                              and x.t_done <= t_end for x in s),
                "in_flight_half": in_flight(s, t_half), "in_flight_end": in_flight(s, t_end),
                "ttft_p50_ms": reduce_samples(ms([x.ttft_s for x in done]), "p50"),
                "ttft_p90_ms": reduce_samples(ms([x.ttft_s for x in done]), "p90"),
                "tpot_p90_ms": reduce_samples(ms([x.tpot_s for x in done]), "p90"),
                "output_tokens_per_s": sum(n for t, n in client.token_log if t <= t_end) / args.seconds,
                "decode_chunks": dep.engine.stats["chunks"] - chunks0,
            }
            rows.append(row)
            print(json.dumps(row), flush=True)
            # let the cancelled rows retire before the next rate
            while dep.engine.busy():
                time.sleep(0.2)
    print(json.dumps({"device": device, "sweep": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
