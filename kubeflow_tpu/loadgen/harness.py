"""CPU-runnable serving-load harness: the REAL stack, in one process.

Assembles exactly the production serving plane — ``InferenceGateway``
edge (policy, retries, SSE failover), ``ServingAutoscaler`` +
``GatewaySignalSource`` + ``ReplicaFleet``, and in-process
``ModelServer`` replicas running the real ``LMEngine`` over a tiny
transformer — drives a seeded open-loop schedule through it over
HTTP/SSE, and returns the goodput report. No mocked seams: every request
crosses the wire twice and every metric the reporter reads is scraped
off ``/metrics`` like any Prometheus would.

This is what ``bench.py serving_load``, the smoke step, and the slow e2e
test share; they differ only in knobs (duration, chaos overlay, KPA
shape). The replicas are in-process engines on JAX's default device —
whatever platform this process was started on, one owner of the chip; on
a CPU backend it yields counts and control-plane behaviour, never a
device time.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import Any

from kubeflow_tpu.loadgen.arrivals import OnOffArrivals, PoissonArrivals
from kubeflow_tpu.loadgen.chaos import ChaosOverlay, apply_overlay
from kubeflow_tpu.loadgen.client import LoadClient
from kubeflow_tpu.loadgen.reporter import build_report, scrape_metrics
from kubeflow_tpu.loadgen.workload import TenantSpec, WorkloadMix

__all__ = ["HarnessConfig", "run_serving_load", "default_mix"]


def default_mix(seed: int = 0) -> WorkloadMix:
    """The bench's standard two-class tenant population: an interactive
    tenant with a deadline and priority riding next to best-effort batch
    traffic pinned to an adapter — the mix the SLO-goodput story is
    about."""
    return WorkloadMix(
        prompt_lens=(6, 10, 16),
        output_lens=(4, 6, 8),
        tenants=(
            TenantSpec(
                "interactive", weight=2.0, priority=2,
                deadline_ms=30_000.0, slo_ms=30_000.0,
            ),
            TenantSpec(
                "batch", weight=1.0, priority=0, adapter="batch-v1",
            ),
        ),
        vocab=80,
        seed=seed,
    )


@dataclasses.dataclass
class HarnessConfig:
    seed: int = 0
    process: str = "poisson"          # poisson | onoff
    rate_rps: float = 6.0
    burst_rps: float = 12.0           # onoff only
    period_s: float = 4.0             # onoff only
    duration_s: float = 10.0
    mix: WorkloadMix | None = None
    model_name: str = "m"
    initial_replicas: int = 1
    max_replicas: int = 2
    min_replicas: int = 1
    kpa_target: float = 2.0
    scale_to_zero_grace_s: float = 1.2
    #: after the measured window: let the fleet drain to zero, then time
    #: one cold request through the activator (needs min_replicas=0)
    measure_cold_recovery: bool = False
    chaos: ChaosOverlay | None = None
    request_timeout_s: float = 180.0
    max_new_tokens_cap: int = 12      # model-level engine cap


def _schedule(cfg: HarnessConfig):
    if cfg.process == "poisson":
        return PoissonArrivals(
            rate_rps=cfg.rate_rps, duration_s=cfg.duration_s,
            seed=cfg.seed,
        ).schedule()
    if cfg.process == "onoff":
        return OnOffArrivals(
            base_rps=cfg.rate_rps, burst_rps=cfg.burst_rps,
            period_s=cfg.period_s, duration_s=cfg.duration_s,
            seed=cfg.seed,
        ).schedule()
    raise ValueError(f"unknown arrival process {cfg.process!r}")


async def run_serving_load(cfg: HarnessConfig) -> dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.autoscale import (
        GatewaySignalSource,
        KPAConfig,
        ReplicaFleet,
        ServingAutoscaler,
    )
    from kubeflow_tpu.gateway.router import ServiceRoute
    from kubeflow_tpu.gateway.server import GatewayConfig, InferenceGateway
    from kubeflow_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from kubeflow_tpu.serve.engine import LMEngineModel
    from kubeflow_tpu.serve.model import BucketSpec
    from kubeflow_tpu.serve.server import ModelServer

    mix = cfg.mix or default_mix(cfg.seed)
    tcfg = TransformerConfig(
        vocab_size=89, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        causal=True, max_seq_len=256, attn_impl="reference",
        dtype=jnp.float32,
    )
    tlm = TransformerLM(tcfg)
    params = tlm.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]

    replicas: dict[str, LMEngineModel] = {}

    async def launch(index: int):
        m = LMEngineModel(
            cfg.model_name, None, config=tcfg, max_batch=4, chunk_steps=2,
            buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
            max_new_tokens=cfg.max_new_tokens_cap, eos_id=tcfg.vocab_size + 1,
            # min_wedge must exceed worst-case CPU compile stalls or the
            # watchdog false-trips during warmup; a chaos-wedged engine
            # recovers via the injector's hold_s expiring + gateway
            # retries/breaker, same as the smoke failover step
            watchdog_interval_s=0.1, watchdog_min_wedge_s=60.0,
            prefix_cache_entries=32,
        )
        m.load()
        m._params = jax.device_put(params)  # identical weights per replica
        m.engine.stop()
        m.engine = m._make_engine().start()
        ms = ModelServer([m], http_port=0)
        await ms.start_async()
        (site,) = ms._runner.sites
        port = site._server.sockets[0].getsockname()[1]
        url = f"http://127.0.0.1:{port}"
        replicas[url] = m

        async def stop():
            replicas.pop(url, None)
            m.unload()
            await ms.stop_async()

        return url, stop

    asc = ServingAutoscaler(tick_interval_s=0.15)
    gw = InferenceGateway(GatewayConfig(
        probe_interval_s=0.25, failure_threshold=2, recovery_s=1.0,
        activation_timeout_s=60.0, retry_budget_floor=100,
        routes=[ServiceRoute(name=cfg.model_name, max_attempts=4)],
    ), http_port=0, scale_up=asc.kick)
    fleet = ReplicaFleet(
        cfg.model_name, launch, pool=gw.pool, model=cfg.model_name,
    )
    source = GatewaySignalSource(gw, cfg.model_name)
    asc.add_service(cfg.model_name, KPAConfig(
        target=cfg.kpa_target, min_replicas=cfg.min_replicas,
        max_replicas=cfg.max_replicas, stable_window_s=3.0,
        panic_window_s=0.6, panic_threshold=1.5, max_scale_down_rate=2.0,
        scale_to_zero_grace_s=cfg.scale_to_zero_grace_s,
    ), source, fleet)

    schedule = _schedule(cfg)
    specs = mix.plan(len(schedule))
    client = LoadClient(
        "http://127.0.0.1:0", cfg.model_name,
        request_timeout_s=cfg.request_timeout_s,
    )

    try:
        await fleet.scale_to(cfg.initial_replicas)
        await gw.start_async()
        client.base_url = f"http://127.0.0.1:{gw.http_port}"

        # warm EVERY initial replica through its compiles OUTSIDE the
        # measured window, over the real streaming path (bare
        # engine.submit misses the stream programs) and WITH a seed
        # header — the gateway stamps x-kft-seed on every generate
        # request, and the seeded sampler is a separate compiled program
        # from the unseeded one. Requests go to the replica DIRECTLY,
        # with no trace header — untraced requests record nothing in the
        # TTFT/TPOT histograms (obs/trace.py contract). One request per
        # distinct (prompt_len, budget) shape in the plan; replicas the
        # autoscaler launches mid-run stay cold on purpose (their
        # compile stall IS scale-up latency).
        import aiohttp as _aiohttp

        from kubeflow_tpu.obs.headers import SEED_HEADER

        shapes: dict[tuple[int, int], Any] = {}
        for spec in specs:
            shapes.setdefault(
                (len(spec.prompt_ids), spec.max_new_tokens), spec
            )
        async with _aiohttp.ClientSession(
            timeout=_aiohttp.ClientTimeout(total=cfg.request_timeout_s)
        ) as warm_session:
            for url in list(replicas):
                for spec in shapes.values():
                    async with warm_session.post(
                        f"{url}/v2/models/{cfg.model_name}/generate_stream",
                        data=json.dumps({
                            "input_ids": list(spec.prompt_ids),
                            "max_new_tokens": min(
                                spec.max_new_tokens,
                                cfg.max_new_tokens_cap,
                            ),
                        }).encode(),
                        headers={SEED_HEADER: "1"},
                    ) as resp:
                        await resp.read()

        def engines(model: str):
            live = set(fleet.urls())
            return [
                m.engine for url, m in replicas.items()
                if url in live and m.name == model and m.engine is not None
            ]

        # baseline scrape: warmup traffic (and any earlier run in this
        # process) is subtracted out of the report's counters/histograms
        baseline = await scrape_metrics(client.base_url + "/metrics")

        asc.start()
        t0 = time.monotonic()
        chaos_task = None
        if cfg.chaos is not None:
            chaos_task = asyncio.ensure_future(
                apply_overlay(cfg.chaos, engines, t0=t0)
            )
        results = await client.run(schedule, specs)
        fired: list[str] = []
        if chaos_task is not None:
            fired = await chaos_task
        await asc.stop()

        gw_metrics = await scrape_metrics(client.base_url + "/metrics")
        # /debug/traces lives on the replica ModelServer (PR 15); any
        # live replica sees the whole in-process ring buffer
        traces = None
        if fleet.urls():
            traces = json.loads(await scrape_metrics(
                fleet.urls()[0] + "/debug/traces?limit=256"
            ))

        extra: dict[str, Any] = {}
        if cfg.measure_cold_recovery and cfg.min_replicas == 0:
            # drain: stable window empties, grace expires, replicas -> 0
            asc.start()
            deadline = time.monotonic() + 60
            while fleet.current() > 0 and time.monotonic() < deadline:
                await asyncio.sleep(0.05)
            await asc.stop()
            if fleet.current() == 0:
                # one cold request parks in the activator, kicks the
                # autoscaler, and times the 0->1 relaunch end to end
                asc.start()
                cold0 = time.monotonic()
                one = await client.run(
                    (0.0,), (dataclasses.replace(specs[0], index=0),)
                )
                await asc.stop()
                extra["cold_recovery"] = {
                    "recovery_s": round(time.monotonic() - cold0, 3),
                    "outcome": one[0].outcome,
                }

        return build_report(
            results=results,
            run={
                "bench": "serving_load",
                "seed": cfg.seed,
                "process": cfg.process,
                "rate_rps": cfg.rate_rps,
                "duration_s": cfg.duration_s,
                "offered_requests": len(schedule),
                "model": cfg.model_name,
                "replicas_initial": cfg.initial_replicas,
                "replicas_max": cfg.max_replicas,
                "tenants": [t.name for t in mix.tenants],
            },
            gateway_metrics=gw_metrics,
            baseline_metrics=baseline,
            traces=traces,
            fleet_events=list(fleet.events),
            run_t0=t0,
            chaos_window=(
                cfg.chaos.window if cfg.chaos is not None else None
            ),
            chaos_faults=fired,
            extra=extra,
        )
    finally:
        await asc.stop()
        await source.close()
        await fleet.close()
        await gw.stop_async()
