"""A serving cell: requests enter over HTTP at ``ModelServer``'s streaming
generate endpoint → ``LMEngineModel`` → ``LMEngine``, from one client
thread running one asyncio loop. The benchmark replaces only where the
weights come from: bf16, made on the device from the seed in one jitted
call (``benchmark/weights.py``).

``serve_closed`` and ``serve_open`` differ only in when the client sends.
Before the window the engine is warmed on exactly the shapes this mix can
reach, then the mix itself runs for ``ramp_s`` (the first wave of a closed
loop arrives all at once, which is not what the window should see); both
count as set-up. Correctness is checked after the window, with the engine's
cache freed: see ``check_outputs``; its limits are data of the cell
(``benchmark/check.py``).
"""

from __future__ import annotations

import asyncio
import json
import threading
import time
from typing import Any

import numpy as np

from benchmark import check as check_rules, harness, traffic as traffic_gen
from benchmark.evidence import Evidence, reduce_samples
from benchmark.manifest import plugin
from benchmark.peaks import device_peaks
from benchmark.stats import RequestSample, highest_reportable_percentile, ms

MODEL_NAME = "lm"
#: tokens a warm-up request decodes past the reach that selects its
#: program: at least two decode chunks at any chunk length up to 8
WARM_EXTRA = 9

#: how often the window's thread, which otherwise sleeps, reads the allocator
MEMORY_SAMPLE_S = 0.2


def build_model(ctx):
    """``LMEngineModel`` with the weights swapped for seeded bf16 ones.
    ``LMRuntimeModel.load`` (the base of ``LMEngineModel.load``) has no way
    to be handed parameters: with no ``storage_path`` it runs an eager
    float32 ``model.init``. The class below sits between the two in the
    method resolution order, so ``LMEngineModel.load``'s ``super().load()``
    lands here and everything above it — executor, engine, watchdog — is
    the program's own."""
    from kubeflow_tpu.serve.engine import LMEngineModel
    from kubeflow_tpu.serve.generate import LMRuntimeModel
    from kubeflow_tpu.serve.model import BucketSpec

    from benchmark.families import DTYPES
    from benchmark.weights import seeded_params

    cfg, serve = ctx.config, ctx.config["serve"]
    family = plugin("families", cfg["family"])
    model, program_cfg = family.serve_model(cfg)
    dtype = DTYPES[cfg["weight_dtype"]]
    seed = ctx.seed

    class SeededWeights(LMRuntimeModel):
        def load(self) -> bool:
            import jax

            self._params = seeded_params(
                family.abstract_params(self._model), seed, dtype
            )
            jax.block_until_ready(self._params)
            self.ready = True
            return True

    class BenchLM(LMEngineModel, SeededWeights):
        pass

    return BenchLM(
        MODEL_NAME, None, config=program_cfg,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(serve["prefill_chunk"],)),
        max_new_tokens=serve["max_new_tokens"],
        # outside the vocabulary: every output has its drawn length
        eos_id=cfg["vocab_size"] + 1,
        max_batch=serve["max_batch"], max_seq=serve["max_seq"],
        prefill_chunk=serve["prefill_chunk"],
        kv_pool_tokens=serve["kv_pool_tokens"], page_size=serve["page_size"],
        watchdog_min_wedge_s=serve["watchdog_min_wedge_s"],
    )


def warm_plan(mix, serve, pages_w) -> list[tuple[int, int]]:
    """(prompt tokens, new tokens) of the warm-up requests: one per decode
    program the mix can reach. The engine compiles a decode program per
    page-table width, which it picks from the furthest token any row will
    reach (``pages_w``, the engine's own rule); prefill programs nest inside
    the longest prompt's pieces."""
    page = serve["page_size"]
    p_lo, p_hi = length_bounds(mix["prompt_tokens"])
    o_lo, o_hi = length_bounds(mix["output_tokens"])
    lo, hi = p_lo + min(WARM_EXTRA, o_lo), p_hi + o_hi
    first_reach: dict[int, int] = {}
    for pages in range(-(-lo // page), -(-hi // page) + 1):
        reach = min(max(lo, (pages - 1) * page + 1), hi)
        first_reach.setdefault(pages_w(reach), reach)
    plan = []
    for reach in first_reach.values():
        # the engine sizes a chunk's table for min(tokens so far + one
        # chunk, prompt + budget): a request that ends at ``reach``
        prompt = min(max(reach - WARM_EXTRA, p_lo), p_hi)
        plan.append((prompt, reach - prompt))
    # the longest prompt, for every prefill piece's program
    plan.append((p_hi, WARM_EXTRA))
    return plan


def length_bounds(spec) -> tuple[int, int]:
    return spec["min"], spec["max"]


# --------------------------------------------------------------------- #
# the client: one thread, one event loop
# --------------------------------------------------------------------- #

class Client:
    """Sends the mix's requests to one URL and records what came back.
    ``mode`` ``closed``: ``clients`` callers, each sending the next unsent
    request when its last one ended. ``open``: every request at its due
    time."""

    def __init__(self, url: str, requests, *, mode: str, clients: int, traced: bool, seed: int):
        self.url, self.requests = url, requests
        self.mode, self.clients, self.traced, self.seed = mode, clients, traced, seed
        self.samples = [
            RequestSample(r.index, len(r.prompt), r.max_new_tokens, t_due=0.0)
            for r in requests
        ]
        #: (arrival time, tokens) of every streamed frame
        self.token_log: list[tuple[float, int]] = []
        self.bodies = [
            json.dumps({"input_ids": list(r.prompt), "max_new_tokens": r.max_new_tokens}).encode()
            for r in requests
        ]
        self.t0: float | None = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._main, name="bench-client")
        self._error: BaseException | None = None
        self._drain_s = 0.0

    def start(self) -> None:
        self._thread.start()
        while self.t0 is None and self._thread.is_alive():
            time.sleep(0.001)

    def stop(self, drain_s: float = 0.0) -> None:
        """Closed loop: stop now (requests in flight are dropped). Open
        loop: send nothing more, give those in flight ``drain_s``."""
        self._drain_s = drain_s
        self._stop.set()
        self._thread.join()
        if self._error is not None:
            raise self._error

    def _main(self) -> None:
        try:
            asyncio.run(self._run())
        except BaseException as e:  # noqa: BLE001 — re-raised by stop()
            self._error = e

    def _headers(self, i: int) -> dict[str, str]:
        h = {"Content-Type": "application/json"}
        if self.traced:
            trace_id = f"{self.seed & 0xFFFFFFFF:08x}{i + 1:024x}"
            self.samples[i].trace_id = trace_id
            h["x-kft-trace"] = f"00-{trace_id}-{i + 1:016x}-01"
        return h

    async def _one(self, session, i: int) -> None:
        s = self.samples[i]
        s.t_sent = time.perf_counter()
        try:
            with harness.annotate("bench.client_send"):
                resp = await session.post(
                    self.url, data=self.bodies[i], headers=self._headers(i)
                )
            async with resp:
                if resp.status != 200:
                    s.error = f"HTTP {resp.status}: {(await resp.text())[:200]}"
                    return
                async for line in resp.content:
                    if not line.startswith(b"data: "):
                        continue
                    frame = json.loads(line[6:])
                    now = time.perf_counter()
                    if "token_ids" in frame:
                        n = len(frame["token_ids"])
                        if s.t_first is None:
                            s.t_first = now
                        s.t_last = now
                        s.n_out += n
                        s.token_ids.extend(frame["token_ids"])
                        self.token_log.append((now, n))
                    elif frame.get("done"):
                        s.t_done = now
                        s.ok = s.n_out == s.max_new_tokens
                        if not s.ok:
                            s.error = f"{s.n_out} tokens of {s.max_new_tokens}"
                    elif "error" in frame:
                        s.error = str(frame["error"])[:200]
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — a failed request is a result
            s.error = f"{type(e).__name__}: {e}"[:200]
        finally:
            if s.t_done is None and s.error is not None:
                s.t_done = time.perf_counter()

    async def _run(self) -> None:
        import aiohttp

        timeout = aiohttp.ClientTimeout(total=None)
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:
            self.t0 = time.perf_counter()
            tasks: list[asyncio.Task] = []
            if self.mode == "closed":
                # one cursor over the list: a free caller takes the next
                # unsent request, so the requests go out in index order and
                # admission follows the traffic's stratified blocks, however
                # long the requests a caller happened to draw. The callers
                # share this loop's thread, so ``next`` needs no lock
                unsent = iter(range(len(self.requests)))

                async def caller() -> None:
                    for i in unsent:
                        self.samples[i].t_due = time.perf_counter()
                        await self._one(session, i)

                tasks = [asyncio.create_task(caller()) for _ in range(self.clients)]
                while not self._stop.is_set():
                    await asyncio.sleep(0.01)
            else:
                for i, r in enumerate(self.requests):
                    due = self.t0 + r.due_s
                    self.samples[i].t_due = due
                    while (wait := due - time.perf_counter()) > 0 and not self._stop.is_set():
                        await asyncio.sleep(min(wait, 0.01))
                    if self._stop.is_set():
                        break
                    tasks.append(asyncio.create_task(self._one(session, i)))
                while not self._stop.is_set():
                    await asyncio.sleep(0.01)
                if tasks and self._drain_s > 0:
                    await asyncio.wait(tasks, timeout=self._drain_s)
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)


# --------------------------------------------------------------------- #
# the program's tracer, read from outside
# --------------------------------------------------------------------- #

class SpanCollector:
    """Every finished trace of the program's tracer. It keeps finished
    traces in short rings meant for ``/debug/traces``, so they are copied
    out a few times a second (public API only: ``sample_every``,
    ``snapshot``)."""

    def __init__(self, period_s: float = 0.25):
        from kubeflow_tpu.obs.trace import TRACER

        self.tracer = TRACER
        self.period_s = period_s
        self.traces: dict[str, dict] = {}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="bench-spans")

    def start(self) -> None:
        self.tracer.clear()
        self.tracer.sample_every = 1
        self._thread.start()

    def _poll(self) -> None:
        for doc in self.tracer.snapshot(limit=1024)["traces"]:
            self.traces.setdefault(doc["trace_id"], doc)

    def _run(self) -> None:
        while not self._stop.wait(self.period_s):
            self._poll()

    def stop(self) -> list[dict]:
        self._stop.set()
        self._thread.join()
        self._poll()
        return list(self.traces.values())


# --------------------------------------------------------------------- #
# the run
# --------------------------------------------------------------------- #

class Deployment:
    """The system under test, started and warmed: ``ModelServer`` on a
    thread of its own with the seeded model loaded. A context manager;
    leaving it stops the server and frees the engine's pool."""

    def __init__(self, ctx):
        self.ctx = ctx

    def __enter__(self) -> "Deployment":
        from kubeflow_tpu.serve.server import ModelServer

        ctx = self.ctx
        self.marks = {"start": time.perf_counter()}
        self.lm = build_model(ctx)
        self.server = ModelServer([self.lm], http_port=0)   # loads the model
        self.marks["loaded"] = time.perf_counter()
        self.params = self.lm._params                       # kept for the check
        self.engine = self.lm.engine
        with harness.annotate("bench.warmup"):
            plan = warm_plan(ctx.traffic, ctx.config["serve"], self.engine._pages_w)
            for prompt, new in plan:
                self.engine.submit([2 + (prompt % 7)] * prompt, max_new_tokens=new)
        self.marks["warmed"] = time.perf_counter()
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever, name="bench-server")
        self.thread.start()
        asyncio.run_coroutine_threadsafe(self.server.start_async(), self.loop).result()
        (site,) = self.server._runner.sites
        port = site._server.sockets[0].getsockname()[1]
        self.url = f"http://127.0.0.1:{port}/v2/models/{MODEL_NAME}/generate_stream"
        return self

    def __exit__(self, *exc) -> None:
        asyncio.run_coroutine_threadsafe(self.server.stop_async(), self.loop).result()
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join()
        self.loop.close()
        self.lm.unload()                                    # frees the KV pool


def mix_requests(ctx, mode: str, duration_s: float):
    """The requests of ``duration_s`` seconds of the mix, from the seed."""
    mix, vocab = ctx.traffic, ctx.config["vocab_size"]
    if mode == "closed":
        # more than any build could finish: callers never run dry
        n = mix["clients"] + int(mix["max_requests_per_s"] * duration_s)
        return traffic_gen.make_requests(mix, vocab, ctx.seed, n)
    due = traffic_gen.arrival_offsets(mix["arrivals"], duration_s, ctx.seed)
    return traffic_gen.make_requests(mix, vocab, ctx.seed, len(due), due=due)


def run(ctx, mode: str, control=None) -> Evidence:
    """``control``: only ``benchmark/control.py`` passes it — a function
    from the served parameters to the same tree in the next lower
    precision, whose reading goes into the notes beside the program's."""
    mix = ctx.traffic
    limits = check_rules.validate(mix)      # before any request is sent
    family = plugin("families", ctx.config["family"])
    ramp_s, seconds = mix["ramp_s"], ctx.seconds
    requests = mix_requests(ctx, mode, ramp_s + seconds)
    spans = SpanCollector() if ctx.trace else None
    profiler = None
    with Deployment(ctx) as dep:
        engine, params = dep.engine, dep.params
        client = Client(
            dep.url, requests, mode=mode, clients=mix.get("clients", 0),
            traced=ctx.trace, seed=ctx.seed,
        )
        if spans is not None:
            spans.start()
        client.start()
        try:
            t_w = client.t0 + ramp_s
            time.sleep(max(0.0, t_w - time.perf_counter()))
            # -- the window opens ---------------------------------------- #
            setup_s = time.perf_counter() - ctx.t_process
            stats0 = dict(engine.stats)
            xla0 = harness.compile_stats()
            if ctx.profiled:
                profiler = harness.TraceWindow(
                    ctx.cell["name"], after=mix["trace_after_s"], seconds=mix["trace_s"]
                )
                profiler.start()
            live = []
            while (left := t_w + seconds - time.perf_counter()) > 0:
                live.append(harness.live_bytes())
                time.sleep(min(left, MEMORY_SAMPLE_S))
            # -- the window closes --------------------------------------- #
            stats1 = dict(engine.stats)
            xla1 = harness.compile_stats()
        finally:
            client.stop(drain_s=mix.get("drain_s", 0.0))
        reduction = profiler.reduction() if profiler is not None else None
        traces = spans.stop() if spans is not None else []
        peak_bytes = harness.memory_peak_bytes(live)
        chunk_steps, max_batch = engine.chunk_steps, engine.max_batch
        marks = dep.marks
        del engine

    t_end = t_w + seconds
    if mode == "closed":
        counted = [
            s for s in client.samples
            if s.t_done is not None and t_w <= s.t_done < t_end
        ]
    else:
        counted = [s for s in client.samples if t_w <= s.t_due < t_end]
    failed = [s for s in counted if not s.ok]
    frames = sorted((t - t_w, n) for t, n in client.token_log if t_w <= t < t_end)
    tokens_in_window = sum(n for _, n in frames)
    compiles_in_window = xla1["programs"] - xla0["programs"]

    # tokens the engine computed in the window: prompt tokens prefilled (not
    # the padded slots) and one decode step for every output token but a
    # request's first, which is the prefill's
    first_tokens = sum(
        1 for s in client.samples if s.t_first is not None and t_w <= s.t_first < t_end
    )
    forward_tokens = (
        stats1["prefill_tokens"] - stats0["prefill_tokens"] + tokens_in_window - first_tokens
    )

    check = check_outputs(ctx, limits, params, [s for s in counted if s.ok], requests, control)
    del params
    correct = bool(
        counted and not failed and check["ok"] and compiles_in_window == 0
    )

    def pct(field: str, how: str):
        return reduce_samples(ms([getattr(s, field) for s in counted if s.ok]), how)

    peaks = device_peaks(ctx.device["kind"])
    ev = Evidence(
        cell=ctx.cell, traces=traces, samples=counted,
        trace=reduction, attempted=len(counted), failed=len(failed), correct=correct,
        check=dict(
            check.get("numbers", {}),
            compiles_in_window=compiles_in_window, compiles_in_window_limit=0,
        ),
    )
    e2e = {
        "output_tokens_per_s": tokens_in_window / seconds,
        "ttft_p50_ms": pct("ttft_s", "p50"), "ttft_p90_ms": pct("ttft_s", "p90"),
        "tpot_p50_ms": pct("tpot_s", "p50"), "tpot_p90_ms": pct("tpot_s", "p90"),
        "setup_s": setup_s,
    }
    ev.numbers.update({f"e2e.{k}": v for k, v in e2e.items() if v is not None})
    ev.numbers.update({
        f"engine.{k}": float(stats1[k] - stats0[k])
        for k in stats1 if isinstance(stats1[k], (int, float))
    })
    ev.numbers.update({
        "client.output_tokens": float(tokens_in_window),
        "client.first_tokens": float(first_tokens),
        "serve.forward_tokens": float(forward_tokens),
        "context.window_s": float(seconds),
        "context.chips": float(ctx.cell["chips"]),
        "context.chunk_steps": float(chunk_steps),
        "context.max_batch": float(max_batch),
        "context.peak_flops_per_chip": peaks["bf16_flops"],
        "context.peak_hbm_bytes_per_s": peaks["hbm_bytes_per_s"],
        "device.memory_peak_bytes": float(peak_bytes),
        "xla.programs": xla1["programs"],
        "xla.cache_hits": xla1["cache_hits"],
        "xla.compile_seconds": xla1["seconds"],
        "xla.compiles_in_window": float(compiles_in_window),
    })
    # what the family counts for this cell (``<cost>_flops`` / ``_bytes`` for
    # the ``roofline`` reader, ``forward_flops_per_token``): a family's own
    serve_context = getattr(family, "serve_context", None)
    if serve_context is not None:
        ev.numbers.update({
            f"context.{k}": float(v)
            for k, v in serve_context(ctx.config, mix, ctx.config["serve"]).items()
        })
    late = ms([s.lateness_s for s in counted])
    # where a stall fell, for whoever reads a slow run: tokens delivered in
    # each second of the window, and the longest silence between two frames
    at = np.array([t for t, _ in frames] or [0.0])
    by_second = np.bincount(
        at.astype(int), weights=[n for _, n in frames] or [0], minlength=int(seconds)
    )
    gaps = np.diff(at) if len(at) > 1 else np.zeros(1)
    ev.notes.update({
        "tokens_by_second": by_second.astype(int).tolist(),
        "token_silence_s_max": float(gaps.max()),
        "token_silence_at_s": float(at[int(gaps.argmax())]),
        "window_s": seconds, "requests_sent": sum(s.t_sent is not None for s in client.samples),
        "generator_lateness_ms_p50": reduce_samples(late, "p50"),
        "generator_lateness_ms_max": reduce_samples(late, "max"),
        "tpot_ms": {q: pct("tpot_s", q) for q in ("p10", "p50", "p90", "p99")},
        "ttft_ms": {q: pct("ttft_s", q) for q in ("p10", "p50", "p90", "p99")},
        # p90 is a measured tail only with ten samples beyond it
        "highest_reportable_percentile": highest_reportable_percentile(len(counted)),
        "first_errors": [s.error for s in failed[:3]],
        "setup_parts_s": {
            "start_to_runner": marks["start"] - ctx.t_process,
            "load": marks["loaded"] - marks["start"],
            "warm_up": marks["warmed"] - marks["loaded"], "ramp": ramp_s,
        },
        "memory_stats": harness.memory_stats(),
        "check": check,
    })
    return ev


def check_outputs(
    ctx, limits, params, done: list[RequestSample], requests, control=None
) -> dict[str, Any]:
    """Teacher-forced: the reference reads prompt + the engine's tokens in
    one pass and rates each generated token (``regrets_of``);
    ``check.judge`` holds the regrets to the cell's ``limits``. Checked:
    the longest completed request — where the sliding window and the most
    pages are in play — and a seeded pick of others."""
    if not done:
        return {"ok": False, "failed": ["no completed request to check"]}
    rng = np.random.default_rng([ctx.seed, 0xC4EC])
    longest = max(done, key=lambda s: s.prompt_tokens + s.n_out)
    others = [s for s in done if s is not longest]
    picks = [longest] + [
        others[i] for i in rng.permutation(len(others))[:limits["requests"] - 1]
    ]
    verdict = check_rules.judge(regrets_of(ctx.config, params, picks, requests), limits)
    verdict.update(
        requests=[s.index for s in picks],
        lengths=[s.prompt_tokens + s.n_out for s in picks],
    )
    if control is not None:
        verdict["control"] = check_rules.judge(
            regrets_of(ctx.config, params, picks, requests, chooser=control(params)), limits
        )
    return verdict


def regrets_of(cfg, params, picks: list[RequestSample], requests, chooser=None) -> np.ndarray:
    """For every token the engine generated in ``picks``: how far the plain
    float32 reference's logit of that token lies below the position's best,
    in standard deviations of the position's logits. With the same bf16
    weights, bf16 activations through 16 layers move a logit by about
    2^-8·sqrt(depth) of its scale, so near-ties flip (PERF.md, PR 21: top-2
    gaps of 0.001–0.009 at logit std 1.0 flipped between two bf16 paths); a
    dropped term — no window, wrong position, stale page — costs whole
    standard deviations. ``chooser`` (the control): parameters whose own
    first choice at each of the same positions is rated in place of the
    engine's token."""
    family = plugin("families", cfg["family"])
    # one padded length for all picks, so the reference compiles once
    pad = -(-max(s.prompt_tokens + s.n_out for s in picks) // 1024) * 1024
    regrets = []
    for s in picks:
        tokens = list(requests[s.index].prompt) + s.token_ids
        # position p's logits choose token p + 1
        rows = np.arange(s.prompt_tokens - 1, len(tokens) - 1)
        padded = np.zeros((pad,), np.int32)
        padded[: len(tokens)] = tokens
        logits = np.asarray(family.reference_logits(params, padded, rows, cfg), np.float64)
        served = np.asarray(s.token_ids)
        if chooser is not None:
            served = np.asarray(family.reference_logits(chooser, padded, rows, cfg)).argmax(axis=1)
        chosen = logits[np.arange(len(rows)), served]
        regrets.append((logits.max(axis=1) - chosen) / logits.std(axis=1))
    return np.concatenate(regrets)
