"""ModelServer: aiohttp REST server speaking v1 + v2 inference protocols.

Reference analog: KServe's ``ModelServer`` (FastAPI/uvicorn + gRPC) and its
``DataPlane`` registry ([kserve] python/kserve/kserve/model_server.py,
protocol/dataplane.py — UNVERIFIED, mount empty, SURVEY.md §0). FastAPI is
not in this image; aiohttp is (SURVEY.md §0), and an async single-process
server is the right shape anyway — the chip serialises predict calls, so the
win is async request admission + batching, not thread pools.

Endpoints (wire-compatible with the reference so clients port unchanged):

- ``GET  /``                                 liveness
- ``GET  /v1/models``                        list models
- ``GET  /v1/models/<m>``                    readiness of one model
- ``POST /v1/models/<m>:predict``            v1 predict
- ``GET  /v2/health/live`` ``/v2/health/ready``
- ``GET  /v2/models/<m>``                    v2 metadata
- ``POST /v2/models/<m>/infer``              v2 infer
- ``GET  /metrics``                          Prometheus text format
"""

from __future__ import annotations

import asyncio
import time
import uuid
from collections import deque
from typing import Any

from aiohttp import web

from kubeflow_tpu.core import compcache
from kubeflow_tpu.obs import names, prom
from kubeflow_tpu.obs import trace as _trace
from kubeflow_tpu.obs.trace import (
    TRACE_HEADER,
    TRACER,
    ctx_from_headers,
    to_perfetto,
)
from kubeflow_tpu.serve import protocol
from kubeflow_tpu.serve.batcher import Batcher, BatcherConfig
from kubeflow_tpu.serve.deadline import (
    DEADLINE_ABS_HEADER,
    DEADLINE_EXPIRED,
    AdmissionShed,
    DeadlineExceeded,
    deadline_from_headers,
)
from kubeflow_tpu.serve.engine import EngineOverloaded
from kubeflow_tpu.serve.logger import RequestLogger
from kubeflow_tpu.serve.model import Model
from kubeflow_tpu.serve.watchdog import EngineRestarting


def _shed_response(e: Exception) -> web.HTTPException | None:
    """HTTP mapping for the SRE error taxonomy (serve/deadline.py).

    Deadline-expired and admission-shed responses CARRY ``Retry-After`` —
    the gateway's marker for "coherent load shed, do not retry/burn
    budget". A watchdog restart is a bare 503: retryable, the gateway
    should re-land the request on a healthy replica. Overload stays 429.
    """
    if isinstance(e, AdmissionShed):
        return web.HTTPServiceUnavailable(
            reason=str(e),
            headers={"Retry-After": str(int(-(-e.retry_after_s // 1)))},
        )
    if isinstance(e, DeadlineExceeded):
        return web.HTTPServiceUnavailable(
            reason=str(e), headers={"Retry-After": "1"}
        )
    if isinstance(e, EngineRestarting):
        return web.HTTPServiceUnavailable(reason=str(e))
    if isinstance(e, EngineOverloaded):
        return web.HTTPTooManyRequests(reason=str(e))
    return None


def _span_status(e: BaseException) -> str:
    """Span terminal status for the SRE error taxonomy — shed/deadline/
    poisoned statuses put the trace in the tail-sampler's keep pool."""
    if isinstance(e, DeadlineExceeded):
        return "deadline"
    if isinstance(e, (AdmissionShed, EngineOverloaded)):
        return "shed"
    if isinstance(e, EngineRestarting):
        return "poisoned"
    if isinstance(e, asyncio.CancelledError):
        return "cancelled"
    return "error"

#: Batcher occupancy gauges (per model) on the process-wide registry, so the
#: ObsServer's shared /metrics shows them next to the engine pool gauges;
#: values refresh at scrape time via a Registry collector per batcher.
BATCHER_BATCHES = prom.REGISTRY.gauge(
    names.BATCHER_BATCHES, "handler calls the batcher has made",
    ("model",),
)
BATCHER_INSTANCES = prom.REGISTRY.gauge(
    names.BATCHER_INSTANCES, "instances the batcher has coalesced",
    ("model",),
)
BATCHER_MEAN_OCCUPANCY = prom.REGISTRY.gauge(
    names.BATCHER_MEAN_OCCUPANCY,
    "mean instances per handler call (batch fill)", ("model",),
)
BATCHER_FAIL_ISOLATIONS = prom.REGISTRY.gauge(
    names.BATCHER_FAIL_ISOLATIONS,
    "co-batched failures re-run per caller (offender isolation)", ("model",),
)

#: Engine prefix-cache and speculative-decode effectiveness on the shared
#: registry (the gateway's prefix affinity and any autoscaler read these
#: from the ObsServer scrape, not just the ModelServer's own /metrics).
ENGINE_PREFIX_HITS = prom.REGISTRY.gauge(
    names.ENGINE_PREFIX_HITS_TOTAL,
    "engine prefix-cache hits (admissions that implanted stored KV)",
    ("model",),
)
ENGINE_PREFIX_TOKENS_REUSED = prom.REGISTRY.gauge(
    names.ENGINE_PREFIX_TOKENS_REUSED_TOTAL,
    "prompt KV tokens served from the prefix cache instead of prefilled",
    ("model",),
)
ENGINE_PREFIX_ENTRIES = prom.REGISTRY.gauge(
    names.ENGINE_PREFIX_ENTRIES, "prefix-cache entries resident", ("model",),
)
ENGINE_PREFIX_TOKENS_STORED = prom.REGISTRY.gauge(
    names.ENGINE_PREFIX_TOKENS_STORED,
    "KV tokens held by the prefix cache", ("model",),
)
ENGINE_SPEC_PROPOSED = prom.REGISTRY.gauge(
    names.ENGINE_SPEC_PROPOSED_TOTAL,
    "speculative draft tokens proposed by prompt-lookup", ("model",),
)
ENGINE_SPEC_ACCEPTED = prom.REGISTRY.gauge(
    names.ENGINE_SPEC_ACCEPTED_TOTAL,
    "speculative draft tokens accepted by the verify forward", ("model",),
)
ENGINE_SPEC_ACCEPTANCE = prom.REGISTRY.gauge(
    names.ENGINE_SPEC_ACCEPTANCE,
    "EWMA accepted/proposed draft ratio", ("model",),
)
ENGINE_KV_OFFLOAD_BYTES = prom.REGISTRY.gauge(
    names.ENGINE_KV_OFFLOAD_BYTES,
    "encoded KV bytes resident in the host-RAM tier", ("model",),
)
ENGINE_KV_OFFLOAD_ROWS = prom.REGISTRY.gauge(
    names.ENGINE_KV_OFFLOAD_RESIDENT_ROWS,
    "swapped-out session rows resident in the host-RAM tier", ("model",),
)


def _engine_collector(name: str, model):
    """Scrape-time refresh of the engine gauges; resolves the engine
    lazily so load/unload cycles (ModelMesh) never leave a stale ref."""

    def collect() -> None:
        eng = getattr(model, "engine", None)
        if eng is None:
            return
        pc = eng.prefix_cache_stats()
        ENGINE_PREFIX_HITS.labels(model=name).set(pc["hits"])
        ENGINE_PREFIX_TOKENS_REUSED.labels(model=name).set(
            pc["tokens_reused"]
        )
        ENGINE_PREFIX_ENTRIES.labels(model=name).set(pc["entries"])
        ENGINE_PREFIX_TOKENS_STORED.labels(model=name).set(
            pc["tokens_stored"]
        )
        ENGINE_SPEC_PROPOSED.labels(model=name).set(
            eng.stats["spec_proposed"]
        )
        ENGINE_SPEC_ACCEPTED.labels(model=name).set(
            eng.stats["spec_accepted"]
        )
        ENGINE_SPEC_ACCEPTANCE.labels(model=name).set(
            eng.overlap["spec_acceptance"]
        )
        tier = getattr(eng, "host_kv_tier", None)
        if tier is not None:
            res = tier.resident()
            ENGINE_KV_OFFLOAD_BYTES.labels(model=name).set(res["bytes"])
            ENGINE_KV_OFFLOAD_ROWS.labels(model=name).set(res["rows"])

    return collect


# -- prefix-KV wire format (cross-replica transfer) ----------------------- #


def encode_prefix_entries(entries) -> bytes:
    """Back-compat name for :func:`kv_codec.encode_kv_entries` — the
    codec moved to serve/kv_codec.py when disaggregated serving
    generalized it from prefix-cache entries to arbitrary per-request
    KV spans and host-tier blobs."""
    from kubeflow_tpu.serve.kv_codec import encode_kv_entries

    return encode_kv_entries(entries)


def decode_prefix_entries(blob: bytes):
    """Inverse of :func:`encode_prefix_entries` (kv_codec wrapper;
    drops the optional span meta — prefix transfers never carry one)."""
    from kubeflow_tpu.serve.kv_codec import decode_kv_entries

    entries, _ = decode_kv_entries(blob)
    return entries


def _batcher_collector(name: str, batcher: Batcher):
    def collect() -> None:
        BATCHER_BATCHES.labels(model=name).set(batcher.stats["batches"])
        BATCHER_INSTANCES.labels(model=name).set(batcher.stats["instances"])
        BATCHER_MEAN_OCCUPANCY.labels(model=name).set(batcher.mean_occupancy)
        BATCHER_FAIL_ISOLATIONS.labels(model=name).set(
            batcher.stats["fail_isolations"]
        )

    return collect


class DataPlane:
    """Model registry + request execution (the per-request hot path).

    ``default_deadline_ms`` is the KServe request-timeout analog: requests
    arriving WITHOUT an ``x-kft-deadline-ms`` budget get this one, so a
    replica never carries open-ended work (the old behavior was a
    hardcoded 300 s engine timeout with no queue accounting)."""

    def __init__(
        self,
        logger: RequestLogger | None = None,
        *,
        default_deadline_ms: float | None = None,
    ):
        self._models: dict[str, Model] = {}
        self._batchers: dict[str, Batcher] = {}
        self.logger = logger
        self.default_deadline_ms = default_deadline_ms
        self.metrics: dict[str, Any] = {"requests_total": {}, "latency_ms": {}}
        #: requests currently executing, per model — the load signal the
        #: gateway's least-outstanding balancer cross-checks, and what
        #: graceful drain waits on (event-loop confined)
        self.inflight: dict[str, int] = {}

    def total_inflight(self) -> int:
        return sum(self.inflight.values())

    def reset_load_signals(self, name: str) -> None:
        """Zero the per-model load signals after a supervised engine
        restart (called from the watchdog thread via the model's restart
        listener — a plain dict store, atomic under the GIL). In-flight
        requests poisoned by the restart unwind through their
        finally-blocks afterwards; those decrements clamp at zero."""
        self.inflight[name] = 0

    # -- registry -----------------------------------------------------------

    def register(self, model: Model, batcher: BatcherConfig | None = None) -> None:
        self._models[model.name] = model
        if batcher is not None:
            buckets = getattr(model, "buckets", None)
            if buckets is not None and batcher.max_batch_size > buckets.batch_sizes[-1]:
                # a chunk larger than the top bucket would fail every caller
                batcher = BatcherConfig(
                    max_batch_size=buckets.batch_sizes[-1],
                    max_latency_ms=batcher.max_latency_ms,
                )
            self._batchers[model.name] = Batcher(
                handler=lambda flat, m=model: self._predict_flat(m, flat),
                config=batcher,
            )
            prom.REGISTRY.add_collector(
                _batcher_collector(model.name, self._batchers[model.name]),
                key=("batcher", model.name),
            )
        if hasattr(model, "engine"):
            # engine-backed LM runtimes: prefix-cache + speculative-decode
            # gauges on the shared registry (collector resolves the engine
            # at scrape time — it may not be loaded yet)
            prom.REGISTRY.add_collector(
                _engine_collector(model.name, model),
                key=("engine", model.name),
            )
        if hasattr(model, "add_restart_listener"):
            # a supervised engine restart poisons all pre-restart work:
            # the load signals the gateway/autoscaler read (inflight,
            # queue depth) must reset with it, or they size against rows
            # that no longer exist
            model.add_restart_listener(
                lambda name=model.name: self.reset_load_signals(name)
            )

    def unregister(self, name: str) -> None:
        m = self._models.pop(name, None)
        if m is not None:
            m.unload()
            if hasattr(m, "engine"):
                prom.REGISTRY.remove_collector(("engine", name))
        if self._batchers.pop(name, None) is not None:
            prom.REGISTRY.remove_collector(("batcher", name))

    def get(self, name: str) -> Model:
        if name not in self._models:
            raise web.HTTPNotFound(reason=f"model '{name}' not found")
        return self._models[name]

    def has(self, name: str) -> bool:
        return name in self._models

    def list_models(self) -> list[str]:
        return sorted(self._models)

    # -- execution ----------------------------------------------------------

    def effective_headers(
        self, headers: dict | None
    ) -> tuple[dict, float | None]:
        """Normalize the deadline contract ONCE at dataplane admission:
        parse the wire budget (or apply the server default), stamp the
        process-local absolute header so the batcher and engine charge
        against the same clock edge, and fail already-expired requests
        before they cost anything."""
        headers = dict(headers or {})
        # an absolute-deadline stamp arriving from a CLIENT is another
        # process's monotonic clock (or a bypass attempt) — only this
        # dataplane stamps it, so strip foreign ones before parsing
        headers.pop(DEADLINE_ABS_HEADER, None)
        headers.pop(DEADLINE_ABS_HEADER.title(), None)
        deadline = deadline_from_headers(headers)
        if deadline is None and self.default_deadline_ms is not None:
            deadline = time.monotonic() + self.default_deadline_ms / 1e3
        if deadline is not None:
            headers[DEADLINE_ABS_HEADER] = repr(deadline)
            if deadline - time.monotonic() <= 0:
                DEADLINE_EXPIRED.labels(stage="admission").inc()
                raise DeadlineExceeded(
                    "deadline already expired at the dataplane",
                    stage="admission",
                )
        return headers, deadline

    async def _predict_flat(self, model: Model, flat: list[Any]) -> list[Any]:
        x = model.preprocess({"instances": flat})
        y = model.predict(x)
        out = model.postprocess(y)
        if isinstance(out, dict) and "predictions" in out:
            out = out["predictions"]
        out = list(out)
        if len(out) != len(flat):
            # a silent mismatch would slice wrong results back to callers
            raise RuntimeError(
                f"model '{model.name}' returned {len(out)} predictions "
                f"for {len(flat)} instances"
            )
        return out

    async def infer(self, name: str, payload: Any, headers=None) -> Any:
        model = self.get(name)
        if not model.ready:
            raise web.HTTPServiceUnavailable(reason=f"model '{name}' not ready")
        if isinstance(payload, dict) and isinstance(payload.get("inputs"), dict):
            # v2 named tensors → per-instance rows so multi-input requests
            # batch correctly and keep attention_mask/token_type_ids intact
            from kubeflow_tpu.serve.model import JAXModel

            payload = {"instances": JAXModel.payload_rows(payload)}
        headers, deadline = self.effective_headers(headers)
        req_id = headers.get("x-request-id") or headers.get(
            "X-Request-Id", str(uuid.uuid4())
        )
        # request tracing: continue the wire context (gateway/client) or
        # mint a fresh trace for direct-to-replica traffic; the restamped
        # header parents the engine-stage spans, and the ambient span
        # correlates the audit log lines below
        span = TRACER.span("dataplane", ctx=ctx_from_headers(headers))
        ctok = None
        if span:
            span.set_attr("model", name)
            span.set_attr("request_id", req_id)
            headers[TRACE_HEADER] = span.header()
            ctok = _trace.set_current(span)
        try:
            if self.logger is not None:
                self.logger.log_request(name, req_id, payload)
            t0 = time.perf_counter()
            self.inflight[name] = self.inflight.get(name, 0) + 1
            try:
                batcher = self._batchers.get(name)
                if batcher is not None and isinstance(payload, dict) and "instances" in payload:
                    preds = await batcher.submit(
                        list(payload["instances"]), deadline=deadline,
                        trace=span if span else None,
                    )
                    result: Any = {"predictions": preds}
                else:
                    result = await model(payload, headers)
            except BaseException as e:
                if span:
                    status = _span_status(e)
                    if status == "error":
                        span.set_attr("error", f"{type(e).__name__}: {e}")
                    span.end(status)
                raise
            finally:
                self.inflight[name] = max(0, self.inflight.get(name, 0) - 1)
            dt = (time.perf_counter() - t0) * 1e3
            self.metrics["requests_total"][name] = self.metrics["requests_total"].get(name, 0) + 1
            # bounded reservoir: long-lived servers must not accumulate a
            # sample per request forever
            self.metrics["latency_ms"].setdefault(name, deque(maxlen=4096)).append(dt)
            if self.logger is not None:
                self.logger.log_response(name, req_id, result)
            if span:
                span.end()
            return result
        finally:
            if ctok is not None:
                _trace.reset_current(ctok)

    async def explain(self, name: str, payload: Any, headers=None) -> Any:
        model = self.get(name)
        if not model.ready:
            raise web.HTTPServiceUnavailable(reason=f"model '{name}' not ready")
        out = model.explain(payload, headers)
        if isinstance(out, dict) and "explanations" in out:
            return out
        return {"explanations": out}


class ModelServer:
    def __init__(
        self,
        models: list[Model] | None = None,
        *,
        http_port: int = 8080,
        grpc_port: int | None = None,
        logger: RequestLogger | None = None,
        batcher: BatcherConfig | None = None,
        drain_grace_s: float = 10.0,
        default_deadline_ms: float | None = None,
        role: str = "both",
    ):
        if role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be 'both', 'prefill' or 'decode'; got {role!r}"
            )
        #: disaggregated-serving pool role (``kft serve --role``): a
        #: ``prefill`` replica serves kv_span:prefill and is excluded
        #: from gateway data-path selection; a ``decode`` replica pulls
        #: spans from the gateway-stamped prefill peer instead of
        #: prefilling locally; ``both`` (default) is classic colocated
        #: serving. Advertised in /v2/health/ready so fleets are
        #: inspectable.
        self.role = role
        self.http_port = http_port
        self.grpc_port = grpc_port
        #: graceful-drain budget: on stop, readiness flips to 503 first
        #: (load balancers stop sending), then in-flight work gets this
        #: long to finish before teardown — lossless rolling restarts
        self.drain_grace_s = drain_grace_s
        self._draining = False
        # cold start is compile-dominated (BASELINE config 5): persist XLA
        # compiles so every server start after the first skips them
        from kubeflow_tpu.core.compcache import enable_compilation_cache

        enable_compilation_cache()
        self.dataplane = DataPlane(
            logger=logger, default_deadline_ms=default_deadline_ms
        )
        self._batcher_cfg = batcher
        self._graphs: dict[str, Any] = {}  # name → InferenceGraph
        for m in models or []:
            self.register(m)
        self._runner: web.AppRunner | None = None
        self._grpc = None

    def register(self, model: Model) -> None:
        if not model.ready:
            model.load()
        self.dataplane.register(model, self._batcher_cfg)

    def register_graph(self, spec) -> None:
        """Materialize a ``GraphSpec`` over this server's dataplane —
        every serviceName must already be registered (admission check).
        Served at ``POST /v1/graphs/{name}:infer``."""
        self._graphs[spec.name] = spec.build(self.dataplane)

    # -- app ----------------------------------------------------------------

    def build_app(self) -> web.Application:
        app = web.Application(client_max_size=64 * 2**20)
        dp = self.dataplane
        app.router.add_get("/", lambda r: web.json_response({"status": "alive"}))
        app.router.add_get("/metrics", self._metrics)
        # tail-sampled request traces (obs/trace.py):
        # ?limit=N bounds the reply, ?format=perfetto converts to
        # Chrome/Perfetto trace_event JSON (what `kft trace dump` reads)
        app.router.add_get("/debug/traces", self._debug_traces)
        app.router.add_get(
            "/v1/models", lambda r: web.json_response({"models": dp.list_models()})
        )
        app.router.add_get("/v1/models/{name}", self._v1_status)
        app.router.add_post("/v1/models/{name}:predict", self._v1_predict)
        app.router.add_post("/v1/models/{name}:explain", self._v1_explain)
        app.router.add_get(
            "/v2/health/live", lambda r: web.json_response({"live": True})
        )
        app.router.add_get("/v2/health/ready", self._v2_ready)
        app.router.add_get("/v2/models/{name}", self._v2_meta)
        app.router.add_post("/v2/models/{name}/infer", self._v2_infer)
        # text-generation extension (KServe v2 generate protocol analog):
        # answered by engine-backed models; 501 elsewhere
        app.router.add_post("/v2/models/{name}/generate", self._v2_generate)
        app.router.add_post(
            "/v2/models/{name}/generate_stream", self._v2_generate_stream
        )
        # cross-replica prefix-KV transfer (autoscale/kv_transfer.py):
        # index what this replica holds, export entries to a peer, or
        # pull the entries a ring remap assigned here from their previous
        # owner — 501 for non-engine models
        app.router.add_get(
            "/v2/models/{name}/prefix_cache", self._prefix_index
        )
        app.router.add_post(
            "/v2/models/{name}/prefix_cache:export", self._prefix_export
        )
        app.router.add_post(
            "/v2/models/{name}/prefix_cache:pull", self._prefix_pull
        )
        # disaggregated serving (gateway/router.py dispatch): a prefill
        # replica runs ONLY the prefill of one request and returns the
        # finished KV span + meta — the per-request generalization of
        # the prefix transfer above, through the same npz codec
        app.router.add_post(
            "/v2/models/{name}/kv_span:prefill", self._kv_span_prefill
        )
        # InferenceGraph routing plane ([kserve] cmd/router analog)
        app.router.add_get(
            "/v1/graphs",
            lambda r: web.json_response({"graphs": sorted(self._graphs)}),
        )
        app.router.add_post("/v1/graphs/{name}:infer", self._graph_infer)
        return app

    async def _graph_infer(self, req: web.Request) -> web.Response:
        name = req.match_info["name"]
        if name not in self._graphs:
            raise web.HTTPNotFound(reason=f"graph '{name}' not found")
        try:
            payload = await req.json()
        except Exception as e:
            raise web.HTTPBadRequest(reason=str(e))
        try:
            out = await self._graphs[name].infer(payload)
        except ValueError as e:  # e.g. switch with no matching branch
            raise web.HTTPBadRequest(reason=str(e))
        except Exception as e:
            shed = _shed_response(e)
            if shed is None:
                raise
            raise shed
        return web.json_response(out)

    async def _v2_generate(self, req: web.Request) -> web.Response:
        name = req.match_info["name"]
        model = self.dataplane.get(name)
        if getattr(model, "stream_row_tokens", None) is None:
            raise web.HTTPNotImplemented(
                reason=f"model '{name}' is not a generative engine runtime"
            )
        try:
            body = await req.json()
        except Exception as e:
            raise web.HTTPBadRequest(reason=str(e))
        try:
            result = await self.dataplane.infer(
                name, {"instances": [body]}, dict(req.headers)
            )
        except ValueError as e:  # same 400 contract as /infer and :predict
            raise web.HTTPBadRequest(reason=str(e))
        except Exception as e:
            shed = _shed_response(e)
            if shed is None:
                raise
            raise shed
        return web.json_response(result["predictions"][0])

    async def _v2_generate_stream(self, req: web.Request) -> web.StreamResponse:
        """Server-sent events: one ``data:`` frame per decode chunk as the
        engine produces it, then a terminal ``done`` frame."""
        import json
        import threading

        name = req.match_info["name"]
        model = self.dataplane.get(name)
        stream_rows = getattr(model, "stream_row_tokens", None)
        if stream_rows is None:
            raise web.HTTPNotImplemented(
                reason=f"model '{name}' does not support streaming "
                "(causal-lm-engine runtimes do)"
            )
        if not model.ready:  # same 503 contract as DataPlane.infer
            raise web.HTTPServiceUnavailable(
                reason=f"model '{name}' not ready"
            )
        try:
            body = await req.json()
            row = model.preprocess({"instances": [body]})[0]
        except Exception as e:
            raise web.HTTPBadRequest(reason=str(e))
        # streamed requests get their own dataplane-stage span — same wire
        # contract as infer(): continue the gateway/client context or mint
        # one, restamp the header so the engine spans parent correctly
        span = TRACER.span(
            "dataplane.stream", ctx=ctx_from_headers(dict(req.headers))
        )
        ctok = None
        if span:
            span.set_attr("model", name)
            ctok = _trace.set_current(span)
        # streamed requests ride the same accounting as the DataPlane hot
        # path — /metrics, the audit log, AND the deadline contract
        req_id = req.headers.get("x-request-id", str(uuid.uuid4()))
        if self.dataplane.logger is not None:
            self.dataplane.logger.log_request(
                name, req_id, {"instances": [body]}
            )
        t0 = time.perf_counter()

        try:
            # admission is EAGER in stream_row_tokens: overload/shed raises
            # here, before any response bytes commit, and becomes a clean
            # 429 (overload) or 503 + Retry-After (deadline shed)
            hdrs, _ = self.dataplane.effective_headers(dict(req.headers))
            if span:
                hdrs[TRACE_HEADER] = span.header()
            gen = stream_rows(row, hdrs)
        except Exception as e:
            if span:
                status = _span_status(e)
                if status == "error":
                    span.set_attr("error", f"{type(e).__name__}: {e}")
                span.end(status)
            if ctok is not None:
                _trace.reset_current(ctok)
            shed = _shed_response(e)
            if shed is None:
                raise
            raise shed

        resp = web.StreamResponse(
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
            }
        )
        await resp.prepare(req)
        # streams occupy engine rows: they count as in-flight for the
        # drain wait and the kft_server_inflight load signal
        dp_inflight = self.dataplane.inflight
        dp_inflight[name] = dp_inflight.get(name, 0) + 1
        loop = asyncio.get_running_loop()
        frames: asyncio.Queue = asyncio.Queue()
        disconnected = threading.Event()

        def pump() -> None:
            def emit(item) -> None:
                try:
                    loop.call_soon_threadsafe(frames.put_nowait, item)
                except RuntimeError:  # loop closed (server shutdown)
                    disconnected.set()

            try:
                for toks in gen:
                    if disconnected.is_set():
                        break
                    emit(("tokens", toks))
                emit(("done", None))
            except Exception as e:  # noqa: BLE001 — surfaced as an SSE frame
                emit(("error", e))
            finally:
                # closing the generator cancels the engine row, so a
                # disconnected client stops consuming decode capacity
                gen.close()

        threading.Thread(
            target=pump, name=f"sse-{name}", daemon=True
        ).start()
        total = 0
        streamed: list[int] = []
        try:
            while True:
                kind, val = await frames.get()
                if kind == "tokens":
                    toks = [int(t) for t in val]
                    total += len(toks)
                    streamed.extend(toks)
                    payload = {"token_ids": toks}
                elif kind == "done":
                    payload = {"done": True, "n_tokens": total}
                else:
                    payload = {"error": str(val)}
                    if isinstance(val, EngineRestarting):
                        # the watchdog's mid-stream poison is NOT terminal
                        # for the generation — only for this replica. Mark
                        # the frame resumable so the gateway re-dispatches
                        # with the committed prefix instead of forwarding
                        # the error to the client.
                        payload["resumable"] = True
                await resp.write(f"data: {json.dumps(payload)}\n\n".encode())
                if kind != "tokens":
                    break
            await resp.write_eof()
        except (ConnectionResetError, ConnectionError, asyncio.CancelledError):
            disconnected.set()  # pump stops; generator close frees the row
            raise
        finally:
            dp_inflight[name] = max(0, dp_inflight.get(name, 0) - 1)
            dt = (time.perf_counter() - t0) * 1e3
            m = self.dataplane.metrics
            m["requests_total"][name] = m["requests_total"].get(name, 0) + 1
            m["latency_ms"].setdefault(name, deque(maxlen=4096)).append(dt)
            if self.dataplane.logger is not None:
                self.dataplane.logger.log_response(
                    name, req_id,
                    {"predictions": [{"token_ids": streamed}],
                     "streamed": True, "complete": not disconnected.is_set()},
                )
            if span:
                span.set_attr("tokens_streamed", total)
                span.end(
                    "cancelled" if disconnected.is_set() else None
                )
            if ctok is not None:
                _trace.reset_current(ctok)
        return resp

    # -- prefix-KV peer transfer ------------------------------------------ #

    def _prefix_engine(self, name: str):
        model = self.dataplane.get(name)
        eng = getattr(model, "engine", None)
        if eng is None or not getattr(eng, "prefix_cache_enabled", False):
            raise web.HTTPNotImplemented(
                reason=f"model '{name}' has no prefix cache to transfer"
            )
        return eng

    async def _prefix_index(self, req: web.Request) -> web.Response:
        eng = self._prefix_engine(req.match_info["name"])
        keys = eng.prefix_index()
        return web.json_response({
            "keys": [list(k) for k in keys],
            "count": len(keys),
            "tokens": sum(len(k) for k in keys),
        })

    async def _prefix_export(self, req: web.Request) -> web.Response:
        eng = self._prefix_engine(req.match_info["name"])
        try:
            body = await req.json() if req.can_read_body else {}
            keys = body.get("keys")
            limit = body.get("limit")
        except Exception as e:
            raise web.HTTPBadRequest(reason=str(e))
        loop = asyncio.get_running_loop()
        # the device→host sync and npz packing leave the event loop
        blob = await loop.run_in_executor(
            None,
            lambda: encode_prefix_entries(
                eng.export_prefix_entries(keys, limit=limit)
            ),
        )
        return web.Response(
            body=blob, content_type="application/octet-stream"
        )

    async def _prefix_pull(self, req: web.Request) -> web.Response:
        """Pull stored prefix entries from ``peer`` into this replica's
        engine — the new-owner side of a hash-ring remap."""
        name = req.match_info["name"]
        eng = self._prefix_engine(name)
        try:
            body = await req.json()
            peer = str(body["peer"]).rstrip("/")
            keys = body.get("keys")
        except Exception as e:
            raise web.HTTPBadRequest(reason=str(e))
        import aiohttp

        try:
            async with aiohttp.ClientSession() as session:
                async with session.post(
                    f"{peer}/v2/models/{name}/prefix_cache:export",
                    json={"keys": keys} if keys is not None else {},
                    timeout=aiohttp.ClientTimeout(total=120.0),
                ) as resp:
                    if resp.status != 200:
                        raise web.HTTPBadGateway(
                            reason=f"peer export returned {resp.status}"
                        )
                    blob = await resp.read()
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
            raise web.HTTPBadGateway(reason=f"peer {peer} unreachable: {e}")
        loop = asyncio.get_running_loop()
        imported = await loop.run_in_executor(
            None,
            lambda: eng.import_prefix_entries(decode_prefix_entries(blob)),
        )
        return web.json_response({"imported": imported, "peer": peer})

    async def _kv_span_prefill(self, req: web.Request) -> web.Response:
        """Disaggregated serving, prefill-pool side: chunk-prefill
        ``ids`` on this replica's engine and stream the finished KV span
        back through the npz codec (``__meta__`` carries real_len /
        first_tok / valid). The caller is a decode replica's
        ``fetch_kv_span``; the ``x-kft-trace`` context it forwards
        parents this engine's spans under the SAME trace id, so one
        trace shows gateway → kv.ship → both engine legs."""
        name = req.match_info["name"]
        model = self.dataplane.get(name)
        eng = getattr(model, "engine", None)
        if eng is None or not hasattr(eng, "prefill_span"):
            raise web.HTTPNotImplemented(
                reason=f"model '{name}' has no engine to prefill spans"
            )
        try:
            body = await req.json()
            ids = [int(t) for t in body["ids"]]
            temperature = float(body.get("temperature", 0.0))
            seed = body.get("seed")
            seed = None if seed is None else int(seed)
            if not ids:
                raise ValueError("empty ids")
        except Exception as e:
            raise web.HTTPBadRequest(reason=str(e))
        ctx = ctx_from_headers(dict(req.headers))
        deadline = deadline_from_headers(dict(req.headers))
        loop = asyncio.get_running_loop()

        def run() -> bytes:
            from kubeflow_tpu.serve.engine import KV_SHIP_BYTES
            from kubeflow_tpu.serve.kv_codec import encode_kv_entries

            tree, meta = eng.prefill_span(
                ids, temperature=temperature, deadline=deadline, trace=ctx,
                seed=seed,
            )
            blob = encode_kv_entries([(tuple(ids), tree)], meta)
            KV_SHIP_BYTES.labels(model=name, direction="export").inc(
                len(blob)
            )
            return blob

        try:
            # prefill + D2H + npz packing leave the event loop
            blob = await loop.run_in_executor(None, run)
        except ValueError as e:
            raise web.HTTPBadRequest(reason=str(e))
        except Exception as e:
            shed = _shed_response(e)
            if shed is None:
                raise
            raise shed
        return web.Response(
            body=blob, content_type="application/octet-stream"
        )

    async def _v1_status(self, req: web.Request) -> web.Response:
        m = self.dataplane.get(req.match_info["name"])
        return web.json_response({"name": m.name, "ready": m.ready})

    async def _v1_predict(self, req: web.Request) -> web.Response:
        name = req.match_info["name"]
        try:
            body = await req.json()
            protocol.decode_v1(body)  # validate shape of the envelope
        except Exception as e:  # malformed client input is 400, not 500
            raise web.HTTPBadRequest(reason=str(e))
        try:
            result = await self.dataplane.infer(name, body, dict(req.headers))
        except ValueError as e:
            raise web.HTTPBadRequest(reason=str(e))
        except Exception as e:
            shed = _shed_response(e)
            if shed is None:
                raise
            raise shed
        return web.json_response(protocol.encode_v1(result))

    async def _v1_explain(self, req: web.Request) -> web.Response:
        name = req.match_info["name"]
        try:
            body = await req.json()
            protocol.decode_v1(body)
        except Exception as e:
            raise web.HTTPBadRequest(reason=str(e))
        try:
            result = await self.dataplane.explain(name, body, dict(req.headers))
        except NotImplementedError as e:
            raise web.HTTPNotImplemented(reason=str(e))
        except ValueError as e:
            raise web.HTTPBadRequest(reason=str(e))
        return web.json_response(result)

    async def _v2_ready(self, req: web.Request) -> web.Response:
        if self._draining:
            # drain protocol: readiness goes 503 FIRST so balancers stop
            # routing here, while in-flight (and straggler) requests still
            # complete during the grace window
            return web.json_response(
                {"ready": False, "draining": True, "role": self.role},
                status=503,
            )
        ready = all(self.dataplane.get(n).ready for n in self.dataplane.list_models())
        return web.json_response({"ready": ready, "role": self.role})

    async def _v2_meta(self, req: web.Request) -> web.Response:
        m = self.dataplane.get(req.match_info["name"])
        return web.json_response(
            {"name": m.name, "ready": m.ready, "platform": "jax-tpu"}
        )

    async def _v2_infer(self, req: web.Request) -> web.Response:
        name = req.match_info["name"]
        try:
            body = await req.json()
            tensors = protocol.decode_v2(body)
            if not tensors:
                raise ValueError("v2 request has no input tensors")
        except Exception as e:
            raise web.HTTPBadRequest(reason=str(e))
        try:
            result = await self.dataplane.infer(
                name, {"inputs": tensors}, dict(req.headers)
            )
        except ValueError as e:
            raise web.HTTPBadRequest(reason=str(e))
        except Exception as e:
            shed = _shed_response(e)
            if shed is None:
                raise
            raise shed
        preds = result["predictions"] if isinstance(result, dict) else result
        import numpy as np

        return web.json_response(protocol.encode_v2(name, np.asarray(preds)))

    async def _debug_traces(self, req: web.Request) -> web.Response:
        try:
            limit = int(req.query.get("limit", "64"))
        except ValueError:
            raise web.HTTPBadRequest(reason="limit must be an integer")
        snap = TRACER.snapshot(limit=max(1, min(limit, 256)))
        if req.query.get("format") == "perfetto":
            return web.json_response(to_perfetto(snap))
        return web.json_response(snap)

    async def _metrics(self, req: web.Request) -> web.Response:
        lines = []
        for name, n in self.dataplane.metrics["requests_total"].items():
            lines.append(
                f'{names.REQUESTS_TOTAL}{{model="{name}"}} {n}'
            )
        for name, lat in self.dataplane.metrics["latency_ms"].items():
            if lat:
                srt = sorted(lat)
                p50 = srt[len(srt) // 2]
                p99 = srt[min(len(srt) - 1, int(len(srt) * 0.99))]
                lines.append(f'{names.LATENCY_P50_MS}{{model="{name}"}} {p50:.3f}')
                lines.append(f'{names.LATENCY_P99_MS}{{model="{name}"}} {p99:.3f}')
        # live load signals for the gateway's least-outstanding balancer
        for name in self.dataplane.list_models():
            n = self.dataplane.inflight.get(name, 0)
            lines.append(f'{names.SERVER_INFLIGHT}{{model="{name}"}} {n}')
        for name, b in sorted(self.dataplane._batchers.items()):
            lines.append(
                f'{names.SERVER_QUEUE_DEPTH}{{model="{name}"}} '
                f"{b.queue_depth}"
            )
        # batcher occupancy gauges, matching the engine's pool gauges
        for name, b in sorted(self.dataplane._batchers.items()):
            lines.append(
                f'{names.BATCHER_BATCHES}{{model="{name}"}} '
                f'{b.stats["batches"]}'
            )
            lines.append(
                f'{names.BATCHER_INSTANCES}{{model="{name}"}} '
                f'{b.stats["instances"]}'
            )
            lines.append(
                f'{names.BATCHER_MEAN_OCCUPANCY}{{model="{name}"}} '
                f"{b.mean_occupancy:.3f}"
            )
            lines.append(
                f'{names.BATCHER_FAIL_ISOLATIONS}{{model="{name}"}} '
                f'{b.stats["fail_isolations"]}'
            )
        # engine-backed models export their scheduler gauges too
        for name in self.dataplane.list_models():
            model = self.dataplane.get(name)
            eng = getattr(model, "engine", None)
            if eng is None or not hasattr(eng, "stats"):
                continue
            for key, val in dict(eng.stats).items():  # snapshot: engine thread writes
                lines.append(
                    f'{names.ENGINE_PREFIX}{key}{{model="{name}"}} {val}'
                )
            lines.append(
                f'{names.ENGINE_ACTIVE_ROWS}{{model="{name}"}} '
                f"{int(eng.active.sum())}"
            )
            ov = getattr(eng, "overlap", None)
            if ov is not None:  # pipelined-decode overlap gauges
                lines.append(
                    f'{names.ENGINE_DECODE_GAP_MS}{{model="{name}"}} '
                    f'{ov["decode_gap_ms"]:.3f}'
                )
                lines.append(
                    f'{names.ENGINE_D2H_DRAIN_MS}{{model="{name}"}} '
                    f'{ov["d2h_drain_ms"]:.3f}'
                )
                lines.append(
                    f'{names.ENGINE_CARRY_UPLOADS_TOTAL}{{model="{name}"}} '
                    f'{ov["carry_uploads"]}'
                )
                lines.append(
                    f'{names.ENGINE_SLOT_OCCUPANCY}{{model="{name}"}} '
                    f'{ov["slot_occupancy"]:.3f}'
                )
                lines.append(
                    f'{names.ENGINE_SPEC_ACCEPTANCE}{{model="{name}"}} '
                    f'{ov["spec_acceptance"]:.3f}'
                )
            # speculative-decode counters + prefix-cache effectiveness
            # (kft_engine_prefix_* — the gateway's prefix affinity reads
            # these to know whether its steering actually lands hits)
            lines.append(
                f'{names.ENGINE_SPEC_PROPOSED_TOTAL}{{model="{name}"}} '
                f'{eng.stats.get("spec_proposed", 0)}'
            )
            lines.append(
                f'{names.ENGINE_SPEC_ACCEPTED_TOTAL}{{model="{name}"}} '
                f'{eng.stats.get("spec_accepted", 0)}'
            )
            pc = eng.prefix_cache_stats()
            lines.append(
                f'{names.ENGINE_PREFIX_HITS_TOTAL}{{model="{name}"}} '
                f'{pc["hits"]}'
            )
            lines.append(
                f'{names.ENGINE_PREFIX_TOKENS_REUSED_TOTAL}'
                f'{{model="{name}"}} {pc["tokens_reused"]}'
            )
            lines.append(
                f'{names.ENGINE_PREFIX_ENTRIES}{{model="{name}"}} '
                f'{pc["entries"]}'
            )
            lines.append(
                f'{names.ENGINE_PREFIX_TOKENS_STORED}{{model="{name}"}} '
                f'{pc["tokens_stored"]}'
            )
            # cross-replica transfer counters: a hit on an imported entry
            # is KV this replica never re-prefilled (the burst e2e's
            # recovery assertion reads these per-replica)
            lines.append(
                f'{names.ENGINE_PREFIX_IMPORTED_TOTAL}{{model="{name}"}} '
                f'{pc["imported"]}'
            )
            lines.append(
                f'{names.ENGINE_PREFIX_EXPORTED_TOTAL}{{model="{name}"}} '
                f'{pc["exported"]}'
            )
            pager = getattr(eng, "pager", None)
            if pager is not None:  # paged-KV engines: live pool pressure
                for key, val in pager.stats().items():
                    lines.append(
                        f'{names.ENGINE_KV_PREFIX}{key}{{model="{name}"}} '
                        f"{val}"
                    )
                # KV quantization health
                if ov is not None and "kv_quant_error" in ov:
                    lines.append(
                        f'{names.ENGINE_KV_QUANT_ERROR}{{model="{name}"}} '
                        f'{ov["kv_quant_error"]:.6f}'
                    )
            # engine watchdog: trips by reason + supervised restarts (the
            # smoke/chaos assertions read these per-replica, so they must
            # be on THIS process's /metrics, not only the shared registry)
            wd = getattr(model, "watchdog", None)
            if wd is not None:
                for reason, n in sorted(wd.stats["trips"].items()):
                    lines.append(
                        f'{names.ENGINE_WATCHDOG_TRIPS_TOTAL}'
                        f'{{model="{name}",reason="{reason}"}} {n}'
                    )
                lines.append(
                    f'{names.ENGINE_RESTARTS_TOTAL}{{model="{name}"}} '
                    f'{wd.stats["restarts"]}'
                )
        # server-side TTFT/TPOT histograms (obs/trace.py) — per-replica
        # exposition so smoke/e2e assertions read them without the shared
        # ObsServer registry scrape
        lines.extend(_trace.TTFT_MS.expose())
        lines.extend(_trace.TPOT_MS.expose())
        # what start-up cost this replica: programs built, seconds spent,
        # persistent-cache hits (core/compcache.py)
        for counter in (
            compcache.XLA_PROGRAMS, compcache.XLA_COMPILE_SECONDS,
            compcache.XLA_CACHE_HITS,
        ):
            lines.extend(counter.expose())
        return web.Response(text="\n".join(lines) + "\n")

    # -- runtime ------------------------------------------------------------

    async def start_async(self) -> None:
        self._runner = web.AppRunner(self.build_app())
        await self._runner.setup()
        site = web.TCPSite(self._runner, "0.0.0.0", self.http_port)
        await site.start()
        if self.grpc_port is not None:
            # same DataPlane answers both transports (v2 protocol parity);
            # MUST share this loop or a shared Batcher deadlocks cross-loop
            import asyncio

            from kubeflow_tpu.serve.grpc_server import GrpcInferenceServer

            self._grpc = GrpcInferenceServer(
                self.dataplane,
                port=self.grpc_port,
                loop=asyncio.get_running_loop(),
            )
            self.grpc_port = self._grpc.start()

    async def stop_async(self) -> None:
        # graceful drain: readiness flips to 503 immediately (balancers
        # stop sending), then in-flight work gets a bounded grace window
        # before the listeners tear down — a rolling restart behind the
        # gateway loses zero requests
        self._draining = True
        deadline = time.monotonic() + self.drain_grace_s
        while (
            self.dataplane.total_inflight() > 0
            and time.monotonic() < deadline
        ):
            await asyncio.sleep(0.02)
        if self._grpc is not None:
            # stop_async drains on an executor thread: a blocking stop() here
            # would park the shared event loop, so in-flight RPCs waiting on
            # coroutines scheduled to this loop could never finish and were
            # always cancelled at the grace deadline (VERDICT r3 weak #4)
            await self._grpc.stop_async()
            self._grpc = None
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    def start(self) -> None:
        """Blocking entrypoint (the container CMD)."""

        async def main():
            await self.start_async()
            while True:
                await asyncio.sleep(3600)

        asyncio.run(main())
