"""Profiling triggers + the per-process observability server.

The reference has no first-party tracer; controllers expose /metrics and
training-side profiling is user-space TensorBoard (SURVEY.md §5.1). On TPU
the XLA profiler is dramatically richer — op-level MXU/HBM/ICI utilization
— so the framework makes it a first-class endpoint on every long-running
process (trainer, model server, controller):

- ``GET /healthz``            → liveness (200 ok)
- ``GET /metrics``            → Prometheus exposition of ``prom.REGISTRY``
- ``POST /profile?seconds=2`` → ``jax.profiler`` trace into the logdir,
  viewable with tensorboard-plugin-profile (installed in this image)
- ``GET /debug/state``        → optional JSON state dump hook

The server runs an aiohttp app on a daemon thread (same stack as the
serving plane — SURVEY.md §0: no fastapi/uvicorn in this image).
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import logging
import threading
import time
from pathlib import Path
from typing import Any, Callable

from kubeflow_tpu.obs import prom
from kubeflow_tpu.obs.trace import TRACER, ctx_from_headers
from kubeflow_tpu.obs.webhost import ThreadedAiohttpServer

logger = logging.getLogger(__name__)


@contextlib.contextmanager
def capture_trace(logdir: str | Path):
    """Trace everything inside the block into ``logdir`` (XLA ops + host)."""
    import jax

    Path(logdir).mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(logdir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class ObsServer(ThreadedAiohttpServer):
    """Observability sidecar-in-process. Thread-hosted aiohttp app."""

    thread_name = "kft-obs-server"

    def __init__(
        self,
        *,
        port: int = 0,
        host: str = "127.0.0.1",
        registry: prom.Registry | None = None,
        profile_logdir: str | Path | None = None,
        state_fn: Callable[[], Any] | None = None,
    ):
        super().__init__(host=host, port=port)
        self.registry = registry or prom.REGISTRY
        self.profile_logdir = Path(profile_logdir or "profiles")
        self.state_fn = state_fn
        self._profiling = threading.Lock()

    # -- handlers ------------------------------------------------------- #

    async def _healthz(self, request):
        from aiohttp import web

        return web.Response(text="ok")

    async def _metrics(self, request):
        from aiohttp import web

        return web.Response(
            text=self.registry.expose(),
            content_type="text/plain",
            charset="utf-8",
        )

    async def _profile(self, request):
        from aiohttp import web

        seconds = float(request.query.get("seconds", "2"))
        seconds = max(0.05, min(seconds, 300.0))
        logdir = self.profile_logdir / time.strftime("%Y%m%d-%H%M%S")
        if not self._profiling.acquire(blocking=False):
            return web.json_response(
                {"error": "a profile capture is already running"}, status=409
            )
        # the capture itself becomes a span: traces answer "who triggered
        # an XLA profile, when, and where did the dump land"
        span = TRACER.span(
            "profile.capture", ctx=ctx_from_headers(request.headers)
        )
        if span:
            span.set_attr("logdir", str(logdir))
            span.set_attr("seconds", seconds)

        def run():
            try:
                with capture_trace(logdir):
                    time.sleep(seconds)
            finally:
                self._profiling.release()

        # Trace on an executor thread: the capture brackets whatever the
        # process's compute threads do during the window, without blocking
        # the event loop.
        try:
            await asyncio.get_running_loop().run_in_executor(None, run)
        except Exception:
            span.end("error")
            raise
        span.end()
        return web.json_response(
            {"logdir": str(logdir), "seconds": seconds}
        )

    async def _state(self, request):
        from aiohttp import web

        if self.state_fn is None:
            return web.json_response({}, status=404)
        return web.Response(
            text=json.dumps(self.state_fn(), default=str),
            content_type="application/json",
        )

    # -- lifecycle ------------------------------------------------------ #

    def _make_app(self):
        from aiohttp import web

        app = web.Application()
        app.router.add_get("/healthz", self._healthz)
        app.router.add_get("/metrics", self._metrics)
        app.router.add_post("/profile", self._profile)
        app.router.add_get("/debug/state", self._state)
        return app

    def start(self) -> "ObsServer":
        super().start()
        logger.info("obs server on http://%s:%d", self.host, self.port)
        return self
