"""ReplicaFleet: the autoscaler's actuator over real serving replicas.

One fleet owns the replica lifecycle for ONE service: launching new
replicas (in-process ``ModelServer``s in tests, ``kft serve``
subprocesses in production — the ``launch`` callable decides), keeping
the gateway's :class:`BackendPool` membership in sync (a ``pool.add``
wakes the activator's parked queue), and running the prefix-KV
rebalance around every membership change:

- **scale-up**: the new replica is launched and — BEFORE it joins the
  pool — pulls the prefix entries the post-add hash ring assigns to it
  from their previous owners, so its first remapped request hits warm KV
  instead of re-prefilling;
- **scale-down**: the leaving replica first evacuates its entries to the
  survivors that now own them, then drains (no new selection, removal
  after the last in-flight release) and stops — zero client-visible
  failures by construction.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import time
from typing import Any, Awaitable, Callable

from kubeflow_tpu.autoscale import kv_transfer
from kubeflow_tpu.obs import names, prom

logger = logging.getLogger(__name__)

KV_TRANSFERS = prom.REGISTRY.counter(
    names.AUTOSCALER_KV_TRANSFERS_TOTAL,
    "prefix-KV entries moved between replicas after a ring remap",
    ("service",),
)
REPLICAS = prom.REGISTRY.gauge(
    names.FLEET_REPLICAS,
    "replicas the fleet currently runs (actuated, not desired)",
    ("service",),
)


@dataclasses.dataclass
class Replica:
    index: int
    url: str
    stop: Callable[[], Awaitable[None]]


class ReplicaFleet:
    """``launch(index) -> (url, async stop)`` creates one serving replica
    and returns once it is accepting HTTP (the launcher owns readiness).
    ``model`` names the engine model whose prefix cache rides the
    transfers; None (or ``transfer_prefix_kv=False``) disables them."""

    def __init__(
        self,
        service: str,
        launch: Callable[[int], Awaitable[tuple[str, Callable[[], Awaitable[None]]]]],
        *,
        pool: Any = None,
        model: str | None = None,
        role: str = "both",
        transfer_prefix_kv: bool = True,
        prefix_tokens: int = 16,
        drain_timeout_s: float = 30.0,
        session: Any = None,
    ):
        self.service = service
        self.launch = launch
        self.pool = pool
        self.model = model
        #: every replica this fleet launches joins the pool with this
        #: disagg role ("both" | "prefill" | "decode") — a prefill pool
        #: and a decode pool are two fleets over the same service
        self.role = role
        self.transfer_prefix_kv = transfer_prefix_kv and model is not None
        self.prefix_tokens = prefix_tokens
        self.drain_timeout_s = drain_timeout_s
        self._session = session
        self._replicas: list[Replica] = []
        self._next_index = 0
        #: serializes scale operations (the autoscaler already serializes
        #: per-service ticks, but kicks and direct calls may interleave)
        self._lock = asyncio.Lock()
        self.stats = {"launched": 0, "stopped": 0, "kv_entries_moved": 0}
        #: read-only scale timeline for reporters (loadgen/reporter.py):
        #: one entry per actuated membership change, monotonic-stamped —
        #: {"t": time.monotonic(), "replicas": n, "direction": "up"|"down"}
        self.events: list[dict] = []

    # -- actuator protocol ----------------------------------------------- #

    def current(self) -> int:
        return len(self._replicas)

    def urls(self) -> list[str]:
        return [r.url for r in self._replicas]

    async def scale_to(self, n: int) -> None:
        async with self._lock:
            while len(self._replicas) < n:
                await self._add_one()
            while len(self._replicas) > n:
                await self._remove_one()

    async def close(self) -> None:
        await self.scale_to(0)
        if self._session is not None:
            await self._session.close()
            self._session = None

    # -- membership ------------------------------------------------------- #

    async def _get_session(self):
        if self._session is None:
            import aiohttp

            self._session = aiohttp.ClientSession()
        return self._session

    async def _add_one(self) -> None:
        index = self._next_index
        self._next_index += 1
        url, stop = await self.launch(index)
        url = url.rstrip("/")
        replica = Replica(index=index, url=url, stop=stop)
        self._replicas.append(replica)
        self.stats["launched"] += 1
        # warm the newcomer BEFORE it takes traffic: pull the prefix
        # entries the post-add ring maps to it from their current holders
        if self.transfer_prefix_kv and len(self._replicas) > 1:
            await self._rebalance(
                urls=self.urls(),
                index_urls=[r.url for r in self._replicas if r is not replica],
            )
        if self.pool is not None:
            # ready → activator flush (prefill-role replicas never become
            # traffic-selectable; they only serve kv_span:prefill pulls)
            self.pool.add(self.service, url, role=self.role)
        self.events.append({
            "t": time.monotonic(),
            "replicas": len(self._replicas),
            "direction": "up",
        })
        REPLICAS.labels(service=self.service).set(len(self._replicas))
        logger.warning(
            "fleet %s: replica #%d up at %s (%d total)",
            self.service, index, url, len(self._replicas),
        )

    async def _remove_one(self) -> None:
        replica = self._replicas.pop()  # LIFO: newest first, oldest stays
        # evacuate its prefix entries to the survivors that now own them —
        # the ring over the remaining urls decides the destinations
        if self.transfer_prefix_kv and self._replicas:
            await self._rebalance(
                urls=self.urls(), index_urls=[replica.url]
            )
        if self.pool is not None:
            self.pool.drain(replica.url)
            deadline = time.monotonic() + self.drain_timeout_s
            while (
                self.pool.find(replica.url) is not None
                and time.monotonic() < deadline
            ):
                await asyncio.sleep(0.02)
        await replica.stop()
        self.stats["stopped"] += 1
        self.events.append({
            "t": time.monotonic(),
            "replicas": len(self._replicas),
            "direction": "down",
        })
        REPLICAS.labels(service=self.service).set(len(self._replicas))
        logger.warning(
            "fleet %s: replica #%d at %s drained and stopped (%d left)",
            self.service, replica.index, replica.url, len(self._replicas),
        )

    async def _rebalance(
        self, *, urls: list[str], index_urls: list[str]
    ) -> None:
        try:
            moved = await kv_transfer.rebalance(
                await self._get_session(),
                self.model,
                urls,
                index_urls=index_urls,
                prefix_tokens=self.prefix_tokens,
            )
        except Exception:  # noqa: BLE001 — a failed transfer costs one
            logger.exception(  # re-prefill, never availability
                "fleet %s: prefix-KV rebalance failed", self.service
            )
            return
        if moved:
            self.stats["kv_entries_moved"] += moved
            KV_TRANSFERS.labels(service=self.service).inc(moved)


def subprocess_launcher(
    command: list[str],
    *,
    ready_path: str = "/v2/health/ready",
    startup_timeout_s: float = 300.0,
    stop_grace_s: float = 15.0,
    workdir: str | None = None,
):
    """Launch helper for production fleets: each replica is a subprocess
    (typically ``kft serve -f isvc.yaml --http-port 0 --port-file
    {port_file}``). ``{port_file}`` in the command is substituted with a
    fresh path the subprocess must write its bound port to; the launcher
    then polls ``ready_path`` until the replica answers ready.

    One process per chip: this launcher (and the gateway it runs beside)
    imports JAX but never opens a backend, so the children own the
    devices — but nothing here restricts which. Every child sees the
    whole host; a fleet of more than one TPU replica per host must pin
    each to its own chip in ``command`` (``{index}`` is substituted), or
    the second child fails or hangs at backend start.

    Returns an async ``launch(index)`` suitable for :class:`ReplicaFleet`.
    """
    import os
    import signal as _signal
    import subprocess
    import tempfile

    async def launch(index: int):
        import aiohttp

        tmp = tempfile.mkdtemp(prefix=f"kft-replica-{index}-")
        port_file = os.path.join(tmp, "port")
        argv = [
            a.replace("{port_file}", port_file).replace(
                "{index}", str(index)
            )
            for a in command
        ]
        log_path = os.path.join(tmp, "replica.log")
        log = open(log_path, "wb")  # noqa: SIM115 — outlives this scope
        proc = subprocess.Popen(
            argv, stdout=log, stderr=subprocess.STDOUT, cwd=workdir
        )
        loop = asyncio.get_running_loop()
        deadline = time.monotonic() + startup_timeout_s

        def read_port() -> int | None:
            try:
                with open(port_file) as f:
                    txt = f.read().strip()
                return int(txt) if txt else None
            except (OSError, ValueError):
                return None

        port = None
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                log.close()
                raise RuntimeError(
                    f"replica #{index} exited rc={proc.returncode} before "
                    f"binding a port (log: {log_path})"
                )
            port = await loop.run_in_executor(None, read_port)
            if port is not None:
                break
            await asyncio.sleep(0.1)
        if port is None:
            proc.kill()
            log.close()
            raise RuntimeError(
                f"replica #{index} never bound a port (log: {log_path})"
            )
        url = f"http://127.0.0.1:{port}"
        async with aiohttp.ClientSession() as session:
            while time.monotonic() < deadline:
                try:
                    async with session.get(
                        url + ready_path,
                        timeout=aiohttp.ClientTimeout(total=5.0),
                    ) as resp:
                        if resp.status == 200:
                            break
                except (aiohttp.ClientError, asyncio.TimeoutError, OSError):
                    pass
                await asyncio.sleep(0.2)

        async def stop() -> None:
            if proc.poll() is None:
                proc.send_signal(_signal.SIGTERM)
                end = time.monotonic() + stop_grace_s
                while proc.poll() is None and time.monotonic() < end:
                    await asyncio.sleep(0.05)
                if proc.poll() is None:
                    proc.kill()
            log.close()

        return url, stop

    return launch
