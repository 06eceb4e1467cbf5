"""Serving SRE layer (serve/deadline.py, serve/watchdog.py): end-to-end
deadlines at every seam, deadline-aware admission control, priority
shedding under overload, and the engine watchdog's supervised restart.

Determinism contract: watchdog trip tests drive ``tick()`` directly with
an injected clock (no wall-time sleeps decide outcomes); wedge faults use
the engine's pre-chunk hook with explicit release events.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
from kubeflow_tpu.obs.prom import REGISTRY
from kubeflow_tpu.serve.deadline import (
    DEADLINE_ABS_HEADER,
    DEADLINE_HEADER,
    PRIORITY_HEADER,
    AdmissionShed,
    DeadlineExceeded,
    deadline_from_headers,
    priority_from_headers,
)
from kubeflow_tpu.serve.engine import EngineOverloaded, LMEngine
from kubeflow_tpu.serve.watchdog import (
    EngineRestarting,
    EngineWatchdog,
    WatchdogConfig,
)

CFG = TransformerConfig(
    vocab_size=89,
    d_model=32,
    n_layers=2,
    n_heads=4,
    d_ff=64,
    causal=True,
    max_seq_len=256,
    attn_impl="reference",
    dtype=jnp.float32,
)
EOS = 1


@pytest.fixture(scope="module")
def model_and_params():
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    return model, params


def _engine(model, params, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("chunk_steps", 2)
    kw.setdefault("prefill_buckets", (32,))
    kw.setdefault("eos_id", EOS)
    return LMEngine(model, CFG, params, **kw).start()


def _wedge(eng, hold_s=30.0):
    """``wedge_engine`` plus an event that is set once the scheduler thread
    stands in the stall — what a test waits for before it counts on the
    loop being wedged. Returns ``(release, caught)``."""
    from kubeflow_tpu.chaos.injectors import wedge_engine

    release = wedge_engine(eng, hold_s=hold_s)
    stall = eng._fault_hooks["pre_chunk"]
    caught = threading.Event()

    def hook(e):
        caught.set()
        stall(e)

    eng._fault_hooks["pre_chunk"] = hook
    return release, caught


def _wait_until(cond, timeout_s=30.0):
    deadline = time.monotonic() + timeout_s
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.005)
    return cond()


def _metric(name, **labels):
    m = REGISTRY._metrics.get(name)
    if m is None:
        return 0.0
    child = m._children.get(tuple(sorted(labels.items())))
    return child.value if child else 0.0


# ------------------------------------------------------------- headers


def test_deadline_header_parsing_and_absolute_precedence():
    clock = lambda: 100.0  # noqa: E731
    assert deadline_from_headers(None) is None
    assert deadline_from_headers({}) is None
    assert deadline_from_headers({DEADLINE_HEADER: "junk"}) is None
    got = deadline_from_headers({DEADLINE_HEADER: "1500"}, clock=clock)
    assert got == pytest.approx(101.5)
    # the title-cased spelling HTTP servers hand us parses identically
    got = deadline_from_headers(
        {DEADLINE_HEADER.title(): "1500"}, clock=clock
    )
    assert got == pytest.approx(101.5)
    # a stamped absolute deadline wins over the relative budget
    got = deadline_from_headers(
        {DEADLINE_HEADER: "1500", DEADLINE_ABS_HEADER: "42.5"}, clock=clock
    )
    assert got == pytest.approx(42.5)
    assert priority_from_headers({PRIORITY_HEADER: "7"}) == 7
    assert priority_from_headers({PRIORITY_HEADER: "x"}) == 0
    assert priority_from_headers({}) == 0


# ---------------------------------------------------- deadline seams


def test_stream_deadline_is_end_to_end_not_per_item(model_and_params):
    """The satellite fix: each live-queue wait used to get the FULL
    timeout, so a slow-but-not-dead stream could overrun its budget by
    tokens × timeout. Now one monotonic deadline governs every wait."""
    from kubeflow_tpu.chaos.injectors import slow_decode

    model, params = model_and_params
    # eos_id outside the vocab: the row can never EOS-retire early, so
    # the decode is deterministically budget-length (no timing race)
    eng = _engine(model, params, eos_id=97)
    stop = slow_decode(eng, delay_s=0.15)
    try:
        t0 = time.monotonic()
        deadline = t0 + 0.5
        chunks = 0
        with pytest.raises(TimeoutError):
            for _ in eng.stream(
                [3, 4, 5], max_new_tokens=30, deadline=deadline
            ):
                chunks += 1
        elapsed = time.monotonic() - t0
        # the old bug: 30 tokens / 2-step chunks × 0.5 s/item ≈ 7.5 s.
        # end-to-end accounting fails it at ~the 0.5 s deadline.
        assert elapsed < 3.0, elapsed
    finally:
        stop()
        eng.stop()


def test_queued_past_deadline_never_admitted(model_and_params):
    """A request whose deadline expires while it waits in the admission
    queue is retired there — it must never cost a decode slot."""
    model, params = model_and_params
    # eos_id outside the vocab: the blocker cannot EOS-retire in its
    # prefill and leave the loop with no chunk to wedge on
    eng = _engine(model, params, max_batch=1, eos_id=97)
    release, caught = _wedge(eng)
    try:
        q0 = _metric("kft_engine_deadline_expired_total", stage="queued")
        # occupy the single row, then wedge the next chunk
        blocker_err: list = []

        def blocker():
            try:
                eng.submit([5, 6, 7], max_new_tokens=30, timeout_s=60)
            except Exception as e:  # noqa: BLE001
                blocker_err.append(e)

        t = threading.Thread(target=blocker, daemon=True)
        t.start()
        assert caught.wait(120)  # the loop stands in the wedge
        admitted0 = eng.stats["admitted"]
        assert admitted0 == 1
        victim_err: list = []

        def victim():
            try:
                eng.submit([8, 9], max_new_tokens=4, timeout_s=0.3)
            except Exception as e:  # noqa: BLE001
                victim_err.append(e)

        tv = threading.Thread(target=victim, daemon=True)
        tv.start()
        tv.join(30)  # the victim's own wait gives up at its deadline
        assert not tv.is_alive()
        release()    # only now can the loop look at the queue again
        t.join(60)
        assert victim_err and isinstance(victim_err[0], DeadlineExceeded)
        assert not blocker_err, blocker_err
        # the loop polls the queue once the blocker's row is free — after
        # the blocker's submit has returned: wait for its verdict
        assert _wait_until(
            lambda: eng.stats["deadline_expired_queued"]
            or eng.stats["admitted"] > admitted0
        )
        # the victim was never admitted: no decode slot consumed
        assert eng.stats["admitted"] == admitted0
        assert eng.stats["deadline_expired_queued"] == 1
        assert _metric(
            "kft_engine_deadline_expired_total", stage="queued"
        ) == q0 + 1
    finally:
        release()
        eng.stop()


def test_mid_decode_deadline_cancelled_at_epoch(model_and_params):
    """A row past its deadline mid-generation is cancelled at the next
    epoch boundary (the carry merge seam): the caller gets
    DeadlineExceeded and the row frees for new work."""
    from kubeflow_tpu.chaos.injectors import slow_decode

    model, params = model_and_params
    # out-of-vocab eos_id: the row cannot EOS-retire early and race the
    # sweep's deadline attribution
    eng = _engine(model, params, max_batch=1, eos_id=97)
    # warm the prefill + chunk compiles FIRST: a cold compile can eat the
    # whole budget while the row is still prefilling (not yet decoding)
    eng.submit([9, 8], max_new_tokens=2, timeout_s=120)
    stop = slow_decode(eng, delay_s=0.1)
    try:
        with pytest.raises(DeadlineExceeded):
            eng.submit(
                [3, 4, 5], max_new_tokens=30,
                deadline=time.monotonic() + 0.4,
            )
        stop()
        # the engine retires the row at the next epoch boundary
        deadline = time.monotonic() + 15
        while (
            eng.stats["deadline_expired_decoding"] == 0
            and time.monotonic() < deadline
        ):
            time.sleep(0.01)
        assert eng.stats["deadline_expired_decoding"] >= 1
        while eng.active.any() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not eng.active.any()
        out = eng.submit([5, 6], max_new_tokens=3, timeout_s=60)
        assert out  # alive after the cancellation
    finally:
        stop()
        eng.stop()


def test_admission_shed_unmeetable_deadline(model_and_params):
    """Admission control sheds a request whose estimated queue wait +
    decode time exceeds its remaining budget — 503 + Retry-After at the
    server, and NO decode slot consumed."""
    model, params = model_and_params
    eng = _engine(model, params)
    try:
        # evidence: 200 ms per 2-token chunk → 32 tokens ≈ 3.2 s
        eng.overlap["decode_gap_ms"] = 200.0
        with pytest.raises(AdmissionShed) as ei:
            eng.submit(
                [3, 4, 5], max_new_tokens=32,
                deadline=time.monotonic() + 0.5,
            )
        assert ei.value.reason == "deadline_unmeetable"
        assert ei.value.retry_after_s >= 1.0
        assert eng.stats["shed_deadline"] == 1
        assert eng.stats["admitted"] == 0
        # a roomy deadline still admits (the estimator is not a gate)
        out = eng.submit([3, 4, 5], max_new_tokens=4, timeout_s=60)
        assert out
    finally:
        eng.stop()


def test_admission_never_sheds_on_cold_ewma(model_and_params):
    """No throughput evidence → no shed: a cold engine admits everything
    rather than guessing clients into 503s."""
    model, params = model_and_params
    eng = _engine(model, params)
    try:
        assert eng.estimate_admission(32) is None
        out = eng.submit(
            [3, 4], max_new_tokens=4, deadline=time.monotonic() + 30
        )
        assert out
    finally:
        eng.stop()


def test_priority_evicts_lowest_queued_under_overload(model_and_params):
    """Sustained overload sheds the lowest-priority QUEUED request to
    admit a higher-priority one; equal/lower priority newcomers still get
    EngineOverloaded."""
    model, params = model_and_params
    # eos_id outside the vocab: see test_queued_past_deadline_never_admitted
    eng = _engine(model, params, max_batch=1, max_queue=2, eos_id=97)
    release, caught = _wedge(eng)
    results: dict[str, Exception | list] = {}

    def bg(key, ids, prio):
        def run():
            try:
                results[key] = eng.submit(
                    ids, max_new_tokens=20, timeout_s=60, priority=prio
                )
            except Exception as e:  # noqa: BLE001
                results[key] = e

        t = threading.Thread(target=run, daemon=True)
        t.start()
        return t

    try:
        t1 = bg("active", [5, 6, 7], 0)   # takes the single row
        assert caught.wait(120)           # loop admitted it, then wedged
        t2 = bg("low", [8, 9], 0)         # queued, priority 0
        t3 = bg("mid", [9, 10], 1)        # queued, priority 1 → capacity full
        assert _wait_until(lambda: eng._pending.qsize() == 2)
        # a priority-3 newcomer evicts the LOWEST queued (priority 0)
        t4 = bg("high", [11, 12], 3)
        assert _wait_until(lambda: "low" in results)
        assert isinstance(results.get("low"), AdmissionShed)
        assert results["low"].reason == "priority_evict"
        # equal-priority newcomer has no one below it: bare overload
        with pytest.raises(EngineOverloaded):
            eng.submit([13, 14], max_new_tokens=4, priority=1)
        assert eng.stats["shed_priority"] == 1
        release()
        for t in (t1, t2, t3, t4):
            t.join(60)
        # survivors all completed
        assert isinstance(results["active"], list)
        assert isinstance(results["mid"], list)
        assert isinstance(results["high"], list)
    finally:
        release()
        eng.stop()


def test_batcher_sheds_expired_entries_at_flush():
    """The batcher seam: an entry whose deadline passed while queued is
    failed with DeadlineExceeded and excluded from the handler call."""
    import asyncio

    from kubeflow_tpu.serve.batcher import Batcher, BatcherConfig

    seen: list[list] = []

    async def handler(flat):
        seen.append(list(flat))
        return [x * 2 for x in flat]

    async def run():
        b = Batcher(handler, BatcherConfig(max_batch_size=8,
                                           max_latency_ms=50.0))
        expired = asyncio.ensure_future(
            b.submit([1, 2], deadline=time.monotonic() - 0.01)
        )
        fresh = asyncio.ensure_future(
            b.submit([10], deadline=time.monotonic() + 30)
        )
        with pytest.raises(DeadlineExceeded):
            await expired
        assert await fresh == [20]
        assert seen == [[10]]  # expired instances never reached a forward
        assert b.stats["deadline_shed"] == 1

    asyncio.run(run())


# ------------------------------------------------------------ watchdog


def _loaded_engine_model(model, params, name="lm", **kw):
    from kubeflow_tpu.serve.engine import LMEngineModel
    from kubeflow_tpu.serve.model import BucketSpec

    # eos_id outside the vocab: every completion has its whole budget of
    # tokens, so "the rebuilt engine serves" is never an empty stream
    m = LMEngineModel(
        name, None, config=CFG, max_batch=2, chunk_steps=2,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
        max_new_tokens=8, eos_id=97, **kw,
    )
    m.load()
    m._params = jax.device_put(params)
    m.engine.stop()
    m.engine = m._make_engine().start()
    return m


def test_watchdog_trips_on_wedged_chunk_and_restarts(model_and_params):
    """Fake-clock trip: a wedged chunk (stale heartbeat + work pending)
    flips readiness, fails the in-flight request with the RETRYABLE
    EngineRestarting, rebuilds the engine, and restores readiness."""
    model, params = model_and_params
    m = _loaded_engine_model(model, params, name="wd-wedge", watchdog=False)
    now = [0.0]
    ready_flips: list[bool] = []

    def on_ready(r):
        ready_flips.append(r)
        m._set_ready(r)

    wd = EngineWatchdog(
        lambda: m.engine, m.restart_engine, on_ready=on_ready,
        config=WatchdogConfig(min_wedge_s=5.0, wedge_factor=8.0),
        clock=lambda: now[0], model_name="wd-wedge",
    )  # no .start(): ticks are driven explicitly, zero wall-clock waits
    t0 = _metric(
        "kft_engine_watchdog_trips_total", model="wd-wedge", reason="wedged"
    )
    r0 = _metric("kft_engine_restarts_total", model="wd-wedge")
    old_engine = m.engine
    release, caught = _wedge(old_engine, hold_s=20.0)
    errs: list = []

    def caller():
        try:
            old_engine.submit([3, 4, 5], max_new_tokens=6, timeout_s=60)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    t = threading.Thread(target=caller, daemon=True)
    t.start()
    try:
        # the loop is demonstrably wedged: it stands in the stall with
        # work pending, so the heartbeat has stopped advancing
        assert caught.wait(120)
        assert old_engine.busy()
        # below threshold: no trip
        now[0] = old_engine.heartbeat() + 1.0
        assert wd.tick() is None
        # past threshold: trip + supervised restart
        now[0] = old_engine.heartbeat() + 10.0
        assert wd.tick() == "wedged"
        assert ready_flips == [False, True]
        assert m.ready is True
        assert m.engine is not old_engine
        t.join(30)
        assert errs and isinstance(errs[0], EngineRestarting)
        assert _metric(
            "kft_engine_watchdog_trips_total", model="wd-wedge",
            reason="wedged",
        ) == t0 + 1
        assert _metric(
            "kft_engine_restarts_total", model="wd-wedge"
        ) == r0 + 1
        assert wd.stats["trips"]["wedged"] == 1
        assert wd.stats["restarts"] == 1
        # the rebuilt engine serves — and a submit racing the poison on
        # the OLD engine fails fast with the retryable error, not a hang
        out = m.engine.submit([5, 6], max_new_tokens=3, timeout_s=60)
        assert out
        with pytest.raises(EngineRestarting):
            old_engine.submit([5, 6], max_new_tokens=3)
    finally:
        release()
        m.unload()


def test_watchdog_trips_on_dead_loop_thread(model_and_params):
    """A scheduler thread that died (fatal device error) trips the
    watchdog without any heartbeat math, and the rebuild recovers."""
    model, params = model_and_params
    m = _loaded_engine_model(model, params, name="wd-dead", watchdog=False)
    wd = EngineWatchdog(
        lambda: m.engine, m.restart_engine, on_ready=m._set_ready,
        config=WatchdogConfig(min_wedge_s=5.0), model_name="wd-dead",
    )
    old_engine = m.engine
    try:
        boom = RuntimeError("injected device failure")

        def exploding_chunk(*a, **k):
            raise boom

        old_engine._chunk = exploding_chunk
        with pytest.raises(RuntimeError, match="injected device failure"):
            old_engine.submit([3, 4, 5], max_new_tokens=6, timeout_s=30)
        assert wd.tick() == "fatal"
        assert m.engine is not old_engine and m.ready
        assert m.engine.submit([5, 6], max_new_tokens=3, timeout_s=60)
        # idle healthy engine: no trip
        assert wd.tick() is None
    finally:
        m.unload()


def test_watchdog_retries_failed_rebuild_until_it_succeeds(
    model_and_params,
):
    """A rebuild that raises leaves the replica not-ready (routed
    around) and is retried on subsequent ticks until one succeeds."""
    model, params = model_and_params
    m = _loaded_engine_model(
        model, params, name="wd-retry", watchdog=False
    )
    attempts = {"n": 0}

    def flaky_rebuild(err):
        attempts["n"] += 1
        if attempts["n"] == 1:
            raise RuntimeError("transient rebuild failure")
        return m.restart_engine(err)

    wd = EngineWatchdog(
        lambda: m.engine, flaky_rebuild, on_ready=m._set_ready,
        config=WatchdogConfig(min_wedge_s=5.0), model_name="wd-retry",
    )
    old_engine = m.engine
    try:
        old_engine._chunk = lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("boom")
        )
        with pytest.raises(RuntimeError, match="boom"):
            old_engine.submit([3, 4, 5], max_new_tokens=4, timeout_s=30)
        assert wd.tick() == "fatal"
        assert m.ready is False  # first rebuild attempt failed
        assert m.engine is old_engine
        assert wd.tick() is None  # retry path, not a fresh trip
        assert attempts["n"] == 2
        assert m.ready is True and m.engine is not old_engine
        assert m.engine.submit([5, 6], max_new_tokens=3, timeout_s=60)
    finally:
        m.unload()


def test_watchdog_no_trip_on_idle_or_deliberate_stop(model_and_params):
    model, params = model_and_params
    m = _loaded_engine_model(model, params, name="wd-idle", watchdog=False)
    wd = EngineWatchdog(
        lambda: m.engine, m.restart_engine, on_ready=m._set_ready,
        config=WatchdogConfig(min_wedge_s=0.0, wedge_factor=0.0),
        clock=lambda: time.monotonic() + 1e6,  # everything looks stale
        model_name="wd-idle",
    )
    try:
        assert wd.tick() is None  # idle: busy() is False, stale is fine
        m.engine.stop()
        assert wd.tick() is None  # deliberate stop is not a fault
    finally:
        m.unload()


# ---------------------------------------------- server + header seams


def test_server_maps_sre_errors_and_default_deadline(model_and_params):
    """HTTP seam: an expired x-kft-deadline-ms budget → 503 carrying
    Retry-After (the gateway's non-retryable shed marker); a roomy budget
    → 200; admission shed → 503 + Retry-After ≥ backlog estimate."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.serve.server import ModelServer

    model, params = model_and_params
    m = _loaded_engine_model(model, params, name="lm", watchdog=False)
    server = ModelServer([m])

    async def drive():
        async with TestClient(TestServer(server.build_app())) as client:
            r = await client.post(
                "/v1/models/lm:predict",
                json={"instances": [{"input_ids": [3, 4, 5]}]},
                headers={DEADLINE_HEADER: "30000"},
            )
            assert r.status == 200
            r = await client.post(
                "/v1/models/lm:predict",
                json={"instances": [{"input_ids": [3, 4, 5]}]},
                headers={DEADLINE_HEADER: "0"},
            )
            assert r.status == 503
            assert r.headers.get("Retry-After") == "1"
            assert "deadline" in (await r.text()).lower()
            # admission shed surfaces its backlog estimate
            m.engine.overlap["decode_gap_ms"] = 500.0
            r = await client.post(
                "/v1/models/lm:predict",
                json={"instances": [{"input_ids": [3, 4, 5]}]},
                headers={DEADLINE_HEADER: "300"},
            )
            assert r.status == 503
            assert int(r.headers.get("Retry-After", "0")) >= 1
            m.engine.overlap["decode_gap_ms"] = 0.0
            # SSE path: an expired budget refuses BEFORE committing a 200
            r = await client.post(
                "/v2/models/lm/generate_stream",
                json={"input_ids": [3, 4, 5]},
                headers={DEADLINE_HEADER: "0"},
            )
            assert r.status == 503
            assert r.headers.get("Retry-After") == "1"

    try:
        asyncio.run(drive())
    finally:
        m.unload()


def test_server_default_deadline_applies_when_header_absent(
    model_and_params,
):
    """The KServe request-timeout analog: default_deadline_ms bounds
    header-less requests; an unmeetable default sheds like a client one."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.serve.server import ModelServer

    model, params = model_and_params
    m = _loaded_engine_model(model, params, name="lm", watchdog=False)
    server = ModelServer([m], default_deadline_ms=250.0)
    # make the default provably unmeetable: ~500 ms/chunk × 4 chunks
    m.engine.overlap["decode_gap_ms"] = 500.0

    async def drive():
        async with TestClient(TestServer(server.build_app())) as client:
            r = await client.post(
                "/v1/models/lm:predict",
                json={"instances": [{"input_ids": [3, 4, 5]}]},
            )
            assert r.status == 503
            assert "Retry-After" in r.headers
            # an explicit client budget overrides the server default
            m.engine.overlap["decode_gap_ms"] = 0.0
            r = await client.post(
                "/v1/models/lm:predict",
                json={"instances": [{"input_ids": [3, 4, 5]}]},
                headers={DEADLINE_HEADER: "60000"},
            )
            assert r.status == 200

    try:
        asyncio.run(drive())
    finally:
        m.unload()


def test_chaos_plan_serving_faults_round_trip():
    from kubeflow_tpu.chaos.plan import FaultPlan, SlowDecode, WedgeEngine

    plan = FaultPlan(
        faults=(WedgeEngine(model="lm", hold_s=12.5),
                SlowDecode(model="lm", delay_s=0.25)),
        seed=7,
    )
    again = FaultPlan.from_dict(plan.to_dict())
    assert again == plan
    assert again.faults[0].kind == "WedgeEngine"
    assert again.faults[1].delay_s == 0.25


def test_chaos_runner_fires_serving_faults_without_cluster(
    model_and_params,
):
    """A serving-only FaultPlan drives the engine seams through the
    runner: no cluster, triggers key off engine presence."""
    from kubeflow_tpu.chaos.plan import FaultPlan, SlowDecode
    from kubeflow_tpu.chaos.runner import ChaosRunner

    model, params = model_and_params
    eng = _engine(model, params)
    try:
        runner = ChaosRunner(
            plan=FaultPlan(faults=(SlowDecode(model="lm", delay_s=0.01),)),
            engines={"lm": eng},
        )
        runner.poll()
        assert runner.done
        assert [f.fault.kind for f in runner.fired] == ["SlowDecode"]
        assert "pre_chunk" in eng._fault_hooks
        # the engine still answers correctly under the inflated latency
        assert eng.submit([3, 4], max_new_tokens=3, timeout_s=60)
    finally:
        eng.stop()
