"""Continuous-batching LM engine (serve/engine.py): scheduling must never
change numerics. Every completion must equal the whole-batch
``make_generate_fn`` path's answer for the same prompt (greedy), while rows
are admitted into a RUNNING batch and recycled as requests finish."""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import GenerateOracle, drive_schedule

from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
from kubeflow_tpu.serve.engine import LMEngine
from kubeflow_tpu.serve.generate import make_generate_fn

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


def _reference_completion(model, params, ids, max_new):
    """The pinned-correct whole-batch path, batch 1, greedy."""
    gen = jax.jit(
        make_generate_fn(model, CFG, max_new_tokens=max_new, eos_id=EOS)
    )
    P = 32 if len(ids) <= 32 else 128
    prompt = np.zeros((1, P), np.int32)
    prompt[0, : len(ids)] = ids
    toks, n_valid = gen(
        params,
        prompt,
        np.asarray([len(ids)], np.int32),
        jax.random.PRNGKey(7),
        np.zeros((1,), np.float32),
    )
    return [int(t) for t in np.asarray(toks)[0, : int(n_valid[0])]]


def _prompts(rng, n, lo=3, hi=20):
    return [
        [int(x) for x in rng.integers(2, CFG.vocab_size, size=rng.integers(lo, hi))]
        for _ in range(n)
    ]


def test_engine_matches_batch_generate_exactly(model_and_params):
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=4, max_seq=64, chunk_steps=4,
        prefill_buckets=(32,), eos_id=EOS,
    ).start()
    try:
        rng = np.random.default_rng(0)
        for ids in _prompts(rng, 6):
            got = eng.submit(ids, max_new_tokens=12)
            want = _reference_completion(model, params, ids, 12)
            assert got == want, (ids, got, want)
    finally:
        eng.stop()


def test_concurrent_staggered_requests_share_the_batch(model_and_params):
    """Requests arriving WHILE others decode join the running batch (the
    defining continuous-batching property), and every answer still equals
    the reference path."""
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=3, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS,
    ).start()
    rng = np.random.default_rng(1)
    prompts = _prompts(rng, 7)
    results: dict[int, list[int]] = {}
    errors: list[Exception] = []

    def worker(i):
        try:
            time.sleep(0.03 * i)  # staggered arrivals
            results[i] = eng.submit(prompts[i], max_new_tokens=16)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(7)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        eng.stop()
    assert not errors, errors
    assert len(results) == 7
    for i, ids in enumerate(prompts):
        want = _reference_completion(model, params, ids, 16)
        assert results[i] == want, (i, results[i], want)
    # 7 requests through 3 rows: recycling happened, and the batch really
    # was shared (more than one row concurrently occupied at some point)
    assert eng.stats["admitted"] == 7
    assert eng.stats["completed"] == 7
    assert eng.stats["max_concurrent"] >= 2
    assert eng.stats["max_concurrent"] <= 3


@pytest.mark.slow
def test_eos_frees_row_early(model_and_params):
    """A prompt whose continuation hits EOS quickly must finish without
    waiting for long-running neighbours."""
    model, params = model_and_params
    # find a prompt with a short greedy completion (EOS within 6 tokens)
    rng = np.random.default_rng(2)
    short = long_ = None
    for ids in _prompts(rng, 200, lo=3, hi=12):
        n = len(_reference_completion(model, params, ids, 24))
        if n < 6 and short is None:
            short = ids
        elif n >= 10 and long_ is None:
            long_ = ids
        if short is not None and long_ is not None:
            break
    if short is None or long_ is None:
        pytest.skip("random init produced no short/long completion pair")
    eng = LMEngine(
        model, CFG, params, max_batch=2, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS,
    ).start()
    try:
        t_long: dict = {}

        def run_long():
            t0 = time.monotonic()
            t_long["out"] = eng.submit(long_, max_new_tokens=24)
            t_long["dt"] = time.monotonic() - t0

        th = threading.Thread(target=run_long)
        th.start()
        time.sleep(0.05)
        t0 = time.monotonic()
        out_short = eng.submit(short, max_new_tokens=24)
        dt_short = time.monotonic() - t0
        th.join(120)
    finally:
        eng.stop()
    assert out_short == _reference_completion(model, params, short, 24)
    assert t_long["out"] == _reference_completion(model, params, long_, 24)
    # the short request must not be held hostage by the long one
    assert dt_short <= t_long["dt"] + 0.5


def test_budget_gating_never_overruns_cache(model_and_params):
    """max_new smaller than chunk_steps: the device must stop advancing the
    row mid-chunk (budget gate), and the answer is exactly the first
    max_new reference tokens."""
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=2, max_seq=64, chunk_steps=8,
        prefill_buckets=(32,), eos_id=EOS,
    ).start()
    try:
        ids = [5, 9, 33, 60]
        got = eng.submit(ids, max_new_tokens=3)
        want = _reference_completion(model, params, ids, 24)[:3]
        # reference may EOS before 3; engine must agree either way
        assert got == _reference_completion(model, params, ids, 3) or got == want
    finally:
        eng.stop()


def test_bad_request_fails_fast_without_killing_engine(model_and_params):
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=2, max_seq=40, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS,
    ).start()
    try:
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit([])
        with pytest.raises(ValueError, match="exceeds engine max_seq"):
            eng.submit([3, 4, 5], max_new_tokens=38)  # 3+38 > 40
        # engine still serves afterwards
        out = eng.submit([3, 4, 5], max_new_tokens=4)
        assert out == _reference_completion(model, params, [3, 4, 5], 4)
    finally:
        eng.stop()


def test_admission_is_by_prompt_length_not_bucket(model_and_params):
    """A row's tokens are contiguous — bucket and piece padding write to
    the scratch page — so a prompt is admitted by its own length: one whose
    padded layout (bucket 32 + 32 new) would not fit max_seq is served,
    and one token over max_seq still says so."""
    model, params = model_and_params
    # eos outside the vocabulary: the row runs to max_seq's last token
    oracle = GenerateOracle(model, CFG, params, eos_id=97)
    for chunked in (None, 16):
        eng = LMEngine(
            model, CFG, params, max_batch=2, max_seq=40, chunk_steps=4,
            prefill_buckets=(32,), eos_id=97, prefill_chunk=chunked,
        ).start()
        try:
            for ids, new in (([3, 4, 5], 37), ([7] * 20, 20)):
                got = eng.submit(ids, max_new_tokens=new)
                assert len(got) == new
                assert got == oracle.submit(ids, new), (chunked, ids)
                with pytest.raises(ValueError, match="exceeds engine max_seq"):
                    eng.submit(ids, max_new_tokens=new + 1)
        finally:
            eng.stop()


@pytest.mark.parametrize("through", ["engine", "model"])
def test_engine_given_no_pool_size_holds_max_seq_in_every_row(
    model_and_params, through
):
    """No ``kv_pool_tokens``: the pool is sized for ``max_batch`` rows of
    ``max_seq`` tokens, so ``max_batch`` requests that each fill a row to
    its last token are resident at once and none is ever held for pages —
    built directly and through ``LMEngineModel``'s defaults."""
    from kubeflow_tpu.serve.engine import LMEngineModel
    from kubeflow_tpu.serve.model import BucketSpec

    model, params = model_and_params
    if through == "engine":
        max_batch, max_seq, page, new = 3, 48, 16, 28
        eng = LMEngine(
            model, CFG, params, max_batch=max_batch, max_seq=max_seq,
            page_size=page, chunk_steps=4, prefill_buckets=(32,), eos_id=97,
        )
        unload = eng.stop
    else:
        max_batch, max_seq, page, new = 2, 32 + 16, 64, 16
        m = LMEngineModel(
            "lm", None, config=CFG, max_batch=max_batch, chunk_steps=2,
            buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
            max_new_tokens=new, eos_id=97, watchdog=False,
        )
        m.load()
        m._params = jax.device_put(params)
        m.engine.stop()
        eng = m.engine = m._make_engine()       # not started yet
        unload = m.unload
    pages_per_row = -(-max_seq // page)
    assert eng.max_seq == max_seq
    assert eng.pager.stats()["pages_total"] == max_batch * pages_per_row
    held = []
    admit_all = eng._admit_all

    def watched():                # _held is only ever set inside _admit_all
        admit_all()
        held.append(eng._held)

    eng._admit_all = watched
    prompts = [[2 + r, 9, 33] * 20 for r in range(max_batch)]
    prompts = [p[: max_seq - new] for p in prompts]
    # queued before the loop starts: one admission pass sees them all
    reqs = [eng._enqueue(p, new, 0.0, live=False) for p in prompts]
    eng.start()
    try:
        for req in reqs:
            assert req.done.wait(180) and req.error is None, req.error
    finally:
        unload()
    oracle = GenerateOracle(model, CFG, params, eos_id=97)
    for p, req in zip(prompts, reqs):
        assert len(req.tokens) == new                 # the row's last token
        assert req.tokens == oracle.submit(p, new)
    assert held and all(h is None for h in held)
    assert eng.stats["max_concurrent"] == max_batch
    assert eng.stats["kv_pages_used_peak"] == max_batch * pages_per_row


def test_rest_concurrent_requests_share_engine(model_and_params):
    """Through the REAL ModelServer: N concurrent HTTP requests must share
    the engine's decode batch (max_concurrent > 1) and each get exactly the
    reference answer."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.serve.engine import LMEngineModel
    from kubeflow_tpu.serve.model import BucketSpec
    from kubeflow_tpu.serve.server import ModelServer

    model, params = model_and_params
    m = LMEngineModel(
        "lm", None, config=CFG, max_batch=4, chunk_steps=2,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
        max_new_tokens=12, eos_id=EOS,
    )
    m.load()
    m._params = jax.device_put(params)  # pin the fixture weights
    m.engine.stop()
    from kubeflow_tpu.serve.engine import LMEngine as _E

    m.engine = _E(
        m._model, CFG, params, max_batch=4, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS,
    ).start()
    server = ModelServer([m])
    rng = np.random.default_rng(3)
    prompts = _prompts(rng, 5)

    async def fire():
        async with TestClient(TestServer(server.build_app())) as client:
            async def one(ids):
                r = await client.post(
                    "/v1/models/lm:predict",
                    json={"instances": [{"input_ids": ids}]},
                )
                assert r.status == 200
                return (await r.json())["predictions"][0]["token_ids"]

            return await asyncio.gather(*[one(p) for p in prompts])

    results = asyncio.run(fire())
    try:
        for ids, got in zip(prompts, results):
            assert got == _reference_completion(model, params, ids, 12)
        assert m.engine.stats["max_concurrent"] >= 2
    finally:
        m.unload()


def test_chunk_failure_fails_requests_not_hangs(model_and_params):
    """If the device chunk program dies, in-flight submits must get the
    REAL error promptly and later submits must fail fast — never a silent
    dead scheduler thread + timeout."""
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=2, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS,
    ).start()
    try:
        boom = RuntimeError("injected device failure")

        def exploding_chunk(*a, **k):
            raise boom

        eng._chunk = exploding_chunk
        with pytest.raises(RuntimeError, match="injected device failure"):
            eng.submit([3, 4, 5], max_new_tokens=8, timeout_s=30)
        with pytest.raises(RuntimeError, match="engine is dead"):
            eng.submit([3, 4, 5], max_new_tokens=8, timeout_s=30)
    finally:
        eng.stop()


def test_generate_stream_sse(model_and_params):
    """generate_stream must deliver tokens INCREMENTALLY (multiple SSE
    frames, chunk-sized), and their concatenation equals the reference
    completion; /generate returns the same thing at once."""
    import asyncio
    import json as jsonlib

    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.serve.engine import LMEngineModel
    from kubeflow_tpu.serve.model import BucketSpec
    from kubeflow_tpu.serve.server import ModelServer

    model, params = model_and_params
    m = LMEngineModel(
        "lm", None, config=CFG, max_batch=2, chunk_steps=2,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
        max_new_tokens=12, eos_id=EOS,
    )
    m.load()
    m._params = jax.device_put(params)
    m.engine.stop()
    m.engine = LMEngine(
        m._model, CFG, params, max_batch=2, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS,
    ).start()
    server = ModelServer([m])
    ids = [7, 11, 13, 17, 19]
    want = _reference_completion(model, params, ids, 12)

    async def drive():
        async with TestClient(TestServer(server.build_app())) as client:
            r = await client.post(
                "/v2/models/lm/generate_stream", json={"input_ids": ids}
            )
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/event-stream")
            frames = []
            async for line in r.content:
                line = line.decode().strip()
                if line.startswith("data: "):
                    frames.append(jsonlib.loads(line[len("data: "):]))
            r2 = await client.post(
                "/v2/models/lm/generate", json={"input_ids": ids}
            )
            assert r2.status == 200
            return frames, await r2.json()

    try:
        frames, whole = asyncio.run(drive())
    finally:
        m.unload()
    token_frames = [f for f in frames if "token_ids" in f]
    got = [t for f in token_frames for t in f["token_ids"]]
    assert got == want
    assert frames[-1] == {"done": True, "n_tokens": len(want)}
    if len(want) > 3:  # chunk_steps=2 → streaming really was incremental
        assert len(token_frames) >= 2
    assert whole["token_ids"] == want


def test_generate_stream_501_for_non_engine_models(model_and_params):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.serve.model import BucketSpec
    from kubeflow_tpu.serve.server import ModelServer
    from kubeflow_tpu.serve.generate import LMRuntimeModel

    m = LMRuntimeModel(
        "plain", None, config=CFG, max_new_tokens=4,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)), eos_id=EOS,
    )
    m.load()
    server = ModelServer([m])

    async def drive():
        async with TestClient(TestServer(server.build_app())) as client:
            r = await client.post(
                "/v2/models/plain/generate_stream", json={"input_ids": [3]}
            )
            return r.status

    assert asyncio.run(drive()) == 501


def test_stop_fails_inflight_requests_promptly(model_and_params):
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=1, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS,
    ).start()
    errors: list[Exception] = []

    def worker():
        try:
            eng.submit([3, 4, 5] * 4, max_new_tokens=24, timeout_s=60)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    th = threading.Thread(target=worker)
    th.start()
    time.sleep(0.3)  # let it admit / start decoding
    t0 = time.monotonic()
    eng.stop()
    th.join(20)
    assert not th.is_alive()
    # the submit either completed before stop() or failed PROMPTLY with
    # the truth — never a 60s timeout hang
    assert time.monotonic() - t0 < 15
    if errors:
        assert "stopped" in str(errors[0])


def test_sse_disconnect_frees_the_row(model_and_params):
    """Client walks away mid-stream: the engine row must be RELEASED (next
    request on a max_batch=1 engine proceeds), not decode to completion
    for nobody."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.serve.engine import LMEngineModel
    from kubeflow_tpu.serve.model import BucketSpec
    from kubeflow_tpu.serve.server import ModelServer

    model, params = model_and_params
    m = LMEngineModel(
        "lm", None, config=CFG, max_batch=1, chunk_steps=1,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
        max_new_tokens=64, eos_id=EOS,
    )
    m.load()
    m._params = jax.device_put(params)
    m.engine.stop()
    m.engine = LMEngine(
        m._model, CFG, params, max_batch=1, max_seq=128, chunk_steps=1,
        prefill_buckets=(32,), eos_id=EOS,
    ).start()
    server = ModelServer([m])

    async def drive():
        async with TestClient(TestServer(server.build_app())) as client:
            r = await client.post(
                "/v2/models/lm/generate_stream",
                json={"input_ids": [3, 5, 7]},
            )
            assert r.status == 200
            # read ONE frame, then abandon the stream
            async for line in r.content:
                if line.decode().startswith("data: "):
                    break
            r.close()
            # the single row must come free for the next request
            r2 = await client.post(
                "/v2/models/lm/generate", json={"input_ids": [9, 2, 4]}
            )
            assert r2.status == 200
            return await r2.json()

    try:
        out = asyncio.run(drive())
        assert isinstance(out["token_ids"], list)
    finally:
        m.unload()


def test_reload_cycle_and_engine_metrics(model_and_params):
    """ModelMesh-style load→unload→load must yield a working engine (fresh
    executor + scheduler), and /metrics exports the engine gauges."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.serve.engine import LMEngineModel
    from kubeflow_tpu.serve.model import BucketSpec
    from kubeflow_tpu.serve.server import ModelServer

    model, params = model_and_params
    m = LMEngineModel(
        "lm", None, config=CFG, max_batch=2, chunk_steps=2,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
        max_new_tokens=6, eos_id=EOS,
    )
    m.load()
    m.unload()
    assert not m.ready and m.engine is None
    m.load()  # the reload a mesh eviction + readmission performs
    try:
        out = m.engine.submit([4, 8, 15], max_new_tokens=4)
        assert isinstance(out, list)
        server = ModelServer([m])

        async def scrape():
            async with TestClient(TestServer(server.build_app())) as client:
                r = await client.post(
                    "/v1/models/lm:predict",
                    json={"instances": [{"input_ids": [16, 23, 42]}]},
                )
                assert r.status == 200
                return await (await client.get("/metrics")).text()

        text = asyncio.run(scrape())
        assert 'kubeflow_tpu_engine_completed{model="lm"}' in text
        assert 'kubeflow_tpu_engine_active_rows{model="lm"}' in text
        # the decode read path's counters ride the same export
        for key in ("decode_chunks_kernel_read", "decode_pages_live",
                    "decode_pages_window", "prefill_pieces_flash_read",
                    "decode_kernel_steps", "decode_kernel_steps_live"):
            assert f'kubeflow_tpu_engine_{key}{{model="lm"}}' in text
    finally:
        m.unload()


def test_overload_sheds_with_429(model_and_params):
    """A full admission queue must answer 429, not queue unboundedly."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.serve.engine import EngineOverloaded, LMEngineModel
    from kubeflow_tpu.serve.model import BucketSpec
    from kubeflow_tpu.serve.server import ModelServer

    model, params = model_and_params
    m = LMEngineModel(
        "lm", None, config=CFG, max_batch=1, chunk_steps=2,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
        max_new_tokens=32, eos_id=EOS,
    )
    m.load()
    m._params = jax.device_put(params)
    m.engine.stop()
    m.engine = LMEngine(
        m._model, CFG, params, max_batch=1, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS, max_queue=1,
    ).start()
    server = ModelServer([m])

    async def drive():
        async with TestClient(TestServer(server.build_app())) as client:
            # saturate: 1 row busy + 1 queued + extras → some 429s
            posts = [
                client.post(
                    "/v1/models/lm:predict",
                    json={"instances": [{"input_ids": [3, 5, i + 2]}]},
                )
                for i in range(6)
            ]
            return [r.status for r in await asyncio.gather(*posts)]

    try:
        statuses = asyncio.run(drive())
    finally:
        m.unload()
    assert 200 in statuses          # the engine kept serving
    assert 429 in statuses, statuses  # and overload was shed, not queued
    # direct API: a FREE engine accepts even at max_queue=0; a busy one
    # sheds with the typed error
    eng2 = LMEngine(
        model, CFG, params, max_batch=1, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS, max_queue=0,
    ).start()
    try:
        bg = threading.Thread(
            target=lambda: eng2.submit([3, 4, 5], max_new_tokens=24)
        )
        bg.start()
        # wait until the row is actually occupied
        deadline = time.monotonic() + 120  # prefill compile under load
        while not any(s is not None for s in eng2._slots):
            assert time.monotonic() < deadline, "row never occupied"
            time.sleep(0.01)
        with pytest.raises(EngineOverloaded):
            eng2.submit([9, 9, 9], max_new_tokens=4)
        bg.join(60)
    finally:
        eng2.stop()


def test_stream_overload_is_429_before_headers(model_and_params):
    """generate_stream under overload must answer a clean 429 — never a
    200 SSE stream carrying an error frame."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.serve.engine import LMEngineModel
    from kubeflow_tpu.serve.model import BucketSpec
    from kubeflow_tpu.serve.server import ModelServer

    model, params = model_and_params
    m = LMEngineModel(
        "lm", None, config=CFG, max_batch=1, chunk_steps=2,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
        max_new_tokens=48, eos_id=EOS,
    )
    m.load()
    m._params = jax.device_put(params)
    m.engine.stop()
    m.engine = LMEngine(
        m._model, CFG, params, max_batch=1, max_seq=96, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS, max_queue=0,
    ).start()
    server = ModelServer([m])

    # occupy the full capacity deterministically (a racing HTTP stream can
    # finish before the second request lands on a fast host)
    g1 = m.stream_row_tokens({"ids": [3, 5, 7], "temperature": 0.0})

    async def drive():
        async with TestClient(TestServer(server.build_app())) as client:
            r2 = await client.post(
                "/v2/models/lm/generate_stream", json={"input_ids": [9, 2]}
            )
            return r2.status

    try:
        # the overloaded stream sheds BEFORE committing a response: a clean
        # 429 status, not a 200 SSE stream carrying an error frame
        assert asyncio.run(drive()) == 429
        g1.close()
        # capacity released on close → streaming works again
        out = list(m.stream_row_tokens({"ids": [9, 2], "temperature": 0.0}))
        assert out and all(isinstance(c, list) for c in out)
    finally:
        m.unload()


def test_prefix_cache_exact_parity_and_reuse(model_and_params):
    """Prefix caching is a COMPUTE optimization, never a numerics change:
    completions with reused prefixes must equal the reference path exactly,
    and the stats must prove reuse actually happened."""
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=2, max_seq=96, chunk_steps=4,
        prefill_buckets=(32,), eos_id=EOS, prefix_cache_entries=4,
    ).start()
    try:
        rng = np.random.default_rng(11)
        system = [int(x) for x in rng.integers(2, CFG.vocab_size, size=20)]
        # first request stores system[:16] as a prefix entry
        first = system[:20]
        out1 = eng.submit(first, max_new_tokens=10)
        assert out1 == _reference_completion(model, params, first, 10)
        assert eng.stats["prefix_hits"] == 0
        # same 16-token prefix, different tails → every one must hit AND
        # match the from-scratch reference bit for bit
        for trial in range(3):
            tail = [int(x) for x in rng.integers(2, CFG.vocab_size, size=5)]
            ids = system[:16] + tail
            got = eng.submit(ids, max_new_tokens=10)
            want = _reference_completion(model, params, ids, 10)
            assert got == want, (trial, got, want)
        assert eng.stats["prefix_hits"] == 3
        assert eng.stats["prefix_tokens_reused"] == 48
    finally:
        eng.stop()


def test_prefix_cache_lru_eviction(model_and_params):
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=1, max_seq=96, chunk_steps=4,
        prefill_buckets=(32,), eos_id=EOS, prefix_cache_entries=2,
    ).start()
    try:
        rng = np.random.default_rng(13)
        prompts = [
            [int(x) for x in rng.integers(2, CFG.vocab_size, size=18)]
            for _ in range(3)
        ]
        for p in prompts:  # three distinct 16-token prefixes, capacity 2
            eng.submit(p, max_new_tokens=4)
        assert len(eng._prefix_cache) == 2
        # oldest evicted → resubmitting prompt 0 gets NO hit; prompt 2 does
        eng.submit(prompts[0][:16] + [7, 8], max_new_tokens=4)
        assert eng.stats["prefix_hits"] == 0
        eng.submit(prompts[2][:16] + [7, 8], max_new_tokens=4)
        assert eng.stats["prefix_hits"] == 1
    finally:
        eng.stop()


def test_prefix_hit_needs_no_layout_room(model_and_params):
    """A hit whose implant + padded suffix piece would overflow max_seq is
    still a hit: only the real tokens take room in the row (the piece's
    padding writes to the scratch page), and the answer is exact."""
    model, params = model_and_params
    # a non-16-multiple bucket (20): prefix 16 + suffix piece 16 + 10 new
    # = 42 slots if padding took room, over max_seq=40; 18 + 10 tokens fit
    eng = LMEngine(
        model, CFG, params, max_batch=1, max_seq=40, chunk_steps=4,
        prefill_buckets=(20,), eos_id=EOS, prefix_cache_entries=2,
    ).start()
    try:
        rng = np.random.default_rng(17)
        base = [int(x) for x in rng.integers(2, CFG.vocab_size, size=18)]
        eng.submit(base, max_new_tokens=4)  # stores base[:16]
        ids = base[:16] + [3, 4]
        got = eng.submit(ids, max_new_tokens=10)
        assert eng.stats["prefix_hits"] == 1
        # reference path uses bucket 32; engine used 20 — same numerics
        assert got == _reference_completion(model, params, ids, 10)
    finally:
        eng.stop()


def test_warmup_compiles_all_buckets_and_prefix_path(model_and_params):
    """After warmup with prefix caching on: every bucket's prefill, the
    implant/extract shapes, and the suffix prefill are compiled, and the
    warmup entries don't occupy the LRU."""
    from kubeflow_tpu.serve.engine import LMEngineModel
    from kubeflow_tpu.serve.model import BucketSpec

    model, params = model_and_params
    m = LMEngineModel(
        "lm", None, config=CFG, max_batch=2, chunk_steps=2, max_seq=96,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(16, 32)),
        max_new_tokens=8, eos_id=EOS, prefix_cache_entries=4,
    )
    m.load()
    try:
        m.warmup()
        eng = m.engine
        assert len(eng._prefix_cache) == 0  # no warmup pollution
        # the suffix warm covered the 16-multiple extract shapes — proof
        # the prefix path (implant/extract/suffix-prefill) compiled
        assert 16 in eng._extract_jits
        # a real shared-prefix workload immediately hits without compiling
        rng = np.random.default_rng(23)
        base = [int(x) for x in rng.integers(2, CFG.vocab_size, size=18)]
        out1 = m.engine.submit(base, max_new_tokens=6)
        out2 = m.engine.submit(base[:16] + [5, 6], max_new_tokens=6)
        assert eng.stats["prefix_hits"] >= 1
        assert out2 == _reference_completion(
            model, params, base[:16] + [5, 6], 6
        )
    finally:
        m.unload()


def test_prefix_cache_token_budget_eviction(model_and_params):
    """prefix_cache_tokens bounds TOTAL stored KV tokens (the HBM cost),
    evicting LRU entries — entry count alone would let memory scale with
    prefix length."""
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=1, max_seq=96, chunk_steps=4,
        prefill_buckets=(32,), eos_id=EOS,
        prefix_cache_entries=64, prefix_cache_tokens=32,
    ).start()
    try:
        rng = np.random.default_rng(29)
        for _ in range(3):  # three 16-token entries against a 32 budget
            ids = [int(x) for x in rng.integers(2, CFG.vocab_size, size=18)]
            eng.submit(ids, max_new_tokens=4)
        assert eng._prefix_tokens_stored <= 32
        assert len(eng._prefix_cache) == 2
        assert sum(k * v for k, v in eng._prefix_lens.items()) == 32
    finally:
        eng.stop()


def test_tp_sharded_engine_matches_unsharded():
    """Tensor-parallel serving: an engine with params laid out by the
    training sharding rules over a model=2 mesh must produce the same
    tokens as the unsharded engine — TP is a layout, not a numerics
    change. (Dims chosen divisible by the model axis.)"""
    from jax.sharding import Mesh

    from kubeflow_tpu.parallel.sharding import transformer_rules

    cfg = TransformerConfig(
        vocab_size=96, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        causal=True, max_seq_len=256, attn_impl="reference",
        dtype=jnp.float32,
    )
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    devs = np.asarray(jax.devices()[:4]).reshape(2, 2)
    mesh = Mesh(devs, ("data", "model"))

    plain = LMEngine(
        model, cfg, params, max_batch=2, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS,
    ).start()
    sharded = LMEngine(
        model, cfg, params, max_batch=2, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS,
        mesh=mesh, rules=transformer_rules(fsdp=False),
    ).start()
    try:
        # params really are sharded over the model axis
        q = sharded.params["layers_0"]["attn"]["q_proj"]["kernel"]
        assert "model" in str(q.sharding.spec)
        k0 = next(iter(sharded.cache.values()))["k"]
        assert "model" in str(k0.sharding.spec)
        rng = np.random.default_rng(31)
        for _ in range(3):
            ids = [int(x) for x in rng.integers(2, 96, size=rng.integers(4, 20))]
            a = plain.submit(ids, max_new_tokens=10)
            b = sharded.submit(ids, max_new_tokens=10)
            assert a == b, (ids, a, b)
    finally:
        plain.stop()
        sharded.stop()


def test_chunked_prefill_parity_and_interleaving(model_and_params):
    """prefill_chunk splits long prompts into pieces interleaved with
    decode — and changes NOTHING about the tokens produced, even with a
    concurrent request decoding mid-prefill."""
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=2, max_seq=128, chunk_steps=2,
        prefill_buckets=(64,), eos_id=EOS, prefill_chunk=16,
    ).start()
    try:
        rng = np.random.default_rng(41)
        # single long prompt: 3 pieces (40 tokens / 16)
        ids = [int(x) for x in rng.integers(2, CFG.vocab_size, size=40)]
        got = eng.submit(ids, max_new_tokens=10)
        assert eng.stats["prefill_pieces"] == 3
        want = _reference_completion(model, params, ids, 10)
        assert got == want, (got, want)

        # a long admission arriving WHILE another row decodes: both match
        long_ids = [int(x) for x in rng.integers(2, CFG.vocab_size, size=48)]
        short_ids = [int(x) for x in rng.integers(2, CFG.vocab_size, size=6)]
        results = {}

        def run_short():
            results["short"] = eng.submit(short_ids, max_new_tokens=16)

        th = threading.Thread(target=run_short)
        th.start()
        time.sleep(0.02)  # short starts decoding first
        results["long"] = eng.submit(long_ids, max_new_tokens=10)
        th.join(120)
    finally:
        eng.stop()
    assert results["short"] == _reference_completion(
        model, params, short_ids, 16
    )
    assert results["long"] == _reference_completion(
        model, params, long_ids, 10
    )


def test_chunked_prefill_with_prefix_cache(model_and_params):
    """Chunked prefill composes with prefix caching: hit implants the
    prefix, the suffix chunks, answers stay exact."""
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=1, max_seq=160, chunk_steps=2,
        prefill_buckets=(64,), eos_id=EOS, prefill_chunk=16,
        prefix_cache_entries=4,
    ).start()
    try:
        rng = np.random.default_rng(43)
        base = [int(x) for x in rng.integers(2, CFG.vocab_size, size=50)]
        eng.submit(base, max_new_tokens=4)  # stores base[:48]
        tail = [int(x) for x in rng.integers(2, CFG.vocab_size, size=20)]
        ids = base[:48] + tail
        got = eng.submit(ids, max_new_tokens=10)
        assert eng.stats["prefix_hits"] == 1
        assert got == _reference_completion(model, params, ids, 10)
    finally:
        eng.stop()


def test_engine_with_gqa_model(model_and_params):
    """The engine serves a GQA config (half-size KV cache) with tokens
    equal to the whole-batch generate path."""
    del model_and_params  # GQA needs its own config/params
    cfg = TransformerConfig(
        vocab_size=89, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, causal=True, max_seq_len=256, attn_impl="reference",
        dtype=jnp.float32,
    )
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    eng = LMEngine(
        model, cfg, params, max_batch=2, max_seq=64, chunk_steps=4,
        prefill_buckets=(32,), eos_id=EOS,
    ).start()
    try:
        assert next(iter(eng.cache.values()))["k"].shape[1] == 2
        gen = jax.jit(
            make_generate_fn(model, cfg, max_new_tokens=10, eos_id=EOS)
        )
        rng = np.random.default_rng(47)
        for _ in range(3):
            ids = [int(x) for x in rng.integers(2, 89, size=rng.integers(4, 20))]
            prompt = np.zeros((1, 32), np.int32)
            prompt[0, : len(ids)] = ids
            toks, n_valid = gen(
                params, prompt, np.asarray([len(ids)], np.int32),
                jax.random.PRNGKey(7), np.zeros((1,), np.float32),
            )
            want = [int(t) for t in np.asarray(toks)[0, : int(n_valid[0])]]
            assert eng.submit(ids, max_new_tokens=10) == want
    finally:
        eng.stop()


def test_engine_gqa_with_prefix_cache(model_and_params):
    """Prefix caching must extract/implant at the GQA cache's kv_heads
    width (regression: it sliced with n_heads and crashed)."""
    del model_and_params
    cfg = TransformerConfig(
        vocab_size=89, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, causal=True, max_seq_len=256, attn_impl="reference",
        dtype=jnp.float32,
    )
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    eng = LMEngine(
        model, cfg, params, max_batch=1, max_seq=96, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS, prefix_cache_entries=2,
    ).start()
    try:
        rng = np.random.default_rng(53)
        base = [int(x) for x in rng.integers(2, 89, size=20)]
        first = eng.submit(base, max_new_tokens=6)
        second = eng.submit(base[:16] + [7, 8], max_new_tokens=6)
        assert eng.stats["prefix_hits"] == 1
        gen = jax.jit(
            make_generate_fn(model, cfg, max_new_tokens=6, eos_id=EOS)
        )
        for ids, got in ((base, first), (base[:16] + [7, 8], second)):
            prompt = np.zeros((1, 32), np.int32)
            prompt[0, : len(ids)] = ids
            toks, n_valid = gen(
                params, prompt, np.asarray([len(ids)], np.int32),
                jax.random.PRNGKey(7), np.zeros((1,), np.float32),
            )
            assert got == [int(t) for t in np.asarray(toks)[0, : int(n_valid[0])]]
    finally:
        eng.stop()


# ------------------------------------------------- pipelined decode (carry)


@pytest.mark.slow
def test_pipelined_inline_token_parity_under_churn(model_and_params):
    """The tentpole contract: pipeline_depth=1 (device-resident carry +
    one-chunk-ahead dispatch) emits byte-identical token streams to the
    inline pipeline_depth=0 path for the same seed, under admission churn
    (9 staggered requests through 3 rows), chunked prefill (prefill_chunk
    splits the long prompts), a one-token budget, a seeded sampled row
    and a mid-stream cancellation — admissions landing while a chunk is
    in flight, whose carry the epoch merges on the device."""
    model, params = model_and_params
    rng = np.random.default_rng(71)
    # mixed lengths: several short, two long enough for multi-piece prefill
    prompts = _prompts(rng, 5, lo=3, hi=14) + [
        [int(x) for x in rng.integers(2, CFG.vocab_size, size=n)]
        for n in (34, 41)
    ] + _prompts(rng, 2, lo=3, hi=14)
    budgets = [12] * 7 + [1, 12]      # 7: done at its first token
    sampled = {8: {"temperature": 0.8, "seed": 99}}

    def run_mode(depth):
        eng = LMEngine(
            model, CFG, params, max_batch=3, max_seq=96, chunk_steps=4,
            prefill_buckets=(48,), eos_id=EOS, prefill_chunk=16, seed=7,
            pipeline_depth=depth,
        ).start()
        outs: dict[int, list[int]] = {}
        errors: list[Exception] = []

        def worker(i):
            try:
                time.sleep(0.02 * i)  # staggered arrivals → admission churn
                outs[i] = eng.submit(
                    prompts[i], max_new_tokens=budgets[i],
                    **sampled.get(i, {}),
                )
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        try:
            threads = [
                threading.Thread(target=worker, args=(i,))
                for i in range(len(prompts))
            ]
            for t in threads:
                t.start()
            # mid-stream cancellation riding along: read one chunk, walk away
            stream = eng.stream(prompts[0], max_new_tokens=12)
            next(iter(stream))
            stream.close()
            for t in threads:
                t.join(180)
            stats = dict(eng.stats)
            uploads = eng.overlap["carry_uploads"]
            merges = eng._merge._cache_size()
        finally:
            eng.stop()
        assert not errors, errors
        return outs, stats, uploads, merges

    pipe, pipe_stats, pipe_uploads, merges = run_mode(1)
    inline, _, _, _ = run_mode(0)
    assert len(pipe) == len(prompts)
    for i in range(len(prompts)):
        assert pipe[i] == inline[i], (i, pipe[i], inline[i])
        if i in sampled:
            continue  # seeded: position-folded draws, equal across depths
        # and both equal the pinned whole-batch reference (greedy)
        want = _reference_completion(model, params, prompts[i], budgets[i])
        assert pipe[i] == want, (i, pipe[i], want)
    assert len(pipe[7]) == 1
    assert pipe_stats["max_concurrent"] >= 2  # churn really happened
    assert pipe_stats["prefill_pieces"] > len(prompts)  # chunked prefills ran
    # epochs, not chunks: uploads bounded by admissions/activations, far
    # below one per chunk once decode is the steady state
    assert pipe_uploads < pipe_stats["chunks"] + 2 * pipe_stats["admitted"]
    # admissions merged into a running batch, by one program
    assert pipe_stats["epochs"] > pipe_stats["epoch_drains"]
    assert merges == 1


def test_pipelined_steady_state_uploads_are_epochs_not_chunks(
    model_and_params,
):
    """Acceptance: steady-state decode performs ZERO per-chunk H2D of the
    per-row arrays — carry uploads grow only on admit/retire/prefill
    epochs. Finds a request whose decode spans several chunks and shows
    its upload delta stays at the admission epoch alone."""
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=2, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS, pipeline_depth=1,
    ).start()
    try:
        rng = np.random.default_rng(73)
        found = False
        for ids in _prompts(rng, 40):
            c0 = eng.stats["chunks"]
            u0 = eng.overlap["carry_uploads"]
            out = eng.submit(ids, max_new_tokens=16)
            dc = eng.stats["chunks"] - c0
            du = eng.overlap["carry_uploads"] - u0
            # every submit is one admission epoch (single-piece prefill):
            # one upload, regardless of how many chunks it decoded for
            assert du <= 2, (ids, du, dc)
            if len(out) >= 10:  # ≥5 chunks at chunk_steps=2
                assert dc > du, (ids, dc, du)
                found = True
                break
        assert found, "no prompt produced a long enough completion"
    finally:
        eng.stop()


def test_pipelined_fatal_inflight_chunk_cannot_leak_requests(
    model_and_params,
):
    """If the device dies while a speculative chunk is in flight, every
    request — including those whose freshest tokens only exist in the
    undrained chunk — must fail promptly with the real error, and later
    submits fail fast. No wedged request, no silent dead scheduler."""
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=2, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS, pipeline_depth=1,
    ).start()
    real_chunk = eng._chunk
    calls = {"n": 0}

    def exploding(*a, **k):
        calls["n"] += 1
        if calls["n"] >= 2:  # chunk 1 dispatches fine and stays in flight
            raise RuntimeError("injected device failure")
        return real_chunk(*a, **k)

    eng._chunk = exploding
    errors: dict[int, Exception] = {}

    def worker(i):
        try:
            eng.submit([3 + i, 5, 7, 11], max_new_tokens=16, timeout_s=30)
        except Exception as e:  # noqa: BLE001
            errors[i] = e

    try:
        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(2)
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(25)
        assert all(not t.is_alive() for t in threads)
        assert time.monotonic() - t0 < 20  # prompt failure, not a timeout
        assert len(errors) == 2, "a request leaked past the fatal path"
        for e in errors.values():
            assert "injected device failure" in str(e)
        with pytest.raises(RuntimeError, match="engine is dead"):
            eng.submit([9, 9, 9], max_new_tokens=4, timeout_s=10)
    finally:
        eng.stop()


def test_idle_parks_without_busy_wake(model_and_params):
    """The idle path must PARK on the work event, not poll at 20 Hz: over
    an idle second the wake-count probe stays flat, and a submit still
    wakes the loop immediately."""
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=1, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS,
    ).start()
    try:
        eng.submit([3, 4, 5], max_new_tokens=4)  # compile + settle
        time.sleep(0.1)  # let the loop reach the park branch
        wakes0 = eng.stats["idle_wakes"]
        time.sleep(1.2)
        # the old 0.05s poll would add ~24 park entries here
        assert eng.stats["idle_wakes"] - wakes0 <= 2
        # and the event wake path still serves promptly
        t0 = time.monotonic()
        out = eng.submit([5, 6, 7], max_new_tokens=4, timeout_s=30)
        assert isinstance(out, list)
        assert time.monotonic() - t0 < 5.0
    finally:
        eng.stop()


def test_carry_upload_never_aliases_host_mirrors(model_and_params):
    """Regression (CPU backend): jnp.asarray of an aligned numpy buffer
    is ZERO-COPY, so an un-snapshotted carry upload aliases the live
    host mirrors — a later in-place host edit (prefill activation, drain
    refresh) retroactively rewrites what an in-flight chunk reads. That
    raced as chunked-prefill rows truncating to their first token under
    churn. The carry (and the paged device table) must be immune to
    mirror mutation after upload — the carry an epoch merges with a
    chunk in flight as much as one built from drained mirrors."""
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=2, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS,
    )
    eng.last_tok[:] = 7
    eng.active[:] = False
    eng._upload_carry()
    c = eng._carry
    eng.last_tok[:] = 99   # host edit AFTER upload
    eng.active[:] = True
    assert list(np.asarray(c["last_tok"])) == [7, 7]
    assert list(np.asarray(c["active"])) == [False, False]
    # paged block-table mirror: same invariant through the memo
    from kubeflow_tpu.serve.paging import PageAllocator

    pager = PageAllocator(
        pool_tokens=16 * 8, page_size=16, max_batch=2, max_pages_per_row=4
    )
    pager.alloc(0, 2)
    dev = pager.device_table(4)
    before = np.asarray(dev).copy()
    pager.free(0)
    pager.alloc(1, 3)
    assert (np.asarray(dev) == before).all()

    # in flight: admissions merged into a running batch — every mirror is
    # scribbled over while the merge and each chunk run, and no stream
    # may notice
    mirrors = ("last_tok", "gen_count", "active", "real_len", "budget",
               "temp", "seeds")

    def scribbling(eng, fn):
        def call(*args, **kw):
            saved = {m: getattr(eng, m).copy() for m in mirrors}
            table = eng.pager.table.copy()
            for m in mirrors:
                arr = getattr(eng, m)
                arr[...] = ~arr if arr.dtype == bool else arr + 7
            eng.pager.table[...] = 0
            try:
                out = fn(*args, **kw)
                jax.block_until_ready(out)
            finally:
                for m in mirrors:
                    getattr(eng, m)[...] = saved[m]
                eng.pager.table[...] = table
            return out

        return call

    def aligned(a):
        # a 64-byte-aligned copy: the CPU backend's jnp.asarray then shares
        # the buffer instead of copying it, so an upload that skipped its
        # snapshot WOULD alias — the race is certain here, not occasional
        buf = np.zeros(a.nbytes + 64, np.uint8)
        off = -buf.ctypes.data % 64
        out = buf[off:off + a.nbytes].view(a.dtype).reshape(a.shape)
        out[...] = a
        return out

    prompts = _prompts(np.random.default_rng(29), 3, lo=4, hi=12)
    eng = LMEngine(
        model, CFG, params, max_batch=3, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS, kv_pool_tokens=16 * 12,
        page_size=16,
    )
    for m in mirrors:
        setattr(eng, m, aligned(getattr(eng, m)))
    eng.pager.table = aligned(eng.pager.table)
    eng._merge = scribbling(eng, eng._merge)
    eng._chunk = scribbling(eng, eng._chunk)
    try:
        reqs = drive_schedule(eng, {
            0: [(prompts[0], 20)], 2: [(prompts[1], 8)],
            3: [(prompts[2], 8)],
        })
    finally:
        eng.stop()
    assert eng.stats["epochs"] > eng.stats["epoch_drains"]  # merged
    for req, new in zip(reqs, (20, 8, 8)):
        assert req.tokens == _reference_completion(model, params, req.ids, new)


def test_engine_config_object_and_depth_validation(model_and_params):
    """LMEngineConfig bundles the knobs; unknown overrides and invalid
    pipeline depths fail loudly."""
    from kubeflow_tpu.serve.engine import LMEngineConfig

    model, params = model_and_params
    cfgobj = LMEngineConfig(
        max_batch=2, max_seq=64, chunk_steps=4, prefill_buckets=(32,),
        eos_id=EOS, pipeline_depth=0,
    )
    eng = LMEngine(model, CFG, params, config=cfgobj).start()
    try:
        assert eng.pipeline_depth == 0
        ids = [5, 9, 33, 60]
        assert eng.submit(ids, max_new_tokens=6) == _reference_completion(
            model, params, ids, 6
        )
    finally:
        eng.stop()
    with pytest.raises(ValueError, match="pipeline_depth"):
        LMEngine(model, CFG, params, max_batch=2, pipeline_depth=2)
    with pytest.raises(TypeError):
        LMEngine(model, CFG, params, not_a_knob=1)
    # the read path is chosen by what the program observes, not set
    import dataclasses

    fields = [f.name for f in dataclasses.fields(LMEngineConfig)]
    assert len(fields) == 20 and "paged_attn_impl" not in fields


@pytest.mark.parametrize("interpret", [False, True], ids=["gather", "kernel"])
def test_decode_read_path_counters(model_and_params, interpret):
    """The three counters of the decode read path, on rows of known
    length: chunks whose attention read through the kernel (all of them
    under the interpreter, none on the gather path), the pages the active
    rows hold up to their reach, and the pages of the window a gather
    reads (max_batch x table width) — counted at each chunk's dispatch."""
    import dataclasses

    _, params = model_and_params
    cfg = dataclasses.replace(CFG, interpret_kernels=interpret)
    eng = LMEngine(
        TransformerLM(cfg), cfg, params, max_batch=4, max_seq=96,
        chunk_steps=4, prefill_buckets=(64,), eos_id=CFG.vocab_size + 1,
        page_size=16, pipeline_depth=0,
    ).start()
    try:
        assert eng.kernel_read == interpret
        # a 20-token prompt: the prefill emits token 1, so the first chunk
        # is dispatched at reach 21 (2 pages of 16), the second at 25 (2),
        # the third at 29 (2): 13 tokens in all
        eng.submit(list(range(2, 22)), max_new_tokens=13)
        assert eng.stats["chunks"] == 3
        assert eng.stats["decode_pages_live"] == 2 + 2 + 2
        # table widths 2, 2, 4: the pages of reach + a chunk's 4 tokens
        # (25, 29, 33 tokens), rounded up to a power of two
        assert eng.stats["decode_pages_window"] == 4 * (2 + 2 + 4)
        # a second, longer row beside nothing: 40 tokens, reach 41 (3
        # pages), one chunk; its table is 4 pages wide (45 tokens -> 3,
        # rounded up to a power of two)
        eng.submit(list(range(2, 42)), max_new_tokens=5)
        assert eng.stats["chunks"] == 4
        assert eng.stats["decode_pages_live"] == 6 + 3
        assert eng.stats["decode_pages_window"] == 32 + 4 * 4
        assert eng.stats["decode_chunks_kernel_read"] == (
            4 if interpret else 0
        )
        # the kernel's grid a step, over both layers: the active row's
        # one block (a table of 2 or 4 pages is one block of 2 or 4) and
        # a step for each of the three rows that hold nothing, which
        # stages no page; four chunks. The gather has no grid.
        assert eng.stats["decode_kernel_steps"] == (
            4 * 2 * 4 if interpret else 0
        )
        assert eng.stats["decode_kernel_steps_live"] == (
            4 * 2 * 1 if interpret else 0
        )
    finally:
        eng.stop()


@pytest.mark.parametrize("interpret", [False, True], ids=["gather", "flash"])
def test_prefill_flash_read_counter(model_and_params, interpret):
    """`stats["prefill_pieces_flash_read"]` beside `stats["prefill_pieces"]`:
    every piece of a chunked prompt under the interpreter (a 128-token
    piece is a whole q block: its gathered window goes through the flash
    forward kernel), none on a plain CPU — and either engine serves the
    whole-batch path's tokens."""
    import dataclasses

    _, params = model_and_params
    cfg = dataclasses.replace(
        CFG, interpret_kernels=interpret, max_seq_len=512
    )
    oracle = GenerateOracle(
        TransformerLM(cfg), cfg, params, eos_id=CFG.vocab_size + 1
    )
    eng = LMEngine(
        TransformerLM(cfg), cfg, params, max_batch=2, max_seq=448,
        chunk_steps=4, prefill_buckets=(128,), prefill_chunk=128,
        eos_id=CFG.vocab_size + 1, page_size=16, pipeline_depth=0,
    ).start()
    try:
        rng = np.random.default_rng(5)
        prompt = [int(t) for t in rng.integers(2, CFG.vocab_size, size=300)]
        assert eng.submit(prompt, max_new_tokens=6) == oracle.submit(prompt, 6)
        assert eng.stats["prefill_pieces"] == 3
        assert eng.stats["prefill_pieces_flash_read"] == (
            3 if interpret else 0
        )
    finally:
        eng.stop()


def test_engine_with_sliding_window(model_and_params):
    """A sliding-window model served through the engine must produce the
    batch path's answers (which window via reference_attention) — exercises
    the windowed chunk-decode kv_mask, the windowed suffix-prefill default
    mask, and prefix reuse under a window."""
    import dataclasses

    wcfg = dataclasses.replace(CFG, attn_window=4)
    model = TransformerLM(wcfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    gen = jax.jit(
        make_generate_fn(model, wcfg, max_new_tokens=12, eos_id=EOS)
    )

    def want_for(ids):
        prompt = np.zeros((1, 32), np.int32)
        prompt[0, : len(ids)] = ids
        toks, n_valid = gen(
            params, prompt, np.asarray([len(ids)], np.int32),
            jax.random.PRNGKey(7), np.zeros((1,), np.float32),
        )
        return [int(t) for t in np.asarray(toks)[0, : int(n_valid[0])]]

    eng = LMEngine(
        model, wcfg, params, max_batch=3, max_seq=64, chunk_steps=3,
        prefill_buckets=(32,), eos_id=EOS, prefix_cache_entries=4,
    ).start()
    try:
        rng = np.random.default_rng(5)
        # prompts LONGER than the window so the boundary is live
        prompts = [
            [int(x) for x in rng.integers(2, CFG.vocab_size, size=n)]
            for n in (6, 9, 17)
        ]
        for ids in prompts:
            assert eng.submit(ids, max_new_tokens=12) == want_for(ids)
        # resubmit the longest prompt: prefix reuse + windowed suffix prefill
        before = eng.stats["prefix_hits"]
        assert eng.submit(prompts[2], max_new_tokens=12) == want_for(prompts[2])
        assert eng.stats["prefix_hits"] > before
    finally:
        eng.stop()


# ------------------------------------------- mid-stream failover resume


def test_engine_resume_tokens_continue_greedy_identically(model_and_params):
    """The resume contract: admitting prompt+committed with a shrunk
    budget emits exactly the tokens an uninterrupted run would have
    produced past the committed prefix — the engine half of the gateway's
    transparent mid-stream failover."""
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=4, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS,
    ).start()
    try:
        rng = np.random.default_rng(11)
        for ids in _prompts(rng, 4):
            full = eng.submit(ids, max_new_tokens=10)
            if len(full) < 3:
                continue  # EOS too early to split meaningfully
            for cut in (1, len(full) // 2, len(full) - 1):
                admits0 = eng.stats["resume_admits"]
                rest = eng.submit(
                    ids, max_new_tokens=10, resume_tokens=full[:cut]
                )
                assert rest == full[cut:], (ids, cut, rest, full)
                assert eng.stats["resume_admits"] == admits0 + 1
    finally:
        eng.stop()


def test_engine_seeded_sampling_deterministic_and_resumable(model_and_params):
    """Seeded temperature>0 draws: token t comes from
    fold_in(PRNGKey(seed), position_of_t), so (a) two runs with the same
    seed agree, (b) a resumed run continues the exact sampling stream,
    and (c) a different seed diverges (the draws are real, not greedy)."""
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=4, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS,
    ).start()
    try:
        ids = [5, 9, 33, 60, 7]
        kw = dict(max_new_tokens=10, temperature=0.9)
        a = eng.submit(ids, seed=1234, **kw)
        b = eng.submit(ids, seed=1234, **kw)
        assert a == b, (a, b)
        if len(a) >= 3:
            cut = len(a) // 2
            rest = eng.submit(ids, seed=1234, resume_tokens=a[:cut], **kw)
            assert rest == a[cut:], (a, cut, rest)
        # a distinct seed must be able to diverge somewhere
        others = [eng.submit(ids, seed=s, **kw) for s in (77, 78, 79)]
        assert any(o != a for o in others), (a, others)
        # unseeded requests still ride the legacy engine-RNG path
        assert eng.submit(ids, max_new_tokens=6) == eng.submit(
            ids, max_new_tokens=6
        )
    finally:
        eng.stop()


def test_engine_resume_validation_errors(model_and_params):
    """A resume prefix that exhausts the budget, or that already contains
    EOS, is a caller error rejected at admission — never a row wasted."""
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=2, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS,
    ).start()
    try:
        with pytest.raises(ValueError, match="no generation budget"):
            eng.submit([5, 6, 7], max_new_tokens=3, resume_tokens=[8, 9, 10])
        with pytest.raises(ValueError, match="EOS"):
            eng.submit([5, 6, 7], max_new_tokens=8, resume_tokens=[8, EOS])
        # boundary: resume leaving exactly one token of budget is admitted
        out = eng.submit([5, 6, 7], max_new_tokens=3, resume_tokens=[8, 9])
        assert len(out) <= 1
    finally:
        eng.stop()


# ------------------- program names the benchmark's device metrics match on


@pytest.mark.parametrize("spec", [0, 3], ids=["plain", "spec"])
def test_program_names_match_the_benchmarks_module_patterns(
    model_and_params, spec
):
    """``engine_decode_device_ms`` and the two ``engine_prefill_device_*``
    metrics find the engine's programs on the trace's module line by name
    (``jit_<function>(<fingerprint>)``). A rename must fail here, not empty
    a metric — and a new name is a new compile-cache entry besides."""
    import re

    from benchmark.manifest import Manifest

    manifest = Manifest()

    def pattern(metric):
        return re.compile(manifest.layer_metric(metric)["module"])

    decode = pattern("engine_decode_device_ms")
    prefills = [
        pattern("engine_prefill_device_ms_p50"), pattern("engine_prefill_device_share"),
    ]
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=2, max_seq=64, chunk_steps=4,
        prefill_buckets=(32,), eos_id=EOS, spec_draft_tokens=spec,
    )

    def _probe_impl(x):
        return x + 1

    # how this JAX names a jitted function's program
    assert "module @jit__probe_impl" in jax.jit(_probe_impl).lower(1.0).as_text()
    chunk = "jit_" + eng._chunk.__name__ + "(12345)"
    prefill = "jit_" + eng._suffix_prefill.__name__ + "(12345)"
    assert chunk == f"jit__chunk{'_spec' if spec else ''}_paged_impl(12345)"
    assert prefill == "jit__suffix_prefill_paged_impl(12345)"
    assert decode.search(chunk) and not decode.search(prefill)
    for rx in prefills:
        assert rx.search(prefill) and not rx.search(chunk)
