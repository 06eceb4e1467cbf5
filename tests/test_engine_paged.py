"""Paged KV cache (serve/paging.py + the engine's one cache layout): the
vLLM block-table analog. The invariant everywhere: PAGING IS A LAYOUT,
NOT A NUMERICS CHANGE — every completion must equal the whole-batch
generate path's (``make_generate_fn`` on a dense rectangle, the oracle),
while HBM is billed per resident token instead of per (row × max_seq)
rectangle. Pools here are named small on purpose, so that pages bite."""

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
from kubeflow_tpu.serve.paging import PageAllocator

CFG = TransformerConfig(
    vocab_size=89, d_model=32, n_layers=2, n_heads=4, d_ff=64,
    causal=True, max_seq_len=256, attn_impl="reference", dtype=jnp.float32,
)
EOS = 1


@pytest.fixture(scope="module")
def model_and_params():
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    return model, params


def _prompts(rng, n, lo=3, hi=25, vocab=89):
    return [
        [int(x) for x in rng.integers(2, vocab, size=rng.integers(lo, hi))]
        for _ in range(n)
    ]


# ---------------------------------------------------------------- allocator


def test_allocator_accounting():
    a = PageAllocator(
        pool_tokens=16 * 8, page_size=16, max_batch=4, max_pages_per_row=4
    )
    assert a.pages_for(1) == 1 and a.pages_for(16) == 1 and a.pages_for(17) == 2
    assert a.free_pages == 7  # page 0 is scratch
    a.alloc(0, 3)
    a.alloc(1, 4)
    assert a.used_pages == 7 and not a.can_alloc(1)
    # tables point at distinct non-scratch pages; unused entries at scratch
    assert len(set(a.table[0, :3]) | set(a.table[1])) == 7
    assert 0 not in a.table[0, :3] and a.table[0, 3] == 0
    with pytest.raises(RuntimeError, match="exhausted"):
        a.alloc(2, 1)
    with pytest.raises(RuntimeError, match="already holds"):
        a.alloc(0, 1)
    a.free(0)
    assert a.free_pages == 3 and np.all(a.table[0] == 0)
    a.free(0)  # idempotent
    with pytest.raises(ValueError, match="max_pages_per_row"):
        a.alloc(2, 5)
    with pytest.raises(ValueError, match="16-multiple"):
        PageAllocator(pool_tokens=64, page_size=10, max_batch=1,
                      max_pages_per_row=1)


def test_device_table_memo_evicts_stale_widths():
    """The device-mirror memo holds at most one entry per width, all
    from the CURRENT table version — a long-lived engine with churning
    horizons must not pin one stale int32 slab per width it ever
    touched."""
    a = PageAllocator(
        pool_tokens=16 * 8, page_size=16, max_batch=4, max_pages_per_row=4
    )
    a.alloc(0, 2)
    a.device_table(2)
    a.device_table(4)
    assert len(a._dev) == 2 and a.device_uploads == 2
    a.alloc(1, 2)  # version bump → both memo entries are now stale
    a.device_table(4)  # miss: evicts the stale pair, uploads one fresh
    assert len(a._dev) == 1 and a.device_uploads == 3
    assert all(ver == a.version for ver, _ in a._dev.values())
    a.device_table(2)
    assert len(a._dev) == 2 and a.device_uploads == 4
    a.device_table(2)  # hit: no upload, no eviction
    assert len(a._dev) == 2 and a.device_uploads == 4


# ------------------------------------------------------------------ parity


def _oracle_and_paged(model, params, *, prefix=0, chunked=None, cfg=CFG,
                      pool_tokens=16 * 20, max_batch=4):
    oracle = GenerateOracle(model, cfg, params, eos_id=EOS)
    paged = LMEngine(
        model, cfg, params, max_batch=max_batch, max_seq=64, chunk_steps=4,
        prefill_buckets=(32,), eos_id=EOS, prefix_cache_entries=prefix,
        prefill_chunk=chunked, kv_pool_tokens=pool_tokens, page_size=16,
    ).start()
    return oracle, paged


def test_paged_matches_dense_exactly(model_and_params):
    model, params = model_and_params
    oracle, paged = _oracle_and_paged(model, params)
    try:
        rng = np.random.default_rng(0)
        for ids in _prompts(rng, 8):
            want = oracle.submit(ids, max_new_tokens=12)
            got = paged.submit(ids, max_new_tokens=12)
            assert got == want, (ids, got, want)
        assert paged.pager.used_pages == 0  # everything freed
    finally:
        paged.stop()


def test_paged_concurrent_staggered(model_and_params):
    """Continuous batching on the paged cache: staggered arrivals share
    the running batch and still match the dense whole-batch path."""
    model, params = model_and_params
    oracle, paged = _oracle_and_paged(model, params, max_batch=3)
    rng = np.random.default_rng(1)
    prompts = _prompts(rng, 7)
    want = {}
    results: dict[int, list[int]] = {}
    errors: list[Exception] = []

    def worker(i):
        try:
            time.sleep(0.03 * i)
            results[i] = paged.submit(prompts[i], max_new_tokens=16)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(7)]
    try:
        for i, ids in enumerate(prompts):
            want[i] = oracle.submit(ids, max_new_tokens=16)
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        paged.stop()
    assert not errors, errors
    assert results == want
    assert paged.stats["max_concurrent"] >= 2


def test_paged_prefix_cache_parity_and_reuse(model_and_params):
    """Automatic prefix caching on the paged cache: exact same tokens,
    real reuse (extract gathers through the table, implant scatters)."""
    model, params = model_and_params
    oracle, paged = _oracle_and_paged(model, params, prefix=4)
    try:
        shared = [7] * 20
        tails = [[11, 12], [13, 14, 15], [16]]
        for tail in tails:
            want = oracle.submit(shared + tail, max_new_tokens=10)
            got = paged.submit(shared + tail, max_new_tokens=10)
            assert got == want, (tail, got, want)
        assert paged.stats["prefix_hits"] >= 2
        assert paged.stats["prefix_tokens_reused"] >= 32
    finally:
        paged.stop()


def test_paged_chunked_prefill_parity(model_and_params):
    model, params = model_and_params
    oracle, paged = _oracle_and_paged(model, params, chunked=16,
                                     pool_tokens=16 * 24)
    try:
        rng = np.random.default_rng(3)
        for ids in _prompts(rng, 4, lo=20, hi=45):
            want = oracle.submit(ids, max_new_tokens=8)
            got = paged.submit(ids, max_new_tokens=8)
            assert got == want, (len(ids), got, want)
        assert paged.stats["prefill_pieces"] > 4  # really chunked
    finally:
        paged.stop()


def test_paged_sliding_window_and_gqa(model_and_params):
    """Window + GQA ride the paged branch's position-space mask."""
    import dataclasses

    for variant in (
        dataclasses.replace(CFG, attn_window=4),
        dataclasses.replace(CFG, n_kv_heads=2),
    ):
        model = TransformerLM(variant)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        oracle, paged = _oracle_and_paged(model, params, cfg=variant)
        try:
            rng = np.random.default_rng(5)
            for ids in _prompts(rng, 4, lo=6, hi=20):
                want = oracle.submit(ids, max_new_tokens=10)
                got = paged.submit(ids, max_new_tokens=10)
                assert got == want, (variant.attn_window, got, want)
        finally:
            paged.stop()


# ------------------------------------------------------- density/backpressure


def test_page_backpressure_queues_and_completes(model_and_params):
    """A pool too small for all concurrent requests must QUEUE the
    overflow (FIFO, no failure) and finish everything as pages free."""
    model, params = model_and_params
    # 8 pages of 16 = 128 tokens; each request needs (20 + 12)/16 -> 2
    # pages, so only 3-4 of the 8 requests fit at once
    eng = LMEngine(
        model, CFG, params, max_batch=8, max_seq=64, chunk_steps=4,
        prefill_buckets=(32,), eos_id=EOS,
        kv_pool_tokens=16 * 9, page_size=16,
    ).start()
    ref = GenerateOracle(model, CFG, params, eos_id=EOS)
    rng = np.random.default_rng(7)
    prompts = _prompts(rng, 8, lo=17, hi=21)
    results: dict[int, list[int]] = {}
    errors: list[Exception] = []

    def worker(i):
        try:
            results[i] = eng.submit(prompts[i], max_new_tokens=12,
                                    timeout_s=120)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(150)
        assert not errors, errors
        assert len(results) == 8
        for i, ids in enumerate(prompts):
            assert results[i] == ref.submit(ids, max_new_tokens=12), i
        # the pool bound really bit: peak pages within budget, and fewer
        # rows ran concurrently than max_batch allows
        assert eng.stats["kv_pages_used_peak"] <= 8
        assert eng.stats["max_concurrent"] <= 4
    finally:
        eng.stop()


def test_paged_density_vs_dense_rectangle(model_and_params):
    """The point of paging: mixed-length rows resident in a pool ~3.6x
    smaller than the dense rectangle. 8 concurrent rows of <=48 tokens
    each fit in 576 pool tokens (9 pages: 8 allocatable + scratch) where
    dense billing would need 8 x 256 = 2048 — >=2x density in the same
    HBM budget."""
    model, params = model_and_params
    max_seq = 256
    pool_tokens = 64 * 9
    dense_rectangle = 8 * max_seq
    assert dense_rectangle / pool_tokens >= 2  # the VERDICT bar, by design
    eng = LMEngine(
        model, CFG, params, max_batch=8, max_seq=max_seq, chunk_steps=4,
        prefill_buckets=(32,), eos_id=EOS,
        kv_pool_tokens=pool_tokens, page_size=64,
    ).start()
    rng = np.random.default_rng(11)
    prompts = _prompts(rng, 8, lo=10, hi=30)
    results: dict[int, list[int]] = {}
    errors: list[Exception] = []

    def worker(i):
        try:
            results[i] = eng.submit(prompts[i], max_new_tokens=16,
                                    timeout_s=120)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(150)
        assert not errors, errors
        # ALL 8 mixed-length rows were resident simultaneously in a pool
        # 4x smaller than their dense rectangle
        assert eng.stats["max_concurrent"] == 8
        assert eng.stats["kv_pages_used_peak"] * 64 <= pool_tokens
    finally:
        eng.stop()


def test_request_larger_than_pool_fails_fast(model_and_params):
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=2, max_seq=128, chunk_steps=2,
        prefill_buckets=(32, 128), eos_id=EOS,
        kv_pool_tokens=16 * 4, page_size=16,
    ).start()
    try:
        with pytest.raises(ValueError, match="raise kv_pool_tokens"):
            eng.submit(list(range(2, 60)), max_new_tokens=32)
        # a fitting request still completes after the rejection (this tiny
        # model may emit EOS immediately — liveness is what's asserted)
        eng.submit([5, 6, 7], max_new_tokens=4)
        assert eng.stats["completed"] == 1 and eng._fatal is None
    finally:
        eng.stop()


def test_tp_paged_engine_matches_unsharded():
    """TP serving + paged cache compose: pooled KV sharded over kv heads
    on the model axis, same tokens as the unsharded whole-batch path."""
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

    plain = GenerateOracle(model, cfg, params, eos_id=EOS)
    sharded = LMEngine(
        model, cfg, params, max_batch=2, max_seq=64, chunk_steps=2,
        prefill_buckets=(32,), eos_id=EOS,
        mesh=mesh, rules=transformer_rules(fsdp=False),
        kv_pool_tokens=16 * 16, page_size=16,
    ).start()
    try:
        # token-major pool: (pool_tokens, kv_heads, D), heads on "model"
        k0 = next(iter(sharded.cache.values()))["k"]
        assert k0.shape == (16 * 16, cfg.kv_heads, cfg.head_dim)
        assert tuple(k0.sharding.spec) == (None, "model", None)
        rng = np.random.default_rng(31)
        for _ in range(3):
            ids = [int(x) for x in rng.integers(2, 96, size=rng.integers(4, 20))]
            a = plain.submit(ids, max_new_tokens=10)
            b = sharded.submit(ids, max_new_tokens=10)
            assert a == b, (ids, a, b)
    finally:
        sharded.stop()


def test_paged_engine_exports_pool_gauges():
    """/metrics on an engine-backed server shows the paged pool's live
    pressure (pages_total/pages_used) next to the scheduler gauges."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.serve.engine import LMEngineModel
    from kubeflow_tpu.serve.model import BucketSpec
    from kubeflow_tpu.serve.server import ModelServer

    m = LMEngineModel(
        "plm", None, config=CFG, max_batch=2, max_seq=64, chunk_steps=4,
        max_new_tokens=6, eos_id=EOS,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
        kv_pool_tokens=16 * 8, page_size=16,
    )
    server = ModelServer([m])

    async def run():
        async with TestClient(TestServer(server.build_app())) as client:
            r = await client.post(
                "/v1/models/plm:predict",
                json={"instances": [{"input_ids": [5, 6, 7]}]},
            )
            assert r.status == 200
            text = await (await client.get("/metrics")).text()
            assert 'kubeflow_tpu_engine_kv_pages_total{model="plm"} 7' in text
            assert 'kubeflow_tpu_engine_kv_pages_used{model="plm"}' in text
            assert 'kubeflow_tpu_engine_kv_pages_used_peak{model="plm"}' in text

    try:
        asyncio.run(run())
    finally:
        m.unload()


# ----------------------------------------------- pipelined decode (carry)


def test_paged_pipelined_parity_across_horizon_growth(model_and_params):
    """Pipelined paged decode must stay byte-identical to the inline path
    while the page read window grows ACROSS speculative chunks: a long
    budget walks the pow2 page-window buckets (1 → 2 → 4 pages at
    page_size=16) mid-generation, exercising the in-epoch table widening
    without a full carry re-upload."""
    model, params = model_and_params
    kw = dict(
        max_batch=2, max_seq=64, chunk_steps=4, prefill_buckets=(32,),
        eos_id=EOS, kv_pool_tokens=16 * 12, page_size=16, seed=7,
    )
    rng = np.random.default_rng(61)
    prompts = _prompts(rng, 3, lo=4, hi=11)
    outs: dict[int, list[list[int]]] = {}
    for depth in (0, 1):
        eng = LMEngine(model, CFG, params, pipeline_depth=depth, **kw).start()
        try:
            outs[depth] = [
                eng.submit(p, max_new_tokens=40) for p in prompts
            ]
            if depth == 1:
                # widenings are log-bounded table uploads, never per-chunk
                assert (
                    eng.overlap["carry_uploads"] < eng.stats["chunks"]
                ), (eng.overlap["carry_uploads"], eng.stats["chunks"])
            # one request at a time: every admission finds the batch empty,
            # so every epoch is built from drained mirrors
            assert eng.stats["epoch_drains"] == eng.stats["epochs"] >= 3
        finally:
            eng.stop()
    assert outs[0] == outs[1], (outs[0], outs[1])
    assert any(len(o) > 0 for o in outs[1])


def _first_token(model, params, ids):
    return GenerateOracle(model, CFG, params, eos_id=EOS).submit(ids, 1)[0]


#: the admission epoch with a chunk in flight, case by case: engine
#: settings beyond ``_EPOCH_KW``, arrivals by scheduler iteration
#: (``conftest.drive_schedule``: prompt indices into ``_epoch_prompts``,
#: a budget, sampling) and cancellations. A long first request keeps a
#: chunk in flight — and the batch from ever running empty — while the
#: others are admitted.
_EPOCH_CASES = {
    # three admissions, the last held in the queue until a row frees
    "admit_in_flight": ({}, {0: [(0, 40)], 2: [(1, 12)], 3: [(2, 12)],
                             5: [(3, 12)]}, {}),
    # the engine's EOS is prompt 6's first token: retired at its first
    "eos_first": ({"eos_id": "first token of 6"},
                  {0: [(0, 40)], 2: [(6, 12)], 3: [(1, 12)]}, {}),
    "budget_one": ({}, {0: [(0, 40)], 2: [(1, 1)], 3: [(2, 12)]}, {}),
    # a row killed by the host and re-admitted in one merge
    "cancel_in_merge": ({"max_batch": 2},
                        {0: [(0, 40)], 1: [(1, 30)], 4: [(2, 12)]},
                        {4: [1]}),
    # three 16-token pieces: the last lands two epochs after admission
    "multi_piece": ({"prefill_chunk": 16},
                    {0: [(0, 40)], 2: [(8, 12)], 3: [(1, 12)]}, {}),
    # prompt 10 stores its prefix; prompt 11 hits it in flight
    "prefix_hit": ({"prefix_cache_entries": 4},
                   {0: [(0, 40)], 1: [(10, 6)], 5: [(11, 10)]}, {}),
    "seeded": ({}, {0: [(0, 40)], 2: [(1, 16, {"temperature": 0.9,
                                               "seed": 1234})],
                    3: [(2, 12)]}, {}),
    "spec": ({"spec_draft_tokens": 3},
             {0: [(9, 40)], 2: [(1, 12)], 3: [(2, 12)]}, {}),
}
_EPOCH_KW = dict(
    max_batch=3, max_seq=64, chunk_steps=4, prefill_buckets=(32,),
    eos_id=EOS, kv_pool_tokens=16 * 12, page_size=16, seed=7,
)


def _epoch_prompts():
    rng = np.random.default_rng(61)
    shared = [7] * 20
    return _prompts(rng, 8, lo=4, hi=11) + [
        [int(x) for x in rng.integers(2, 89, size=40)],   # 8: three pieces
        [5, 6, 7] * 4,                                    # 9: drafts match
        shared + [11, 12],                                # 10, 11: a prefix
        shared + [13, 14, 15],
    ]


@pytest.mark.parametrize("case", list(_EPOCH_CASES))
def test_paged_pipelined_parity_in_epochs(model_and_params, case):
    """An admission epoch with a chunk in flight merges the next chunk's
    carry on the device and dispatches it before any first token is read
    (serve/engine.py ``_upload_carry``): every stream equals the inline
    ``pipeline_depth=0`` engine's token for token — greedy ones the
    whole-batch path's too — while the pipeline is never drained for an
    epoch after the first dispatch, and the merge is one program."""
    model, params = model_and_params
    overrides, arrivals, cancel = _EPOCH_CASES[case]
    prompts = _epoch_prompts()
    kw = dict(_EPOCH_KW, **overrides)
    if kw["eos_id"] != EOS:
        kw["eos_id"] = _first_token(model, params, prompts[6])
    schedule = {
        it: [(prompts[i], *rest) for i, *rest in reqs]
        for it, reqs in arrivals.items()
    }
    outs, stats = {}, {}
    for depth in (0, 1):
        eng = LMEngine(model, CFG, params, pipeline_depth=depth, **kw)
        try:
            reqs = drive_schedule(eng, schedule, cancel=cancel)
            assert not [r.error for r in reqs if r.error is not None]
            outs[depth] = [r.tokens for r in reqs]
            stats[depth] = dict(eng.stats)
            assert eng.pager.used_pages == 0
            if depth == 1:
                assert eng._merge._cache_size() == 1
        finally:
            eng.stop()
    oracle = GenerateOracle(model, CFG, params, eos_id=kw["eos_id"])
    flat = [r for it in sorted(schedule) for r in schedule[it]]
    cancelled = {i for ids in cancel.values() for i in ids}
    for i, (ids, new, *sampling) in enumerate(flat):
        want = oracle.submit(ids, new) if not sampling else outs[0][i]
        if i in cancelled:  # walked away mid-stream: a prefix of its own
            assert outs[1][i] == want[: len(outs[1][i])], (i, outs[1][i])
            continue
        assert outs[1][i] == outs[0][i], (i, outs[1][i], outs[0][i])
        assert outs[1][i] == want, (i, outs[1][i], want)
    s = stats[1]
    # merged with a chunk in flight; drained only for the first dispatch
    assert s["epochs"] > s["epoch_drains"] == 1, s
    assert stats[0]["epochs"] == stats[0]["epoch_drains"] > 0
    if case == "eos_first":
        assert outs[1][1] == []
    if case == "budget_one":
        assert len(outs[1][1]) == 1
    if case == "multi_piece":
        assert s["prefill_pieces"] >= len(flat) + 2
    if case == "prefix_hit":
        assert s["prefix_hits"] >= 1
    if case == "spec":
        assert s["spec_proposed"] > 0


def test_paged_pipelined_concurrent_with_backpressure(model_and_params):
    """Pipelined paged mode under page backpressure (held admissions) and
    concurrent mixed-length traffic: answers equal the inline engine's,
    and the pool frees fully afterwards — a speculative chunk must never
    leak pages of a retired row."""
    model, params = model_and_params
    kw = dict(
        max_batch=3, max_seq=64, chunk_steps=4, prefill_buckets=(32,),
        eos_id=EOS, kv_pool_tokens=16 * 7, page_size=16, seed=3,
    )
    rng = np.random.default_rng(67)
    prompts = _prompts(rng, 6, lo=3, hi=14)

    def run_mode(depth):
        eng = LMEngine(model, CFG, params, pipeline_depth=depth, **kw).start()
        outs: dict[int, list[int]] = {}
        errors: list[Exception] = []

        def worker(i):
            try:
                time.sleep(0.015 * i)
                outs[i] = eng.submit(prompts[i], max_new_tokens=10)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        try:
            threads = [
                threading.Thread(target=worker, args=(i,))
                for i in range(len(prompts))
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(180)
            assert not errors, errors
            assert eng.pager.used_pages == 0  # no leaked pages
        finally:
            eng.stop()
        return outs

    pipe = run_mode(1)
    inline = run_mode(0)
    for i in range(len(prompts)):
        assert pipe[i] == inline[i], (i, pipe[i], inline[i])


# ----------------------------------------------- speculative decoding (spec)


def test_paged_spec_parity_across_horizon_growth(model_and_params):
    """Speculative paged decode must stay byte-identical to the non-spec
    paged engine while the page read window grows across chunks — the
    horizon bound now grows +K per step (chunk_span), and beyond-budget
    span positions must route to the scratch page, never clamp into the
    row's own pages."""
    model, params = model_and_params
    kw = dict(
        max_batch=2, max_seq=64, chunk_steps=4, prefill_buckets=(32,),
        eos_id=EOS, kv_pool_tokens=16 * 12, page_size=16, seed=7,
    )
    rng = np.random.default_rng(61)
    prompts = _prompts(rng, 3, lo=4, hi=11) + [[5, 6, 7] * 4]
    outs = {}
    for spec in (0, 4):
        for depth in (0, 1):
            eng = LMEngine(
                model, CFG, params, pipeline_depth=depth,
                spec_draft_tokens=spec, **kw
            ).start()
            try:
                outs[(spec, depth)] = [
                    eng.submit(p, max_new_tokens=40) for p in prompts
                ]
                assert eng.pager.used_pages == 0
            finally:
                eng.stop()
    assert outs[(4, 0)] == outs[(0, 0)]
    assert outs[(4, 1)] == outs[(0, 0)]
    assert outs[(0, 1)] == outs[(0, 0)]


def test_paged_spec_concurrent_with_backpressure(model_and_params):
    """Spec + page backpressure (held admissions) + concurrent traffic:
    answers equal the non-spec paged engine's, and the pool frees fully —
    a speculative span must never leak pages of a retired row."""
    model, params = model_and_params
    kw = dict(
        max_batch=3, max_seq=64, chunk_steps=4, prefill_buckets=(32,),
        eos_id=EOS, kv_pool_tokens=16 * 7, page_size=16, seed=3,
    )
    rng = np.random.default_rng(67)
    prompts = _prompts(rng, 6, lo=3, hi=14)

    def run_mode(spec):
        eng = LMEngine(
            model, CFG, params, spec_draft_tokens=spec, **kw
        ).start()
        outs: dict[int, list[int]] = {}
        errors: list[Exception] = []

        def worker(i):
            try:
                time.sleep(0.015 * i)
                outs[i] = eng.submit(prompts[i], max_new_tokens=10)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        try:
            threads = [
                threading.Thread(target=worker, args=(i,))
                for i in range(len(prompts))
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(180)
            assert not errors, errors
            assert eng.pager.used_pages == 0  # no leaked pages
        finally:
            eng.stop()
        return outs

    assert run_mode(4) == run_mode(0)


def test_paged_spec_temperature_determinism(model_and_params):
    """Seeded rejection sampling on the paged cache: same seed → same
    stream, twice, through fresh engines."""
    model, params = model_and_params

    def run():
        eng = LMEngine(
            model, CFG, params, max_batch=1, max_seq=64, chunk_steps=4,
            prefill_buckets=(32,), eos_id=EOS, kv_pool_tokens=16 * 8,
            page_size=16, seed=11, spec_draft_tokens=4,
        ).start()
        try:
            return eng.submit([7, 8, 9] * 4, max_new_tokens=16,
                              temperature=0.9)
        finally:
            eng.stop()

    a, b = run(), run()
    assert a == b and len(a) > 0


# ------------------------------- scheduler phases and prefill padding (PR 24)


def _burst(eng, prompts, *, max_new_tokens=12, trace=None):
    """Every prompt at once from a thread of its own; the first carries
    ``trace``. Returns when all have completed."""
    errors: list[Exception] = []

    def worker(i):
        try:
            eng.submit(prompts[i], max_new_tokens=max_new_tokens,
                       trace=trace if i == 0 else None)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(len(prompts))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(180)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)


def _chunked_paged_engine(model, params):
    return LMEngine(
        model, CFG, params, max_batch=4, max_seq=64, chunk_steps=4,
        prefill_buckets=(32,), eos_id=EOS, prefill_chunk=16,
        kv_pool_tokens=16 * 24, page_size=16,
    )


def test_scheduler_phases_add_up_and_count_what_the_counters_count(
    model_and_params,
):
    """``_phase`` gives each scheduler phase a sum and a count at the
    boundary the profiler annotation has: with the loop's own time they
    must account for the thread (what no phase names stays small), and
    their counts must agree with the counters of the same work."""
    from kubeflow_tpu.obs.trace import TRACER
    from kubeflow_tpu.serve.engine import _SCHED_PHASES

    model, params = model_and_params
    eng = _chunked_paged_engine(model, params)
    # pre-initialised: /metrics iterates the dict from another thread
    assert eng.stats["sched_loop_s"] == 0.0
    for name in _SCHED_PHASES:
        assert eng.stats[f"sched_{name}_s"] == 0.0
        assert eng.stats[f"sched_{name}_n"] == 0
    keys = set(eng.stats)
    eng.start()
    rng = np.random.default_rng(24)
    prompts = _prompts(rng, 9, lo=5, hi=45)
    old_sampling = TRACER.sample_every
    TRACER.clear()
    TRACER.sample_every = 1
    try:
        root = TRACER.span("test.root")
        _burst(eng, prompts, trace=root)
        root.end()
        traces = TRACER.snapshot()["traces"]
        # the last request returns from inside a drain; a chunk dispatched
        # ahead of it may still be in flight — let the loop drain it
        settle = time.monotonic() + 30
        while (
            eng.stats["sched_drain_emit_n"] < eng.stats["chunks"]
            and time.monotonic() < settle
        ):
            time.sleep(0.01)
    finally:
        eng.stop()
        TRACER.sample_every = old_sampling
        TRACER.clear()
    assert not eng._thread.is_alive()
    stats = eng.stats
    assert set(stats) == keys, "a key inserted after start races /metrics"
    named = sum(stats[f"sched_{name}_s"] for name in _SCHED_PHASES)
    loop = stats["sched_loop_s"]
    assert 0.0 < named <= loop + 1e-6
    assert loop - named < 0.1 * loop, (loop, named)
    assert all(stats[f"sched_{name}_s"] >= 0.0 for name in _SCHED_PHASES)
    assert stats["sched_chunk_dispatch_n"] == stats["chunks"] > 0
    # the batch ran empty: no dispatch time is left to measure a gap from
    assert eng._last_dispatch is None
    assert stats["sched_prefill_dispatch_n"] == stats["prefill_pieces"]
    assert stats["sched_drain_wait_n"] == stats["sched_drain_emit_n"] == stats["chunks"]
    # one blocking read per request: its last piece's first token
    assert stats["sched_prefill_wait_n"] == len(prompts)
    assert stats["sched_park_n"] == stats["idle_wakes"] >= 1
    # every prompt is padded to whole 16-token pieces
    assert stats["prefill_tokens"] == sum(map(len, prompts))
    assert stats["prefill_padded_tokens"] == stats["prefill_pieces"] * 16
    assert stats["prefill_pieces"] == sum(-(-len(p) // 16) for p in prompts)
    (prefill,) = [
        s for t in traces for s in t["spans"] if s["name"] == "prefill"
    ]
    assert prefill["attrs"]["prompt_tokens"] == len(prompts[0])
    assert prefill["attrs"]["padded_tokens"] == -(-len(prompts[0]) // 16) * 16


def test_scheduler_phases_are_on_the_profilers_clock(
    model_and_params, tmp_path
):
    """The same phases as host spans in a ``jax.profiler`` capture, found
    the way the benchmark's reduction finds them (``/host:CPU`` events by
    name), so idle gaps of the device get a cause."""
    from benchmark import trace_reduce

    model, params = model_and_params
    eng = _chunked_paged_engine(model, params).start()
    rng = np.random.default_rng(25)
    try:
        _burst(eng, _prompts(rng, 3, lo=5, hi=20))     # compile outside the capture
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0                   # host annotations only
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _burst(eng, _prompts(rng, 6, lo=5, hi=20))
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.stop()
    trace = trace_reduce.load(trace_reduce.newest_xplane(str(tmp_path)))
    seen = {e.name for e in trace.host if e.name.startswith("engine.")}
    assert {
        "engine.admit", "engine.prefill_dispatch", "engine.prefill_wait",
        "engine.carry_upload", "engine.chunk_dispatch", "engine.drain_wait",
        "engine.drain_emit",
    } <= seen, seen
    # no keyword arguments on the annotation: one name per phase
    assert all("#" not in name for name in seen), seen
    waits = [e for e in trace.host if e.name == "engine.drain_wait"]
    assert all(e.dur >= 0.0 for e in waits) and len(waits) >= 2
