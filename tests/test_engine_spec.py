"""In-graph speculative decoding (serve/speculative.py + engine spec mode):
SPECULATION IS A SCHEDULING OPTIMIZATION, NEVER A NUMERICS CHANGE. Greedy
decode with ``spec_draft_tokens=K`` must be byte-identical to K=0 and to
the whole-batch generate path, on the derived pool and on a small named
one × inline/pipelined, under admission churn, chunked prefill, prefix caching
and cancellation; temperature>0 must be seed-deterministic via the
distribution-preserving rejection rule."""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import GenerateOracle

from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
from kubeflow_tpu.serve.engine import LMEngine

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


def _prompts(rng, n, lo=3, hi=20):
    return [
        [int(x) for x in rng.integers(2, CFG.vocab_size, size=rng.integers(lo, hi))]
        for _ in range(n)
    ]


def _mk(model, params, *, spec=4, small_pool=False, depth=1, **kw):
    """``small_pool``: 16-token pages in a pool under max_batch × max_seq,
    so spans cross pages and rows wait for pages; else the derived pool."""
    base = dict(
        max_batch=3, max_seq=96, chunk_steps=4, prefill_buckets=(32,),
        eos_id=EOS, pipeline_depth=depth, spec_draft_tokens=spec, seed=7,
    )
    base.update(kw)
    if small_pool:
        base.setdefault("kv_pool_tokens", 16 * 20)
        base.setdefault("page_size", 16)
    return LMEngine(model, CFG, params, **base).start()


# ----------------------------------------------------------- drafter unit


def test_propose_draft_matches_and_degrades():
    from kubeflow_tpu.serve.speculative import propose_draft

    hist = jnp.asarray([
        # periodic row: ...5 6 7 5 6 7 5 6 7 (L=9) → ctx [7,5,6]? no:
        # last 3 = [5,6,7] at 6..8; full-window match at 0 → draft 5 6 7 5
        [5, 6, 7, 5, 6, 7, 5, 6, 7, 0, 0, 0],
        # no repetition: no match
        [2, 3, 4, 5, 6, 7, 8, 9, 10, 0, 0, 0],
        # too little history for ngram+1
        [4, 4, 4, 0, 0, 0, 0, 0, 0, 0, 0, 0],
    ], jnp.int32)
    hist_len = jnp.asarray([9, 9, 2], jnp.int32)
    draft, draft_len = propose_draft(hist, hist_len, ngram=3, k=4)
    draft, draft_len = np.asarray(draft), np.asarray(draft_len)
    assert draft_len[0] == 4
    # continuation after the EARLIEST [5,6,7] occurrence (full window):
    # positions 3..6 → [5, 6, 7, 5]
    assert list(draft[0]) == [5, 6, 7, 5]
    assert draft_len[1] == 0
    assert draft_len[2] == 0


def test_propose_draft_prefers_recent_full_window():
    from kubeflow_tpu.serve.speculative import propose_draft

    # [1 2 3 9 9] then [1 2 3 4 4] then context [1 2 3]: the most recent
    # full-window match (start 5) wins over the older one (start 0)
    row = [1, 2, 3, 9, 9, 1, 2, 3, 4, 4, 1, 2, 3]
    hist = jnp.asarray([row + [0] * 3], jnp.int32)
    draft, draft_len = propose_draft(
        hist, jnp.asarray([len(row)], jnp.int32), ngram=3, k=2
    )
    assert int(draft_len[0]) == 2
    assert list(np.asarray(draft)[0]) == [4, 4]


# ------------------------------------------------------------- parity core


def test_spec_greedy_byte_identical_all_modes(model_and_params):
    """The tentpole contract: spec_draft_tokens=4 produces byte-identical
    greedy token streams to spec_draft_tokens=0 and to the whole-batch
    path, on the derived and on a small pool × inline/pipelined —
    including prompts engineered to draft heavily (repetitive) and
    prompts that rarely match."""
    model, params = model_and_params
    rng = np.random.default_rng(0)
    prompts = _prompts(rng, 4) + [[7, 8, 9] * 6, [11, 12] * 9]
    oracle = GenerateOracle(model, CFG, params, eos_id=EOS)
    want = {i: oracle.submit(p, max_new_tokens=12) for i, p in enumerate(prompts)}
    base = _mk(model, params, spec=0)
    try:
        for i, p in enumerate(prompts):
            assert base.submit(p, max_new_tokens=12) == want[i], i
    finally:
        base.stop()
    for small_pool in (False, True):
        for depth in (0, 1):
            eng = _mk(
                model, params, spec=4, small_pool=small_pool, depth=depth
            )
            try:
                for i, p in enumerate(prompts):
                    got = eng.submit(p, max_new_tokens=12)
                    assert got == want[i], (small_pool, depth, i, got, want[i])
                assert eng.stats["spec_proposed"] >= 0
            finally:
                eng.stop()


def test_spec_matches_whole_batch_reference(model_and_params):
    """Speculative completions equal the pinned make_generate_fn path —
    not just the non-spec engine (no shared-bug blind spot)."""
    model, params = model_and_params
    oracle = GenerateOracle(model, CFG, params, eos_id=EOS)
    eng = _mk(model, params, spec=4)
    try:
        rng = np.random.default_rng(3)
        for ids in _prompts(rng, 5):
            want = oracle.submit(ids, max_new_tokens=12)
            assert eng.submit(ids, max_new_tokens=12) == want, ids
    finally:
        eng.stop()


def test_spec_parity_under_admission_churn_and_cancellation(
    model_and_params,
):
    """Spec decode under the full engine life: staggered concurrent
    requests through fewer rows (churn + epochs), chunked prefill pieces
    interleaving with speculative chunks, and a mid-stream cancellation.
    Tokens identical to the non-spec engine, pipelined and inline."""
    model, params = model_and_params
    rng = np.random.default_rng(71)
    prompts = _prompts(rng, 5, lo=3, hi=14) + [
        [int(x) for x in rng.integers(2, CFG.vocab_size, size=n)]
        for n in (34, 41)
    ]

    def run_mode(spec, depth):
        eng = _mk(
            model, params, spec=spec, depth=depth, max_seq=112,
            prefill_buckets=(48,), prefill_chunk=16,
        )
        outs: dict[int, list[int]] = {}
        errors: list[Exception] = []

        def worker(i):
            try:
                time.sleep(0.02 * i)
                outs[i] = eng.submit(prompts[i], max_new_tokens=12)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        try:
            threads = [
                threading.Thread(target=worker, args=(i,))
                for i in range(len(prompts))
            ]
            for t in threads:
                t.start()
            stream = eng.stream(prompts[0], max_new_tokens=12)
            next(iter(stream))
            stream.close()
            for t in threads:
                t.join(180)
            stats = dict(eng.stats)
        finally:
            eng.stop()
        assert not errors, errors
        return outs, stats

    want, _ = run_mode(0, 1)
    for depth in (0, 1):
        got, stats = run_mode(4, depth)
        assert got == want, (depth, got, want)
        assert stats["max_concurrent"] >= 2
        assert stats["prefill_pieces"] > len(prompts)


def test_spec_with_prefix_cache_parity(model_and_params):
    """Prefix-cache hits implant KV and the history mirror must still be
    exact (it is host data either way): spec completions with reuse equal
    non-spec completions with reuse."""
    model, params = model_and_params
    outs = {}
    for spec in (0, 4):
        eng = _mk(
            model, params, spec=spec, max_batch=1,
            prefix_cache_entries=4,
        )
        try:
            rng = np.random.default_rng(11)
            base = [int(x) for x in rng.integers(2, CFG.vocab_size, size=20)]
            outs[spec] = [eng.submit(base, max_new_tokens=10)]
            for tail in ([3, 4], [5, 6, 7]):
                outs[spec].append(
                    eng.submit(base[:16] + tail, max_new_tokens=10)
                )
            assert eng.stats["prefix_hits"] == 2
        finally:
            eng.stop()
    assert outs[0] == outs[4]


def test_spec_no_match_rows_emit_one_token_per_step(model_and_params):
    """Rows whose history never matches the n-gram context must degrade
    to classic one-token steps: zero proposals, and the same number of
    decode chunks as the non-spec engine (no wasted verify width)."""
    model, params = model_and_params
    # all-distinct prompt, tiny budget: nothing for the drafter to match
    ids = list(range(2, 22))
    chunks, outs = {}, {}
    for spec in (0, 4):
        eng = _mk(model, params, spec=spec, max_batch=1)
        try:
            outs[spec] = eng.submit(ids, max_new_tokens=4)
            chunks[spec] = eng.stats["chunks"]
            if spec:
                assert eng.stats["spec_proposed"] == 0
                assert eng.stats["spec_accepted"] == 0
        finally:
            eng.stop()
    assert outs[4] == outs[0]
    assert chunks[4] == chunks[0]


def test_spec_acceptance_counters_and_fewer_chunks(model_and_params):
    """A strongly repetitive greedy continuation must actually accept
    drafts: counters move and the same tokens cost fewer chunks. The
    copy-deterministic model (attention/MLP write-back zeroed) makes the
    greedy chain periodic, so acceptance is structural, not luck."""
    import flax

    model, params = model_and_params
    flat = flax.traverse_util.flatten_dict(params)
    cp = flax.traverse_util.unflatten_dict({
        k: (jnp.zeros_like(v) if k[-2] in ("o_proj", "down_proj") else v)
        for k, v in flat.items()
    })
    ids = [5, 6, 7, 8] * 4
    results = {}
    for spec in (0, 4):
        eng = LMEngine(
            model, CFG, cp, max_batch=1, max_seq=160, chunk_steps=2,
            prefill_buckets=(32,), eos_id=CFG.vocab_size + 1,
            spec_draft_tokens=spec,
        ).start()
        try:
            out = eng.submit(ids, max_new_tokens=64)
            results[spec] = (out, eng.stats["chunks"],
                             eng.stats["spec_accepted"])
        finally:
            eng.stop()
    out0, chunks0, _ = results[0]
    out4, chunks4, accepted = results[4]
    assert out4 == out0
    assert accepted > 0
    # the acceptance bar: >= 1.5x fewer forwards for the same tokens
    assert chunks0 >= 1.5 * chunks4, (chunks0, chunks4)


# ------------------------------------------------------------ temperature


def test_spec_temperature_seeded_determinism(model_and_params):
    """temperature>0 under speculation: rejection sampling must be
    deterministic per engine seed — two fresh engines, same seed, same
    requests → identical streams; a different seed may diverge."""
    model, params = model_and_params

    def run(seed):
        eng = _mk(model, params, spec=4, seed=seed)
        try:
            return [
                eng.submit([7, 8, 9] * 4, max_new_tokens=16, temperature=0.8),
                eng.submit([3, 4] * 6, max_new_tokens=10, temperature=1.3),
            ]
        finally:
            eng.stop()

    a, b = run(7), run(7)
    assert a == b
    for toks in a:
        assert toks and all(0 <= t < CFG.vocab_size for t in toks)


def test_spec_mixed_greedy_and_sampled_rows(model_and_params):
    """Greedy rows co-batched with sampling rows: the greedy row's stream
    must STILL equal the non-spec greedy reference (per-row temperature
    semantics survive the span-verify path)."""
    model, params = model_and_params
    base = _mk(model, params, spec=0)
    try:
        want = base.submit([5, 9, 33, 60, 2], max_new_tokens=12)
    finally:
        base.stop()
    eng = _mk(model, params, spec=4)
    try:
        results = {}

        def sampled():
            results["s"] = eng.submit(
                [7, 8, 9] * 4, max_new_tokens=12, temperature=1.0
            )

        th = threading.Thread(target=sampled)
        th.start()
        results["g"] = eng.submit([5, 9, 33, 60, 2], max_new_tokens=12)
        th.join(120)
    finally:
        eng.stop()
    assert results["g"] == want
    assert len(results["s"]) > 0


# ------------------------------------------------------------- validation


def test_spec_config_validation(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="spec_draft_tokens"):
        LMEngine(model, CFG, params, max_batch=1, spec_draft_tokens=-1)
    with pytest.raises(ValueError, match="spec_ngram"):
        LMEngine(
            model, CFG, params, max_batch=1, spec_draft_tokens=2,
            spec_ngram=0,
        )
    # ngram knob is inert while spec is off — no validation error
    eng = LMEngine(
        model, CFG, params, max_batch=1, max_seq=64,
        prefill_buckets=(32,), spec_draft_tokens=0, spec_ngram=0,
    )
    assert eng.spec_k == 0


def test_spec_needs_no_headroom(model_and_params):
    """A row may fill max_seq to its last token with speculation on: span
    positions past the budgeted region write to the scratch page, so no K
    slots are reserved and the stream is the one K=0 gives. The eos id
    is outside the vocabulary: every row runs its whole budget, so the
    last spans really do reach past max_seq."""
    model, params = model_and_params
    ids, max_seq = [3, 4, 5] * 3, 40
    budget = max_seq - len(ids)
    want = GenerateOracle(model, CFG, params, eos_id=97).submit(ids, budget)
    assert len(want) == budget
    for spec in (0, 4):
        eng = _mk(
            model, params, spec=spec, max_batch=1, max_seq=max_seq,
            page_size=16, eos_id=97,
        )
        try:
            assert eng.submit(ids, max_new_tokens=budget) == want, spec
            with pytest.raises(ValueError, match="exceeds engine max_seq"):
                eng.submit(ids, max_new_tokens=budget + 1)
            assert eng.pager.used_pages == 0
        finally:
            eng.stop()


def test_spec_engine_model_warmup_resets_spec_metrics(model_and_params):
    """LMEngineModel.warmup with spec on compiles the verify program and
    leaves every spec counter at zero — warmup traffic must not pollute
    the acceptance gauges."""
    from kubeflow_tpu.serve.engine import LMEngineModel
    from kubeflow_tpu.serve.model import BucketSpec

    model, params = model_and_params
    m = LMEngineModel(
        "lm", None, config=CFG, max_batch=2, chunk_steps=2,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
        max_new_tokens=8, eos_id=EOS, spec_draft_tokens=4,
    )
    m.load()
    try:
        m._params = jax.device_put(params)
        m.warmup()
        eng = m.engine
        assert eng.spec_k == 4
        assert eng.stats["spec_proposed"] == 0
        assert eng.stats["spec_accepted"] == 0
        assert eng.overlap["spec_acceptance"] == 0.0
        # and the engine still serves correctly after the reset
        out = m.engine.submit([4, 8, 15], max_new_tokens=4)
        assert isinstance(out, list)
    finally:
        m.unload()


# -------------------------------------------------------------- satellites


def test_prefix_lens_sorted_cache_invalidation(model_and_params):
    """The memoized descending length list must track store/evict — a
    stale cache would silently miss (or ghost-probe) prefix lengths."""
    model, params = model_and_params
    eng = LMEngine(
        model, CFG, params, max_batch=1, max_seq=96, chunk_steps=4,
        prefill_buckets=(32,), eos_id=EOS, prefix_cache_entries=2,
    ).start()
    try:
        rng = np.random.default_rng(13)
        a = [int(x) for x in rng.integers(2, CFG.vocab_size, size=18)]
        eng.submit(a, max_new_tokens=4)  # stores a[:16]
        eng.submit(a[:16] + [7, 8], max_new_tokens=4)
        assert eng.stats["prefix_hits"] == 1
        assert eng._prefix_lens_sorted == [16]
        # eviction pressure: two new distinct prefixes evict the first
        for _ in range(2):
            ids = [int(x) for x in rng.integers(2, CFG.vocab_size, size=18)]
            eng.submit(ids, max_new_tokens=4)
        # cache coherent: sorted view equals a fresh sort of the truth
        probe = sorted(eng._prefix_lens, reverse=True)
        eng._lookup_prefix(a)  # forces rebuild if invalidated
        assert eng._prefix_lens_sorted == probe
    finally:
        eng.stop()


def test_spec_and_prefix_metrics_on_server(model_and_params):
    """/metrics exports kft_engine_prefix_* and kft_engine_spec_* for
    engine-backed models — the gateway's prefix affinity and the
    speculation dashboards read these."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from kubeflow_tpu.serve.engine import LMEngineModel
    from kubeflow_tpu.serve.model import BucketSpec
    from kubeflow_tpu.serve.server import ModelServer

    model, params = model_and_params
    m = LMEngineModel(
        "lm", None, config=CFG, max_batch=2, chunk_steps=2,
        buckets=BucketSpec(batch_sizes=(1,), seq_lens=(32,)),
        max_new_tokens=6, eos_id=EOS, spec_draft_tokens=4,
        prefix_cache_entries=4,
    )
    m.load()
    m._params = jax.device_put(params)
    server = ModelServer([m])

    async def drive():
        async with TestClient(TestServer(server.build_app())) as client:
            r = await client.post(
                "/v1/models/lm:predict",
                json={"instances": [{"input_ids": [5, 6, 7] * 6}]},
            )
            assert r.status == 200
            return await (await client.get("/metrics")).text()

    try:
        text = asyncio.run(drive())
    finally:
        m.unload()
    for name in (
        "kft_engine_prefix_hits_total",
        "kft_engine_prefix_tokens_reused_total",
        "kft_engine_prefix_entries",
        "kft_engine_prefix_tokens_stored",
        "kft_engine_spec_proposed_total",
        "kft_engine_spec_accepted_total",
        "kft_engine_spec_acceptance",
    ):
        assert f'{name}{{model="lm"}}' in text, name
