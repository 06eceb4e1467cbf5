"""Disaggregated prefill/decode KV spans + the host-RAM KV tier.

The contract under test: a decode replica that is handed a prefill
replica's finished KV span (through the real npz wire codec) produces
EXACTLY the tokens a colocated engine would — while executing zero
prefill chunks itself — and rejects, rather than silently accepts, any
span whose quantization or layout does not match its own cache. Below
HBM, an idle session swapped out to the host tier must swap back in
byte-identically: the continuation decodes as if the row never left.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.chaos import injectors
from kubeflow_tpu.models.transformer import TransformerConfig, TransformerLM
from kubeflow_tpu.serve.engine import LMEngine, fetch_kv_span
from kubeflow_tpu.serve.kv_codec import decode_kv_entries, encode_kv_entries
from kubeflow_tpu.serve.kv_tier import HostKVTier

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

PROMPT = [5, 9, 11, 3, 7, 22, 40, 8, 15, 2, 33, 6, 19, 44, 12, 9, 27, 5, 61, 3]


@pytest.fixture(scope="module")
def model_and_params():
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    return model, params


def _engine(model_and_params, **kw) -> LMEngine:
    model, params = model_and_params
    return LMEngine(
        model, CFG, params, max_batch=2, max_seq=128,
        prefill_buckets=(32, 64), chunk_steps=4, **kw,
    ).start()


def _ship(pre: LMEngine, dec: LMEngine, ids):
    """One prefill→decode span ship through the REAL wire codec (encode
    on the prefill side, decode + validate on the decode side) — the
    same bytes `kv_span:prefill` serves, minus the HTTP."""
    tree, meta = pre.prefill_span(ids)
    blob = encode_kv_entries([(tuple(ids), tree)], meta)
    entries, got_meta = decode_kv_entries(blob)
    (key, host_tree), = entries
    assert list(key) == list(ids)
    return dec.prepare_kv_span(ids, host_tree, got_meta)


PAGED = {"kv_pool_tokens": 1024, "page_size": 16}
PAGED_INT8 = {**PAGED, "kv_quant": "int8"}


@pytest.mark.parametrize(
    "mode", [{}, PAGED, PAGED_INT8],
    ids=["derived-pool", "small-pool", "small-pool-int8"],
)
def test_disagg_parity_decode_runs_zero_prefill(model_and_params, mode):
    ref = _engine(model_and_params, **mode)
    want = ref.submit(PROMPT, max_new_tokens=12)
    ref.stop()

    pre = _engine(model_and_params, **mode)
    dec = _engine(model_and_params, **mode)
    try:
        span = _ship(pre, dec, PROMPT)
        assert pre.stats["kv_spans_exported"] == 1
        assert pre.stats["prefill_pieces"] >= 1
        got = dec.submit(PROMPT, max_new_tokens=12, kv_span=span)
        # the acceptance criterion: the decode engine NEVER ran a
        # prefill chunk, and still matched the colocated answer exactly
        assert dec.stats["prefill_pieces"] == 0, dec.stats
        assert dec.stats["kv_injected"] == 1
        assert got == want, (mode, got, want)
    finally:
        pre.stop()
        dec.stop()


def test_mixed_quantization_rejected_both_directions(model_and_params):
    """A float span must not enter an int8 cache and vice versa — the
    key-SET on the wire (k/v vs k/v/k_scale/v_scale) is the
    discriminator, and BOTH directions ride the real codec."""
    f32 = _engine(model_and_params, **PAGED)
    i8 = _engine(model_and_params, **PAGED_INT8)
    try:
        # float → int8 engine
        tree, meta = f32.prefill_span(PROMPT)
        entries, m = decode_kv_entries(
            encode_kv_entries([(tuple(PROMPT), tree)], meta)
        )
        with pytest.raises(ValueError, match="quant|keys"):
            i8.prepare_kv_span(PROMPT, entries[0][1], m)
        # int8 → float engine
        tree8, meta8 = i8.prefill_span(PROMPT)
        entries8, m8 = decode_kv_entries(
            encode_kv_entries([(tuple(PROMPT), tree8)], meta8)
        )
        assert any("scale" in k for kv in tree8.values() for k in kv)
        with pytest.raises(ValueError, match="quant|keys"):
            f32.prepare_kv_span(PROMPT, entries8[0][1], m8)
    finally:
        f32.stop()
        i8.stop()


def test_layout_mismatch_rejected(model_and_params):
    """A span shaped for a different head layout (here: 2 heads of 16
    instead of 4 of 8) must be rejected at validation, not crash the
    scheduler at implant time."""
    other_cfg = TransformerConfig(
        vocab_size=89, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        causal=True, max_seq_len=256, attn_impl="reference",
        dtype=jnp.float32,
    )
    other = TransformerLM(other_cfg)
    oparams = other.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    pre = LMEngine(
        other, other_cfg, oparams, max_batch=2, max_seq=128,
        prefill_buckets=(32, 64), chunk_steps=4, **PAGED,
    ).start()
    dec = _engine(model_and_params, **PAGED)
    try:
        tree, meta = pre.prefill_span(PROMPT)
        entries, m = decode_kv_entries(
            encode_kv_entries([(tuple(PROMPT), tree)], meta)
        )
        with pytest.raises(ValueError):
            dec.prepare_kv_span(PROMPT, entries[0][1], m)
    finally:
        pre.stop()
        dec.stop()


def test_malformed_meta_rejected(model_and_params):
    dec = _engine(model_and_params, **PAGED)
    pre = _engine(model_and_params, **PAGED)
    try:
        tree, meta = pre.prefill_span(PROMPT)
        with pytest.raises(ValueError):
            dec.prepare_kv_span(PROMPT, tree, {**meta, "real_len": 3})
        with pytest.raises(ValueError):
            dec.prepare_kv_span(PROMPT, tree, {"first_tok": "nope"})
    finally:
        pre.stop()
        dec.stop()


def test_host_tier_swap_is_byte_identical(model_and_params):
    """Turn 1 of a session decodes, finishes, swaps out through the npz
    codec into host RAM; turn 2 (prompt = turn-1 context) swaps it back
    in and must continue EXACTLY like an engine whose row never left."""
    first = [4, 6, 8, 10] * 5
    ref = _engine(model_and_params, **PAGED)
    t1 = ref.submit(first, max_new_tokens=8)
    full = ref.submit(first + t1 + [12, 13], max_new_tokens=8)
    ref.stop()

    eng = _engine(model_and_params, **PAGED, host_kv_bytes=1 << 20)
    try:
        t1b = eng.submit(first, max_new_tokens=8, session="s1")
        assert t1b == t1
        assert eng.flush_offload()
        assert eng.stats["kv_offload_out"] == 1, eng.stats
        res = eng.host_kv_tier.resident()
        assert res["rows"] == 1 and res["bytes"] > 0
        t2 = eng.submit(
            first + t1b + [12, 13], max_new_tokens=8, session="s1"
        )
        assert eng.stats["kv_offload_in"] == 1, eng.stats
        assert t2 == full, (t2, full)
        # take() consumed turn 1's entry; the finished turn 2 swapped
        # back out, so the tier again holds exactly this one session
        assert eng.flush_offload()
        assert eng.stats["kv_offload_out"] == 2, eng.stats
        assert eng.host_kv_tier.resident()["rows"] == 1
    finally:
        eng.stop()


def test_host_tier_divergent_session_reprefills(model_and_params):
    """A session whose new prompt does NOT extend the stored context must
    miss the tier (the stale KV can never be valid) and re-prefill."""
    eng = _engine(model_and_params, **PAGED, host_kv_bytes=1 << 20)
    try:
        eng.submit([4, 6, 8, 10] * 5, max_new_tokens=4, session="s1")
        assert eng.flush_offload()
        before = eng.stats["prefill_pieces"]
        eng.submit([7, 7, 7] * 8, max_new_tokens=4, session="s1")
        assert eng.stats["kv_offload_in"] == 0
        assert eng.stats["prefill_pieces"] > before
        assert eng.host_kv_tier.stats["misses"] >= 1
    finally:
        eng.stop()


def test_host_tier_lru_bounds_bytes():
    tier = HostKVTier(max_bytes=100)
    assert tier.put("a", (1, 2), b"x" * 60)
    assert tier.put("b", (3, 4), b"y" * 60)  # evicts a
    assert tier.resident() == {"bytes": 60, "rows": 1}
    assert tier.stats["evictions"] == 1
    assert tier.take("a", [1, 2, 3]) is None
    assert tier.take("b", [3, 4, 5]) == b"y" * 60
    assert not tier.put("c", (5,), b"z" * 101)  # larger than the pool


def test_drop_kv_ship_falls_back_to_local_prefill(model_and_params):
    """Chaos: the prefill peer dies mid-ship (DropKVShip's injector seam
    raises at the wire). fetch_kv_span returns None — never raises — and
    the request decodes via local prefill with identical tokens."""
    ref = _engine(model_and_params, **PAGED)
    want = ref.submit(PROMPT, max_new_tokens=10)
    ref.stop()

    dec = _engine(model_and_params, **PAGED)
    try:
        stop = injectors.drop_kv_ship(dec, count=1)
        span = fetch_kv_span(
            dec, "http://127.0.0.1:1", "m", PROMPT, 0.0, timeout_s=2.0
        )
        assert span is None
        assert dec.stats["kv_ship_fallbacks"] == 1
        # hook self-uninstalled after its single fire
        assert "kv_ship" not in dec._fault_hooks
        got = dec.submit(PROMPT, max_new_tokens=10)  # the fallback path
        assert got == want
        assert dec.stats["kv_injected"] == 0
        stop()
    finally:
        dec.stop()


def test_dead_peer_falls_back_without_error(model_and_params):
    """No chaos hook needed: an unreachable peer URL (connection refused)
    is the same client-invisible fallback."""
    dec = _engine(model_and_params, **PAGED)
    try:
        span = fetch_kv_span(
            dec, "http://127.0.0.1:1", "m", PROMPT, 0.0, timeout_s=2.0
        )
        assert span is None
        assert dec.stats["kv_ship_fallbacks"] == 1
    finally:
        dec.stop()
