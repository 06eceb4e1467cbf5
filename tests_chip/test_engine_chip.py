"""On-chip engine + long-seq flash kernels, compiled. Runs under
tests_chip's conftest, which fails the session when there is no TPU."""

import numpy as np
import pytest


def test_flash_s512_fwd_bwd_parity_bf16():
    """The native-dtype MXU-operand kernels at the S512 regime that showed
    23.8% MFU pre-fix: outputs and grads must still match the reference
    attention within bf16 tolerance."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops.flash_attention import (
        flash_attention,
        reference_attention,
    )

    rng = jax.random.PRNGKey(0)
    kq, kk, kv, kg = jax.random.split(rng, 4)
    B, H, S, D = 4, 8, 512, 64
    q = jax.random.normal(kq, (B, H, S, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, H, S, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, H, S, D), jnp.bfloat16)

    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
        q, k, v
    )
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2,
    )

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True).astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True).astype(jnp.float32) ** 2)

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=5e-1, rtol=5e-2,
        )


def _bf16_flash_model_and_reference():
    """The bf16 flash model both engine tests serve, and the whole-batch
    path (``make_generate_fn`` on a dense rectangle) on the same weights:
    the reference an engine's greedy stream must equal."""
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
    )
    from kubeflow_tpu.serve.generate import make_generate_fn

    cfg = TransformerConfig(
        vocab_size=512, d_model=256, n_layers=2, n_heads=8, d_ff=512,
        attn_impl="flash", dtype=jnp.bfloat16,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]

    gen = jax.jit(make_generate_fn(model, cfg, max_new_tokens=12, eos_id=1))

    def reference(ids):
        prompt = np.zeros((1, 128), np.int32)
        prompt[0, : len(ids)] = ids
        toks, n_valid = gen(
            params, prompt, np.asarray([len(ids)], np.int32),
            jax.random.PRNGKey(7), np.zeros((1,), np.float32),
        )
        return [int(t) for t in np.asarray(toks)[0, : int(n_valid[0])]]

    return model, cfg, params, reference


def test_engine_on_chip_matches_batch_generate():
    """Continuous batching end-to-end on the real chip: bf16 flash model,
    engine (no pool size named: the derived pool) answers equal the
    whole-batch path, prefix reuse included."""
    from kubeflow_tpu.serve.engine import LMEngine

    model, cfg, params, reference = _bf16_flash_model_and_reference()
    eng = LMEngine(
        model, cfg, params, max_batch=4, max_seq=256, chunk_steps=4,
        prefill_buckets=(128,), eos_id=1, prefix_cache_entries=4,
    ).start()
    try:
        rng = np.random.default_rng(3)
        base = [int(x) for x in rng.integers(2, 512, size=40)]
        for tail_len in (3, 7):
            tail = [int(x) for x in rng.integers(2, 512, size=tail_len)]
            ids = base[:32] + tail
            got = eng.submit(ids, max_new_tokens=12)
            assert got == reference(ids), (tail_len, got)
        assert eng.stats["prefix_hits"] >= 1  # second request reused 32
    finally:
        eng.stop()


def test_paged_engine_on_chip_matches_dense():
    """Paged KV (block-table scatter/gather) compiled for real TPU — the
    path CPU interpret mode cannot exercise — on a pool named small enough
    that pages bite. Completions must equal the whole-batch path's (the
    dense rectangle of ``make_generate_fn``) on the same bf16 flash model,
    prefix reuse and page backpressure included."""
    from kubeflow_tpu.serve.engine import LMEngine

    model, cfg, params, reference = _bf16_flash_model_and_reference()
    # pool sized so 4 concurrent (40+12)-token rows force real paging
    paged = LMEngine(
        model, cfg, params, max_batch=4, max_seq=256, chunk_steps=4,
        prefill_buckets=(128,), eos_id=1, prefix_cache_entries=4,
        kv_pool_tokens=64 * 9, page_size=64,
    ).start()
    try:
        rng = np.random.default_rng(5)
        base = [int(x) for x in rng.integers(2, 512, size=40)]
        for tail_len in (3, 7, 11):
            ids = base[:32] + [
                int(x) for x in rng.integers(2, 512, size=tail_len)
            ]
            got = paged.submit(ids, max_new_tokens=12)
            assert got == reference(ids), (tail_len, got)
        assert paged.stats["prefix_hits"] >= 1
        assert paged.stats["kv_pages_used_peak"] >= 1
        assert paged.pager.used_pages == 0  # all freed
    finally:
        paged.stop()
