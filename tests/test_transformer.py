"""Flagship transformer: impl equivalence across parallel strategies,
sharded training with FSDP+TP rules, MoE variant, remat."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from kubeflow_tpu.core.mesh import Axis, MeshSpec, build_mesh
from kubeflow_tpu.data.synthetic import TokenLMDataset, local_shard_iterator
from kubeflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    make_init_fn,
    make_loss_fn,
    moe_every_kinds,
)
from kubeflow_tpu.parallel.expert import MoEConfig
from kubeflow_tpu.parallel.sharding import transformer_rules
from kubeflow_tpu.train.loop import TrainConfig, Trainer

VOCAB, SEQ, DM, HEADS = 128, 256, 64, 8


def _cfg(**kw):
    base = dict(
        vocab_size=VOCAB,
        d_model=DM,
        n_layers=2,
        n_heads=HEADS,
        d_ff=128,
        attn_impl="reference",
        interpret_kernels=True,
    )
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(
        np.random.RandomState(0).randint(0, VOCAB, (4, SEQ)), jnp.int32
    )


@pytest.fixture(scope="module")
def ref_setup(tokens):
    cfg = _cfg()
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    logits = model.apply({"params": params}, tokens)
    return params, logits


def test_forward_shape_and_finite(ref_setup, tokens):
    _, logits = ref_setup
    assert logits.shape == (4, SEQ, VOCAB)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize(
    "impl,mesh_kw",
    [
        ("flash", {}),                       # no mesh: direct pallas call
        ("flash", {"data": 2, "model": 4}),  # TP head sharding via shard_map
        ("ring", {"data": 2, "seq": 4}),     # context parallel
        ("ulysses", {"seq": 8}),             # sequence parallel
    ],
)
def test_attention_impls_match_reference(ref_setup, tokens, devices8, impl, mesh_kw):
    params, ref_logits = ref_setup
    cfg = _cfg(attn_impl=impl)
    model = TransformerLM(cfg)
    if mesh_kw:
        mesh = build_mesh(MeshSpec(**mesh_kw))
        with jax.set_mesh(mesh):
            logits = jax.jit(
                lambda p, t: model.apply({"params": p}, t)
            )(params, tokens)
    else:
        logits = model.apply({"params": params}, tokens)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), atol=2e-3,
        err_msg=f"{impl} vs reference ({mesh_kw})",
    )


def test_flash_rejects_seq_sharding(ref_setup, tokens, devices8):
    params, _ = ref_setup
    model = TransformerLM(_cfg(attn_impl="flash"))
    mesh = build_mesh(MeshSpec(seq=8))
    with jax.set_mesh(mesh):
        with pytest.raises(ValueError, match="ring|ulysses"):
            jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens)


def _train(cfg_model, mesh_spec, steps=6, rules=None, seq=64, batch=16):
    model = TransformerLM(cfg_model)
    trainer = Trainer(
        init_params=make_init_fn(model, seq, mesh_spec.batch_partitions),
        loss_fn=make_loss_fn(model),
        optimizer=optax.adam(1e-2),
        config=TrainConfig(
            mesh=mesh_spec, global_batch=batch, steps=steps, log_every=2
        ),
        param_spec_fn=rules,
    )
    ds = TokenLMDataset(vocab_size=cfg_model.vocab_size, seq_len=seq)
    state, history = trainer.fit(
        lambda s: local_shard_iterator(ds, batch, start_step=s)
    )
    return trainer, state, history


def test_train_fsdp_tp_sharded(devices8):
    cfg = _cfg(n_layers=2, attn_impl="flash")
    rules = transformer_rules()
    trainer, state, history = _train(cfg, MeshSpec(data=2, fsdp=2, model=2), rules=rules)
    assert history[-1]["loss"] < history[0]["loss"]
    # check a TP param really is sharded over model and fsdp
    q = state.params["layers_0"]["attn"]["q_proj"]["kernel"]
    spec = q.sharding.spec
    assert spec == (Axis.FSDP, Axis.MODEL), spec
    # optimizer moments colocated with params
    mu_q = state.opt_state[0].mu["layers_0"]["attn"]["q_proj"]["kernel"]
    assert mu_q.sharding.spec == q.sharding.spec


@pytest.mark.slow
def test_train_ring_attention_long_context(devices8):
    cfg = _cfg(n_layers=1, attn_impl="ring", attn_block_q=64, attn_block_k=64)
    _, _, history = _train(cfg, MeshSpec(data=2, seq=4), seq=256)
    assert history[-1]["loss"] < history[0]["loss"]


def test_train_moe_expert_parallel(devices8):
    cfg = _cfg(
        n_layers=2,
        attn_impl="reference",
        layer_kinds=moe_every_kinds(2, 2),
        moe=MoEConfig(num_experts=4, expert_dim=64, top_k=2),
    )
    trainer, state, history = _train(
        cfg, MeshSpec(data=2, expert=4), rules=transformer_rules()
    )
    assert history[-1]["loss"] < history[0]["loss"]
    assert "moe_aux" in history[0]
    up = state.params["layers_1"]["experts"]["up_proj"]["kernel"]
    assert up.sharding.spec[0] == Axis.EXPERT


def test_remat_matches(ref_setup, tokens):
    params, ref_logits = ref_setup
    model = TransformerLM(_cfg(remat=True))
    logits = model.apply({"params": params}, tokens)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), atol=1e-5
    )


def test_bidirectional_encoder_mode(tokens):
    cfg = _cfg(causal=False, use_rope=False)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    assert "pos_embedding" in params
    logits = model.apply({"params": params}, tokens)
    # bidirectional: flipping future tokens must change position-0 logits
    toks2 = tokens.at[:, -1].set((tokens[:, -1] + 1) % VOCAB)
    logits2 = model.apply({"params": params}, toks2)
    assert not np.allclose(np.asarray(logits[:, 0]), np.asarray(logits2[:, 0]))


def test_embed_onehot_matches_gather(ref_setup, tokens):
    # same params, same numbers — onehot is the SPMD-clean lookup form
    params, ref_logits = ref_setup
    model = TransformerLM(_cfg(embed_impl="onehot"))
    logits = model.apply({"params": params}, tokens)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), atol=1e-5
    )


# ----------------------- grouped-query attention ----------------------- #

def test_gqa_equals_mha_with_tied_kv_groups():
    """A GQA model must equal an MHA model whose k/v kernels tie each
    group of query heads to one shared kv head — GQA is a weight-sharing
    pattern, not new math."""
    import numpy as np

    cfg_gqa = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, causal=True, attn_impl="reference", dtype=jnp.float32,
    )
    cfg_mha = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4,
        d_ff=64, causal=True, attn_impl="reference", dtype=jnp.float32,
    )
    m_gqa, m_mha = TransformerLM(cfg_gqa), TransformerLM(cfg_mha)
    p_gqa = m_gqa.init(jax.random.PRNGKey(5), jnp.zeros((1, 4), jnp.int32))[
        "params"
    ]
    D = cfg_gqa.head_dim
    groups = cfg_gqa.n_heads // cfg_gqa.kv_heads

    def tie(kernel):  # (d_model, Hkv*D) → (d_model, H*D), group-shared
        cols = [kernel[:, g * D:(g + 1) * D] for g in range(cfg_gqa.kv_heads)]
        return jnp.concatenate(
            [cols[j // groups] for j in range(cfg_gqa.n_heads)], axis=1
        )

    p_mha = jax.tree_util.tree_map(lambda x: x, p_gqa)  # copy structure
    p_mha = jax.device_get(p_mha)
    for layer in [k for k in p_mha if k.startswith("layers_")]:
        attn = p_mha[layer]["attn"]
        attn["k_proj"]["kernel"] = tie(attn["k_proj"]["kernel"])
        attn["v_proj"]["kernel"] = tie(attn["v_proj"]["kernel"])

    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 12), 0, 64)
    out_gqa = m_gqa.apply({"params": p_gqa}, toks)
    out_mha = m_mha.apply({"params": p_mha}, toks)
    np.testing.assert_allclose(
        np.asarray(out_gqa), np.asarray(out_mha), rtol=2e-5, atol=1e-5
    )


def test_gqa_cache_decode_matches_full_forward():
    """KV-cache decode with GQA: the cache holds kv_heads (half the memory
    here), and teacher-forced decode logits equal the full forward."""
    import numpy as np

    from kubeflow_tpu.models.transformer import init_kv_cache

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, causal=True, attn_impl="reference", dtype=jnp.float32,
    )
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))[
        "params"
    ]
    B, S, P, MAX = 2, 12, 7, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, 64)
    full = model.apply({"params": params}, toks)
    cache = init_kv_cache(cfg, B, MAX)
    assert next(iter(cache.values()))["k"].shape[1] == 2  # kv_heads, not 4
    lg, cache = model.apply(
        {"params": params}, toks[:, :P], cache=cache, cache_index=0
    )
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(full[:, :P]), rtol=2e-5, atol=1e-5
    )
    for t in range(P, S):
        kv_mask = jnp.broadcast_to(jnp.arange(MAX) <= t, (B, MAX))
        lg, cache = model.apply(
            {"params": params}, toks[:, t:t + 1],
            cache=cache, cache_index=t, kv_mask=kv_mask,
        )
        np.testing.assert_allclose(
            np.asarray(lg[:, 0]), np.asarray(full[:, t]),
            rtol=2e-5, atol=1e-5, err_msg=f"gqa decode step {t}",
        )


def test_sliding_window_model_flash_matches_reference():
    """attn_window at the model level: flash and reference agree, and the
    window genuinely restricts attention (differs from full causal)."""
    import numpy as np

    kw = dict(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
        causal=True, attn_window=16, attn_block_q=16, attn_block_k=16,
        interpret_kernels=True, dtype=jnp.float32,
    )
    cfg_f = TransformerConfig(attn_impl="flash", **kw)
    cfg_r = TransformerConfig(attn_impl="reference", **kw)
    model_f, model_r = TransformerLM(cfg_f), TransformerLM(cfg_r)
    params = model_r.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 64)
    out_f = model_f.apply({"params": params}, toks)
    out_r = model_r.apply({"params": params}, toks)
    np.testing.assert_allclose(
        np.asarray(out_f), np.asarray(out_r), rtol=2e-4, atol=2e-4
    )
    cfg_full = TransformerConfig(
        attn_impl="reference", **{**kw, "attn_window": None}
    )
    out_full = TransformerLM(cfg_full).apply({"params": params}, toks)
    assert not np.allclose(np.asarray(out_r), np.asarray(out_full))


def test_sliding_window_config_validation():
    import pytest as _pytest

    with _pytest.raises(ValueError, match="causal"):
        TransformerConfig(causal=False, attn_window=8).validate()
    with _pytest.raises(ValueError, match="context parallelism"):
        TransformerConfig(attn_impl="ring", attn_window=8).validate()


@pytest.mark.slow
def test_remat_policies_preserve_loss_and_grads(devices8):
    """remat and remat_policy='dots' trade memory for recompute — they
    must change NOTHING numerically (same loss, same grads)."""
    import optax

    def make(remat, policy):
        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
            attn_impl="reference", dtype=jnp.float32,
            remat=remat, remat_policy=policy,
        )
        return TransformerLM(cfg)

    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 64)
    tgts = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    base = make(False, None)
    params = base.init(jax.random.PRNGKey(2), toks)["params"]

    def loss_fn(model):
        def f(p):
            lg = model.apply({"params": p}, toks)
            return optax.softmax_cross_entropy_with_integer_labels(
                lg, tgts
            ).mean()
        return f

    l0, g0 = jax.value_and_grad(loss_fn(base))(params)
    for remat, policy in ((True, None), (True, "dots")):
        l1, g1 = jax.value_and_grad(loss_fn(make(remat, policy)))(params)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            ),
            g0, g1,
        )
    with pytest.raises(ValueError, match="remat_policy"):
        TransformerConfig(remat=True, remat_policy="bogus").validate()
    with pytest.raises(ValueError, match="requires remat=True"):
        TransformerConfig(remat=False, remat_policy="dots").validate()
