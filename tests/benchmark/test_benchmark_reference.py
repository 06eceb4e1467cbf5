"""The plain float32 references against the program's models, tiny sizes,
seeded weights. Tolerances: both sides are float32 on the CPU, so they
agree to rounding (1e-4 on logits of order 1); anything structural — a
missing window, a wrong rotation, a dropped bias — is orders larger."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import bert as bert_family
from benchmark.families import mistral as mistral_family
from benchmark.reference import bert as bert_ref
from benchmark.reference import decoder as decoder_ref
from benchmark.weights import seeded_params

ATOL = 2e-4

MISTRAL = {
    "family": "mistral", "hidden_size": 64, "intermediate_size": 160,
    "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 2,
    "vocab_size": 384, "max_position_embeddings": 256, "sliding_window": 24,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "tie_word_embeddings": False, "activation_dtype": "float32",
}
BERT = {
    "family": "bert", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4, "vocab_size": 256,
    "max_position_embeddings": 64, "type_vocab_size": 2, "layer_norm_eps": 1e-12,
    "hidden_act": "gelu", "position_embedding_type": "absolute",
    "activation_dtype": "float32",
}


@pytest.fixture(scope="module")
def decoder():
    from kubeflow_tpu.models.transformer import TransformerLM

    # the CPU has no flash kernel: the test asks for the einsum path itself
    cfg = mistral_family.program_config(MISTRAL, attn_impl="reference")
    model = TransformerLM(cfg)
    params = seeded_params(mistral_family.abstract_params(model), 3, jnp.float32)
    return model, cfg, params


def test_decoder_logits_match_the_whole_sequence_forward(decoder):
    model, _, params = decoder
    tokens = np.random.default_rng(0).integers(2, 384, size=(60,), dtype=np.int32)
    want = np.asarray(model.apply({"params": params}, tokens[None])[0])
    rows = np.arange(60)
    got = np.asarray(decoder_ref.logits_at(params, tokens, rows, MISTRAL, q_block=16))
    # 60 tokens against a window of 24: the window bites
    np.testing.assert_allclose(got, want, atol=ATOL)
    no_window = np.asarray(decoder_ref.logits_at(
        params, tokens, rows, dict(MISTRAL, sliding_window=None)))
    assert np.abs(no_window[30:] - want[30:]).max() > 100 * ATOL


def test_decoder_matches_prefill_then_decode_through_the_paged_cache(decoder):
    """What the engine runs: a prompt written through a block table in
    pieces, then one token at a time — against the reference's one pass."""
    from kubeflow_tpu.models.transformer import init_paged_kv_cache

    model, cfg, params = decoder
    page, n_pages = 8, 12
    tokens = np.random.default_rng(1).integers(2, 384, size=(50,), dtype=np.int32)
    cache = init_paged_kv_cache(cfg, n_pages * page)
    table = jnp.arange(1, 9)[None, :]            # pages 1..8; 0 is scratch
    kw = dict(page_table=table, page_size=page)
    got = []
    for lo, hi in ((0, 16), (16, 32), (32, 40)):  # prefill pieces
        logits, cache = model.apply(
            {"params": params}, tokens[None, lo:hi], cache=cache,
            positions=jnp.arange(lo, hi)[None], **kw)
        got.append(np.asarray(logits[0]))
    for i in range(40, 50):                       # decode steps
        logits, cache = model.apply(
            {"params": params}, tokens[None, i:i + 1], cache=cache,
            positions=jnp.asarray([[i]]), **kw)
        got.append(np.asarray(logits[0]))
    want = np.asarray(decoder_ref.logits_at(params, tokens, np.arange(50), MISTRAL))
    np.testing.assert_allclose(np.concatenate(got), want, atol=ATOL)


def test_decoder_loss_matches_the_programs(decoder):
    from kubeflow_tpu.models.transformer import make_loss_fn

    model, _, params = decoder
    rng = np.random.default_rng(2)
    toks = rng.integers(2, 384, size=(3, 41), dtype=np.int32)
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    want = float(make_loss_fn(model)(params, batch, None)[0])
    got = decoder_ref.token_nll(params, batch["inputs"], batch["targets"], MISTRAL)
    assert got.shape == (3, 40) and got.dtype == np.float32
    assert got.mean() == pytest.approx(want, rel=1e-5)


def test_the_family_refuses_what_the_program_cannot_run():
    with pytest.raises(ValueError, match="rope base 10000 and RMSNorm eps 1e-6"):
        mistral_family.program_config(dict(MISTRAL, rms_norm_eps=1e-5))
    with pytest.raises(ValueError, match="untied"):
        mistral_family.program_config(dict(MISTRAL, tie_word_embeddings=True))
    with pytest.raises(ValueError, match="exact GELU"):
        bert_family.program_config(dict(BERT, hidden_act="gelu_new"))


def test_bert_logits_and_loss_match_the_programs():
    from kubeflow_tpu.models.bert import MASK_TOKEN, BertForMaskedLM, make_mlm_loss_fn

    model = BertForMaskedLM(bert_family.program_config(BERT, attn_impl="reference"))
    params = model.init(jax.random.PRNGKey(4), jnp.zeros((1, 32), jnp.int32))["params"]
    # biases and LayerNorm offsets start at zero: make them count
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.1 if str(path[-1].key) == "bias" else x, params)
    tokens = np.random.default_rng(5).integers(4, 256, size=(4, 32), dtype=np.int32)
    want = np.asarray(model.apply({"params": params}, tokens))
    np.testing.assert_allclose(
        np.asarray(bert_ref.mlm_logits(params, tokens, BERT)), want, atol=ATOL)
    key = jax.random.PRNGKey(6)
    loss = float(make_mlm_loss_fn(model, 0.15)(params, {"inputs": tokens}, key)[0])
    mask = np.asarray(jax.random.bernoulli(key, 0.15, tokens.shape))
    nll = bert_ref.token_nll(params, np.where(mask, MASK_TOKEN, tokens), tokens, BERT, rows=3)
    assert nll.shape == tokens.shape
    assert (nll * mask).sum() / mask.sum() == pytest.approx(loss, rel=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_seeded_weights_are_a_value_of_the_seed(decoder, dtype):
    model = decoder[0]
    abstract = mistral_family.abstract_params(model)
    a, b, c = (seeded_params(abstract, s, dtype) for s in (1, 1, 2))
    flat = lambda t: jax.tree_util.tree_leaves(t)
    assert all(x.dtype == dtype and x.shape == y.shape for x, y in zip(flat(a), flat(abstract)))
    assert all((x == y).all() for x, y in zip(flat(a), flat(b)))
    assert any((x != y).any() for x, y in zip(flat(a), flat(c)))
    k = a["layers_0"]["mlp"]["down_proj"]["kernel"].astype(jnp.float32)
    assert float(k.std()) == pytest.approx(160 ** -0.5, rel=0.05)   # fan_in ** -0.5
    assert (a["ln_f"]["scale"] == 1).all()
