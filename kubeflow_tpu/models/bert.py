"""BERT encoder — BASELINE configs 3 (MPIJob Horovod BERT allreduce) and 5
(KServe bert-base-uncased predictor).

Faithful bert-base structure (learned positions + token-type embeddings,
post-LN blocks, GELU intermediate, pooler over [CLS]) expressed with this
framework's parallel-native pieces: attention routes through
``models.transformer.dispatch_attention`` (flash/TP/SP capable), padding is
handled with the segment-id trick (pad tokens get segment 0, valid tokens
segment 1+type), and param names match ``parallel.sharding.transformer_rules``
so FSDP/TP layouts apply unchanged.

Reference analog (UNVERIFIED upstream layout, SURVEY.md §0): [kserve]
python/huggingfaceserver (serves HF BERT on torch); the model itself was
never first-party in the reference — here it is, so the serving and
allreduce benchmarks are self-contained in a zero-egress environment.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from kubeflow_tpu.core.parts import HEAD, LOSS
from kubeflow_tpu.models.transformer import TransformerConfig, dispatch_attention


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    dtype: Any = jnp.float32
    attn_impl: str = "flash"
    interpret_kernels: bool = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def attention_cfg(self) -> TransformerConfig:
        return TransformerConfig(
            vocab_size=self.vocab_size,
            d_model=self.hidden_size,
            n_heads=self.num_heads,
            d_ff=self.intermediate_size,
            causal=False,
            use_rope=False,
            dtype=self.dtype,
            attn_impl=self.attn_impl,
            interpret_kernels=self.interpret_kernels,
        )


def bert_base(**overrides) -> BertConfig:
    return BertConfig(**overrides)


def bert_tiny(**overrides) -> BertConfig:
    """4-layer test-size config (fast CI / CPU sim)."""
    base = dict(
        hidden_size=128, num_layers=4, num_heads=8, intermediate_size=256,
        vocab_size=1024,
    )
    base.update(overrides)
    return BertConfig(**base)


class BertSelfAttention(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, attention_mask):
        cfg = self.cfg
        B, S, _ = x.shape
        H, D = cfg.num_heads, cfg.head_dim
        dense = lambda name: nn.Dense(H * D, dtype=cfg.dtype, name=name)
        q = dense("q_proj")(x).reshape(B, S, H, D).transpose(0, 2, 1, 3)
        k = dense("k_proj")(x).reshape(B, S, H, D).transpose(0, 2, 1, 3)
        v = dense("v_proj")(x).reshape(B, S, H, D).transpose(0, 2, 1, 3)
        # padding via segments: pad→0, valid→1 (pads attend only to pads,
        # and their outputs are dropped downstream)
        seg = attention_mask.astype(jnp.int32)
        o = dispatch_attention(q, k, v, cfg.attention_cfg(), segment_ids=seg)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, H * D)
        return nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="o_proj")(o)


class BertLayer(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, attention_mask):
        cfg = self.cfg
        # post-LN, as in the original
        h = BertSelfAttention(cfg, name="attn")(x, attention_mask)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="ln1")(x + h)
        y = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype, name="up_proj")(x)
        # exact (erf) GELU as in the original BERT — the tanh approximation
        # breaks bit-parity with converted HF checkpoints
        y = nn.gelu(y, approximate=False)
        y = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="down_proj")(y)
        return nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="ln2")(x + y)


class BertEncoder(nn.Module):
    """Returns (sequence_output (B,S,H), pooled_output (B,H))."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None):
        cfg = self.cfg
        B, S = input_ids.shape
        if attention_mask is None:
            attention_mask = jnp.ones((B, S), jnp.int32)
        if token_type_ids is None:
            token_type_ids = jnp.zeros((B, S), jnp.int32)

        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype, name="embed"
        )(input_ids)
        pos = self.param(
            "pos_embedding", nn.initializers.normal(0.02),
            (cfg.max_position, cfg.hidden_size),
        )
        types = nn.Embed(
            cfg.type_vocab_size, cfg.hidden_size,
            dtype=cfg.dtype, name="type_embed",
        )(token_type_ids)
        x = embed + pos[None, :S].astype(cfg.dtype) + types
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="ln_embed")(x)

        for i in range(cfg.num_layers):
            x = BertLayer(cfg, name=f"layers_{i}")(x, attention_mask)

        pooled = nn.tanh(
            nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="pooler")(x[:, 0])
        )
        return x, pooled


class BertForMaskedLM(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None):
        seq, _ = BertEncoder(self.cfg, name="encoder")(
            input_ids, attention_mask, token_type_ids
        )
        h = nn.Dense(self.cfg.hidden_size, dtype=self.cfg.dtype, name="mlm_transform")(seq)
        with jax.named_scope(HEAD):
            h = nn.gelu(h, approximate=False)
        h = nn.LayerNorm(epsilon=self.cfg.layer_norm_eps, name="mlm_ln")(h)
        return nn.Dense(
            self.cfg.vocab_size, use_bias=True, dtype=jnp.float32, name="unembed"
        )(h)


class BertForSequenceClassification(nn.Module):
    cfg: BertConfig
    num_classes: int = 2

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None):
        _, pooled = BertEncoder(self.cfg, name="encoder")(
            input_ids, attention_mask, token_type_ids
        )
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="classifier")(
            pooled
        )


# --------------------------------------------------------------------------- #
# Trainer plumbing (BASELINE config 3: the Horovod-allreduce analog)
# --------------------------------------------------------------------------- #

MASK_TOKEN = 3  # conventionally [MASK]; synthetic data just needs an id


def make_mlm_loss_fn(model: BertForMaskedLM, mask_rate: float = 0.15):
    """(params, {"inputs"}, rng) → (loss, metrics): random-mask MLM."""
    import optax

    def loss_fn(params, batch, rng):
        tokens = batch["inputs"]
        with jax.named_scope(LOSS):
            mask = jax.random.bernoulli(rng, mask_rate, tokens.shape)
            corrupted = jnp.where(mask, MASK_TOKEN, tokens)
        logits = model.apply({"params": params}, corrupted)
        with jax.named_scope(LOSS):
            per_tok = optax.softmax_cross_entropy_with_integer_labels(logits, tokens)
            denom = jnp.maximum(mask.sum(), 1)
            loss = jnp.where(mask, per_tok, 0.0).sum() / denom
            acc = jnp.where(
                mask, jnp.argmax(logits, -1) == tokens, False
            ).sum() / denom
        return loss, {"masked_accuracy": acc}

    return loss_fn


def make_mlm_init_fn(model: BertForMaskedLM, seq_len: int, batch_size: int = 1):
    def init_params(rng):
        return model.init(rng, jnp.zeros((batch_size, seq_len), jnp.int32))[
            "params"
        ]

    return init_params
