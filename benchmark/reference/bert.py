"""BERT-base's masked-language-model forward pass and per-token loss (arXiv:1810.04805;
Hugging Face ``modeling_bert``): learned position and token-type
embeddings, post-LayerNorm blocks with biased projections, exact (erf) GELU,
and the MLM head (dense, GELU, LayerNorm, vocabulary projection with bias).
No departures; no padding in the benchmark's batches, so no attention mask.
"""

from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32) + p["bias"].astype(F32)


def _dense(x, p):
    return x @ p["kernel"].astype(F32) + p["bias"].astype(F32)


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(F32(2.0))))


def _layer(p, x, cfg: Mapping):
    B, S, _ = x.shape
    H = cfg["num_attention_heads"]
    D = cfg["hidden_size"] // H
    eps = cfg["layer_norm_eps"]
    split = lambda t: t.reshape(B, S, H, D)
    q, k, v = (split(_dense(x, p["attn"][n])) for n in ("q_proj", "k_proj", "v_proj"))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(D))
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = _ln(x + _dense(ctx.reshape(B, S, H * D), p["attn"]["o_proj"]), p["ln1"], eps)
    y = _dense(_gelu(_dense(x, p["up_proj"])), p["down_proj"])
    return _ln(x + y, p["ln2"], eps)


def mlm_logits(params, input_ids, cfg: Mapping):
    """(B, S) token ids → (B, S, vocab) float32 logits."""
    enc = params["encoder"]
    eps = cfg["layer_norm_eps"]
    S = input_ids.shape[1]
    x = (
        jnp.take(enc["embed"]["embedding"], input_ids, axis=0).astype(F32)
        + enc["pos_embedding"][:S].astype(F32)[None]
        + enc["type_embed"]["embedding"][0].astype(F32)
    )
    x = _ln(x, enc["ln_embed"], eps)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(enc[f"layers_{i}"], x, cfg)
    h = _ln(_gelu(_dense(x, params["mlm_transform"])), params["mlm_ln"], eps)
    return _dense(h, params["unembed"])


def token_nll(params, inputs, targets, cfg: Mapping, *, rows: int = 8):
    """Cross-entropy of ``targets`` at every position of the (B, S) batch
    ``inputs`` (the masked positions already replaced), ``rows`` sequences
    at a time → (B, S) float32 on the host."""
    @jax.jit
    def part(params, inputs, targets):
        logp = jax.nn.log_softmax(mlm_logits(params, inputs, cfg), -1)
        return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]

    with jax.default_matmul_precision("highest"):
        return np.concatenate([
            np.asarray(part(params, inputs[r:r + rows], targets[r:r + rows]))
            for r in range(0, inputs.shape[0], rows)
        ])
