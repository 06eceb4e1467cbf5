"""Mistral-7B's forward pass (arXiv:2310.06825; Hugging Face
``modeling_mistral``): pre-norm RMSNorm, bias-free q/k/v/o with grouped KV
heads, half-split rotary embeddings, sliding-window causal attention, gated
SiLU MLP, untied vocabulary projection.

Departures from the published model, both the program's and so the
reference's, stated in the configuration file: RMSNorm epsilon is the
program's 1e-6 (published 1e-5), and a query sees the ``sliding_window``
keys ending at itself (the paper's "at most W tokens"; Hugging Face's mask
admits W + 1).
"""

from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rotate(x, positions, theta):
    """x: (S, H, D); rotate pairs (i, i + D/2) by position · theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, window, q_block):
    """q: (S, H, D), k/v: (S, Hkv, D). Softmax over the causal window,
    computed a block of queries at a time so an 8k context fits."""
    S, H, D = q.shape
    groups = H // k.shape[1]
    k = jnp.repeat(k, groups, axis=1)
    v = jnp.repeat(v, groups, axis=1)
    kpos = jnp.arange(S)
    outs = []
    for s0 in range(0, S, q_block):
        qb = q[s0:s0 + q_block]
        qpos = s0 + jnp.arange(qb.shape[0])
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(F32(D))
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        scores = jnp.where(mask[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v))
    return jnp.concatenate(outs, axis=0)


def _layer(p, x, positions, cfg: Mapping, q_block):
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg.get("head_dim") or cfg["hidden_size"] // H
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    S = x.shape[0]
    w = lambda *path: _leaf(p, path).astype(F32)
    h = _rms_norm(x, _leaf(p, ("ln1", "scale")), eps)
    q = (h @ w("attn", "q_proj", "kernel")).reshape(S, H, D)
    k = (h @ w("attn", "k_proj", "kernel")).reshape(S, Hkv, D)
    v = (h @ w("attn", "v_proj", "kernel")).reshape(S, Hkv, D)
    q, k = _rotate(q, positions, theta), _rotate(k, positions, theta)
    a = _attention(q, k, v, cfg.get("sliding_window"), q_block)
    x = x + a.reshape(S, H * D) @ w("attn", "o_proj", "kernel")
    h = _rms_norm(x, _leaf(p, ("ln2", "scale")), eps)
    gate = h @ w("mlp", "gate_proj", "kernel")
    up = h @ w("mlp", "up_proj", "kernel")
    return x + (jax.nn.silu(gate) * up) @ w("mlp", "down_proj", "kernel")


def _leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def hidden_states(params, tokens, cfg: Mapping, *, q_block: int = 512):
    """tokens (S,) → final-norm hidden states (S, hidden), float32. Each
    layer is one jitted call, so only one layer's float32 copy of the
    weights is alive at a time."""
    layer = jax.jit(
        lambda p, x, pos: _layer(p, x, pos, cfg, q_block)
    )
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(F32)
        positions = jnp.arange(tokens.shape[0])
        for i in range(cfg["num_hidden_layers"]):
            x = layer(params[f"layers_{i}"], x, positions)
        return jax.jit(_rms_norm, static_argnums=2)(
            x, params["ln_f"]["scale"], cfg["rms_norm_eps"]
        )


def logits_at(params, tokens, rows, cfg: Mapping, **kw):
    """Logits (len(rows), vocab) at the given positions of one sequence."""
    x = hidden_states(params, tokens, cfg, **kw)
    with jax.default_matmul_precision("highest"):
        return jax.jit(
            lambda x, w, r: jnp.take(x, r, axis=0) @ w.astype(F32)
        )(x, params["unembed"]["kernel"], jnp.asarray(rows))


def token_nll(params, inputs, targets, cfg: Mapping, **kw):
    """Next-token cross-entropy at every position of a batch (B, S), one
    sequence at a time → (B, S) float32 on the host."""
    def one(x, w, t):
        logp = jax.nn.log_softmax(x @ w.astype(F32), axis=-1)
        return -jnp.take_along_axis(logp, t[:, None], axis=-1)[:, 0]

    one = jax.jit(one)
    out = []
    for b in range(inputs.shape[0]):
        x = hidden_states(params, inputs[b], cfg, **kw)
        with jax.default_matmul_precision("highest"):
            out.append(np.asarray(one(x, params["unembed"]["kernel"], targets[b])))
    return np.stack(out)
