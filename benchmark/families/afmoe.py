"""Arcee Trinity (``model_type: afmoe``) through
``kubeflow_tpu.models.transformer``: layers of two attention kinds (window
with rope, global without), gated QK-normed attention under sandwich
norms, leading dense layers, then sigmoid-routed dropless gated experts
beside a shared one. The configuration file keeps Hugging Face's key
names; what the program cannot express is refused here instead of being
run as something it is not."""

from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import opcount, traffic as traffic_gen
from benchmark.families import DTYPES
from benchmark.reference import afmoe as reference

KINDS = ("sliding_attention", "full_attention")


def program_config(cfg: Mapping[str, Any], **overrides):
    from kubeflow_tpu.models.transformer import LayerKind, TransformerConfig
    from kubeflow_tpu.parallel.expert import MoEConfig

    if cfg["rope_theta"] != 10000:
        raise ValueError(f"the program's rope() has base 10000; the file asks for {cfg['rope_theta']}")
    if cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]:
        raise ValueError("the program's decoder is gated SiLU with an untied head")
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"] or set(types) - set(KINDS):
        raise ValueError(f"layer_types must name one of {KINDS} for each of the layers")
    kinds = tuple(
        LayerKind(
            window=cfg["sliding_window"] if t == "sliding_attention" else None,
            rope=t == "sliding_attention",
            ffn="dense" if i < cfg["num_dense_layers"] else "moe",
        )
        for i, t in enumerate(types)
    )
    first, count = reference.held_experts(cfg)
    kw = dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], max_seq_len=cfg["max_position_embeddings"],
        causal=True, use_rope=True, layer_kinds=kinds,
        norm_eps=cfg["rms_norm_eps"], qk_norm=True, attn_gate=True,
        sandwich_norm=True, embed_scale=bool(cfg["mup_enabled"]),
        dtype=DTYPES[cfg["activation_dtype"]],
        moe=MoEConfig(
            num_experts=cfg["num_experts"], expert_dim=cfg["moe_intermediate_size"],
            top_k=cfg["num_experts_per_tok"], capacity_factor=None,
            score_func=cfg["score_func"], route_norm=cfg["route_norm"],
            route_scale=cfg["route_scale"], select_bias=True,
            expert_form="gated_silu", shared_experts=cfg["num_shared_experts"],
            first_expert=first, held_experts=count,
        ),
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


# -- serving ------------------------------------------------------------ #

def serve_model(cfg: Mapping[str, Any]):
    from kubeflow_tpu.models.transformer import TransformerLM

    pc = program_config(cfg)
    return TransformerLM(pc), pc


def abstract_params(model):
    return jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    )


def reference_logits(params, tokens, rows, cfg: Mapping[str, Any]):
    return reference.logits_at(params, np.asarray(tokens, np.int32), rows, cfg)


# -- counts -------------------------------------------------------------- #

def active_params_per_token(cfg: Mapping[str, Any]) -> dict[str, float]:
    """Parameters one token's forward pass multiplies by — *active*, not
    held: the attention of every layer, the dense layers' FFN, and of an
    expert layer the router, the experts per token and the shared ones;
    the head. (The embedding is a lookup.)"""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layers, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    attn = h * heads * d * 3 + 2 * h * kv * d        # q, gate, o; k, v
    expert = 3 * h * cfg["moe_intermediate_size"]
    moe = (
        h * cfg["num_experts"]
        + (cfg["num_experts_per_tok"] + cfg["num_shared_experts"]) * expert
    )
    return {
        "body": layers * attn + dense * 3 * h * cfg["intermediate_size"] + (layers - dense) * moe,
        "head": h * cfg["vocab_size"],
    }


def forward_flops(cfg: Mapping[str, Any], tokens: float, head_tokens: float, pairs: Mapping[str, float]) -> float:
    """Matrix operations the engine is required to do for ``tokens`` tokens
    through the body, ``head_tokens`` through the head, and attention over
    ``pairs[kind]`` (query, key) pairs a layer of each kind."""
    active = active_params_per_token(cfg)
    per_pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    attn = sum(
        per_pair * pairs[kind] * cfg["layer_types"].count(kind) for kind in KINDS
    )
    return 2.0 * (tokens * active["body"] + head_tokens * active["head"]) + attn


def serve_context(cfg: Mapping[str, Any], mix: Mapping[str, Any], serve: Mapping[str, Any]) -> dict:
    """What this family counts for a serving cell (published by the runner
    as ``context.<key>``). ``forward_flops_per_token``: the required
    operations of the mix's requests over their forward tokens — the body
    at its *active* parameters for every token; the head once per decode
    token and once per prompt (the prefill's last position: the program
    computes the head at wanted positions only, and no other is required);
    attention by layer kind, ``min(context, window)`` keys on the sliding
    layers and all of them on the full ones. Every seed offers the same
    lengths (the stratified quantile midpoints), so these are constants of
    the mix. ``moe_*``: the shapes the grouped product's roofline reads."""
    window = cfg["sliding_window"]
    mid = (np.arange(traffic_gen.BLOCK) + 0.5) / traffic_gen.BLOCK
    tokens = heads = 0
    pairs = dict.fromkeys(KINDS, 0)
    # prompt and output lengths are drawn independently: every pairing
    for p in traffic_gen.lengths_at(mix["prompt_tokens"], mid):
        for o in traffic_gen.lengths_at(mix["output_tokens"], mid):
            n = int(p + o - 1)          # the last output token is never fed back
            tokens += n
            heads += int(o)             # one prompt position + (o - 1) decode steps
            pairs["sliding_attention"] += opcount.causal_pairs(n, window)
            pairs["full_attention"] += opcount.causal_pairs(n, None)
    first, count = reference.held_experts(cfg)
    expert_params = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    itemsize = jnp.dtype(DTYPES[cfg["weight_dtype"]]).itemsize
    return {
        "forward_flops_per_token": forward_flops(cfg, tokens, heads, pairs) / tokens,
        "mean_context_tokens": pairs["full_attention"] / tokens,
        "mean_window_context_tokens": pairs["sliding_attention"] / tokens,
        "moe_experts": float(count),
        "moe_top_k": float(cfg["num_experts_per_tok"]),
        "moe_flops_per_assignment": 2.0 * expert_params,
        "moe_bytes_per_expert": float(expert_params * itemsize),
        # an assignment's activations: a row of hidden read twice (gate,
        # up) and written once, a row of the expert's width written once
        # and read once, in the activations' type
        "moe_bytes_per_assignment": float(
            jnp.dtype(DTYPES[cfg["activation_dtype"]]).itemsize
            * (3 * cfg["hidden_size"] + 2 * cfg["moe_intermediate_size"])
        ),
    }
