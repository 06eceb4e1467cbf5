"""Mistral-style decoders through ``kubeflow_tpu.models.transformer``.

The configuration file keeps Hugging Face's key names. The program has no
setting for ``rope_theta`` (10,000 is written into ``rope()``) or
``rms_norm_eps`` (1e-6 in ``RMSNorm``): a file that asks for anything else
is refused here instead of being run as something it is not."""

from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import opcount, traffic as traffic_gen
from benchmark.families import DTYPES
from benchmark.reference import decoder as reference

# what ``benchmark/opcount.py`` asks of a family
forward_flops_per_sequence = opcount.decoder_forward_flops_per_sequence


def attention_pairs(cfg: Mapping[str, Any], seq: int) -> int:
    """(query, key) pairs of one sequence: causal, inside the window."""
    return opcount.causal_pairs(seq, cfg.get("sliding_window"))


def program_config(cfg: Mapping[str, Any], **overrides):
    from kubeflow_tpu.models.transformer import TransformerConfig

    if cfg["rope_theta"] != 10000.0 or cfg["rms_norm_eps"] != 1e-6:
        raise ValueError(
            "the program's decoder has rope base 10000 and RMSNorm eps 1e-6 "
            f"built in; the file asks for {cfg['rope_theta']}, {cfg['rms_norm_eps']}"
        )
    if cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]:
        raise ValueError("the program's decoder is SwiGLU with an untied head")
    kw = dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"], causal=True, use_rope=True,
        attn_window=cfg["sliding_window"],
        dtype=DTYPES[cfg["activation_dtype"]],
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


# -- training ----------------------------------------------------------- #

def train_setup(cfg: Mapping[str, Any], mix: Mapping[str, Any], seed: int) -> dict:
    from kubeflow_tpu.models.transformer import (
        TransformerLM, make_init_fn, make_loss_fn,
    )

    seq, batch = mix["seq_len"], mix["global_batch"]
    model = TransformerLM(program_config(cfg, **cfg.get("train", {}).get("program", {})))
    batch_partitions = 1
    for axis in ("data", "fsdp"):
        batch_partitions *= cfg.get("train", {}).get("mesh", {}).get(axis, 1)
    cost = opcount.attention_train_cost("mistral", cfg, seq, batch)
    return {
        "init_params": make_init_fn(model, seq, batch_partitions),
        "loss_fn": make_loss_fn(model),
        "data": traffic_gen.token_batches(cfg["vocab_size"], seq, batch, seed),
        "tokens_per_step": seq * batch,
        "flops_per_token": opcount.train_flops_per_token("mistral", cfg, seq),
        "attn_flops": cost["flops"], "attn_bytes": cost["bytes"],
        "forward": lambda params, inputs: model.apply({"params": params}, inputs),
        "check_batch": lambda batch0, rng0: (
            np.asarray(batch0["inputs"]), np.asarray(batch0["targets"]),
            np.ones(batch0["inputs"].shape, bool),
        ),
        "reference_nll": lambda params, inputs, targets: reference.token_nll(
            params, inputs, targets, cfg
        ),
    }


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


def serve_context(cfg: Mapping[str, Any], mix: Mapping[str, Any], serve: Mapping[str, Any]) -> dict:
    """What this family counts for a serving cell (published by the runner
    as ``context.<key>``). ``forward_flops_per_token``: one token through
    the held parameters and the head, plus attention at the mean context of
    the mix's tokens — every seed offers the same lengths (the stratified
    quantile midpoints), so the mean is a constant of the mix. Prompt token
    ``i`` sees ``min(i + 1, window)`` keys, and so does the output token at
    position ``i``; a request's last output token is never fed back."""
    window = cfg.get("sliding_window")
    mid = (np.arange(traffic_gen.BLOCK) + 0.5) / traffic_gen.BLOCK
    tokens = pairs = 0
    # prompt and output lengths are drawn independently: every pairing
    for p in traffic_gen.lengths_at(mix["prompt_tokens"], mid):
        for o in traffic_gen.lengths_at(mix["output_tokens"], mid):
            tokens += p + o - 1
            pairs += opcount.causal_pairs(int(p + o - 1), window)
    return {
        "forward_flops_per_token": opcount.decoder_forward_flops_per_token(cfg, pairs / tokens),
        "mean_context_tokens": pairs / tokens,
    }
