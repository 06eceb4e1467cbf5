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
