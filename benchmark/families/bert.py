"""BERT encoders through ``kubeflow_tpu.models.bert`` (training only)."""

from __future__ import annotations

from typing import Any, Mapping

import jax
import numpy as np

from benchmark import opcount, traffic as traffic_gen
from benchmark.families import DTYPES
from benchmark.reference import bert as reference

# what ``benchmark/opcount.py`` asks of a family
forward_flops_per_sequence = opcount.bert_forward_flops_per_sequence


def attention_pairs(cfg: Mapping[str, Any], seq: int) -> int:
    """(query, key) pairs of one sequence: bidirectional, every pair."""
    return seq * seq


def program_config(cfg: Mapping[str, Any], **overrides):
    from kubeflow_tpu.models.bert import BertConfig

    if cfg["hidden_act"] != "gelu" or cfg["position_embedding_type"] != "absolute":
        raise ValueError("the program's BERT is exact GELU with learned positions")
    kw = dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        layer_norm_eps=cfg["layer_norm_eps"],
        dtype=DTYPES[cfg["activation_dtype"]],
    )
    kw.update(overrides)
    return BertConfig(**kw)


def train_setup(cfg: Mapping[str, Any], mix: Mapping[str, Any], seed: int) -> dict:
    from kubeflow_tpu.models.bert import (
        MASK_TOKEN, BertForMaskedLM, make_mlm_init_fn, make_mlm_loss_fn,
    )

    seq, batch = mix["seq_len"], mix["global_batch"]
    mask_rate = mix["mask_rate"]
    model = BertForMaskedLM(program_config(cfg, **cfg.get("train", {}).get("program", {})))
    cost = opcount.attention_train_cost("bert", cfg, seq, batch)

    def check_batch(batch0, rng0):
        tokens = np.asarray(batch0["inputs"])
        # the program draws its mask from the step's key; the reference is
        # given the same mask, not the same model code
        mask = np.asarray(jax.random.bernoulli(rng0, mask_rate, tokens.shape))
        return np.where(mask, MASK_TOKEN, tokens).astype(tokens.dtype), tokens, mask

    return {
        "init_params": make_mlm_init_fn(model, seq, 1),
        "loss_fn": make_mlm_loss_fn(model, mask_rate),
        "data": traffic_gen.token_batches(cfg["vocab_size"], seq, batch, seed),
        "tokens_per_step": seq * batch,
        "flops_per_token": opcount.train_flops_per_token("bert", cfg, seq),
        "attn_flops": cost["flops"], "attn_bytes": cost["bytes"],
        "forward": lambda params, inputs: model.apply({"params": params}, inputs),
        "check_batch": check_batch,
        "reference_nll": lambda params, inputs, targets: reference.token_nll(
            params, inputs, targets, cfg
        ),
    }
