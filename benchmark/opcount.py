"""Operations and bytes an algorithm *requires*, from shapes alone. These
are the yardstick for model FLOP/s utilization and roofline shares, so they
live with the benchmark: recomputation (remat) and padding never count.

A multiply-add is two operations. A training step is three forward passes'
worth of matrix operations (the backward pass does each product twice).
"""

from __future__ import annotations

from typing import Mapping

from benchmark.manifest import plugin


def bert_forward_flops_per_sequence(cfg: Mapping, seq: int) -> float:
    """Matrix operations of one BertForMaskedLM forward pass over one
    sequence: the 12 blocks, the MLM transform and the vocabulary
    projection, plus QK^T and PV (bidirectional: no causal halving).
    Same arithmetic as ``bench.py::bert_train_flops_per_step``."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    p_blocks = layers * (4 * h * h + 2 * h * inter)
    p_head = h * h + h * vocab
    return 2.0 * seq * (p_blocks + p_head) + 4.0 * layers * seq * seq * h


def decoder_forward_flops_per_sequence(cfg: Mapping, seq: int) -> float:
    """Matrix operations of one Mistral-style decoder forward pass over one
    sequence of ``seq`` tokens: q/k/v/o with grouped KV heads, the gated
    MLP (three products), the untied vocabulary projection, and attention
    over the causal, windowed key set (query i sees min(i + 1, window)
    keys)."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // heads
    p_attn = h * heads * d + 2 * h * kv * d + heads * d * h
    p_mlp = 3 * h * inter
    matmul = 2.0 * seq * (layers * (p_attn + p_mlp) + h * vocab)
    return matmul + 4.0 * layers * heads * d * causal_pairs(
        seq, cfg.get("sliding_window")
    )


def causal_pairs(seq: int, window: int | None) -> int:
    """Number of (query, key) pairs a causal mask with an optional sliding
    window keeps: sum over i of min(i + 1, window)."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def train_flops_per_token(family: str, cfg: Mapping, seq: int) -> float:
    """Forward + backward matrix operations per token of a training step
    (3x forward), at sequence length ``seq``. The forward count is the
    family's own (``families/<family>.py::forward_flops_per_sequence``)."""
    fwd = plugin("families", family).forward_flops_per_sequence(cfg, seq)
    return 3.0 * fwd / seq


def attention_train_cost(
    family: str, cfg: Mapping, seq: int, batch: int
) -> dict[str, float]:
    """What the attention kernels of one training step must do, per step:
    forward QK^T + PV (4·pairs·d per head), backward 2.5x that (dq: S and
    dP recomputed plus one product; dkv likewise — the flash-attention
    count), and the least bytes: q, k, v, o read or written once forward,
    and q, k, v, o, do read and dq, dk, dv written once backward, in the
    activation type (2 bytes). Which (query, key) pairs a sequence keeps
    is the family's own (``attention_pairs``)."""
    heads = cfg["num_attention_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // heads
    layers = cfg["num_hidden_layers"]
    pairs = plugin("families", family).attention_pairs(cfg, seq)
    fwd = 4.0 * pairs * d * heads
    flops = batch * layers * (fwd + 2.5 * fwd)
    # q, k, v, o forward; q, k, v, o, do, dq, dk, dv backward. K and V count
    # at the query heads' size: the program repeats grouped KV heads before
    # the kernel, so that is what the kernel is given to read.
    elems = seq * heads * d
    return {"flops": flops, "bytes": batch * layers * 2.0 * (4 + 8) * elems}


def decoder_forward_flops_per_token(cfg: Mapping, context: float) -> float:
    """Matrix operations of one token's forward pass through the same
    decoder while serving: every product of ``decoder_forward_flops_per_
    sequence`` once, and QK^T and PV against ``context`` keys (the mean
    number a token of the mix attends to, itself included)."""
    heads = cfg["num_attention_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // heads
    one_key = 4.0 * cfg["num_hidden_layers"] * heads * d
    return decoder_forward_flops_per_sequence(cfg, 1) + one_key * (context - 1)


def roofline_seconds(flops: float, nbytes: float, peaks: Mapping) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
