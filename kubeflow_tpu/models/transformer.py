"""Flagship Transformer (decoder LM / bidirectional encoder), parallel-native.

The model family behind BASELINE configs 3 and 5 (BERT-base allreduce
training; transformer serving) and the long-context story (SURVEY.md §5.7).
Design choices are TPU-first:

- bf16 compute / f32 params+softmax; matmul shapes padded to MXU-friendly
  multiples by configuration, not runtime checks;
- attention strategy per config: ``reference`` (XLA oracle), ``flash``
  (Pallas kernel), ``ring`` (context parallel over ``seq``), ``ulysses``
  (all_to_all SP) — the last two run in shard_map over the live mesh;
- activations carry sharding constraints (batch over data/fsdp, seq over
  seq) so pjit propagates layouts instead of guessing;
- a per-layer pattern of kinds (``LayerKind``): window or global
  attention, rope or none, a dense FFN of its own width or experts
  (``parallel.expert``: capacity dispatch over the ``expert`` axis for
  training, dropless grouped dispatch for serving);
- param names line up with ``parallel.sharding.transformer_rules`` so
  FSDP/TP layouts are one function call.

Reference analog (UNVERIFIED upstream layout, SURVEY.md §0): the models live
in user containers (HF ``transformers`` BERT for KServe's huggingfaceserver,
Megatron-style layouts via MPIJob) — the platform never owned them; here the
model zoo is first-party so every parallel strategy is testable end-to-end.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.core.mesh import Axis
from kubeflow_tpu.core.parts import HEAD, LOSS
from kubeflow_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_span,
    reference_attention,
)
from kubeflow_tpu.ops.flash_tuning import span_kv_block
from kubeflow_tpu.ops.paged_attention import (
    dequantize_kv,
    paged_attention,
    quantize_kv,
)
from kubeflow_tpu.parallel.expert import MoEConfig, dropless_moe_ffn, moe_ffn
from kubeflow_tpu.parallel.ring_attention import ring_attention_local
from kubeflow_tpu.parallel.ulysses import ulysses_attention_local

ATTN_IMPLS = ("reference", "flash", "ring", "ulysses")


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What one layer of the stack is: its attention (the keys a query
    sees, whether q and k are rotated) and its FFN."""

    #: sliding window: a query sees the ``window`` keys ending at itself
    #: (None = every earlier key)
    window: int | None = None
    #: rotary embeddings on q and k (a model with ``use_rope=False`` has
    #: learned positions and rotates nothing)
    rope: bool = True
    ffn: str = "dense"               # "dense" | "moe" (``cfg.moe``)
    #: width of a dense FFN (None = ``cfg.d_ff``)
    d_ff: int | None = None


def moe_every_kinds(
    n_layers: int, every: int, **kind
) -> tuple[LayerKind, ...]:
    """The pattern "every ``every``-th layer routes to experts, the others
    are dense" (``kind``: what all of them share)."""
    return tuple(
        LayerKind(ffn="moe" if (i + 1) % every == 0 else "dense", **kind)
        for i in range(n_layers)
    )


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 512
    d_model: int = 256
    n_layers: int = 4
    n_heads: int = 8
    #: GQA: number of key/value heads (None = n_heads, i.e. plain MHA).
    #: Shrinks the KV cache — the serving memory bill — by n_heads/kv.
    n_kv_heads: int | None = None
    d_ff: int = 1024
    max_seq_len: int = 2048
    causal: bool = True              # False → bidirectional encoder (BERT)
    use_rope: bool = True            # False → learned positions (BERT)
    dtype: Any = jnp.float32         # activation/compute dtype (bf16 on TPU)
    attn_impl: str = "flash"
    #: width of one head (None = d_model // n_heads)
    d_head: int | None = None
    #: sliding-window attention (requires causal; flash/reference impls):
    #: each position attends to the previous ``attn_window`` tokens only.
    #: The window of every layer of a model whose layers are all alike;
    #: ``layer_kinds`` gives each layer its own
    attn_window: int | None = None
    #: one entry per layer where layers differ (None = every layer is
    #: ``LayerKind(attn_window, use_rope, "dense")``)
    layer_kinds: tuple[LayerKind, ...] | None = None
    #: epsilon of every RMS norm
    norm_eps: float = 1e-6
    #: RMS norm (a learned scale over the head's width) of every head of
    #: q and k, before the rotation
    qk_norm: bool = False
    #: o = attention(...) * sigmoid(x . W_g): an output gate with a
    #: projection of its own
    attn_gate: bool = False
    #: a second pair of norms a block, on the attention's and the FFN's
    #: results before they join the residual stream
    sandwich_norm: bool = False
    #: the embedding times sqrt(d_model)
    embed_scale: bool = False
    #: None → per-shape selection (ops/flash_tuning.py: measured table
    #: when a sweep has run on hardware, heuristic otherwise)
    attn_block_q: int | None = None
    attn_block_k: int | None = None
    interpret_kernels: bool = False  # Pallas interpret mode (CPU tests)
    remat: bool = False
    #: rematerialization policy when remat=True (the HBM-vs-FLOPs MFU
    #: lever): None = full remat (recompute everything — max memory
    #: saving, most recompute); "dots" = save matmul outputs, recompute
    #: only the cheap elementwise/softmax work (jax
    #: dots_with_no_batch_dims_saveable — usually the throughput sweet
    #: spot on TPU: MXU results are kept, VPU work is replayed).
    remat_policy: str | None = None
    #: the expert layers' rule (the layers whose kind says ``ffn="moe"``)
    moe: MoEConfig = dataclasses.field(default_factory=MoEConfig)
    dropout_rate: float = 0.0
    # "gather" = table lookup (best single-chip/serving). "onehot" = one-hot
    # matmul — the SPMD-clean form when the table is sharded P(model, fsdp):
    # a sharded-vocab gather forces the partitioner into involuntary full
    # rematerialization (replicate-then-reshard), while the one-hot
    # contraction over vocab partitions into a plain psum over the model
    # axis and rides the MXU.
    embed_impl: str = "gather"

    @property
    def head_dim(self) -> int:
        if self.d_head is not None:
            return self.d_head
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    def kind(self, layer: int) -> LayerKind:
        if self.layer_kinds is not None:
            return self.layer_kinds[layer]
        return LayerKind(window=self.attn_window, rope=self.use_rope)

    @property
    def kinds(self) -> tuple[LayerKind, ...]:
        return tuple(self.kind(i) for i in range(self.n_layers))

    @property
    def moe_layers(self) -> int:
        return sum(k.ffn == "moe" for k in self.kinds)

    @property
    def kv_heads(self) -> int:
        # explicit None check: `or` would silently turn an invalid 0 into
        # full MHA instead of letting validate() reject it
        return self.n_heads if self.n_kv_heads is None else self.n_kv_heads

    def validate(self) -> None:
        if self.attn_impl not in ATTN_IMPLS:
            raise ValueError(
                f"attn_impl {self.attn_impl!r} not in {ATTN_IMPLS}"
            )
        if self.layer_kinds is not None:
            if len(self.layer_kinds) != self.n_layers:
                raise ValueError(
                    f"layer_kinds has {len(self.layer_kinds)} entries for "
                    f"{self.n_layers} layers"
                )
            if self.attn_window is not None:
                raise ValueError(
                    "attn_window is the window of a model whose layers are "
                    "alike; with layer_kinds each kind carries its own"
                )
        for kind in set(self.kinds):
            if kind.ffn not in ("dense", "moe"):
                raise ValueError(f"LayerKind.ffn {kind.ffn!r} not dense/moe")
            if kind.rope and not self.use_rope:
                raise ValueError(
                    "a layer kind with rope needs use_rope=True (False = "
                    "learned positions, nothing rotated)"
                )
            if kind.window is None:
                continue
            if kind.window < 1:
                raise ValueError(
                    f"attn_window must be >= 1, got {kind.window}"
                )
            if not self.causal:
                raise ValueError("attn_window requires causal=True")
            if self.attn_impl not in ("flash", "reference"):
                raise ValueError(
                    "attn_window supports attn_impl 'flash'/'reference' "
                    f"(got {self.attn_impl!r}); window + context parallelism "
                    "is not implemented"
                )
        if self.moe_layers:
            self.moe.validate()
        if self.remat_policy not in (None, "dots"):
            raise ValueError(
                f"remat_policy {self.remat_policy!r} not in (None, 'dots')"
            )
        if self.remat_policy is not None and not self.remat:
            # an inert policy field would read as "remat enabled"
            raise ValueError("remat_policy requires remat=True")
        if self.n_kv_heads is not None and self.n_kv_heads < 1:
            raise ValueError(f"n_kv_heads must be >= 1, got {self.n_kv_heads}")
        if self.n_heads % self.kv_heads:
            raise ValueError(
                f"n_heads {self.n_heads} must be a multiple of n_kv_heads "
                f"{self.kv_heads}"
            )
        if self.attn_impl == "ring" and not self.use_rope and self.causal:
            pass  # fine; just unusual


# --------------------------------------------------------------------------- #
# building blocks
# --------------------------------------------------------------------------- #

def _act_constraint(x: jax.Array, *, seq_dim: int = 1) -> jax.Array:
    """(batch, seq, d) activations: batch over data+fsdp, seq over seq."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or Axis.DATA not in mesh.axis_names:
        return x
    if not {Axis.FSDP, Axis.SEQ} <= set(mesh.axis_names):
        # not `build_mesh`'s mesh, which lays every axis (a serving
        # engine's ("data", "model"), traced with it in force): no hint
        return x
    spec = [None] * x.ndim
    spec[0] = (Axis.DATA, Axis.FSDP)
    spec[seq_dim] = Axis.SEQ
    return jax.lax.with_sharding_constraint(x, P(*spec))


class Embedding(nn.Module):
    """Token embedding with a choice of lookup implementation.

    Param path matches ``nn.Embed`` ("embedding", same default init), so
    checkpoints and sharding rules are interchangeable. ``impl="onehot"``
    trades a gather for an MXU one-hot contraction — required for clean
    SPMD partitioning when the table is sharded P(model, fsdp); see
    ``TransformerConfig.embed_impl``.
    """

    vocab_size: int
    features: int
    dtype: Any = jnp.float32
    impl: str = "gather"

    @nn.compact
    def __call__(self, tokens: jax.Array) -> jax.Array:
        table = self.param(
            "embedding",
            nn.initializers.variance_scaling(1.0, "fan_in", "normal", out_axis=0),
            (self.vocab_size, self.features),
        )
        if self.impl == "onehot":
            oh = jax.nn.one_hot(tokens, self.vocab_size, dtype=self.dtype)
            return oh @ table.astype(self.dtype)
        return jnp.take(table, tokens, axis=0).astype(self.dtype)


def rope(x: jax.Array, positions: jax.Array, *, base: float = 10_000.0) -> jax.Array:
    """Rotary embeddings; x: (B, H, S, D), positions: (B, S)."""
    d = x.shape[-1]
    half = d // 2
    freq = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[:, None, :, None].astype(jnp.float32) * freq  # (B,1,S,half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + self.eps)
        return (y * scale).astype(x.dtype)


def _grouped_cache_attention(q, K, V, mask, groups):
    """Cache-side attention in grouped (GQA) form: q (B, H, S, D) against
    an Hkv-head cache view K/V (B, Hkv, T, D) with mask (B, S, T). q is
    reshaped (B, Hkv, g, S, D) so the repeated n_heads view of the whole
    cache is never materialized (it would be a 2x-of-the-cache transient
    on EVERY decode step)."""
    B, H, S, D = q.shape
    Hkv = K.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.float32(D))
    qg = q.reshape(B, Hkv, groups, S, D)
    scores = (
        jnp.einsum(
            "bhgsd,bhtd->bhgst",
            qg.astype(jnp.float32),
            K.astype(jnp.float32),
        )
        * scale
    )
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(V.dtype)
    o = jnp.einsum("bhgst,bhtd->bhgsd", probs, V)
    return o.reshape(B, H, S, D)


#: the longest query span the paged branch reads through the Pallas
#: kernel when the call holds more than one row — the decode step (1) and
#: the speculative verify span (K + 1) of ``max_batch`` rows. Measured on
#: a v5e at `mistral-7b_gen-closed`'s shape, kernel against gather
#: (PERF.md section 6, PR 33): spans 1 to 16 of 32 rows read 3.5 to 9
#: times faster through the kernel — the gather reads every row's share
#: of the widest row's window, which mostly does not exist.
PAGED_KERNEL_MAX_SPAN = 16
#: the same for a call of one row — an engine of one row, or a short
#: prefill piece. One row's window is its own, so gathering it wastes
#: nothing: at 480 and 1,000 keys the two read within 15 % of each other
#: up to 8 queries (a table of one page: the kernel, 1.5 to 3.6 times), at
#: 16 the gather is 1.2 to 1.35 times faster and at 32 and over 1.7 to 3
#: times. What a long span pays for in the gather is not the window but
#: the float32 scores over it: from ``PAGED_FLASH_MIN_SPAN`` on the
#: gathered window goes through the flash forward kernel instead.
PAGED_KERNEL_MAX_SPAN_ONE_ROW = 8
#: the shortest span whose gathered window is read by the flash forward
#: kernel (`ops/flash_attention.py::flash_attention_span`), and the
#: granule a longer one has to be a multiple of: the kernel's q block is
#: a whole number of 128-row lane tiles. An engine's prefill piece is 512
#: or 1,024 tokens; spans of 17 to 127, and lengths off the granule, keep
#: `paged_gather_attention`. Measured on a v5e, one row of 32 heads
#: (PERF.md section 6, PR 35): from 67 MB of float32 scores on (512
#: queries x 1,024 keys) the kernel reads 2.4 to 6 times faster; under
#: about 33 MB (512 x 512) XLA keeps the scores on the chip and the
#: gather is 0.01 to 0.025 ms a layer ahead — the rule stays span alone.
PAGED_FLASH_MIN_SPAN = 128


def _kernels_usable(cfg: TransformerConfig) -> bool:
    """Whether a Pallas kernel can run where this call is traced: the
    backend is a TPU (or the configuration asks for the interpreter,
    which is how the CPU suites run one) and no mesh is in force (a
    Mosaic kernel is not partitioned automatically)."""
    return (
        (cfg.interpret_kernels or jax.default_backend() == "tpu")
        and jax.sharding.get_abstract_mesh().empty
    )


def paged_kernel_read(cfg: TransformerConfig, rows: int, span: int) -> bool:
    """Whether the paged branch reads K and V through the Pallas paged
    kernel (`ops/paged_attention.py`): decided by what the code can
    observe. The kernel where one can run (:func:`_kernels_usable`) and
    the call is a decode or verify shape: a short span of several rows,
    or a shorter one of one row. The engine asks the same question for
    its counter (`stats["decode_chunks_kernel_read"]`)."""
    limit = PAGED_KERNEL_MAX_SPAN if rows > 1 else PAGED_KERNEL_MAX_SPAN_ONE_ROW
    return span <= limit and _kernels_usable(cfg)


def paged_flash_read(cfg: TransformerConfig, span: int) -> bool:
    """Whether the paged branch gathers the rows' windows and attends
    through the flash forward kernel (:func:`paged_flash_attention`): a
    prefill piece's shape — a span of whole q blocks, too long for the
    paged kernel — where a kernel can run. Everything else gathers and
    attends in XLA (:func:`paged_gather_attention`). The engine asks the
    same question for `stats["prefill_pieces_flash_read"]`."""
    return span % PAGED_FLASH_MIN_SPAN == 0 and _kernels_usable(cfg)


def _gathered_window(cache, page_table, positions, *, page_size, window,
                     whole_blocks=False):
    """Each row's keys and values out of the token-major pool ``cache``
    (a layer's ``k`` / ``v``, with ``k_scale`` / ``v_scale`` when int8,
    dequantized here): ``(K, V, first)`` with K and V ``(B, Hkv, W, D)``
    and ``first`` the position of each row's key 0 (None: position 0).
    W is the table's width x page size — a row's first W logical tokens —
    unless the layer has a window whose reach over the span is narrower:
    then only the pages that hold the ``window + S - 1`` keys ending at
    the span's last query are gathered, from the page the first one lies
    on (positions (B, S) contiguous along S, as every engine caller's
    are), so a prefill piece far into a long prompt reads what its window
    holds, not the prompt. ``whole_blocks``: the gathered pages are made
    up to a width of whole kv blocks (`flash_tuning.span_kv_block`) with
    the scratch page (0); the keys added lie after the row's last query."""
    B, S = positions.shape
    P = page_size
    Hkv, D = cache["k"].shape[1:]
    pages = page_table.shape[1]
    reach = None if window is None else -(-(window + S - 1) // P) + 1
    first = None          # the first page gathered, where not the table's
    n = pages
    if reach is not None and reach < pages:
        n = reach
        first = jnp.minimum(
            jnp.maximum(positions[:, 0] - window + 1, 0) // P, pages - reach
        )                                                          # (B,)
        page_table = jnp.take_along_axis(
            page_table, first[:, None] + jnp.arange(reach)[None, :], axis=1
        )
    if whole_blocks:
        kv_block = span_kv_block(n * P)
        step = kv_block // math.gcd(P, kv_block)    # pages a whole block
        page_table = jnp.pad(page_table, ((0, 0), (0, -n % step)))
    W = page_table.shape[1] * P
    j = jnp.arange(W)
    flat_r = (
        page_table[:, j // P] * P + (j % P)[None, :]
    ).reshape(-1)                                              # (B*W,)
    Kg = cache["k"][flat_r].reshape(B, W, Hkv, D).transpose(0, 2, 1, 3)
    Vg = cache["v"][flat_r].reshape(B, W, Hkv, D).transpose(0, 2, 1, 3)
    if "k_scale" in cache:
        # dequantize with the SAME broadcast multiply the kernel uses,
        # so gather/kernel parity holds
        Ksg = cache["k_scale"][:, flat_r].reshape(Hkv, B, W).transpose(1, 0, 2)
        Vsg = cache["v_scale"][:, flat_r].reshape(Hkv, B, W).transpose(1, 0, 2)
        Kg = dequantize_kv(Kg, Ksg)
        Vg = dequantize_kv(Vg, Vsg)
    return Kg, Vg, None if first is None else first * P


def paged_gather_attention(q, cache, page_table, positions, *, page_size,
                           window):
    """The paged branch's XLA read: gather each row's window
    (:func:`_gathered_window`), mask by position and run grouped
    attention over all of it, the scores a float32 array ``(B, Hkv,
    groups, S, W)``. q (B, H, S, D), positions (B, S). What a CPU without
    the interpreter, a mesh, and spans the kernels do not take (17 to
    127, or off the flash kernel's granule) read through."""
    Kg, Vg, first = _gathered_window(
        cache, page_table, positions, page_size=page_size, window=window
    )
    kpos = jnp.arange(Kg.shape[2])[None, None, :]   # the keys' positions
    if first is not None:
        kpos = kpos + first[:, None, None]
    mask = kpos <= positions[:, :, None]                       # (B,S,W)
    if window is not None:
        mask &= kpos > positions[:, :, None] - window
    return _grouped_cache_attention(q, Kg, Vg, mask, q.shape[1] // Kg.shape[1])


def paged_flash_attention(q, cache, page_table, positions, *, page_size,
                          window, interpret=False):
    """A prefill piece's read: gather each row's window as
    :func:`paged_gather_attention` does — a few MB — and attend through
    the flash forward kernel, block by block with the running max and
    denominator in VMEM: no score array is written. The queries' offset
    among the gathered keys (the span's first position less the first
    gathered key's) rides into the kernel as a scalar; the causal and
    window masks are the same arithmetic on positions. The gathered
    width is made up to whole kv blocks (`flash_tuning.span_kv_block`)
    so that the geometry rule tiles every table width alike; the kernel
    skips the blocks added."""
    Kg, Vg, first = _gathered_window(
        cache, page_table, positions, page_size=page_size, window=window,
        whole_blocks=True,
    )
    q_offset = positions[:, 0] if first is None else positions[:, 0] - first
    # an int8 pool comes back dequantized to float32: the kernel's
    # operands share one type
    ctype = jnp.promote_types(q.dtype, Kg.dtype)
    return flash_attention_span(
        q.astype(ctype), Kg.astype(ctype), Vg.astype(ctype), q_offset,
        window=window, interpret=interpret,
    ).astype(q.dtype)


class Attention(nn.Module):
    cfg: TransformerConfig
    #: this layer's own window and rotation
    kind: LayerKind

    @nn.compact
    def __call__(
        self,
        x,
        positions,
        segment_ids=None,
        layer_cache=None,
        cache_index=None,
        kv_mask=None,
        page_table=None,
        page_size=None,
        page_write_ok=None,
        kv_quant="none",
    ):
        cfg, kind = self.cfg, self.kind
        window = kind.window
        B, S, _ = x.shape
        H, D = cfg.n_heads, cfg.head_dim
        Hkv = cfg.kv_heads
        groups = H // Hkv
        dense = lambda name, nh: nn.Dense(
            nh * D, use_bias=False, dtype=cfg.dtype, name=name
        )
        # per head over its width, before the heads move in front
        head_norm = (
            (lambda name, t: RMSNorm(cfg.norm_eps, name=name)(t))
            if cfg.qk_norm else (lambda name, t: t)
        )
        q = head_norm(
            "q_norm", dense("q_proj", H)(x).reshape(B, S, H, D)
        ).transpose(0, 2, 1, 3)
        k = head_norm(
            "k_norm", dense("k_proj", Hkv)(x).reshape(B, S, Hkv, D)
        ).transpose(0, 2, 1, 3)
        v = dense("v_proj", Hkv)(x).reshape(B, S, Hkv, D).transpose(0, 2, 1, 3)
        if kind.rope:
            q, k = rope(q, positions), rope(k, positions)
        # GQA: the CACHE and projections hold Hkv heads (the memory bill);
        # attention itself sees the repeated view
        expand = (
            (lambda t: jnp.repeat(t, groups, axis=1)) if groups > 1
            else (lambda t: t)
        )

        new_cache = None
        if page_table is not None:
            # PAGED decode/prefill (serve/paging.py): the cache is one flat
            # token axis per layer, TOKEN-MAJOR — (pool_tokens, Hkv, D) —
            # and row b's logical token j lives at index
            # table[b, j//P]*P + j%P of axis 0. The scatter and the gather
            # below index that axis, and the TPU compiler wants an indexed
            # axis outermost: stored (Hkv, pool_tokens, D) it re-laid every
            # layer's K and V out on entry to and exit from each program
            # (64 pool-sized copies per program at 16 layers; none now —
            # tests/test_tpu_compile.py counts them). Because a row's
            # token space is CONTIGUOUS (no quantized gen gap), the
            # causal + sliding-window mask is just arithmetic on positions;
            # no kv_mask operand exists in this mode.
            P = page_size
            # scatter this call's keys/values into the pool. Pad positions
            # and dead rows route to the scratch page (0) via page_write_ok.
            wpage = jnp.take_along_axis(page_table, positions // P, axis=1)
            flat_w = wpage * P + positions % P                    # (B, S)
            if page_write_ok is not None:
                # scratch slots: distinct per (b,s) within the page where
                # possible, but collisions are harmless — never read
                scratch = (
                    jnp.arange(B * S, dtype=flat_w.dtype).reshape(B, S) % P
                )
                flat_w = jnp.where(page_write_ok, flat_w, scratch)
            idx = flat_w.reshape(-1)
            if kv_quant == "int8":
                # quantize-on-write: per-token-per-head symmetric int8
                # codes + f32 scales ride the same scatter indices (see
                # ops/paged_attention.py for why NOT per-page scales)
                kq, ks = quantize_kv(k)                # codes (B,Hkv,S,D)
                vq, vs = quantize_kv(v)                # scales (B,Hkv,S)
                K = layer_cache["k"].at[idx].set(
                    kq.transpose(0, 2, 1, 3).reshape(B * S, Hkv, D)
                )
                V = layer_cache["v"].at[idx].set(
                    vq.transpose(0, 2, 1, 3).reshape(B * S, Hkv, D)
                )
                Ks = layer_cache["k_scale"].at[:, idx].set(
                    ks.transpose(1, 0, 2).reshape(Hkv, B * S)
                )
                Vs = layer_cache["v_scale"].at[:, idx].set(
                    vs.transpose(1, 0, 2).reshape(Hkv, B * S)
                )
                new_cache = {"k": K, "v": V, "k_scale": Ks, "v_scale": Vs}
                # quantization-error telemetry: a no-op (XLA-dead) unless
                # the caller requests mutable=["quant_stats"] — the engine
                # does so only in its suffix-prefill program
                kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
                err = (
                    jnp.sum(jnp.abs(dequantize_kv(kq, ks) - kf))
                    + jnp.sum(jnp.abs(dequantize_kv(vq, vs) - vf))
                )
                den = jnp.sum(jnp.abs(kf)) + jnp.sum(jnp.abs(vf))
                self.sow("quant_stats", "kv_quant_err", jnp.stack([err, den]))
            elif kv_quant == "none":
                K = layer_cache["k"].at[idx].set(
                    k.astype(layer_cache["k"].dtype)
                    .transpose(0, 2, 1, 3).reshape(B * S, Hkv, D)
                )
                V = layer_cache["v"].at[idx].set(
                    v.astype(layer_cache["v"].dtype)
                    .transpose(0, 2, 1, 3).reshape(B * S, Hkv, D)
                )
                new_cache = {"k": K, "v": V}
            else:
                raise ValueError(f"unknown kv_quant {kv_quant!r}")
            if paged_kernel_read(cfg, B, S):
                # Pallas kernel read: the block table rides the grid as a
                # scalar-prefetch operand and only the pages a row holds
                # leave HBM, once, in the pool's own layout. Assumes
                # contiguous span positions (positions[b] ==
                # positions[b, 0] + arange(S)), which holds for every
                # engine caller — decode steps, the speculative verify
                # span, and chunked-prefill pieces.
                o = paged_attention(
                    q,
                    new_cache["k"],
                    new_cache["v"],
                    page_table,
                    positions[:, 0],
                    page_size=P,
                    window=window,
                    k_scale=new_cache.get("k_scale"),
                    v_scale=new_cache.get("v_scale"),
                    interpret=cfg.interpret_kernels,
                )
            elif paged_flash_read(cfg, S):
                # a prefill piece: the rows' windows gathered, then the
                # flash forward kernel over them (same assumption on the
                # positions)
                o = paged_flash_attention(
                    q, new_cache, page_table, positions, page_size=P,
                    window=window, interpret=cfg.interpret_kernels,
                )
            else:
                o = paged_gather_attention(
                    q, new_cache, page_table, positions, page_size=P,
                    window=window,
                )
        elif layer_cache is not None:
            # Autoregressive decode path (SURVEY.md §2.2 "vLLM backend"
            # analog): keys/values accumulate in an explicit functional
            # cache — (B, H, max_len, D) — threaded through apply(), never
            # flax mutable state. Already-roped keys are cached, so decode
            # steps pay one GEMV against the cache, not a re-prefill.
            if getattr(cache_index, "ndim", 0) == 1:
                # PER-ROW slots (B,): continuous batching writes each row at
                # its own progress point (rows admitted at different times)
                upd = lambda c, new, i: jax.lax.dynamic_update_slice(
                    c, new, (0, i, 0)
                )
                K = jax.vmap(upd)(
                    layer_cache["k"], k.astype(layer_cache["k"].dtype),
                    cache_index,
                )
                V = jax.vmap(upd)(
                    layer_cache["v"], v.astype(layer_cache["v"].dtype),
                    cache_index,
                )
            else:
                K = jax.lax.dynamic_update_slice(
                    layer_cache["k"], k.astype(layer_cache["k"].dtype),
                    (0, 0, cache_index, 0),
                )
                V = jax.lax.dynamic_update_slice(
                    layer_cache["v"], v.astype(layer_cache["v"].dtype),
                    (0, 0, cache_index, 0),
                )
            new_cache = {"k": K, "v": V}
            T = K.shape[2]
            kpos = jnp.arange(T)
            if kv_mask is None:
                # default: causal over absolute slots (prefill) — here slot
                # index == token position, so the sliding window (if any)
                # applies directly: key slot must be within the last
                # attn_window positions of the query
                if getattr(cache_index, "ndim", 0) == 1:
                    qpos = cache_index[:, None] + jnp.arange(S)[None, :]
                    mask = kpos[None, None, :] <= qpos[:, :, None]  # (B,S,T)
                    if window is not None:
                        mask &= kpos[None, None, :] > (
                            qpos[:, :, None] - window
                        )
                else:
                    qpos = cache_index + jnp.arange(S)
                    mask = kpos[None, :] <= qpos[:, None]
                    if window is not None:
                        mask &= kpos[None, :] > qpos[:, None] - window
                    mask = jnp.broadcast_to(mask[None, :, :], (B, S, T))
            else:
                # caller-supplied slot mask: slot index need NOT equal token
                # position (continuous-batching gen regions start at a
                # quantized slot), so the window can only be applied by the
                # caller, who owns the slot→position mapping. generate.py
                # does (decode_kv_mask); anything else must too.
                # (B, T) masks every query position the same way (classic
                # one-token decode); (B, S, T) gives each query its own
                # slot bound — a multi-token step where query j must not
                # see the span's later keys (no caller since the engine
                # serves from the paged pool only: ROADMAP D4).
                kvm = kv_mask if kv_mask.ndim == 3 else kv_mask[:, None, :]
                mask = jnp.broadcast_to(kvm, (B, S, T))
            o = _grouped_cache_attention(q, K, V, mask, groups)
        else:
            o = dispatch_attention(
                q, expand(k), expand(v), cfg, segment_ids=segment_ids,
                window=window,
            )

        o = o.transpose(0, 2, 1, 3).reshape(B, S, H * D)
        if cfg.attn_gate:
            o = o * nn.sigmoid(dense("gate_proj", H)(x))
        out = nn.Dense(
            cfg.d_model, use_bias=False, dtype=cfg.dtype, name="o_proj"
        )(o)
        if layer_cache is not None:
            return out, new_cache
        return out


def dispatch_attention(
    q, k, v, cfg: TransformerConfig, *, segment_ids=None, window=None
):
    """Route to the configured attention strategy. q/k/v: (B, H, S, D);
    ``window``: the calling layer's own."""
    mesh = jax.sharding.get_abstract_mesh()
    kw = dict(
        causal=cfg.causal,
        block_q=cfg.attn_block_q,
        block_k=cfg.attn_block_k,
        interpret=cfg.interpret_kernels,
    )
    if cfg.attn_impl == "reference" or (
        cfg.attn_impl == "flash" and mesh.empty
    ):
        if cfg.attn_impl == "reference":
            return reference_attention(
                q, k, v, causal=cfg.causal, window=window,
                q_segment_ids=segment_ids, kv_segment_ids=segment_ids,
            )
        return flash_attention(
            q, k, v, q_segment_ids=segment_ids, kv_segment_ids=segment_ids,
            window=window, **kw,
        )
    if mesh.empty:
        raise ValueError(
            f"attn_impl {cfg.attn_impl!r} needs a mesh context (jax.set_mesh)"
        )

    spec = P((Axis.DATA, Axis.FSDP), Axis.MODEL, Axis.SEQ, None)
    seg_spec = P((Axis.DATA, Axis.FSDP), Axis.SEQ)
    # unpacked batches must not pay the seg machinery (per-tile mask loads,
    # an extra ring ppermute per hop, the ulysses all_gather): the dummy
    # zeros below exist only to give shard_map a concrete operand
    has_seg = segment_ids is not None

    if cfg.attn_impl == "flash":
        def local(q, k, v, seg):
            seg = seg if has_seg else None
            return flash_attention(
                q, k, v, window=window,
                q_segment_ids=seg, kv_segment_ids=seg, **kw,
            )
    elif cfg.attn_impl == "ring":
        def local(q, k, v, seg):
            return ring_attention_local(
                q, k, v, axis_name=Axis.SEQ, causal=cfg.causal,
                segment_ids=seg if has_seg else None,
                block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                interpret=cfg.interpret_kernels,
            )
    else:  # ulysses
        def local(q, k, v, seg):
            return ulysses_attention_local(
                q, k, v, axis_name=Axis.SEQ, causal=cfg.causal,
                segment_ids=seg if has_seg else None,
                block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                interpret=cfg.interpret_kernels,
            )

    if segment_ids is None:
        segment_ids = jnp.zeros(q.shape[:1] + q.shape[2:3], jnp.int32)
    if cfg.attn_impl == "flash" and mesh.shape.get(Axis.SEQ, 1) > 1:
        raise ValueError(
            "attn_impl='flash' cannot shard the seq axis; use 'ring' or "
            "'ulysses' for sequence parallelism"
        )
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec, seg_spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v, segment_ids)


class Mlp(nn.Module):
    cfg: TransformerConfig
    d_ff: int | None = None          # None = cfg.d_ff

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d_ff = self.d_ff or cfg.d_ff
        up = nn.Dense(d_ff, use_bias=False, dtype=cfg.dtype, name="up_proj")(x)
        gate = nn.Dense(d_ff, use_bias=False, dtype=cfg.dtype, name="gate_proj")(x)
        return nn.Dense(
            cfg.d_model, use_bias=False, dtype=cfg.dtype, name="down_proj"
        )(nn.silu(gate) * up)


class _Kernel(nn.Module):
    """One ``kernel`` leaf under a scope of its own — a stack of experts
    ``(E, in, out)`` or a router ``(in, E)`` — so that it is named, laid
    out and seeded like a ``Dense``'s; ``bias``: a zero vector beside it."""

    shape: tuple[int, ...]
    bias: bool = False

    @nn.compact
    def __call__(self):
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(
                in_axis=-2, out_axis=-1,
                batch_axis=tuple(range(len(self.shape) - 2)),
            ),
            self.shape,
        )
        if not self.bias:
            return kernel
        return kernel, self.param(
            "bias", nn.initializers.zeros, self.shape[-1:]
        )


class Experts(nn.Module):
    """An expert layer under ``cfg.moe``'s rule. With a capacity it is the
    training path (`parallel.expert.moe_ffn`: dense dispatch over the
    ``expert`` axis, tokens past an expert's capacity dropped); without
    one it is dropless (`dropless_moe_ffn`: no token dropped, a row's
    result independent of its neighbours — what a continuous batch
    needs). ``live`` marks the tokens that count (pad slots and dead rows
    do not) for the routing counters sown into ``moe_stats``."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x2d, live=None):
        cfg, moe = self.cfg, self.cfg.moe
        d, f, held = cfg.d_model, moe.expert_dim, moe.held
        router = _Kernel(
            (d, moe.num_experts), bias=moe.select_bias, name="router"
        )()
        router, bias = router if moe.select_bias else (router, None)
        gate = (
            _Kernel((held, d, f), name="gate_proj")()
            if moe.expert_form == "gated_silu" else None
        )
        up = _Kernel((held, d, f), name="up_proj")()
        down = _Kernel((held, f, d), name="down_proj")()
        if moe.capacity_factor is None:
            out, counts = dropless_moe_ffn(
                x2d, router, bias, gate, up, down, moe, live=live,
                interpret=cfg.interpret_kernels,
            )
            self.sow("moe_stats", "assignments", counts)
        else:
            out, aux, _ = moe_ffn(x2d, router, up, down, moe)
            self.sow("losses", "moe_aux", aux)
        if moe.shared_experts:
            out = out + Mlp(
                cfg, d_ff=moe.shared_experts * f, name="shared"
            )(x2d)
        return out


class Block(nn.Module):
    cfg: TransformerConfig
    kind: LayerKind

    @nn.compact
    def __call__(
        self,
        x,
        positions,
        segment_ids=None,
        layer_cache=None,
        cache_index=None,
        kv_mask=None,
        page_table=None,
        page_size=None,
        page_write_ok=None,
        kv_quant="none",
    ):
        cfg, kind = self.cfg, self.kind
        norm = lambda name: RMSNorm(cfg.norm_eps, name=name)
        new_cache = None
        attn_in = norm("ln1")(x)
        if layer_cache is not None:
            h, new_cache = Attention(cfg, kind, name="attn")(
                attn_in, positions, segment_ids,
                layer_cache=layer_cache, cache_index=cache_index,
                kv_mask=kv_mask, page_table=page_table,
                page_size=page_size, page_write_ok=page_write_ok,
                kv_quant=kv_quant,
            )
        else:
            h = Attention(cfg, kind, name="attn")(attn_in, positions, segment_ids)
        if cfg.sandwich_norm:
            h = norm("ln1_post")(h)
        x = _act_constraint(x + h)
        y = norm("ln2")(x)
        if kind.ffn == "moe":
            B, S, d = y.shape
            live = None if page_write_ok is None else page_write_ok.reshape(B * S)
            out = Experts(cfg, name="experts")(y.reshape(B * S, d), live)
            y = out.reshape(B, S, d)
        else:
            y = Mlp(cfg, d_ff=kind.d_ff, name="mlp")(y)
        if cfg.sandwich_norm:
            y = norm("ln2_post")(y)
        out = _act_constraint(x + y)
        if layer_cache is not None:
            return out, new_cache
        return out


class TransformerLM(nn.Module):
    """Decoder LM (causal=True) or encoder (causal=False)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(
        self,
        tokens,
        *,
        segment_ids=None,
        positions=None,
        cache=None,
        cache_index=None,
        kv_mask=None,
        page_table=None,
        page_size=None,
        page_write_ok=None,
        kv_quant="none",
        logit_positions=None,
    ):
        """Training/scoring: ``(tokens) -> logits``. Autoregressive serving:
        pass ``cache`` (from :func:`init_kv_cache`) + ``cache_index`` →
        ``(logits, new_cache)``; prefill writes slots [idx, idx+S), decode
        steps pass S=1. ``kv_mask`` (B, max_len) marks which cache slots a
        query may attend (ragged-prompt batches exclude padding slots).
        Paged serving (serve/paging.py) instead passes a pooled cache from
        :func:`init_paged_kv_cache` + ``page_table``/``page_size``/
        ``page_write_ok`` and explicit ``positions``; masking is derived
        from positions in-branch (kv_mask unused). ``logit_positions`` (B, K):
        the indices along S whose logits are wanted — the hidden state is
        gathered there before the head, so logits are (B, K, vocab) and the
        head's product is K rows, not S (a prefill piece wants one)."""
        cfg = self.cfg
        cfg.validate()
        B, S = tokens.shape
        if positions is None:
            start = 0 if cache_index is None else cache_index
            positions = jnp.broadcast_to(start + jnp.arange(S), (B, S))
        x = Embedding(
            cfg.vocab_size, cfg.d_model,
            dtype=cfg.dtype, impl=cfg.embed_impl, name="embed",
        )(tokens)
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.dtype)
        if not cfg.use_rope:
            pos_emb = self.param(
                "pos_embedding",
                nn.initializers.normal(0.02),
                (cfg.max_seq_len, cfg.d_model),
            )
            x = x + jnp.take(pos_emb, positions, axis=0).astype(cfg.dtype)
        x = _act_constraint(x)

        policy = (
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            if cfg.remat_policy == "dots" else None
        )
        BlockCls = nn.remat(Block, policy=policy) if cfg.remat else Block
        new_cache = {} if cache is not None else None
        for i in range(cfg.n_layers):
            block = BlockCls(cfg, cfg.kind(i), name=f"layers_{i}")
            if cache is not None:
                x, new_cache[f"layers_{i}"] = block(
                    x, positions, segment_ids,
                    layer_cache=cache[f"layers_{i}"],
                    cache_index=cache_index,
                    kv_mask=kv_mask,
                    page_table=page_table,
                    page_size=page_size,
                    page_write_ok=page_write_ok,
                    kv_quant=kv_quant,
                )
            else:
                x = block(x, positions, segment_ids)
        if logit_positions is not None:
            with jax.named_scope(HEAD):
                x = jnp.take_along_axis(x, logit_positions[:, :, None], axis=1)
        x = RMSNorm(cfg.norm_eps, name="ln_f")(x)
        logits = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=jnp.float32, name="unembed"
        )(x)
        if cache is not None:
            return logits, new_cache
        return logits


def init_kv_cache(
    cfg: TransformerConfig, batch: int, max_len: int, dtype: Any | None = None
) -> dict:
    """Zeroed decode cache: one (B, kv_heads, max_len, head_dim) K and V per
    layer — GQA configs pay for kv_heads, not n_heads."""
    dtype = dtype or cfg.dtype
    shape = (batch, cfg.kv_heads, max_len, cfg.head_dim)
    return {
        f"layers_{i}": {
            "k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)
        }
        for i in range(cfg.n_layers)
    }


def init_paged_kv_cache(
    cfg: TransformerConfig,
    pool_tokens: int,
    dtype: Any | None = None,
    kv_quant: str = "none",
) -> dict:
    """Zeroed PAGED decode cache: one flat, token-major (pool_tokens,
    kv_heads, head_dim) K and V per layer, shared by every row through a
    block table (serve/paging.py). HBM is billed per resident TOKEN, not
    per (row × max_seq) rectangle. The token axis is first because every
    program scatters and gathers along it and the TPU compiler keeps an
    indexed axis outermost: in this order the array's default layout is
    the one the programs compute in, so the donated pool passes through
    them with no relayout (heads first, each program copied every layer's
    K and V in and out). ``kv_quant="int8"`` stores int8 codes plus
    per-(kv_head, token) f32 ``k_scale``/``v_scale`` side arrays — the
    pool arrays themselves cost a quarter of f32 (half of bf16), scales
    add ~1/head_dim on top. The scale planes stay (kv_heads,
    pool_tokens): rehearsed in both orders, the compiler keeps an
    8-wide f32 plane heads-major at a program's boundary and token-major
    (padded to 128 lanes) inside it either way, so they are still
    re-laid out per call (PERF.md section 7) and the order that the
    stored prefix entries and the kernel's view use was kept."""
    dtype = dtype or cfg.dtype
    shape = (pool_tokens, cfg.kv_heads, cfg.head_dim)
    scale_shape = (cfg.kv_heads, pool_tokens)
    if kv_quant == "int8":
        return {
            f"layers_{i}": {
                "k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(scale_shape, jnp.float32),
                "v_scale": jnp.zeros(scale_shape, jnp.float32),
            }
            for i in range(cfg.n_layers)
        }
    if kv_quant != "none":
        raise ValueError(f"unknown kv_quant {kv_quant!r}")
    return {
        f"layers_{i}": {
            "k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)
        }
        for i in range(cfg.n_layers)
    }


# --------------------------------------------------------------------------- #
# Trainer plumbing
# --------------------------------------------------------------------------- #

def make_init_fn(model: TransformerLM, seq_len: int, batch_size: int = 1):
    """``batch_size`` must be divisible by the mesh's batch partitions when
    the model's attention runs in shard_map (pass
    ``MeshSpec.batch_partitions``)."""

    def init_params(rng):
        dummy = jnp.zeros((batch_size, seq_len), jnp.int32)
        return model.init(rng, dummy)["params"]

    return init_params


def make_loss_fn(model: TransformerLM):
    """(params, {"inputs","targets"}, rng) → (loss, metrics). Includes MoE
    aux losses sown by Experts blocks."""
    import optax

    def loss_fn(params, batch, rng):
        del rng
        logits, vars_out = model.apply(
            {"params": params}, batch["inputs"], mutable=["losses"]
        )
        with jax.named_scope(LOSS):
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["targets"]
            ).mean()
            metrics = {"lm_loss": loss}
            aux_tree = vars_out.get("losses", {})
            aux = sum(jnp.sum(v) for v in jax.tree_util.tree_leaves(aux_tree))
            if aux_tree:
                loss = loss + aux
                metrics["moe_aux"] = aux
            acc = (jnp.argmax(logits, -1) == batch["targets"]).mean()
            metrics["accuracy"] = acc
        return loss, metrics

    return loss_fn
