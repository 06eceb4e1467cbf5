"""Pallas paged decode-attention: the vLLM PagedAttention analog, TPU-form.

The engine's paged KV pool (`serve/paging.py`) stores each layer's keys
and values as ONE flat token axis, token-major —
``(pool_tokens, kv_heads, head_dim)`` — and a row's logical token ``j``
lives at flat slot ``table[row, j // P] * P + j % P`` of axis 0.  (Token-
major because the engine's scatters and gathers index that axis and the
TPU compiler keeps an indexed axis outermost: stored heads-first, every
program re-laid the whole pool out on entry and exit —
`models/transformer.py::init_paged_kv_cache`.)  The in-graph read path
gathers the row's whole pow2-bucketed window back into a dense
``(B, H, W, D)`` tensor and runs masked softmax attention on it (XLA
gather; see `models/transformer.py`).  This module is the kernel form of
that read: the block table rides the grid as a **scalar-prefetch
operand**, so each kv grid step's BlockSpec index map picks the page to
stage —

    ``lambda b, i, tbl, pos0: (tbl[b, i], 0, 0)``

— a ``(P, kv_heads, D)`` block: one page of ALL kv heads is one
contiguous piece of the pool, fetched in one grid step — and the
pallas_call pipeline itself performs the HBM→VMEM page fetch
(double-buffered against compute), fused with online-softmax attention
over the staged page.  One kv block == one pool page, which is why the
sweepable "block size" for this kernel IS the engine's ``page_size``
(`ops/flash_tuning.py` ``select_paged_page_size``).

Span support: queries are a contiguous (K+1)-position speculative verify
span (or a prefill piece) starting at per-row position ``pos0[b]`` —
query s sits at absolute position ``pos0[b] + s``.  The in-span causal
mask (query s must not see the span's later keys)
falls out of pure position arithmetic inside the tile mask here
(key position ``i*P + lane`` is visible to query s iff it is ``<=
pos0 + s`` and inside the sliding window), so speculative verify needs
no separate program.  GQA: a grid step holds the page for every kv head
and loops over them; all ``H // kv_heads`` query heads of a group attend
to their kv head's slice in-tile.

int8 KV: ``quantize_kv`` produces per-token-per-head symmetric int8
codes plus an f32 scale per (kv_head, token) vector; the kernel
dequantizes in-register after the page lands in VMEM, so HBM traffic and
pool bytes halve vs bf16 (quarter vs f32).  Per-token scales — not
per-page — because pool pages fill incrementally across decode steps:
a page-granular scale would force lossy requantization of codes already
written by earlier chunks.

Numerics: scores and the softmax accumulate in f32 exactly like the
gather path's f32 einsum; the online rescaling uses the flash-attention
idiom (`ops/flash_attention.py`) with one hardening — masked lanes
contribute exactly 0 via ``where(mask, exp(s - m), 0)`` so a fully
masked page (sliding-window skip, scratch-page read for a dead row)
can never poison the accumulator.  Everything runs under
``interpret=True`` on CPU; the engine matrix pins greedy token streams
byte-identical to the gather path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # matches the gather path's masked-score fill


# --------------------------------------------------------------------- #
# int8 KV quantization helpers (shared by the write scatter and the
# gather-impl read so both dequantize with bit-identical math)
# --------------------------------------------------------------------- #

def quantize_kv(x: jax.Array):
    """Symmetric per-vector int8 quantization over the trailing head_dim.

    ``x`` is ``(..., D)``; returns ``(codes int8 (..., D), scales f32
    (...,))`` with ``codes = clip(round(x / scale), -127, 127)`` and
    ``scale = max(|x|) / 127`` per vector (floored so all-zero vectors
    quantize to zeros with a harmless tiny scale).
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    codes = jnp.clip(jnp.round(xf / scale[..., None]), -127.0, 127.0)
    return codes.astype(jnp.int8), scale


def dequantize_kv(codes: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_kv`: ``codes (..., D) * scale (...,)``."""
    return codes.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


# --------------------------------------------------------------------- #
# kernel
# --------------------------------------------------------------------- #

def _paged_attn_kernel(
    # scalar prefetch (SMEM)
    tbl_ref,    # (B, W) int32 page table
    pos0_ref,   # (B,) int32 span start positions
    # VMEM blocks
    q_ref,      # (1, Hkv, G*S, D) — queries, GQA group folded into the span axis
    k_ref,      # (P, Hkv, D) — the page picked by the index map, every kv head
    v_ref,      # (P, Hkv, D)
    ks_ref,     # (Hkv, 1, 1, P) f32 or None
    vs_ref,     # (Hkv, 1, 1, P) f32 or None
    o_ref,      # (1, Hkv, G*S, D)
    # VMEM scratch
    acc_ref,    # (Hkv, G*S, D) f32
    m_ref,      # (Hkv, G*S, 1) f32
    l_ref,      # (Hkv, G*S, 1) f32
    *,
    scale: float | None,
    window: int | None,
    page_size: int,
    groups: int,
    span: int,
    num_pages: int,
):
    b = pl.program_id(0)
    i = pl.program_id(1)
    P, G, S = page_size, groups, span
    GS = G * S

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos0 = pos0_ref[b]  # SMEM scalar
    first = i * P
    # skip pages wholly past the span's last query...
    run = first <= pos0 + S - 1
    if window is not None:
        # ...and, when windowed, pages wholly before the earliest
        # query's window start
        run = run & (first + P - 1 >= pos0 - window + 1)

    @pl.when(run)
    def _body():
        d = q_ref.shape[-1]
        if scale is None:
            mult = 1.0 / jnp.sqrt(jnp.float32(d))  # gather-path spelling
        else:
            mult = jnp.float32(scale)
        # absolute positions: row r of the GS axis is query s = r % S at
        # position pos0 + s; lane j is key position first + j
        kpos = first + jax.lax.broadcasted_iota(jnp.int32, (GS, P), 1)
        qpos = pos0 + (jax.lax.broadcasted_iota(jnp.int32, (GS, P), 0) % S)
        mask = kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        for h in range(q_ref.shape[1]):  # static: one kv head at a time
            q = q_ref[0, h].astype(jnp.float32)  # (GS, D)
            k = k_ref[:, h, :].astype(jnp.float32)  # (P, D)
            v = v_ref[:, h, :].astype(jnp.float32)
            if ks_ref is not None:
                k = k * ks_ref[h, 0].T
                v = v * vs_ref[h, 0].T
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * mult  # (GS, P)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[h]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # masked lanes contribute EXACTLY 0 even when the whole tile
            # is masked (exp(s - m_cur) would be exp(0)=1 garbage at
            # m==NEG_INF)
            p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
            alpha = jnp.exp(m_prev - m_cur)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32
            )
            m_ref[h] = m_cur

    @pl.when(i == num_pages - 1)
    def _finish():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "window", "scale", "interpret"),
)
def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    pos0: jax.Array,
    *,
    page_size: int,
    window: int | None = None,
    scale: float | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over a paged KV pool, addressed by block table.

    Args:
      q: ``(B, H, S, D)`` queries — a contiguous span of S positions per
        row (S=1 plain decode, S=K+1 speculative verify, S=piece for
        chunked prefill).
      k_pool / v_pool: ``(pool_tokens, kv_heads, D)`` flat token-major
        pools (int8 codes when quantized) — the engine's own order; a
        page of all kv heads is one contiguous block.
      page_table: ``(B, W_pages)`` int32 — page ordinal → pool page.
      pos0: ``(B,)`` int32 — absolute position of each row's first query
        (query s sits at ``pos0 + s``).
      page_size: tokens per page; one kv grid step stages one page of
        every kv head.
      window: optional sliding-window width (same semantics as the
        gather path's ``attn_window``).
      scale: score multiplier; defaults to ``1/sqrt(D)`` computed in f32
        exactly like the gather path.
      k_scale / v_scale: ``(kv_heads, pool_tokens)`` f32 per-token
        dequant scales; both or neither.
      interpret: run the Pallas interpreter (CPU-verifiable).

    Returns ``(B, H, S, D)`` in q's dtype.
    """
    B, H, S, D = q.shape
    T, Hkv, Dk = k_pool.shape
    if Dk != D or v_pool.shape != k_pool.shape:
        raise ValueError(f"pool shapes {k_pool.shape}/{v_pool.shape} vs D={D}")
    if H % Hkv:
        raise ValueError(f"{H} query heads not a multiple of {Hkv} kv heads")
    if T % page_size:
        raise ValueError(f"pool_tokens {T} not a multiple of page {page_size}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    quant = k_scale is not None
    if quant and k_scale.shape != (Hkv, T):
        raise ValueError(f"scale shape {k_scale.shape} != {(Hkv, T)}")
    G = H // Hkv
    W = page_table.shape[1]

    kernel = functools.partial(
        _paged_attn_kernel,
        scale=scale,
        window=window,
        page_size=page_size,
        groups=G,
        span=S,
        num_pages=W,
    )
    if not quant:
        # keep the kernel signature uniform: drop the scale refs
        kernel = functools.partial(_strip_scale_refs, kernel)

    # fold the GQA group into the span axis: head h = hkv*G + g maps to
    # row g*S + s of the (G*S) query axis for kv head hkv
    qg = q.reshape(B, Hkv, G * S, D)

    row_spec = pl.BlockSpec(
        (1, Hkv, G * S, D), lambda b, i, tbl, p0: (b, 0, 0, 0)
    )
    # the block's last two dims equal the pool's, so any kv_heads / D
    # tiles; the page axis (outermost) is the one the table indexes
    page_spec = pl.BlockSpec(
        (page_size, Hkv, D), lambda b, i, tbl, p0: (tbl[b, i], 0, 0)
    )
    in_specs = [row_spec, page_spec, page_spec]
    operands = [qg, k_pool, v_pool]
    if quant:
        # a (kv_heads, page) block over the (kv_heads, pool_tokens)
        # scale array is off Mosaic's (8, 128) tiling for pages under
        # 128; viewed as (kv_heads, pages, 1, page) the block's last two
        # dims equal the array's. The pool layout is untouched; XLA makes
        # the view a relayout of the two scale arrays (4 bytes per token
        # per head) on each call.
        scale_spec = pl.BlockSpec(
            (Hkv, 1, 1, page_size),
            lambda b, i, tbl, p0: (0, tbl[b, i], 0, 0),
        )
        in_specs += [scale_spec, scale_spec]
        operands += [
            s.reshape(Hkv, T // page_size, 1, page_size)
            for s in (k_scale, v_scale)
        ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, W),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G * S, D), jnp.float32),
            pltpu.VMEM((Hkv, G * S, 1), jnp.float32),
            pltpu.VMEM((Hkv, G * S, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G * S, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(Hkv * G * S, D, q.dtype.itemsize),
        ),
        interpret=interpret,
    )(
        page_table.astype(jnp.int32), pos0.astype(jnp.int32), *operands
    )
    return out.reshape(B, H, S, D)


def _vmem_limit(rows: int, d: int, q_bytes: int) -> int | None:
    """The page block carries every kv head, so a row's queries, output
    (both double-buffered) and f32 accumulators for ALL heads are
    resident at once: ``rows`` = kv_heads x group x span of them, the
    (rows, 1) running max and sum padded to 128 lanes. A decode step or a
    verify span is far under the compiler's default scoped limit (None
    leaves it alone); a 512-token prefill piece at 8 kv heads x 4 x 128
    needs ~45 MB of a v5e's 128 MiB, so the limit is asked for by size."""
    resident = rows * (4 * d * q_bytes + 4 * d + 2 * 4 * 128)
    return None if resident < (8 << 20) else 2 * resident


def _strip_scale_refs(kernel, tbl_ref, pos0_ref, q_ref, k_ref, v_ref,
                      o_ref, acc_ref, m_ref, l_ref):
    kernel(tbl_ref, pos0_ref, q_ref, k_ref, v_ref, None, None,
           o_ref, acc_ref, m_ref, l_ref)
