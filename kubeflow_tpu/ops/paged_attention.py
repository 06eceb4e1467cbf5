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
operand**, and each page operand's BlockSpec index map picks the page to
stage —

    ``lambda c, k, tbl, lo, hi, pos0, row, blk, *_: (tbl[row[k], blk[k] * n + j], 0, 0)``

— a ``(P, kv_heads, D)`` block: one page of ALL kv heads is one
contiguous piece of the pool — and the pallas_call pipeline itself
performs the HBM→VMEM page fetch (double-buffered against compute), fused
with online-softmax attention over the staged pages. One grid step
stages **n pages**: the pool is passed n times, operand j reading the
table at the step's block ``* n + j`` (`ops/flash_tuning.py`
``select_paged_geometry`` chooses n from the call's shape — a grid of
rows x pages, a page a step, costs more in steps than the pages' bytes).
**The grid is a work list** (:func:`paged_work`): one step per (row,
block of n pages) that holds a page the row's span can see, rows in
order and blocks ascending within a row, worked out in front of the call
and passed beside the table as scalar-prefetch operands (``row``,
``blk`` and whether the step is its row's first or last). A short row takes as
many steps as its pages fill blocks, not the table's width, and a window
layer's row only the blocks its window reaches; a row with no page to
read takes one step, which writes its (zero) output. The grid's length
is that count, a traced value: no step is left over to walk.
The table the kernel walks is resolved in front of the call too: an
entry the row's span cannot see (past its reach, before its window, past
the pages the row holds) names the pool's first page, and a block index
that repeats from one step to the next is not fetched again — so only
the pages a row holds leave HBM, once. The page size is
the engine's (``select_paged_page_size``).

Span support: queries are a contiguous (K+1)-position speculative verify
span (or a prefill piece) starting at per-row position ``pos0[b]`` —
query s sits at absolute position ``pos0[b] + s``.  The in-span causal
mask (query s must not see the span's later keys)
falls out of pure position arithmetic inside the tile mask here
(key position ``page*P + lane`` is visible to query s iff it is ``<=
pos0 + s`` and inside the sliding window), so speculative verify needs
no separate program.  GQA: a grid step holds its pages for every kv
head. For every span the engine sends here (up to 16 queries a row) the
heads are **folded** into one product a page (the page read as ``(P *
kv_heads, D)`` rows, scores masked to the query's own head: the MXU's
time is the K and V rows it takes in, which the fold does not multiply)
and two or four pages share one softmax update; int8 pools — their
scale planes lie heads-major — and a direct call with a longer span loop
over the kv heads, all ``H // kv_heads`` query heads of a group
attending to their kv head's slice in-tile.

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
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops.flash_tuning import PagedTile, select_paged_geometry

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

def _live_pages(pos0, *, page_size, span, window, table_pages, held=None):
    """The page ordinals ``lo <= page <= hi`` (each ``(B,)`` int32) that
    hold a key some query of the span at ``pos0`` may see: not wholly
    past the span's last query (``page * P <= pos0 + S - 1``), inside the
    table and the ``held`` pages the row holds (where given), not wholly
    before the earliest query's window (``page * P + P - 1 >= pos0 -
    window + 1``). A dead row (position -1, or no page held) has none.
    Plain array arithmetic, so a numpy ``pos0`` gives numpy bounds (the
    engine's counters) and a traced one traced bounds (the kernel's):
    the index maps and the kernel read the two numbers, so a page that
    is not computed is not fetched either and neither works the bounds
    out again."""
    hi = ((pos0 + span - 1) // page_size).clip(max=table_pages - 1)
    if held is not None:
        hi = hi.clip(max=held - 1)
    if window is None:
        return hi * 0, hi
    return ((pos0 - window + 1) // page_size).clip(min=0), hi


#: a grid step's flags (``flags`` operand): the first step of its row
#: (the accumulators are reset) and its last (the output is written)
_FIRST, _LAST = 1, 2


class PagedWork(NamedTuple):
    """What one call of the kernel works on: each row's live pages ``lo``
    ... ``hi`` and the blocks of ``pages`` pages they fill (``blocks``, 0
    for a dead row). The grid takes a step per live block and one step
    for each dead row (its output is written)."""

    lo: object
    hi: object
    blocks: object

    @property
    def steps(self):
        """The grid's length: live blocks, and a step a dead row."""
        return (self.blocks + (self.blocks == 0)).sum()

    @property
    def live(self):
        """The grid's steps that stage a page a row reads."""
        return self.blocks.sum()


def paged_work(pos0, *, table_pages, page_size, span, window, pages,
               held=None) -> PagedWork:
    """The kernel's work in numbers, for rows whose spans start at
    ``pos0`` (``(B,)``, numpy or traced) over a table ``table_pages``
    wide, ``pages`` staged a grid step, where each row holds ``held``
    pages (None: the table's width). The kernel's wrapper builds its
    grid from it and the engine counts that grid with it
    (`stats["decode_kernel_steps"]`, `["decode_kernel_steps_live"]`), so
    the count cannot drift from the grid."""
    lo, hi = _live_pages(
        pos0, page_size=page_size, span=span, window=window,
        table_pages=table_pages, held=held,
    )
    return PagedWork(lo, hi, (hi // pages - lo // pages + 1) * (hi >= lo))


def _work_list(work: PagedWork, pages: int, length: int):
    """The grid of :func:`paged_work` as three ``(length,)`` int32 arrays
    — each step's row, its block (pages ``block * n ...``) and its flags
    — and the grid's length. ``length`` exceeds every count the call can
    have (rows x the table's blocks, and one), so the arrays have a shape;
    the entries past the count repeat the last step's row and block with
    no flag, so an index map read one step past the grid names blocks
    already staged."""
    live = work.blocks > 0
    count = jnp.maximum(work.blocks, 1).astype(jnp.int32)
    ends = jnp.cumsum(count)
    steps = ends[-1]
    k = jnp.arange(length, dtype=jnp.int32)
    kk = jnp.minimum(k, steps - 1)
    row = jnp.sum(kk[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    t = kk - (ends[row] - count[row])
    block = jnp.where(live[row], work.lo[row] // pages + t, 0)
    flags = (k < steps) * (_FIRST * (t == 0) + _LAST * (t == count[row] - 1))
    return row, block.astype(jnp.int32), flags.astype(jnp.int32), steps


def _paged_attn_kernel(
    # scalar prefetch (SMEM)
    tbl_ref,    # (B, blocks * n) int32 page table, dead entries 0
    lo_ref,     # (B,) int32 first live page ordinal
    hi_ref,     # (B,) int32 last live page ordinal
    pos0_ref,   # (B,) int32 span start positions
    row_ref,    # (B * blocks + 1,) int32 each grid step's row
    blk_ref,    # (B * blocks + 1,) int32 its block: pages blk * n ... + n - 1
    flag_ref,   # (B * blocks + 1,) int32 _FIRST | _LAST
    # VMEM blocks
    q_ref,      # folded: (1, Hkv*G*S, D); else (1, Hkv, G*S, D)
    *refs,      # n K pages, n V pages (P, Hkv, D) each; when quantized n
                # + n scale blocks (Hkv, 1, 1, P) f32; the output block
                # (q's shape); scratch acc, m, l (f32)
    scale: float | None,
    window: int | None,
    page_size: int,
    kv_heads: int,
    groups: int,
    span: int,
    pages: int,
    quant: bool,
    fold: int,
):
    n = pages
    k_refs, v_refs, refs = refs[:n], refs[n:2 * n], refs[2 * n:]
    if quant:
        ks_refs, vs_refs, refs = refs[:n], refs[n:2 * n], refs[2 * n:]
    o_ref, acc_ref, m_ref, l_ref = refs
    k = pl.program_id(1)
    b, i, flags = row_ref[k], blk_ref[k], flag_ref[k]
    P, Hkv, S = page_size, kv_heads, span
    GS = groups * span

    @pl.when(flags & _FIRST != 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos0 = pos0_ref[b]  # SMEM scalar
    d = q_ref.shape[-1]
    if scale is None:
        mult = 1.0 / jnp.sqrt(jnp.float32(d))  # gather-path spelling
    else:
        mult = jnp.float32(scale)
    # operands go to the MXU in the pool's own type (a bf16 x bf16 product
    # is exact in the f32 it accumulates in); dequantized int8 is f32
    ctype = jnp.float32 if quant else jnp.promote_types(
        q_ref.dtype, k_refs[0].dtype
    )

    def online_update(sl, s, mask, vs):
        """Scores ``s`` (rows, keys) f32 of one or more pages — ``vs``
        their V rows, page by page — into the running max, sum and
        accumulator at ``sl``; masked lanes contribute EXACTLY 0 even
        when the whole tile is masked (exp(s - m_cur) would be exp(0)=1
        garbage at m==NEG_INF)."""
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[sl]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[sl] = l_ref[sl] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        p = p.astype(vs[0].dtype)
        keys = p.shape[-1] // len(vs)
        acc_ref[sl] = acc_ref[sl] * alpha + sum(
            jax.lax.dot(
                p[:, t * keys:(t + 1) * keys], v,
                preferred_element_type=jnp.float32,
            )
            for t, v in enumerate(vs)
        )
        m_ref[sl] = m_cur

    lo, hi = lo_ref[b], hi_ref[b]

    if fold:
        # every kv head in ONE product: a page is read as (P*Hkv, D) rows
        # in (token, head) order — the block as it lies in VMEM — against
        # all Hkv*G*S query rows; a score counts only where the key's
        # head is the query's. The MXU's time is the K and V rows it
        # takes in, which this does not multiply; what it saves is the
        # per-head slicing and Hkv tiny products a page. ``fold`` pages
        # (consecutive ordinals, so consecutive positions) share one
        # softmax update: their products are independent work in one
        # block of straight-line code, where a page under a condition of
        # its own is a chain of dependent steps nothing else fills.
        R, L = Hkv * GS, fold * P * Hkv
        lane = jax.lax.broadcasted_iota(jnp.int32, (R, L), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (R, L), 0)
        same_head = lane % Hkv == row // GS
        tok = lane // Hkv
        qpos = pos0 + row % S
        for j in range(0, n, fold):  # static: the pages this step staged
            first = i * n + j

            @pl.when((first <= hi) & (first + fold - 1 >= lo))
            def _pages(j=j, first=first):
                kpos = first * P + tok
                mask = same_head & (kpos <= qpos)
                if window is not None:
                    mask &= kpos > qpos - window
                q = q_ref[0].astype(ctype)
                s = jnp.concatenate([
                    jax.lax.dot_general(
                        q, k_refs[j + t][...].reshape(P * Hkv, d).astype(ctype),
                        dimension_numbers=(((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    for t in range(fold)
                ], axis=1) * mult  # (R, fold*P*Hkv)
                online_update(slice(None), s, mask, [
                    v_refs[j + t][...].reshape(P * Hkv, d).astype(ctype)
                    for t in range(fold)
                ])
    else:
        # row r of the GS axis is query s = r % S at position pos0 + s;
        # lane j is key position first + j
        tok = jax.lax.broadcasted_iota(jnp.int32, (GS, P), 1)
        qpos = pos0 + (jax.lax.broadcasted_iota(jnp.int32, (GS, P), 0) % S)
        for j in range(n):  # static: the pages this step staged
            page = i * n + j

            @pl.when((page <= hi) & (page >= lo))
            def _page(j=j, page=page):
                kpos = page * P + tok
                mask = kpos <= qpos
                if window is not None:
                    mask &= kpos > qpos - window
                for h in range(Hkv):  # static: one kv head at a time
                    q = q_ref[0, h].astype(ctype)  # (GS, D)
                    k = k_refs[j][:, h, :].astype(ctype)  # (P, D)
                    v = v_refs[j][:, h, :].astype(ctype)
                    if quant:
                        k = k * ks_refs[j][h, 0].T
                        v = v * vs_refs[j][h, 0].T
                    s = jax.lax.dot_general(
                        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ) * mult  # (GS, P)
                    online_update(h, s, mask, [v])

    @pl.when(flags & _LAST != 0)
    def _finish():
        l = l_ref[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


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
    tile: PagedTile | None = None,
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
      page_size: tokens per page.
      window: optional sliding-window width (same semantics as the
        gather path's ``attn_window``).
      scale: score multiplier; defaults to ``1/sqrt(D)`` computed in f32
        exactly like the gather path.
      k_scale / v_scale: ``(kv_heads, pool_tokens)`` f32 per-token
        dequant scales; both or neither.
      interpret: run the Pallas interpreter (CPU-verifiable).
      tile: how many pages one grid step stages and how it computes
        them; ``None`` = ``flash_tuning.select_paged_geometry`` on the
        call's shape.

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
    if tile is None:
        tile = select_paged_geometry(
            table_pages=page_table.shape[1], page_size=page_size,
            kv_heads=Hkv, groups=H // Hkv, span=S, head_dim=D,
            itemsize=k_pool.dtype.itemsize, quant=quant,
        )
    return _paged_attention(
        q, k_pool, v_pool, page_table, pos0, k_scale, v_scale,
        page_size=page_size, window=window, scale=scale,
        interpret=interpret, tile=tile,
    )


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "window", "scale", "interpret", "tile"),
)
def _paged_attention(
    q, k_pool, v_pool, page_table, pos0, k_scale, v_scale, *,
    page_size, window, scale, interpret, tile,
):
    B, H, S, D = q.shape
    T, Hkv, _ = k_pool.shape
    quant = k_scale is not None
    G = H // Hkv
    W = page_table.shape[1]
    n, fold = tile.pages, tile.fold
    blocks = -(-W // n)
    page_table = page_table.astype(jnp.int32)
    # the pages a row holds: its table up to the last entry that names a
    # page of its own (the allocator fills a row's table from the left;
    # a free row's is all scratch)
    ordinal = jnp.arange(blocks * n, dtype=jnp.int32)[None, :]
    held = jnp.max(jnp.where(page_table != 0, ordinal[:, :W] + 1, 0), axis=1)
    work = paged_work(
        pos0.astype(jnp.int32), table_pages=W, page_size=page_size, span=S,
        window=window, pages=n, held=held,
    )
    row, blk, flags, steps = _work_list(work, n, B * blocks + 1)
    lo, hi = work.lo, work.hi
    # the table as the grid walks it: blocks * n columns, an entry the
    # row's span cannot see naming the pool's first page — a block index
    # that repeats from one step to the next is not fetched again, so
    # pages a row does not hold (past its reach, before its window) cost
    # no traffic. A (B, W) integer select in front of the call.
    table = jnp.where(
        (ordinal >= lo[:, None]) & (ordinal <= hi[:, None]),
        jnp.pad(page_table, ((0, 0), (0, blocks * n - W))),
        0,
    )

    kernel = functools.partial(
        _paged_attn_kernel,
        scale=scale,
        window=window,
        page_size=page_size,
        kv_heads=Hkv,
        groups=G,
        span=S,
        pages=n,
        quant=quant,
        fold=fold,
    )

    # fold the GQA group into the span axis: head h = hkv*G + g maps to
    # row g*S + s of the (G*S) query axis for kv head hkv; a folded call
    # takes all Hkv*G*S rows as one axis
    q_shape = (B, Hkv * G * S, D) if fold else (B, Hkv, G * S, D)
    # grid step k works on row[k]: consecutive steps of one row keep its
    # query and output blocks resident, so each is moved once a row
    row_spec = pl.BlockSpec(
        (1,) + q_shape[1:],
        lambda c, k, tbl, lo, hi, pos0, row, *_: (row[k],)
        + (0,) * (len(q_shape) - 1),
    )
    # the block's last two dims equal the pool's, so any kv_heads / D
    # tiles; the page axis (outermost) is the one the table indexes:
    # step k's j-th operand stages the page at table[row, blk * n + j]
    page_specs = [
        pl.BlockSpec(
            (page_size, Hkv, D),
            lambda c, k, tbl, lo, hi, pos0, row, blk, _, j=j: (
                tbl[row[k], blk[k] * n + j], 0, 0
            ),
        )
        for j in range(n)
    ]
    in_specs = [row_spec] + page_specs + page_specs
    operands = [q.reshape(q_shape)] + [k_pool] * n + [v_pool] * n
    if quant:
        # a (kv_heads, page) block over the (kv_heads, pool_tokens)
        # scale array is off Mosaic's (8, 128) tiling for pages under
        # 128; viewed as (kv_heads, pages, 1, page) the block's last two
        # dims equal the array's. The pool layout is untouched; XLA makes
        # the view a relayout of the two scale arrays (4 bytes per token
        # per head) on each call.
        scale_specs = [
            pl.BlockSpec(
                (Hkv, 1, 1, page_size),
                lambda c, k, tbl, lo, hi, pos0, row, blk, _, j=j: (
                    0, tbl[row[k], blk[k] * n + j], 0, 0
                ),
            )
            for j in range(n)
        ]
        in_specs += scale_specs + scale_specs
        views = [
            s.reshape(Hkv, T // page_size, 1, page_size)
            for s in (k_scale, v_scale)
        ]
        operands += [views[0]] * n + [views[1]] * n

    stat_shape = q_shape[1:-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        # the steps under a leading axis of one: on a v5e a grid of one
        # "arbitrary" axis halted the core at a step that reads no page
        # (a dead row's), where the same steps under this axis run
        grid=(1, steps),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM(stat_shape + (D,), jnp.float32),
            pltpu.VMEM(stat_shape + (1,), jnp.float32),
            pltpu.VMEM(stat_shape + (1,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                Hkv * G * S, D, q.dtype.itemsize,
                n * page_size * Hkv * D * k_pool.dtype.itemsize,
            ),
        ),
        interpret=interpret,
        name=paged_kernel_name(page_size, tile, Hkv),
    )(table, lo, hi, pos0.astype(jnp.int32), row, blk, flags, *operands)
    return out.reshape(B, H, S, D)


def paged_kernel_name(page_size: int, tile: PagedTile, kv_heads: int) -> str:
    """The call's name in a device trace: page size, pages a grid step,
    kv heads a step (all of them) and, where products fold them, the
    pages a softmax update takes together."""
    return (
        f"paged_decode_p{page_size}_n{tile.pages}_h{kv_heads}"
        f"{f'_f{tile.fold}' if tile.fold else ''}"
    )


def _vmem_limit(rows: int, d: int, q_bytes: int, staged: int) -> int | None:
    """The page blocks carry every kv head, so a row's queries, output
    (both double-buffered) and f32 accumulators for ALL heads are
    resident at once: ``rows`` = kv_heads x group x span of them, the
    (rows, 1) running max and sum padded to 128 lanes; beside them the
    ``staged`` bytes of K pages a step, again for V, double-buffered. A
    decode step or a verify span is far under the compiler's default
    scoped limit (None leaves it alone); a 512-token prefill piece at 8
    kv heads x 4 x 128 needs ~45 MB of a v5e's 128 MiB, so the limit is
    asked for by size (no engine path sends a piece here — it gathers
    its row's window and attends through the flash forward kernel,
    `models/transformer.py::paged_flash_attention` — but the call is
    public and `tests/test_tpu_compile.py` keeps the piece compiling)."""
    resident = rows * (4 * d * q_bytes + 4 * d + 2 * 4 * 128) + 4 * staged
    return None if resident < (8 << 20) else 2 * resident
