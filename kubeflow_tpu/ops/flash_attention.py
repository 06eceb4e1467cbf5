"""Pallas flash attention (TPU): online-softmax blockwise attention.

The serving-path kernel of the north star (BASELINE config 5: "Pallas
attention kernel for transformer serving") and the inner kernel of ring
attention (SURVEY.md §5.7). Design per the TPU kernel playbook
(/opt/skills/guides/pallas_guide.md):

- grid (batch, head groups, q-blocks, kv-blocks); kv innermost and
  "arbitrary" so the online-softmax accumulator lives in VMEM scratch across
  kv steps. How much one step does is the kernel's ``Tile``
  (``ops/flash_tuning.py``: q rows and kv rows staged, the sub-tile an
  in-kernel loop computes at a time, heads sharing the step), chosen per
  kernel from the call's shape: a step costs about 0.35 us whatever it
  computes, so it stages whole rows where they fit;
- q/k/v blocks staged HBM→VMEM by pallas_call's pipeline; MXU matmuls with
  ``preferred_element_type=f32``; VPU for the softmax algebra;
- causal / windowed sub-tiles that are entirely masked are skipped
  (predicated) and their blocks are not fetched (the index maps clamp to
  the live range); the positional mask is built only on sub-tiles the
  diagonal or the band's edge crosses;
- optional segment ids give block-diagonal masking (serving batches,
  packed sequences);
- per-row statistics (lse, delta) and segment ids travel as lane-dense
  rows and become columns in the kernel;
- backward: Pallas dq and dk/dv kernels (``flash_attention_bwd``) that
  recompute the probabilities blockwise against the saved logsumexp — the
  training path never materializes the S×S matrix. Ring attention reuses
  the same backward entry per ring hop;
- each ``pallas_call`` is named for its kernel and geometry
  (``flash_fwd_q512_k4096_t1024_h1``), which is how a device trace tells
  them apart.

Returns optionally the (max, logsumexp) residuals, which is what lets
``kubeflow_tpu.parallel.ring_attention`` merge partial results across ring
steps.

``flash_attention_span`` is the forward kernel for a serving engine's
prefill piece: a span of queries that starts anywhere among its keys (the
row's window, gathered out of the paged pool). The span's offset is a
traced value, so it rides as a scalar-prefetch operand beside the live kv
blocks it implies, and grouped query heads read their kv head in place.
The training entry, query i at position i, carries no such operand.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeflow_tpu.ops.flash_tuning import (
    LANES,
    Geometry,
    Tile,
    geometry_from_blocks,
    resolve_blocks,
    select_geometry,
    select_span_tile,
)

NEG_INF = -1e30  # large-but-finite: keeps exp() well-defined on fully-masked rows

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def float0_zeros(seg):
    """Symbolic-zero cotangent for an integer segment-id array (or None) —
    the one convention every seg-carrying custom_vjp shares."""
    return None if seg is None else np.zeros(seg.shape, jax.dtypes.float0)


def _col(row):
    """(1, n) lane row → (n, 1) column: a sublane broadcast and one XLU
    transpose per 128 rows. Per-row inputs (lse, delta, segment ids) live
    in HBM as lane-dense rows — a ``(block, 1)`` block of 4-byte rows costs
    a 4 KB tile of DMA per 8 rows — and turn into columns here, once a
    grid step."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T[:, :1]


def _row(col):
    """(n, 1) column → (1, n) lane row (the forward's lse, see _col)."""
    return jnp.broadcast_to(col, (col.shape[0], LANES)).T[:1, :]


def _tile_mask(q0, k0, shape, *, band, window, qseg, kseg, transposed=False):
    """(mask or None) for the score tile whose first q row is ``q0`` and
    first kv row ``k0`` — the ONE place the causal/window/segment masking
    lives; forward and backward kernels must agree or gradients silently
    diverge. ``band``: build the causal (and window) compare — only tiles
    that the diagonal or the band's lower edge crosses need it. ``qseg`` /
    ``kseg`` broadcast against each other to ``shape``. ``transposed``:
    the tile is (kv rows, q columns), as the dkv kernel computes it."""
    mask = None
    if band:
        q_axis, k_axis = (1, 0) if transposed else (0, 1)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, k_axis)
        mask = qpos >= kpos
        if window is not None:
            # sliding window: query attends to keys in
            # [qpos - window + 1, qpos] (Mistral-style local attention)
            mask = mask & (qpos - kpos < window)
    if qseg is not None:
        seg = qseg == kseg
        mask = seg if mask is None else (mask & seg)
    return mask


def _for_tile(body, q0, nq, k0, nk, *, causal, window):
    """Run ``body(band)`` for the score tile of q rows [q0, q0+nq) and kv
    rows [k0, k0+nk): not at all where causality or the window masks the
    whole tile, with ``band=False`` (no positional mask built) where they
    mask none of it, with ``band=True`` on the diagonal and the band's
    lower edge."""
    if not causal:
        body(False)
        return
    q_last, k_last = q0 + nq - 1, k0 + nk - 1
    live = k0 <= q_last
    interior = k_last <= q0
    if window is not None:
        live &= k_last >= q0 - window + 1
        interior &= q_last - k0 < window
    pl.when(live & interior)(lambda: body(False))
    pl.when(live & jnp.logical_not(interior))(lambda: body(True))


def _scores(a, b, *, scale):
    """a·bᵀ in f32 off native-dtype MXU operands; ``scale`` None where it
    was folded into an operand."""
    s = jax.lax.dot_general(
        a, b,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return s if scale is None else s * scale


def _fwd_kernel(
    q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
    out_ref, lse_ref,
    acc_ref, m_ref, l_ref,
    *,
    scale: float,
    fold: bool,
    causal: bool,
    window: int | None,
    tile: Tile,
    num_k_blocks: int,
    q_base=None,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    block_q, block_k, sub, heads = tile

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the block's first query in the keys' coordinates: row i is position
    # i unless the caller's queries start further in (``q_base``)
    q0 = iq * block_q
    if q_base is not None:
        q0 = q0 + q_base
    qseg = None if qseg_ref is None else _col(qseg_ref[0])

    for j in range(block_k // sub):
        ks = slice(j * sub, (j + 1) * sub)
        k0 = ik * block_k + j * sub

        def body(band, ks=ks, k0=k0):
            mask = _tile_mask(
                q0, k0, (block_q, sub), band=band, window=window, qseg=qseg,
                kseg=None if kseg_ref is None else kseg_ref[0, :, ks],
            )
            for h in range(heads):
                # MXU operands stay in the INPUT dtype (bf16 on TPU:
                # full-rate MXU passes; fp32 operands would run it 4-8x
                # slower) — accumulation is f32 via preferred_element_type,
                # and bf16→f32 is exact, so QKᵀ is bit-identical to an
                # upcast-first fp32 matmul. Softmax math is f32. A scale
                # that is a power of two moves onto q exactly.
                q = q_ref[0, h]  # (Bq, D)
                if fold:
                    q = q * scale
                v = v_ref[0, h, ks, :]  # (sub, D)
                s = _scores(
                    q, k_ref[0, h, ks, :], scale=None if fold else scale
                )  # (Bq, sub) f32
                if mask is not None:
                    s = jnp.where(mask, s, NEG_INF)
                m_prev = m_ref[h]  # (Bq, 1)
                m_cur = jnp.maximum(
                    m_prev, jnp.max(s, axis=-1, keepdims=True)
                )
                p = jnp.exp(s - m_cur)  # (Bq, sub)
                alpha = jnp.exp(m_prev - m_cur)  # (Bq, 1)
                l_ref[h] = l_ref[h] * alpha + jnp.sum(
                    p, axis=-1, keepdims=True
                )
                acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32
                )
                m_ref[h] = m_cur

        _for_tile(body, q0, block_q, k0, sub, causal=causal, window=window)

    @pl.when(ik == num_k_blocks - 1)
    def _finish():
        for h in range(heads):
            l = l_ref[h]
            safe_l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros
            out_ref[0, h] = (acc_ref[h] / safe_l).astype(out_ref.dtype)
            lse_ref[0, h] = _row(m_ref[h] + jnp.log(safe_l))


def _kv_block_map(tile: Tile, *, causal, window):
    """Index of the kv block a (q block, kv step) pair stages: the step's
    own, clamped to the q block's live range so that a step causality or
    the window predicates off names the block already resident and moves
    nothing."""
    def clamp(iq, ik):
        if not causal:
            return ik
        ik = jnp.minimum(ik, (iq * tile.block_q + tile.block_q - 1) // tile.block_k)
        if window is not None:
            first = jnp.maximum(iq * tile.block_q - window + 1, 0)
            ik = jnp.maximum(ik, first // tile.block_k)
        return ik

    return clamp


def _q_block_map(tile: Tile, *, causal, window, num_q_blocks):
    """dkv's counterpart of :func:`_kv_block_map`: the q block a (kv
    block, q step) pair stages, clamped to the kv block's live range."""
    def clamp(ik, iq):
        if not causal:
            return iq
        iq = jnp.maximum(iq, (ik * tile.block_k) // tile.block_q)
        if window is not None:
            last = ik * tile.block_k + tile.block_k - 1 + window - 1
            iq = jnp.minimum(
                iq, jnp.minimum(last // tile.block_q, num_q_blocks - 1)
            )
        return iq

    return clamp


def _with_segment_refs(impl, has_seg: bool, n_inputs: int):
    """The kernel as pallas_call sees it: without segment ids their two refs
    (which follow the ``n_inputs`` array refs) are absent, and ``impl``
    gets None for them."""
    if has_seg:
        return impl
    return lambda *refs, **kw: impl(
        *refs[:n_inputs], None, None, *refs[n_inputs:], **kw
    )


def _check_tile(tile: Tile, sq, skv, heads, *, looped: str) -> Tile:
    """``tile`` as a kernel will really run it for these lengths: blocks
    no longer than the rows, the sub-tile no longer than the block it
    loops over (``looped``: "k" for forward and dq, "q" for dkv)."""
    block_q, block_k = min(tile.block_q, sq), min(tile.block_k, skv)
    if sq % block_q or skv % block_k:
        raise ValueError(
            f"seq lens (q={sq}, kv={skv}) must divide block sizes "
            f"({block_q}, {block_k}); pad inputs"
        )
    if heads % tile.heads:
        raise ValueError(f"{heads} heads do not divide by {tile.heads} a step")
    block = block_k if looped == "k" else block_q
    sub = min(tile.sub, block)
    if block % sub:
        raise ValueError(f"sub-tile {sub} must divide its block of {block}")
    return Tile(block_q, block_k, sub, tile.heads)


def _tile_name(kind: str, tile: Tile) -> str:
    """The kernel's name in a device trace: which kernel, which geometry."""
    return (
        f"flash_{kind}_q{tile.block_q}_k{tile.block_k}_t{tile.sub}"
        f"_h{tile.heads}"
    )


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
)


def _live_kv_blocks(q_offset, tile: Tile, nq: int, nk: int, window):
    """The kv blocks ``lo <= block <= hi`` (each ``(B, nq)`` int32) that
    hold a key some query of a q block may see, the queries of row ``b``
    starting at ``q_offset[b]`` in the keys' coordinates: not wholly after
    the block's last query, not wholly before its first one's window.
    Worked out once in front of the call; the kv operands' index maps
    read the two numbers."""
    first = q_offset[:, None] + jnp.arange(nq, dtype=jnp.int32) * tile.block_q
    hi = jnp.clip((first + tile.block_q - 1) // tile.block_k, 0, nk - 1)
    if window is None:
        return jnp.zeros_like(hi), hi
    return jnp.minimum(jnp.maximum(first - window + 1, 0) // tile.block_k, hi), hi


def _span_kernel(off_ref, lo_ref, hi_ref, *refs, kernel):
    """The forward kernel under a grid with scalar prefetch: the row's
    offset becomes the kernel's ``q_base``; the live range is the index
    maps' business."""
    del lo_ref, hi_ref
    kernel(*refs, q_base=off_ref[pl.program_id(0)])


# The kernel entry points are jitted on their static arguments so that a
# model's twelfth layer reuses the first one's trace and lowering: tracing a
# kernel body and serialising it for Mosaic costs about a tenth of a second
# a call, on every start-up, compile cache or not.
@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "tile", "interpret", "window"),
)
def _flash_forward(
    q, k, v, q_segment_ids, kv_segment_ids, q_offset=None,
    *, causal, scale, tile: Tile, interpret, window=None,
):
    """``q_offset`` (B,) int32, or None: query i of row b sits at key
    position ``q_offset[b] + i`` — a traced value, so it rides as a
    scalar-prefetch operand beside the live kv range it implies. None is
    the training case, query i at position i, with no such operand.
    ``k`` / ``v`` may hold fewer heads than ``q`` (grouped queries, one
    head a step): q head h reads kv head ``h // groups``."""
    batch, heads, sq, d = q.shape
    _, kv_heads, skv, _ = k.shape
    tile = _check_tile(tile, sq, skv, heads, looped="k")
    block_q, block_k, _, hb = tile
    nq, nk = sq // block_q, skv // block_k
    if window is not None and window >= skv:
        window = None  # the band is as wide as the rows: it never bites
    groups = heads // kv_heads
    if groups > 1 and hb > 1:
        raise ValueError(f"grouped heads take one head a step, not {hb}")

    impl = functools.partial(
        _fwd_kernel,
        scale=scale,
        fold=_is_pow2(scale),
        causal=causal,
        window=window,
        tile=tile,
        num_k_blocks=nk,
    )
    has_seg = q_segment_ids is not None
    if q_offset is None:
        clamp = _kv_block_map(tile, causal=causal, window=window)
        kv_block = lambda b, iq, ik, *_: clamp(iq, ik)
    else:
        q_offset = q_offset.astype(jnp.int32)
        live = _live_kv_blocks(q_offset, tile, nq, nk, window)
        kv_block = lambda b, iq, ik, off, lo, hi: jnp.clip(
            ik, lo[b, iq], hi[b, iq]
        )
    kv_head = (lambda h: h) if groups == 1 else (lambda h: h // groups)
    q_spec = pl.BlockSpec(
        (1, hb, block_q, d), lambda b, h, iq, ik, *_: (b, h, iq, 0)
    )
    kv_spec = pl.BlockSpec(
        (1, hb, block_k, d),
        lambda b, h, iq, ik, *s: (b, kv_head(h), kv_block(b, iq, ik, *s), 0),
    )
    in_specs = [q_spec, kv_spec, kv_spec]
    inputs = [q, k, v]
    if has_seg:
        # (B, S) → (B, 1, S): TPU block shapes need the trailing two dims
        # to tile cleanly (1 matches the singleton dim; block divides S).
        in_specs.append(
            pl.BlockSpec((1, 1, block_q), lambda b, h, iq, ik, *_: (b, 0, iq))
        )
        in_specs.append(
            pl.BlockSpec(
                (1, 1, block_k),
                lambda b, h, iq, ik, *s: (b, 0, kv_block(b, iq, ik, *s)),
            )
        )
        inputs.extend(
            [q_segment_ids[:, None, :], kv_segment_ids[:, None, :]]
        )

    kernel = _with_segment_refs(impl, has_seg, 3)
    grid = dict(
        grid=(batch, heads // hb, nq, nk),
        in_specs=in_specs,
        out_specs=[
            q_spec,
            # per-row statistics leave as lane-dense rows: (B, H, 1, S)
            pl.BlockSpec(
                (1, hb, 1, block_q), lambda b, h, iq, ik, *_: (b, h, 0, iq)
            ),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb, block_q, d), jnp.float32),   # acc
            pltpu.VMEM((hb, block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((hb, block_q, 1), jnp.float32),   # running denom
        ],
    )
    if q_offset is not None:
        kernel = functools.partial(_span_kernel, kernel=kernel)
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, **grid
        ))
        inputs = [q_offset, *live, *inputs]
    out, lse4 = pl.pallas_call(
        kernel,
        **grid,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((batch, heads, 1, sq), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_tile_name("fwd", tile),
    )(*inputs)
    return out, lse4[:, :, 0, :]


# --------------------------------------------------------------------------- #
# backward kernels
# --------------------------------------------------------------------------- #
#
# Standard flash backward split: one kernel accumulates dq (kv blocks
# innermost), one accumulates dk/dv (q blocks innermost). Both recompute the
# probability block p = exp(s - lse) from the saved per-row logsumexp, so
# peak live memory stays O(tile) — never S×S. The dkv kernel computes its
# tiles TRANSPOSED (kv rows × q columns): pᵀ·do and dsᵀ·q are then plain
# matmuls with no transpose of a score-sized tile, and lse / delta
# broadcast down the sublanes straight from their lane-dense rows. Masked
# entries are set to −1e30 before the exp, so p is exactly 0 there; rows
# the forward found fully masked arrive with lse = +1e30 (see
# flash_attention_bwd) and are exactly 0 throughout. ``scale`` is applied
# once to the accumulated dq and dk, not to every ds.


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref, kseg_ref,
    dq_ref,
    dq_acc,
    *,
    scale: float,
    fold: bool,
    causal: bool,
    window: int | None,
    tile: Tile,
    num_k_blocks: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    block_q, block_k, sub, heads = tile

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q0 = iq * block_q
    qseg = None if qseg_ref is None else _col(qseg_ref[0])
    lse = [_col(lse_ref[0, h]) for h in range(heads)]
    delta = [_col(delta_ref[0, h]) for h in range(heads)]

    for j in range(block_k // sub):
        ks = slice(j * sub, (j + 1) * sub)
        k0 = ik * block_k + j * sub

        def body(band, ks=ks, k0=k0):
            mask = _tile_mask(
                q0, k0, (block_q, sub), band=band, window=window, qseg=qseg,
                kseg=None if kseg_ref is None else kseg_ref[0, :, ks],
            )
            for h in range(heads):
                # native-dtype MXU operands, f32 accumulate (see fwd
                # kernel note); ds is cast back to the input dtype for its
                # matmul — the standard flash-bwd mixed-precision contract
                q = q_ref[0, h]        # (Bq, D)
                if fold:
                    q = q * scale
                k = k_ref[0, h, ks, :]  # (sub, D)
                s = _scores(q, k, scale=None if fold else scale)
                if mask is not None:
                    s = jnp.where(mask, s, NEG_INF)
                p = jnp.exp(s - lse[h])
                dp = _scores(do_ref[0, h], v_ref[0, h, ks, :], scale=None)
                ds = p * (dp - delta[h])
                dq_acc[h] += jax.lax.dot(
                    ds.astype(k.dtype), k, preferred_element_type=jnp.float32
                )

        _for_tile(body, q0, block_q, k0, sub, causal=causal, window=window)

    @pl.when(ik == num_k_blocks - 1)
    def _finish():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref, kseg_ref,
    dk_ref, dv_ref,
    dk_acc, dv_acc,
    *,
    scale: float,
    fold: bool,
    causal: bool,
    window: int | None,
    tile: Tile,
    num_q_blocks: int,
):
    ik = pl.program_id(2)
    iq = pl.program_id(3)
    block_q, block_k, sub, heads = tile

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    k0 = ik * block_k
    kseg = None if kseg_ref is None else _col(kseg_ref[0])

    for j in range(block_q // sub):
        qs = slice(j * sub, (j + 1) * sub)
        q0 = iq * block_q + j * sub

        def body(band, qs=qs, q0=q0):
            mask = _tile_mask(
                q0, k0, (block_k, sub), band=band, window=window,
                qseg=None if qseg_ref is None else qseg_ref[0, :, qs],
                kseg=kseg, transposed=True,
            )
            for h in range(heads):
                # native-dtype MXU operands, f32 accumulate (see fwd note)
                q = q_ref[0, h, qs, :]    # (sub, D)
                if fold:
                    q = q * scale  # dk = dsᵀ·(q·scale): the fold carries it
                do = do_ref[0, h, qs, :]  # (sub, D)
                st = _scores(
                    k_ref[0, h], q, scale=None if fold else scale
                )  # (Bk, sub) f32
                if mask is not None:
                    st = jnp.where(mask, st, NEG_INF)
                pt = jnp.exp(st - lse_ref[0, h, :, qs])
                # dv += pᵀ · do
                dv_acc[h] += jax.lax.dot(
                    pt.astype(do.dtype), do,
                    preferred_element_type=jnp.float32,
                )
                dpt = _scores(v_ref[0, h], do, scale=None)  # (Bk, sub)
                dst = pt * (dpt - delta_ref[0, h, :, qs])
                # dk += dsᵀ · q
                dk_acc[h] += jax.lax.dot(
                    dst.astype(q.dtype), q,
                    preferred_element_type=jnp.float32,
                )

        _for_tile(body, q0, sub, k0, block_k, causal=causal, window=window)

    @pl.when(iq == num_q_blocks - 1)
    def _finish():
        dk = dk_acc[...]
        dk_ref[0] = (dk if fold else dk * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(
    q, k, v, out, lse, dout,
    *,
    causal: bool,
    scale: float | None = None,
    q_segment_ids=None,
    kv_segment_ids=None,
    block_q: int | None = DEFAULT_BLOCK_Q,
    block_k: int | None = DEFAULT_BLOCK_K,
    interpret: bool = False,
    accum_dtype=jnp.float32,
    window: int | None = None,
):
    """Flash-attention gradients from saved residuals, fully blockwise.

    ``lse`` is the forward's per-row logsumexp (B,H,Sq) — for ring attention
    pass the globally-merged lse and out, and the returned (dq, dk, dv) are
    this hop's partial contributions (exactly the per-shard terms of the
    global softmax gradient). Returns float32 by default so ring hops can
    accumulate without precision loss. ``block_q`` / ``block_k`` as in
    :func:`flash_attention`.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash_backward(
        q, k, v, out, lse, dout,
        causal=causal, scale=scale, window=window,
        q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
        geometry=_geometry(q, k, block_q, block_k),
        interpret=interpret, accum_dtype=accum_dtype,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "geometry", "interpret", "accum_dtype", "window",
    ),
)
def _flash_backward(
    q, k, v, out, lse, dout,
    *, causal, scale, q_segment_ids, kv_segment_ids, geometry: Geometry,
    interpret, accum_dtype, window,
):
    batch, heads, sq, d = q.shape
    _, _, skv, _ = k.shape
    if window is not None and window >= skv:
        window = None  # the band is as wide as the rows: it never bites
    fold = _is_pow2(scale)

    delta = jnp.sum(
        dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )[:, :, None, :]  # (B,H,1,Sq): lane-dense rows, as the kernels read them
    # rows the forward found fully masked carry the −1e30 sentinel; +1e30
    # makes exp(s − lse) exactly 0 for them with no per-element select
    lse = lse.astype(jnp.float32)
    lse4 = jnp.where(lse > NEG_INF / 2, lse, -NEG_INF)[:, :, None, :]

    has_seg = q_segment_ids is not None
    inputs = [q, k, v, dout, lse4, delta]
    if has_seg:
        inputs.extend([q_segment_ids[:, None, :], kv_segment_ids[:, None, :]])

    def specs(tile, q_block, kv_block):
        """In-specs for a kernel whose grid is (b, h, i, j); ``q_block`` /
        ``kv_block`` map (i, j) to the staged q / kv block."""
        hb = tile.heads
        qi = lambda b, h, i, j: (b, h, q_block(i, j), 0)
        ki = lambda b, h, i, j: (b, h, kv_block(i, j), 0)
        ri = lambda b, h, i, j: (b, h, 0, q_block(i, j))
        sp = [
            pl.BlockSpec((1, hb, tile.block_q, d), qi),   # q
            pl.BlockSpec((1, hb, tile.block_k, d), ki),   # k
            pl.BlockSpec((1, hb, tile.block_k, d), ki),   # v
            pl.BlockSpec((1, hb, tile.block_q, d), qi),   # dout
            pl.BlockSpec((1, hb, 1, tile.block_q), ri),   # lse
            pl.BlockSpec((1, hb, 1, tile.block_q), ri),   # delta
        ]
        if has_seg:
            sp.append(pl.BlockSpec(
                (1, 1, tile.block_q), lambda b, h, i, j: (b, 0, q_block(i, j))
            ))
            sp.append(pl.BlockSpec(
                (1, 1, tile.block_k), lambda b, h, i, j: (b, 0, kv_block(i, j))
            ))
        return sp

    # ---- dq ----
    tile = _check_tile(geometry.dq, sq, skv, heads, looped="k")
    bq, bk = tile.block_q, tile.block_k
    kv_block = _kv_block_map(tile, causal=causal, window=window)
    dq = pl.pallas_call(
        _with_segment_refs(
            functools.partial(
                _bwd_dq_kernel, scale=scale, fold=fold, causal=causal,
                window=window, tile=tile, num_k_blocks=skv // bk,
            ),
            has_seg, 6,
        ),
        grid=(batch, heads // tile.heads, sq // bq, skv // bk),
        in_specs=specs(tile, lambda i, j: i, kv_block),
        out_specs=pl.BlockSpec(
            (1, tile.heads, bq, d), lambda b, h, i, j: (b, h, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, accum_dtype),
        scratch_shapes=[pltpu.VMEM((tile.heads, bq, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_tile_name("dq", tile),
    )(*inputs)

    # ---- dk / dv ----
    tile = _check_tile(geometry.dkv, sq, skv, heads, looped="q")
    bq, bk = tile.block_q, tile.block_k
    q_block = _q_block_map(
        tile, causal=causal, window=window, num_q_blocks=sq // bq
    )
    kv_out = pl.BlockSpec(
        (1, tile.heads, bk, d), lambda b, h, i, j: (b, h, i, 0)
    )
    dk, dv = pl.pallas_call(
        _with_segment_refs(
            functools.partial(
                _bwd_dkv_kernel, scale=scale, fold=fold, causal=causal,
                window=window, tile=tile, num_q_blocks=sq // bq,
            ),
            has_seg, 6,
        ),
        grid=(batch, heads // tile.heads, skv // bk, sq // bq),
        in_specs=specs(tile, q_block, lambda i, j: i),
        out_specs=[kv_out, kv_out],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, accum_dtype),
            jax.ShapeDtypeStruct(v.shape, accum_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile.heads, bk, d), jnp.float32),
            pltpu.VMEM((tile.heads, bk, d), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name=_tile_name("dkv", tile),
    )(*inputs)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# public API with recompute VJP
# --------------------------------------------------------------------------- #

@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8)
)
def _flash(q, k, v, q_seg, kv_seg, causal, scale, geometry, interp_window):
    return _flash_fwd(
        q, k, v, q_seg, kv_seg, causal, scale, geometry, interp_window
    )[0]


def _flash_fwd(q, k, v, q_seg, kv_seg, causal, scale, geometry, interp_window):
    interpret, window = interp_window
    out, lse = _flash_forward(
        q, k, v, q_seg, kv_seg,
        causal=causal, scale=scale, window=window,
        tile=geometry.fwd, interpret=interpret,
    )
    return out, (q, k, v, q_seg, kv_seg, out, lse)


def _flash_bwd(causal, scale, geometry, interp_window, res, dout):
    interpret, window = interp_window
    q, k, v, q_seg, kv_seg, out, lse = res
    # the kernels accumulate in f32 scratch and round once on the way out:
    # asking for the input dtype saves an f32 round trip through HBM
    dq, dk, dv = _flash_backward(
        q, k, v, out, lse, dout,
        causal=causal, scale=scale, window=window,
        q_segment_ids=q_seg, kv_segment_ids=kv_seg,
        geometry=geometry, interpret=interpret, accum_dtype=q.dtype,
    )
    # integer segment ids carry symbolic-zero (float0) cotangents
    return dq, dk, dv, float0_zeros(q_seg), float0_zeros(kv_seg)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _is_pow2(x: float) -> bool:
    """True where multiplying by ``x`` only moves a float's exponent."""
    return math.frexp(x)[0] == 0.5


def _geometry(q, k, block_q, block_k) -> Geometry:
    """Both blocks None → the rule's geometry for this call's shape; else
    the explicit blocks (a missing one filled in from the rule)."""
    if block_q is None and block_k is None:
        return select_geometry(
            q.shape[2], k.shape[2], q.shape[3], heads=q.shape[1],
            itemsize=q.dtype.itemsize,
        )
    return geometry_from_blocks(*resolve_blocks(q, k, block_q, block_k))


def _full_mask(q_shape, k_shape, q_seg, kv_seg, causal, window=None):
    _, _, sq, _ = q_shape
    _, _, skv, _ = k_shape
    mask = None
    if causal:
        mask = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)[None, None]
        if window is not None:
            qpos = jnp.arange(sq)[:, None] + (skv - sq)
            kpos = jnp.arange(skv)[None, :]
            mask = mask & ((qpos - kpos) < window)[None, None]
    if q_seg is not None:
        seg = (q_seg[:, None, :, None] == kv_seg[:, None, None, :])
        mask = seg if mask is None else (mask & seg)
    return mask


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: float | None = None,
    q_segment_ids: jax.Array | None = None,
    kv_segment_ids: jax.Array | None = None,
    block_q: int | None = DEFAULT_BLOCK_Q,
    block_k: int | None = DEFAULT_BLOCK_K,
    interpret: bool = False,
    return_residuals: bool = False,
    window: int | None = None,
):
    """Fused attention. Shapes: q (B,H,Sq,D); k/v (B,H,Skv,D).

    ``window`` (requires ``causal``): sliding-window attention — each
    query sees keys in [qpos - window + 1, qpos]; out-of-band tiles are
    skipped entirely, so compute is O(S·window) not O(S²).

    ``block_q``/``block_k`` None → tile geometry chosen from the call's
    shape by ``ops.flash_tuning.select_geometry`` (each kernel its own);
    explicit values are the blocks every kernel stages per grid step.

    ``return_residuals`` additionally returns (lse,) — the per-row
    log-sum-exp — for cross-block merging (ring attention). Differentiable
    only in the default (no-residual) form.
    """
    if q.shape[1] != k.shape[1]:
        raise ValueError(
            f"q heads {q.shape[1]} != kv heads {k.shape[1]} "
            "(repeat kv heads for GQA before calling)"
        )
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError("pass both q_segment_ids and kv_segment_ids or neither")
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} needs causal=True and window >= 1"
        )
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    geometry = _geometry(q, k, block_q, block_k)
    if return_residuals:
        return _flash_forward(
            q, k, v, q_segment_ids, kv_segment_ids,
            causal=causal, scale=scale, window=window,
            tile=geometry.fwd, interpret=interpret,
        )
    return _flash(
        q, k, v, q_segment_ids, kv_segment_ids,
        causal, scale, geometry, (interpret, window),
    )


def flash_attention_span(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offset: jax.Array,
    *,
    window: int | None = None,
    scale: float | None = None,
    interpret: bool = False,
    tile: Tile | None = None,
) -> jax.Array:
    """Causal attention of a span of queries that starts anywhere among
    its keys — a serving engine's prefill piece against its row's cached
    window. q (B, H, Sq, D); k / v (B, Hkv, Skv, D) with ``H`` a multiple
    of ``Hkv`` (grouped heads are read in place, never repeated);
    ``q_offset`` (B,) int32: query i of row b sits at key position
    ``q_offset[b] + i``, key j at position j, and every query lies among
    the keys (``q_offset + Sq <= Skv``). Keys after a query, and
    those ``window`` or more before it, are masked by position, and kv
    blocks that hold only such keys are neither fetched nor computed — so
    a caller may pad ``Skv`` up to whole blocks with anything that lies
    after the last query. Forward only (no VJP, no segment ids);
    ``tile`` None = ``flash_tuning.select_span_tile``'s."""
    if q.shape[1] % k.shape[1]:
        raise ValueError(
            f"{q.shape[1]} query heads not a multiple of {k.shape[1]} kv heads"
        )
    if window is not None and window < 1:
        raise ValueError(f"window={window} must be >= 1")
    if window is not None and window >= k.shape[2]:
        # as wide as the keys: it never bites, and the call shares the
        # global layers' trace of the kernel
        window = None
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if tile is None:
        tile = select_span_tile(
            q.shape[2], k.shape[2], q.shape[3], itemsize=q.dtype.itemsize
        )
    return _flash_forward(
        q, k, v, None, None, q_offset,
        causal=True, scale=scale, window=window, tile=tile,
        interpret=interpret,
    )[0]


def reference_attention(
    q, k, v, *, causal=False, scale=None,
    q_segment_ids=None, kv_segment_ids=None, window=None,
):
    """Plain-XLA attention; numerics oracle for the kernels and the
    small-shape fallback."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum(
        "bhqd,bhkd->bhqk",
        q.astype(jnp.float32), k.astype(jnp.float32),
    ) * scale
    mask = _full_mask(
        q.shape, k.shape, q_segment_ids, kv_segment_ids, causal, window
    )
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
