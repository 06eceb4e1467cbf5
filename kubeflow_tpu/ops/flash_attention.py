"""Pallas flash attention (TPU): online-softmax blockwise attention.

The serving-path kernel of the north star (BASELINE config 5: "Pallas
attention kernel for transformer serving") and the inner kernel of ring
attention (SURVEY.md §5.7). Design per the TPU kernel playbook
(/opt/skills/guides/pallas_guide.md):

- grid (batch, heads, q-blocks, kv-blocks); kv innermost and "arbitrary" so
  the online-softmax accumulator lives in VMEM scratch across kv steps;
- q/k/v blocks staged HBM→VMEM by pallas_call's pipeline; MXU matmuls with
  ``preferred_element_type=f32``; VPU for the softmax algebra;
- causal blocks that are entirely in the future are skipped (predicated);
- optional segment ids give block-diagonal masking (serving batches,
  packed sequences);
- backward: Pallas dq and dk/dv kernels (``flash_attention_bwd``) that
  recompute the probabilities blockwise against the saved logsumexp — the
  training path never materializes the S×S matrix. Ring attention reuses
  the same backward entry per ring hop.

Returns optionally the (max, logsumexp) residuals, which is what lets
``kubeflow_tpu.parallel.ring_attention`` merge partial results across ring
steps.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # large-but-finite: keeps exp() well-defined on fully-masked rows

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def float0_zeros(seg):
    """Symbolic-zero cotangent for an integer segment-id array (or None) —
    the one convention every seg-carrying custom_vjp shares."""
    return None if seg is None else np.zeros(seg.shape, jax.dtypes.float0)


def _attn_kernel(
    q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
    out_ref, lse_ref,
    acc_ref, m_ref, l_ref,
    *,
    scale: float,
    causal: bool,
    window: int | None,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal: skip kv blocks strictly in the future of this q block;
    # window: also skip blocks entirely below the attention band.
    q_start = iq * block_q
    k_start = ik * block_k
    run = (k_start <= q_start + block_q - 1) if causal else True
    if window is not None:
        run = run & (k_start + block_k - 1 >= q_start - window + 1)

    @pl.when(run)
    def _body():
        # MXU operands stay in the INPUT dtype (bf16 on TPU: full-rate MXU
        # passes; fp32 operands would run it 4-8x slower) — accumulation is
        # f32 via preferred_element_type, and bf16→f32 is exact, so QKᵀ is
        # bit-identical to an upcast-first fp32 matmul. Softmax math is f32.
        q = q_ref[0, 0]  # (Bq, D)
        k = k_ref[0, 0]  # (Bk, D)
        v = v_ref[0, 0]  # (Bk, D)
        s = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (Bq, Bk) f32

        mask = _tile_mask(
            iq, ik, causal=causal, window=window, block_q=block_q,
            block_k=block_k, qseg_ref=qseg_ref, kseg_ref=kseg_ref,
        )
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0:1]                     # (Bq, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)                     # (Bq, Bk)
        alpha = jnp.exp(m_prev - m_cur)            # (Bq, 1)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[:] = m_cur

    @pl.when(ik == num_k_blocks - 1)
    def _finish():
        l = l_ref[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows → zeros
        out_ref[0, 0] = (acc_ref[:] / safe_l).astype(out_ref.dtype)
        lse_ref[0, 0] = (m_ref[:, 0:1] + jnp.log(safe_l)).astype(lse_ref.dtype)


def _flash_forward(
    q, k, v, q_segment_ids, kv_segment_ids,
    *, causal, scale, block_q, block_k, interpret, window=None,
):
    batch, heads, sq, d = q.shape
    _, _, skv, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    if sq % block_q or skv % block_k:
        raise ValueError(
            f"seq lens (q={sq}, kv={skv}) must divide block sizes "
            f"({block_q}, {block_k}); pad inputs"
        )
    nq, nk = sq // block_q, skv // block_k

    impl = functools.partial(
        _attn_kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=nk,
    )
    has_seg = q_segment_ids is not None
    if has_seg:
        def kernel(q_r, k_r, v_r, qs_r, ks_r, out_r, lse_r, acc, m, l):
            impl(q_r, k_r, v_r, qs_r, ks_r, out_r, lse_r, acc, m, l)
    else:
        def kernel(q_r, k_r, v_r, out_r, lse_r, acc, m, l):
            impl(q_r, k_r, v_r, None, None, out_r, lse_r, acc, m, l)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_k, d), lambda b, h, iq, ik: (b, h, ik, 0)),
        pl.BlockSpec((1, 1, block_k, d), lambda b, h, iq, ik: (b, h, ik, 0)),
    ]
    inputs = [q, k, v]
    if has_seg:
        # (B, S) → (B, 1, S): TPU block shapes need the trailing two dims
        # to tile cleanly (1 matches the singleton dim; block divides S).
        in_specs.append(
            pl.BlockSpec((1, 1, block_q), lambda b, h, iq, ik: (b, 0, iq))
        )
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b, h, iq, ik: (b, 0, ik))
        )
        inputs.extend(
            [q_segment_ids[:, None, :], kv_segment_ids[:, None, :]]
        )

    out, lse4 = pl.pallas_call(
        kernel,
        grid=(batch, heads, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((batch, heads, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*inputs)
    return out, lse4[..., 0]


# --------------------------------------------------------------------------- #
# backward kernels
# --------------------------------------------------------------------------- #
#
# Standard flash backward split: one kernel accumulates dq (kv blocks
# innermost), one accumulates dk/dv (q blocks innermost). Both recompute the
# probability block p = exp(s - lse) from the saved per-row logsumexp, so
# peak live memory stays O(block_q × block_k) — never S×S.


def _tile_mask(iq, ik, *, causal, window, block_q, block_k, qseg_ref,
               kseg_ref):
    """(mask or None) for the (block_q, block_k) tile at (iq, ik) — the ONE
    place the causal/segment/window tile masking lives; forward and
    backward kernels must agree or gradients silently diverge."""
    mask = None
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        qpos = iq * block_q + rows
        kpos = ik * block_k + cols
        mask = qpos >= kpos
        if window is not None:
            # sliding window: query attends to keys in
            # [qpos - window + 1, qpos] (Mistral-style local attention)
            mask = mask & (qpos - kpos < window)
    if qseg_ref is not None:
        qs = qseg_ref[0, 0]  # (Bq,)
        ks = kseg_ref[0, 0]  # (Bk,)
        seg = qs[:, None] == ks[None, :]
        mask = seg if mask is None else (mask & seg)
    return mask


def _prob_block(q, k, lse, mask, *, scale):
    """p = exp(q·kᵀ·scale − lse), with masked entries exactly 0 and
    fully-masked rows (lse = −inf sentinel) exactly 0 instead of overflow."""
    s = jax.lax.dot_general(
        q, k,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (Bq, Bk)
    live = lse > NEG_INF / 2  # (Bq, 1)
    p = jnp.exp(s - jnp.where(live, lse, 0.0))
    p = jnp.where(live, p, 0.0)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    return p


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref, kseg_ref,
    dq_ref,
    dq_acc,
    *,
    scale: float,
    causal: bool,
    window: int | None,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (ik * block_k <= iq * block_q + block_q - 1) if causal else True
    if window is not None:
        run = run & (
            ik * block_k + block_k - 1 >= iq * block_q - window + 1
        )

    @pl.when(run)
    def _body():
        # native-dtype MXU operands, f32 accumulate (see fwd kernel note);
        # ds is cast back to the input dtype for its matmuls — the standard
        # flash-bwd mixed-precision contract
        q = q_ref[0, 0]        # (Bq, D)
        k = k_ref[0, 0]        # (Bk, D)
        v = v_ref[0, 0]        # (Bk, D)
        do = do_ref[0, 0]      # (Bq, D)
        lse = lse_ref[0, 0]                    # (Bq, 1)
        delta = delta_ref[0, 0]                # (Bq, 1)
        mask = _tile_mask(
            iq, ik, causal=causal, window=window, block_q=block_q,
            block_k=block_k, qseg_ref=qseg_ref, kseg_ref=kseg_ref,
        )
        p = _prob_block(q, k, lse, mask, scale=scale)
        dp = jax.lax.dot_general(
            do, v,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (Bq, Bk) f32
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dq_acc[:] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ik == num_k_blocks - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref, kseg_ref,
    dk_ref, dv_ref,
    dk_acc, dv_acc,
    *,
    scale: float,
    causal: bool,
    window: int | None,
    block_q: int,
    block_k: int,
    num_q_blocks: int,
):
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # Causal: q blocks strictly before this kv block contribute nothing;
    # window: q blocks entirely above the band contribute nothing either.
    run = (iq * block_q + block_q - 1 >= ik * block_k) if causal else True
    if window is not None:
        run = run & (
            ik * block_k + block_k - 1 >= iq * block_q - window + 1
        )

    @pl.when(run)
    def _body():
        # native-dtype MXU operands, f32 accumulate (see fwd kernel note)
        q = q_ref[0, 0]        # (Bq, D)
        k = k_ref[0, 0]        # (Bk, D)
        v = v_ref[0, 0]        # (Bk, D)
        do = do_ref[0, 0]      # (Bq, D)
        lse = lse_ref[0, 0]                    # (Bq, 1)
        delta = delta_ref[0, 0]                # (Bq, 1)
        mask = _tile_mask(
            iq, ik, causal=causal, window=window, block_q=block_q,
            block_k=block_k, qseg_ref=qseg_ref, kseg_ref=kseg_ref,
        )
        p = _prob_block(q, k, lse, mask, scale=scale)
        # dv += pᵀ · do
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (Bq, Bk) f32
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        # dk += dsᵀ · q
        dk_acc[:] += jax.lax.dot_general(
            ds, q,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(iq == num_q_blocks - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def flash_attention_bwd(
    q, k, v, out, lse, dout,
    *,
    causal: bool,
    scale: float | None = None,
    q_segment_ids=None,
    kv_segment_ids=None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    accum_dtype=jnp.float32,
    window: int | None = None,
):
    """Flash-attention gradients from saved residuals, fully blockwise.

    ``lse`` is the forward's per-row logsumexp (B,H,Sq) — for ring attention
    pass the globally-merged lse and out, and the returned (dq, dk, dv) are
    this hop's partial contributions (exactly the per-shard terms of the
    global softmax gradient). Returns float32 by default so ring hops can
    accumulate without precision loss.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    batch, heads, sq, d = q.shape
    _, _, skv, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    if sq % block_q or skv % block_k:
        raise ValueError(
            f"seq lens (q={sq}, kv={skv}) must divide block sizes "
            f"({block_q}, {block_k}); pad inputs"
        )
    nq, nk = sq // block_q, skv // block_k

    doutf = dout.astype(jnp.float32)
    delta = jnp.sum(doutf * out.astype(jnp.float32), axis=-1, keepdims=True)
    lse4 = lse[..., None].astype(jnp.float32)  # (B,H,Sq,1)

    has_seg = q_segment_ids is not None
    qseg = kseg = None
    if has_seg:
        qseg = q_segment_ids[:, None, :]
        kseg = kv_segment_ids[:, None, :]

    def specs(order):
        """order: 'qk' (iq=pid2, ik=pid3) or 'kq' (ik=pid2, iq=pid3)."""
        if order == "qk":
            qi = lambda b, h, i, j: (b, h, i, 0)
            ki = lambda b, h, i, j: (b, h, j, 0)
            qsi = lambda b, h, i, j: (b, 0, i)
            ksi = lambda b, h, i, j: (b, 0, j)
        else:
            qi = lambda b, h, i, j: (b, h, j, 0)
            ki = lambda b, h, i, j: (b, h, i, 0)
            qsi = lambda b, h, i, j: (b, 0, j)
            ksi = lambda b, h, i, j: (b, 0, i)
        sp = [
            pl.BlockSpec((1, 1, block_q, d), qi),   # q
            pl.BlockSpec((1, 1, block_k, d), ki),   # k
            pl.BlockSpec((1, 1, block_k, d), ki),   # v
            pl.BlockSpec((1, 1, block_q, d), qi),   # dout
            pl.BlockSpec((1, 1, block_q, 1), qi),   # lse
            pl.BlockSpec((1, 1, block_q, 1), qi),   # delta
        ]
        if has_seg:
            sp.append(pl.BlockSpec((1, 1, block_q), qsi))
            sp.append(pl.BlockSpec((1, 1, block_k), ksi))
        return sp

    inputs = [q, k, v, dout, lse4, delta]
    if has_seg:
        inputs.extend([qseg, kseg])

    # ---- dq ----
    dq_impl = functools.partial(
        _bwd_dq_kernel,
        scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_k_blocks=nk,
    )
    if has_seg:
        def dq_kernel(q_r, k_r, v_r, do_r, l_r, d_r, qs_r, ks_r, dq_r, acc):
            dq_impl(q_r, k_r, v_r, do_r, l_r, d_r, qs_r, ks_r, dq_r, acc)
    else:
        def dq_kernel(q_r, k_r, v_r, do_r, l_r, d_r, dq_r, acc):
            dq_impl(q_r, k_r, v_r, do_r, l_r, d_r, None, None, dq_r, acc)

    dq = pl.pallas_call(
        dq_kernel,
        grid=(batch, heads, nq, nk),
        in_specs=specs("qk"),
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, accum_dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*inputs)

    # ---- dk / dv ----
    dkv_impl = functools.partial(
        _bwd_dkv_kernel,
        scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_q_blocks=nq,
    )
    if has_seg:
        def dkv_kernel(q_r, k_r, v_r, do_r, l_r, d_r, qs_r, ks_r,
                       dk_r, dv_r, dk_a, dv_a):
            dkv_impl(q_r, k_r, v_r, do_r, l_r, d_r, qs_r, ks_r,
                     dk_r, dv_r, dk_a, dv_a)
    else:
        def dkv_kernel(q_r, k_r, v_r, do_r, l_r, d_r, dk_r, dv_r, dk_a, dv_a):
            dkv_impl(q_r, k_r, v_r, do_r, l_r, d_r, None, None,
                     dk_r, dv_r, dk_a, dv_a)

    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(batch, heads, nk, nq),
        in_specs=specs("kq"),
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, accum_dtype),
            jax.ShapeDtypeStruct(v.shape, accum_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*inputs)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# public API with recompute VJP
# --------------------------------------------------------------------------- #

@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8)
)
def _flash(q, k, v, q_seg, kv_seg, causal, scale, block_q, block_k_and_interp):
    block_k, interpret, window = block_k_and_interp
    out, _ = _flash_forward(
        q, k, v, q_seg, kv_seg,
        causal=causal, scale=scale, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out


def _flash_fwd(q, k, v, q_seg, kv_seg, causal, scale, block_q, block_k_and_interp):
    block_k, interpret, window = block_k_and_interp
    out, lse = _flash_forward(
        q, k, v, q_seg, kv_seg,
        causal=causal, scale=scale, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out, (q, k, v, q_seg, kv_seg, out, lse)


def _flash_bwd(causal, scale, block_q, block_k_and_interp, res, dout):
    block_k, interpret, window = block_k_and_interp
    q, k, v, q_seg, kv_seg, out, lse = res
    dq, dk, dv = flash_attention_bwd(
        q, k, v, out, lse, dout,
        causal=causal, scale=scale, window=window,
        q_segment_ids=q_seg, kv_segment_ids=kv_seg,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    dq, dk, dv = dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)
    # integer segment ids carry symbolic-zero (float0) cotangents
    return dq, dk, dv, float0_zeros(q_seg), float0_zeros(kv_seg)


_flash.defvjp(_flash_fwd, _flash_bwd)


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

    ``block_q``/``block_k`` None → per-shape selection via
    ``ops.flash_tuning.select_blocks`` (a measured table when one has
    been swept on hardware, a heuristic otherwise).

    ``return_residuals`` additionally returns (lse,) — the per-row
    log-sum-exp — for cross-block merging (ring attention). Differentiable
    only in the default (no-residual) form.
    """
    if block_q is None or block_k is None:
        from kubeflow_tpu.ops.flash_tuning import resolve_blocks

        block_q, block_k = resolve_blocks(q, k, block_q, block_k)
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
    if return_residuals:
        out, lse = _flash_forward(
            q, k, v, q_segment_ids, kv_segment_ids,
            causal=causal, scale=scale, window=window,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
        return out, lse
    return _flash(
        q, k, v, q_segment_ids, kv_segment_ids,
        causal, scale, block_q, (block_k, interpret, window),
    )


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
