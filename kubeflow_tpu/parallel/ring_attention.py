"""Ring attention: context parallelism over the ``seq`` mesh axis.

SURVEY.md §5.7's headline differentiator. Sequence-sharded Q stays put; the
KV shards rotate around the ICI ring via ``lax.ppermute`` (torus neighbors →
each hop is a single physical link), and every rank merges the per-block
partial attention results with online-softmax algebra. Memory per chip is
O(S/n · S/n) blockwise — never the full S×S matrix — which is what makes
million-token contexts fit.

The per-block compute is the Pallas flash kernel
(``kubeflow_tpu.ops.flash_attention``) with ``return_residuals=True`` — its
(out, logsumexp) pairs are exactly the mergeable form. The backward pass is
a second ring sweep: dq accumulates at home, dk/dv accumulate on the
rotating shard and arrive home after n hops (both passes are n ppermutes of
the same payload size — communication-optimal for the ring).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeflow_tpu.core.mesh import Axis
from kubeflow_tpu.ops.flash_attention import (
    NEG_INF,
    flash_attention,
    flash_attention_bwd,
    float0_zeros,
    reference_attention,
)


def global_seg_operand(mesh, seg_spec, segment_ids, q):
    """Shared wrapper plumbing: shard_map needs a concrete seg operand even
    when the caller passed None — substitute zeros (ignored by the local fn
    when has_seg is False) and place it on the seq-sharded layout."""
    if segment_ids is None:
        segment_ids = jnp.zeros(q.shape[:1] + q.shape[2:3], jnp.int32)
    return jax.device_put(segment_ids, NamedSharding(mesh, seg_spec))


def _rotate(x, axis_name: str):
    """One ring hop: shard i → shard i+1."""
    n = lax.axis_size(axis_name)
    return lax.ppermute(x, axis_name, [(i, (i + 1) % n) for i in range(n)])


def _block_flash(q, k, v, q_seg, kv_seg, *, step: int, src, me, causal,
                 scale, block_q, block_k, interpret):
    """Partial attention of local q vs the kv shard currently held (from
    ring rank ``src``). Returns (out, lse).

    ``step`` is a Python int (the ring loop is unrolled), so the causal
    structure resolves statically where possible: step 0 always holds the
    home shard (src == me → diagonal block); later steps are never
    diagonal, leaving one traced full-vs-skip choice. This keeps each hop
    to a single flash kernel instead of tracing all three branches."""
    B, H, S, D = q.shape

    seg_kw = (
        {"q_segment_ids": q_seg, "kv_segment_ids": kv_seg}
        if q_seg is not None
        else {}
    )

    def full(_):
        return flash_attention(
            q, k, v, causal=False, scale=scale, **seg_kw,
            block_q=block_q, block_k=block_k,
            interpret=interpret, return_residuals=True,
        )

    def skip(_):
        return (
            jnp.zeros_like(q),
            jnp.full((B, H, S), NEG_INF, jnp.float32),
        )

    if not causal:
        return full(None)
    if step == 0:
        return flash_attention(
            q, k, v, causal=True, scale=scale, **seg_kw,
            block_q=block_q, block_k=block_k,
            interpret=interpret, return_residuals=True,
        )
    return lax.cond(src < me, full, skip, None)


def _merge(o, lse, o_t, lse_t):
    """Online-softmax merge of normalized partials (o, lse)."""
    lse_new = jnp.logaddexp(lse, lse_t)
    w = jnp.exp(lse - lse_new)[..., None]
    w_t = jnp.exp(lse_t - lse_new)[..., None]
    return o * w + o_t * w_t.astype(o.dtype), lse_new


def _ring_fwd_pass(
    q, k, v, q_seg, kv_seg, axis_name, causal, scale, block_q, block_k,
    interpret,
):
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    B, H, S, D = q.shape
    o = jnp.zeros_like(q)
    lse = jnp.full((B, H, S), NEG_INF, jnp.float32)
    for step in range(n):
        src = (me - step) % n  # whose kv shard we currently hold
        o_t, lse_t = _block_flash(
            q, k, v, q_seg, kv_seg, step=step, src=src, me=me,
            causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
        o, lse = _merge(o, lse, o_t, lse_t)
        if step != n - 1:
            k = _rotate(k, axis_name)
            v = _rotate(v, axis_name)
            if kv_seg is not None:
                # the segment labels belong to the kv shard: they ride the
                # same ring hop so masking stays aligned with the data
                kv_seg = _rotate(kv_seg, axis_name)
    return o, lse


# --------------------------------------------------------------------------- #
# custom VJP (operates on LOCAL shards inside shard_map)
# --------------------------------------------------------------------------- #

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _ring_local(q, k, v, q_seg, kv_seg, axis_name, causal, scale, blocks,
                interpret):
    o, _ = _ring_fwd_pass(
        q, k, v, q_seg, kv_seg, axis_name, causal, scale, blocks[0],
        blocks[1], interpret
    )
    return o


def _ring_local_fwd(q, k, v, q_seg, kv_seg, axis_name, causal, scale,
                    blocks, interpret):
    o, lse = _ring_fwd_pass(
        q, k, v, q_seg, kv_seg, axis_name, causal, scale, blocks[0],
        blocks[1], interpret
    )
    return o, (q, k, v, q_seg, kv_seg, o, lse)


def _ring_local_bwd(axis_name, causal, scale, blocks, interpret, res, do):
    """Second ring sweep reusing the Pallas backward kernel per hop.

    The forward saved the GLOBAL (merged) out/lse, so each hop's
    ``flash_attention_bwd`` — probabilities normalized against the global
    lse — yields exactly that kv shard's partial terms of the global
    softmax gradient. Peak memory per hop is O(block_q × block_k), same as
    the forward; the whole-shard S×S matrix is never built.
    """
    block_q, block_k = blocks
    q, k, v, q_seg, kv_seg, o, lse = res
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)

    dq = jnp.zeros_like(q, dtype=jnp.float32)
    dk = jnp.zeros_like(k, dtype=jnp.float32)  # rides the ring with k,v
    dv = jnp.zeros_like(v, dtype=jnp.float32)

    def hop(step, src, k, v, kv_seg):
        # mirrors _block_flash's static structure: step 0 = diagonal,
        # later causal steps = traced full-vs-skip, non-causal = full
        seg_kw = (
            {"q_segment_ids": q_seg, "kv_segment_ids": kv_seg}
            if q_seg is not None
            else {}
        )

        def bwd(hop_causal):
            return flash_attention_bwd(
                q, k, v, o, lse, do, causal=hop_causal, scale=scale,
                **seg_kw,
                block_q=block_q, block_k=block_k, interpret=interpret,
            )

        def skip(_):
            return (
                jnp.zeros_like(q, dtype=jnp.float32),
                jnp.zeros_like(k, dtype=jnp.float32),
                jnp.zeros_like(v, dtype=jnp.float32),
            )

        if not causal:
            return bwd(False)
        if step == 0:
            return bwd(True)
        return lax.cond(src < me, lambda _: bwd(False), skip, None)

    for step in range(n):
        src = (me - step) % n  # whose kv shard we currently hold
        dq_t, dk_t, dv_t = hop(step, src, k, v, kv_seg)
        dq = dq + dq_t
        dk = dk + dk_t
        dv = dv + dv_t
        if step != n - 1:
            k = _rotate(k, axis_name)
            v = _rotate(v, axis_name)
            if kv_seg is not None:
                kv_seg = _rotate(kv_seg, axis_name)
            dk = _rotate(dk, axis_name)
            dv = _rotate(dv, axis_name)
    # after n-1 hops the accumulators sit one hop short of home
    dk = _rotate(dk, axis_name)
    dv = _rotate(dv, axis_name)
    return (
        dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
        float0_zeros(q_seg), float0_zeros(kv_seg),
    )


_ring_local.defvjp(_ring_local_fwd, _ring_local_bwd)


def ring_attention_local(
    q, k, v, *,
    axis_name: str = Axis.SEQ,
    causal: bool = False,
    scale: float | None = None,
    segment_ids=None,
    block_q: int | None = 128,
    block_k: int | None = 128,
    interpret: bool = False,
):
    """Ring attention on LOCAL seq shards — call inside shard_map where
    ``axis_name`` is a mesh axis and q/k/v are (B, H, S_local, D).
    ``segment_ids`` (B, S_local): packed-sequence block-diagonal masking —
    the local labels mask q, and a rotating copy rides the ring with each
    kv shard. ``block_q``/``block_k`` None → per-LOCAL-shape selection
    (ops/flash_tuning.py), resolved here once so fwd and bwd hops agree."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    if block_q is None or block_k is None:
        from kubeflow_tpu.ops.flash_tuning import resolve_blocks

        block_q, block_k = resolve_blocks(q, k, block_q, block_k)
    return _ring_local(
        q, k, v, segment_ids, segment_ids, axis_name, causal, scale,
        (block_q, block_k), interpret
    )


def ring_attention(
    q, k, v, mesh: Mesh, *,
    axis_name: str = Axis.SEQ,
    causal: bool = False,
    scale: float | None = None,
    segment_ids=None,
    interpret: bool = False,
):
    """Global-array convenience wrapper: shards seq over ``axis_name``,
    batch over data, heads over model; ``segment_ids`` (B, S) for packed
    sequences shards with the seq axis."""
    spec = P(Axis.DATA, Axis.MODEL, axis_name, None)
    seg_spec = P(Axis.DATA, axis_name)
    has_seg = segment_ids is not None

    def local(q, k, v, seg):
        return ring_attention_local(
            q, k, v, axis_name=axis_name, causal=causal,
            scale=scale, segment_ids=seg if has_seg else None,
            interpret=interpret,
        )

    fn = jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec, seg_spec),
        out_specs=spec, check_vma=False,
    )
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    return fn(q, k, v, global_seg_operand(mesh, seg_spec, segment_ids, q))
