"""Ulysses sequence parallelism: all_to_all seq<->heads re-sharding.

The DeepSpeed-Ulysses pattern (SURVEY.md §2.6 SP row), TPU-native: on entry
each rank holds all heads for a sequence shard; two ``lax.all_to_all``s swap
to all-sequence/head-shard around a standard (full-sequence) flash kernel,
then swap back. Cheaper than ring when heads >= ring size and sequence fits
per-chip after the head split; ring wins beyond that (SURVEY.md §5.7 chooses
per layer via config).
"""

from __future__ import annotations

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeflow_tpu.core.mesh import Axis
from kubeflow_tpu.ops.flash_attention import flash_attention
from kubeflow_tpu.parallel.ring_attention import global_seg_operand


def ulysses_attention_local(
    q, k, v, *,
    axis_name: str = Axis.SEQ,
    causal: bool = False,
    scale: float | None = None,
    segment_ids=None,
    block_q: int | None = 128,
    block_k: int | None = 128,
    interpret: bool = False,
):
    """Inside shard_map: q/k/v are (B, H, S_local, D); H must divide the
    axis size. ``segment_ids`` (B, S_local) gives packed-sequence
    block-diagonal masking. Returns (B, H, S_local, D). None block sizes
    resolve per the FULL-sequence shapes the inner kernel sees (after the
    all_to_all the local view is full-seq, head-sharded)."""
    seg_kw = {}
    if segment_ids is not None:
        # after the all_to_all each rank attends over the FULL sequence, so
        # it needs the full segment vector — a (B, S) int gather, cheap
        # next to the qkv all_to_alls
        full_seg = lax.all_gather(
            segment_ids, axis_name, axis=1, tiled=True
        )
        seg_kw = {"q_segment_ids": full_seg, "kv_segment_ids": full_seg}
    n = lax.axis_size(axis_name)
    if n == 1:
        return flash_attention(
            q, k, v, causal=causal, scale=scale, **seg_kw,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
    H = q.shape[1]
    if H % n:
        raise ValueError(
            f"Ulysses needs heads ({H}) divisible by seq axis size ({n}); "
            "use ring attention instead"
        )

    def seq_to_heads(x):  # (B, H, S/n, D) → (B, H/n, S, D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def heads_to_seq(x):  # (B, H/n, S, D) → (B, H, S/n, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    q, k, v = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    o = flash_attention(
        q, k, v, causal=causal, scale=scale, **seg_kw,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return heads_to_seq(o)


def ulysses_attention(
    q, k, v, mesh: Mesh, *,
    axis_name: str = Axis.SEQ,
    causal: bool = False,
    scale: float | None = None,
    segment_ids=None,
    interpret: bool = False,
):
    """Global-array convenience wrapper (batch over data, heads over model,
    seq over ``axis_name``); ``segment_ids`` (B, S) for packed sequences
    shards with the seq axis."""
    spec = P(Axis.DATA, Axis.MODEL, axis_name, None)
    seg_spec = P(Axis.DATA, axis_name)
    has_seg = segment_ids is not None

    def local(q, k, v, seg):
        return ulysses_attention_local(
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
