"""Expert parallelism: MoE routing + dispatch over the ``expert`` axis.

The DeepSpeed-MoE row of SURVEY.md §2.6, in the canonical TPU (GShard/
Switch) dense-dispatch form: top-k routing builds a (tokens, experts,
capacity) dispatch tensor, expert inputs/outputs are einsums against it, and
``with_sharding_constraint`` over the ``expert`` axis makes XLA emit the
token all_to_all on ICI — no manual collective code, which is exactly the
TPU-native translation of the reference's explicit all_to_all dispatch.

Includes the standard load-balancing auxiliary loss and router z-loss.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.core.mesh import Axis


def _constrain(x: jax.Array, spec: P) -> jax.Array:
    """Sharding constraint that no-ops outside a mesh context (pure
    single-device use keeps working)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or Axis.EXPERT not in mesh.axis_names:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    expert_dim: int = 256        # per-expert FFN hidden dim
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 1e-2
    z_loss_weight: float = 1e-3


def router_probs(logits: jax.Array) -> jax.Array:
    return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)


def top_k_routing(
    probs: jax.Array, k: int, capacity: int
) -> tuple[jax.Array, jax.Array]:
    """Build combine/dispatch tensors.

    probs: (T, E). Returns combine (T, E, C) float and dispatch (T, E, C)
    bool. Tokens beyond an expert's capacity are dropped (Switch semantics).
    Position within each expert's buffer is assigned in token order via a
    cumulative count over the top-k choice masks.
    """
    T, E = probs.shape
    _, top_idx = jax.lax.top_k(probs, k)               # (T, k)
    onehot = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)  # (T, k, E)

    # Position of each (token, choice) in its expert's buffer: tokens first,
    # then choice rank (priority to primary experts at equal token index).
    flat = onehot.transpose(1, 0, 2).reshape(k * T, E)  # choice-major
    pos_flat = jnp.cumsum(flat, axis=0) - flat          # (k*T, E)
    pos = pos_flat.reshape(k, T, E).transpose(1, 0, 2)  # (T, k, E)

    within = (pos < capacity) & (onehot > 0)            # (T, k, E)
    gate = probs[:, None, :] * onehot                   # (T, k, E)
    # renormalize over the k kept choices
    denom = jnp.sum(gate * within, axis=(1, 2), keepdims=True)
    gate = jnp.where(within, gate, 0.0) / jnp.maximum(denom, 1e-9)

    pos_clip = jnp.clip(pos.astype(jnp.int32), 0, capacity - 1)
    cap_onehot = jax.nn.one_hot(pos_clip, capacity, dtype=jnp.float32)  # (T,k,E,C)
    combine = jnp.einsum("tke,tkec->tec", gate, cap_onehot * within[..., None])
    dispatch = combine > 0.0
    return combine, dispatch


def load_balancing_loss(probs: jax.Array, dispatch: jax.Array) -> jax.Array:
    """Switch-style aux loss: E * dot(mean router prob, mean tokens/expert)."""
    E = probs.shape[-1]
    density = jnp.mean(dispatch.any(-1).astype(jnp.float32), axis=0)  # (E,)
    mean_prob = jnp.mean(probs, axis=0)                                # (E,)
    return E * jnp.sum(density * mean_prob)


def moe_ffn(
    x: jax.Array,                 # (T, d_model) token activations
    router_kernel: jax.Array,     # (d_model, E)
    up_kernel: jax.Array,         # (E, d_model, expert_dim)
    down_kernel: jax.Array,       # (E, expert_dim, d_model)
    cfg: MoEConfig,
) -> tuple[jax.Array, jax.Array, dict]:
    """Dense-dispatch MoE FFN. Returns (out (T, d_model), aux_loss, stats)."""
    T, d = x.shape
    E = cfg.num_experts
    capacity = max(int(cfg.capacity_factor * cfg.top_k * T / E), 1)

    logits = x.astype(jnp.float32) @ router_kernel.astype(jnp.float32)
    probs = router_probs(logits)
    combine, dispatch = top_k_routing(probs, cfg.top_k, capacity)

    # all_to_all moment #1: token-sharded → expert-sharded (XLA emits it
    # from this constraint when x is dp/fsdp-sharded and buffers are
    # expert-sharded).
    expert_in = jnp.einsum(
        "tec,td->ecd", dispatch.astype(x.dtype), x
    )
    expert_in = _constrain(expert_in, P(Axis.EXPERT, None, None))
    h = jnp.einsum("ecd,edf->ecf", expert_in, up_kernel.astype(x.dtype))
    h = jax.nn.gelu(h)
    expert_out = jnp.einsum("ecf,efd->ecd", h, down_kernel.astype(x.dtype))
    expert_out = _constrain(expert_out, P(Axis.EXPERT, None, None))
    # all_to_all moment #2: back to token sharding, weighted combine.
    out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_out)

    aux = cfg.aux_loss_weight * load_balancing_loss(probs, dispatch)
    z = cfg.z_loss_weight * jnp.mean(
        jax.nn.logsumexp(logits, axis=-1) ** 2
    )
    stats = {
        "moe_dropped_frac": 1.0
        - jnp.sum(dispatch.astype(jnp.float32)) / (cfg.top_k * T),
        "moe_aux_loss": aux,
        "moe_z_loss": z,
    }
    return out.astype(x.dtype), aux + z, stats
