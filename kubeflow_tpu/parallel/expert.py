"""Expert parallelism: MoE routing + dispatch over the ``expert`` axis.

The DeepSpeed-MoE row of SURVEY.md §2.6, in the canonical TPU (GShard/
Switch) dense-dispatch form: top-k routing builds a (tokens, experts,
capacity) dispatch tensor, expert inputs/outputs are einsums against it, and
``with_sharding_constraint`` over the ``expert`` axis makes XLA emit the
token all_to_all on ICI — no manual collective code, which is exactly the
TPU-native translation of the reference's explicit all_to_all dispatch.

Includes the standard load-balancing auxiliary loss and router z-loss.

That is the *capacity* path (``MoEConfig.capacity_factor`` a number): the
training path under a mesh, whose result for a token depends on how many
of its neighbours chose the same expert. A serving engine's continuous
batch cannot have that, so with ``capacity_factor=None`` the layer is
*dropless* (:func:`dropless_moe_ffn`): the ``T * k`` assignments are sorted
by expert, each matrix of the experts held takes one grouped product
(`ops/grouped_matmul.py`, linear in tokens), and every token gets every
expert it chose. One configuration states the whole rule: the score
function, whether the chosen weights are normalised, their scale, a
selection bias, the expert's form, shared experts, and which experts of
the published count this layer holds.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.core.mesh import Axis
from kubeflow_tpu.ops.grouped_matmul import grouped_matmul


def _constrain(x: jax.Array, spec: P) -> jax.Array:
    """Sharding constraint that no-ops outside a mesh context (pure
    single-device use keeps working)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or Axis.EXPERT not in mesh.axis_names:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8         # the router's width: the published count
    expert_dim: int = 256        # per-expert FFN hidden dim
    top_k: int = 2
    #: an expert's buffer, in multiples of an even share of the tokens;
    #: tokens past it are dropped. None = dropless (no buffer, no drop)
    capacity_factor: float | None = 1.25
    aux_loss_weight: float = 1e-2
    z_loss_weight: float = 1e-3
    #: "softmax" over the experts, or an independent "sigmoid" each
    score_func: str = "softmax"
    #: the chosen experts' weights divided by their sum
    route_norm: bool = True
    #: and multiplied by this
    route_scale: float = 1.0
    #: a stored vector added to the scores for the selection only (the
    #: weights come from the scores themselves)
    select_bias: bool = False
    #: "gelu": down(gelu(up x)); "gated_silu": down(silu(gate x) * up x)
    expert_form: str = "gelu"
    #: experts every token passes through, weight 1, beside the routed ones
    shared_experts: int = 0
    #: the experts this layer holds, ``first_expert`` onwards (None = all
    #: ``num_experts``). It routes over all of them and computes its own
    #: experts' part of the result; the rest — other chips' — is left out
    first_expert: int = 0
    held_experts: int | None = None

    @property
    def held(self) -> int:
        return self.num_experts if self.held_experts is None else self.held_experts

    def validate(self) -> None:
        if self.score_func not in ("softmax", "sigmoid"):
            raise ValueError(f"score_func {self.score_func!r} not softmax/sigmoid")
        if self.expert_form not in ("gelu", "gated_silu"):
            raise ValueError(f"expert_form {self.expert_form!r} not gelu/gated_silu")
        if not 0 <= self.first_expert <= self.first_expert + self.held <= self.num_experts:
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert + self.held}) "
                f"are not among the {self.num_experts} routed over"
            )
        if self.capacity_factor is not None and (
            self.score_func != "softmax" or not self.route_norm
            or self.route_scale != 1.0 or self.select_bias
            or self.held != self.num_experts or self.expert_form != "gelu"
        ):
            raise ValueError(
                "the capacity path routes by softmax, normalised over the "
                "choices it kept, over every expert of two-matrix GELU form; "
                "any other rule is dropless (capacity_factor=None)"
            )


def router_probs(logits: jax.Array) -> jax.Array:
    return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)


def route(x, router_kernel, select_bias, cfg: MoEConfig):
    """Choices and weights of every token under ``cfg``'s rule:
    ``(experts (T, k) int32, weights (T, k) f32)``. The
    scores are float32 sums of exact products (the kernel is taken in the
    activations' type): a near-tie between two experts is decided as the
    float32 reference decides it, as far as the activations agree."""
    logits = jnp.dot(
        x, router_kernel.astype(x.dtype), preferred_element_type=jnp.float32
    )
    scores = (
        jax.nn.sigmoid(logits) if cfg.score_func == "sigmoid"
        else jax.nn.softmax(logits, axis=-1)
    )
    chosen_by = scores if select_bias is None else scores + select_bias.astype(jnp.float32)
    _, experts = jax.lax.top_k(chosen_by, cfg.top_k)
    weights = jnp.take_along_axis(scores, experts, axis=1)
    if cfg.route_norm:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return experts, weights * cfg.route_scale


def _expert_act(gate, up, cfg: MoEConfig):
    return jax.nn.gelu(up) if cfg.expert_form == "gelu" else jax.nn.silu(gate) * up


def dropless_moe_ffn(
    x: jax.Array,                     # (T, d_model)
    router_kernel: jax.Array,         # (d_model, E)
    select_bias: jax.Array | None,    # (E,)
    gate_kernel: jax.Array | None,    # (held, d_model, expert_dim), gated form
    up_kernel: jax.Array,             # (held, d_model, expert_dim)
    down_kernel: jax.Array,           # (held, expert_dim, d_model)
    cfg: MoEConfig,
    *,
    live: jax.Array | None = None,    # (T,) bool: the tokens that count
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """The routed experts' part of the layer, no token dropped: returns
    ``(out (T, d_model), assignments (E,) int32)`` — the second counts, for
    each expert held, the ``live`` tokens it was given (telemetry; the
    result itself does not read ``live``). Sort the ``T * k`` assignments by
    expert (those of experts held elsewhere last), gather the tokens in
    that order, one grouped product per matrix, and each token sums its own
    ``k`` rows by weight in float32. Work and memory are linear in ``T``."""
    T, _ = x.shape
    k, held = cfg.top_k, cfg.held
    experts, weights = route(x, router_kernel, select_bias, cfg)
    local = experts.reshape(-1) - cfg.first_expert               # (T*k,)
    mine = (local >= 0) & (local < held)
    key = jnp.where(mine, local, held)
    order = jnp.argsort(key, stable=True)
    group_sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    xs = jnp.take(x, order // k, axis=0)                         # (T*k, d)
    gmm = lambda a, w: grouped_matmul(a, w.astype(x.dtype), group_sizes, interpret=interpret)
    gate = None if gate_kernel is None else gmm(xs, gate_kernel)
    ys = gmm(_expert_act(gate, gmm(xs, up_kernel), cfg), down_kernel)
    # back in assignment order; rows of experts held elsewhere hold
    # nothing the product computed
    inverse = jnp.zeros_like(order).at[order].set(jnp.arange(T * k, dtype=order.dtype))
    ys = jnp.take(ys, inverse, axis=0).reshape(T, k, -1)
    w = jnp.where(mine.reshape(T, k), weights, 0.0)
    out = jnp.einsum(
        "tkd,tk->td", jnp.where(mine.reshape(T, k, 1), ys, 0).astype(jnp.float32), w
    )
    counted = mine if live is None else mine & jnp.repeat(live, k)
    assignments = jnp.zeros((cfg.num_experts,), jnp.int32).at[
        experts.reshape(-1)
    ].add(counted.astype(jnp.int32))
    return out.astype(x.dtype), assignments


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
