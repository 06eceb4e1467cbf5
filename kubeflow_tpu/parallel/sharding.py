"""Rule-based parameter sharding: param path regex → PartitionSpec.

The reference stack's TP/FSDP layouts live in user containers as
Megatron/DeepSpeed config (SURVEY.md §2.6 rows FSDP/TP); TPU-natively they
are just PartitionSpecs over named mesh axes, assigned here by first-match
path rules (the t5x/maxtext idiom, re-implemented):

- FSDP:  shard a big dim of every weight over ``fsdp``; XLA inserts the
  ZeRO all-gather (params) / reduce-scatter (grads) on ICI.
- TP:    Megatron pattern over ``model``: column-parallel in-projections
  (qkv, ffn-up) shard the OUTPUT dim; row-parallel out-projections (attn-o,
  ffn-down) shard the INPUT dim, so each pair needs one psum, which XLA
  emits from the specs.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Sequence

import jax
from jax.sharding import PartitionSpec as P

from kubeflow_tpu.core.mesh import Axis


def path_str(path) -> str:
    """jax key-path → 'layers/0/attn/q_proj/kernel' style string."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return "/".join(parts)


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Ordered (regex, PartitionSpec) pairs; first match wins.

    ``default`` applies when nothing matches (P() = replicate). Call the
    instance on a param pytree to get the spec tree (the ``param_spec_fn``
    contract of ``kubeflow_tpu.train.loop.Trainer``).
    """

    rules: Sequence[tuple[str, P]]
    default: P = P()

    def spec_for(self, path: str, shape: tuple[int, ...]) -> P:
        for pattern, spec in self.rules:
            if re.search(pattern, path):
                if len([a for a in spec if a is not None]) > len(shape):
                    raise ValueError(
                        f"rule {pattern!r} spec {spec} has more axes than "
                        f"param {path} shape {shape}"
                    )
                return spec
        return self.default

    def __call__(self, params: Any) -> Any:
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: self.spec_for(path_str(path), leaf.shape),
            params,
        )

    def validate_divisibility(self, params: Any, mesh_shape: dict[str, int]) -> None:
        """Fail fast when a sharded dim doesn't divide by its axis size."""

        def check(path, leaf):
            spec = self.spec_for(path_str(path), leaf.shape)
            for dim, axes in enumerate(spec):
                if axes is None:
                    continue
                for ax in (axes if isinstance(axes, tuple) else (axes,)):
                    n = mesh_shape.get(ax, 1)
                    if leaf.shape[dim] % n:
                        raise ValueError(
                            f"{path_str(path)} dim {dim} ({leaf.shape[dim]}) "
                            f"not divisible by axis {ax!r} size {n}"
                        )

        jax.tree_util.tree_map_with_path(check, params)


def transformer_rules(
    *,
    fsdp: bool = True,
    tensor: bool = True,
) -> ShardingRules:
    """Standard rules for ``kubeflow_tpu.models.transformer`` param names.

    Matrix layout conventions (flax kernels are (in, out)):

    - embed/unembed: shard vocab over model (TP) + d_model over fsdp
    - q/k/v proj (in=d_model, out=heads*head_dim): column-parallel → out dim
      over ``model``; fsdp shards the in dim
    - o proj (in=heads*head_dim, out=d_model): row-parallel → in dim over
      ``model``; fsdp shards the out dim
    - mlp up/gate (in=d_model, out=d_ff): column-parallel
    - mlp down (in=d_ff, out=d_model): row-parallel
    - layernorm scales/biases: replicated
    """
    m = Axis.MODEL if tensor else None
    f = Axis.FSDP if fsdp else None
    rules: list[tuple[str, P]] = [
        # MoE experts: stacks (n_experts, in, out) — expert dim over the
        # expert axis — before the projections' own rules, which their
        # names would also match
        (r"experts/(up|gate)_proj/kernel$", P(Axis.EXPERT, f, m)),
        (r"experts/down_proj/kernel$", P(Axis.EXPERT, m, f)),
        (r"experts/router/kernel$", P(f, None)),
        (r"embed/embedding$", P(m, f)),            # (vocab, d_model)
        (r"(q_proj|k_proj|v_proj)/kernel$", P(f, m)),
        (r"o_proj/kernel$", P(m, f)),
        (r"(up_proj|gate_proj)/kernel$", P(f, m)),
        (r"down_proj/kernel$", P(m, f)),
        (r"unembed/kernel$", P(f, m)),             # (d_model, vocab)
        (r"(q_proj|k_proj|v_proj|up_proj|gate_proj)/bias$", P(m)),
        (r"(scale|bias)$", P()),
    ]
    return ShardingRules(tuple(rules))
