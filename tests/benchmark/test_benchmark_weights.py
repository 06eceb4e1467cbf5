"""Seeded weights: the accepted cells' trees are what they were, and a
stack of experts is scaled like its members."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.manifest import Manifest, plugin
from benchmark.weights import seeded_params

TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=128, vocab_size=512, max_position_embeddings=64)


def rule_before(abstract, seed, dtype):
    """``seeded_params`` as it stood before stacked kernels: a kernel's
    fan-in was its first axis."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def make(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            name, k = str(getattr(path[-1], "key", path[-1])), jax.random.fold_in(key, i)
            if name == "scale":
                x = jnp.ones(leaf.shape, dtype)
            elif name == "bias":
                x = jnp.zeros(leaf.shape, dtype)
            elif name == "kernel":
                x = jax.random.normal(k, leaf.shape, dtype) * leaf.shape[0] ** -0.5
            elif name == "embedding":
                x = jax.random.normal(k, leaf.shape, dtype) * leaf.shape[1] ** -0.5
            else:
                x = jax.random.normal(k, leaf.shape, dtype) * 0.02
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed))


@pytest.mark.parametrize("config", ["bert-base", "mistral-7b-l16", "mistral-7b-l12-x4"])
def test_the_accepted_configurations_weights_are_bit_identical(config):
    cfg = dict(Manifest().config(config), **TINY)
    family = plugin("families", cfg["family"])
    if cfg["family"] == "bert":
        from kubeflow_tpu.models.bert import BertForMaskedLM

        model = BertForMaskedLM(family.program_config(cfg))
        abstract = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    else:
        abstract = family.abstract_params(family.serve_model(cfg)[0])
    leaves = jax.tree_util.tree_flatten_with_path(abstract)[0]
    kernels = [leaf for path, leaf in leaves if getattr(path[-1], "key", None) == "kernel"]
    assert kernels and all(leaf.ndim == 2 for leaf in kernels)
    dtype = jnp.bfloat16 if config == "mistral-7b-l16" else jnp.float32
    got, want = seeded_params(abstract, 3000000019, dtype), rule_before(abstract, 3000000019, dtype)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))


def test_a_stack_of_experts_is_scaled_like_its_members():
    shape = jax.ShapeDtypeStruct
    abstract = {"experts": {"up": {"kernel": shape((16, 512, 64), jnp.float32)},
                            "down": {"kernel": shape((16, 64, 512), jnp.float32)}},
                "dense": {"kernel": shape((512, 64), jnp.float32)}}
    p = seeded_params(abstract, 7, jnp.float32)
    assert float(p["experts"]["up"]["kernel"].std()) == pytest.approx(512 ** -0.5, rel=0.02)
    assert float(p["experts"]["down"]["kernel"].std()) == pytest.approx(64 ** -0.5, rel=0.02)
    assert float(p["dense"]["kernel"].std()) == pytest.approx(512 ** -0.5, rel=0.05)
    # every expert of the stack its own draw
    assert not np.array_equal(p["experts"]["up"]["kernel"][0], p["experts"]["up"]["kernel"][1])
