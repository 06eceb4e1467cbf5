"""Seeded weights made on the device in one jitted call, in the type they
are served in. The program's own path for a model with no checkpoint is an
eager float32 ``model.init`` (PERF.md, program faults): at Mistral widths
that is twice the chip's memory and hundreds of small compiles."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seeded_params(abstract, seed: int, dtype):
    """A parameter tree shaped like ``abstract`` (from ``jax.eval_shape`` of
    the model's ``init``): projection kernels and embeddings normal with
    standard deviation ``fan_in ** -0.5`` (a kernel's fan-in is its
    second-to-last axis, so a stack of experts ``(E, in, out)`` is scaled
    like its members ``(in, out)``), norm scales one, biases zero,
    learned positions normal 0.02 — the scales of the program's
    initializers, so activations have the size they have after its
    ``init``. Leaf ``i`` depends on ``(seed, i)`` alone."""
    paths_and_leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def make(key):
        out = []
        for i, (path, leaf) in enumerate(paths_and_leaves):
            name = str(getattr(path[-1], "key", path[-1]))
            k = jax.random.fold_in(key, i)
            if name == "scale":
                x = jnp.ones(leaf.shape, dtype)
            elif name == "bias":
                x = jnp.zeros(leaf.shape, dtype)
            elif name == "kernel":
                x = jax.random.normal(k, leaf.shape, dtype) * leaf.shape[-2] ** -0.5
            elif name == "embedding":
                x = jax.random.normal(k, leaf.shape, dtype) * leaf.shape[1] ** -0.5
            else:
                x = jax.random.normal(k, leaf.shape, dtype) * 0.02
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed))
