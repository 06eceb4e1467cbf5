"""Compiled on the chip: the grouped product of a dropless expert layer
(`ops/grouped_matmul.py`) at `trinity-mini_mixed-closed`'s two shapes, and
an engine that serves a small model of that pattern — window and global
layers mixed, gated QK-normed attention, sandwich norms, a leading dense
layer, sigmoid-routed experts beside a shared one — through the paged
kernel and the grouped product, rated by the plain float32 reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops.grouped_matmul import (
    gmm_kernel_runs,
    grouped_matmul,
    select_gmm_tiling,
)

E = 128


def _case(m, k, n, seed, *, empty=()):
    """``m`` assignments spread over 128 experts as 8 distinct picks a
    token give them, ``empty`` experts given none."""
    rng = np.random.default_rng(seed)
    p = np.ones(E)
    p[list(empty)] = 0
    picks = np.stack([rng.choice(E, 8, replace=False, p=p / p.sum()) for _ in range(m // 8)])
    sizes = np.bincount(picks.reshape(-1), minlength=E).astype(np.int32)
    key = jax.random.PRNGKey(seed)
    lhs = jax.random.normal(key, (m, k), jnp.bfloat16)
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (E, k, n), jnp.bfloat16) * k ** -0.5
    return lhs, rhs, sizes


@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)], ids=["up", "down"])
def test_decode_step_against_the_masked_einsum(k, n):
    """64 rows x 8 experts a token: about 4 rows an expert, some experts
    with none. Every expert on every row, masked to the row's own group."""
    assert gmm_kernel_runs(False)
    lhs, rhs, sizes = _case(512, k, n, 0, empty=(5, 77))
    got = grouped_matmul(lhs, rhs, jnp.asarray(sizes))
    group = np.repeat(np.arange(E), sizes)
    every = jnp.einsum("tk,ekn->etn", lhs, rhs, preferred_element_type=jnp.float32)
    want = np.asarray(every)[group, np.arange(512)]
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("m", [8192, 16384], ids=["piece1024", "piece2048"])
def test_prefill_piece_against_each_groups_own_product(m):
    """64-128 rows an expert; group by group, each group's rows times its
    own matrix (the masked einsum over all 128 would be 4 TFLOP)."""
    lhs, rhs, sizes = _case(m, 2048, 1024, 1)
    got = np.asarray(grouped_matmul(lhs, rhs, jnp.asarray(sizes)), np.float32)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    dot = jax.jit(lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32))
    for g in range(0, E, 9):
        lo, hi = offsets[g], offsets[g + 1]
        want = np.asarray(dot(lhs[lo:hi], rhs[g]))
        np.testing.assert_allclose(got[lo:hi], want, atol=3e-2, rtol=3e-2)
    assert select_gmm_tiling(m, 2048, 1024)[1:] == (2048, 1024)


def test_rows_past_the_groups_total_do_not_reach_the_rows_before():
    """A share of the experts: the rows of experts held elsewhere lie past
    the total and belong to no group; what the held groups' rows read is
    what they read without them."""
    lhs, rhs, sizes = _case(512, 2048, 1024, 2)
    held = sizes.copy()
    held[64:] = 0
    total = int(held.sum())
    got = np.asarray(grouped_matmul(lhs, rhs[:64], jnp.asarray(held[:64])), np.float32)
    want = np.asarray(grouped_matmul(lhs, rhs, jnp.asarray(sizes)), np.float32)
    np.testing.assert_allclose(got[:total], want[:total], atol=1e-6)


# -- the engine ------------------------------------------------------------ #

def test_engine_serves_the_small_pattern_compiled():
    """`chip_smoke.py`'s ``kinds`` phase, which raises where the engine is
    not the reference's model: prompts of 40, 200 and 300 tokens (past the
    window of 128, prefilled in pieces of 128), decoded together through
    the paged kernel and the grouped product in bfloat16, every served
    token rated by the float32 reference's full forward pass."""
    import chip_smoke

    out = chip_smoke.phase_kinds(
        chip_smoke.KindsPhaseConfig(model=chip_smoke.kinds_config())
    )
    assert out["tokens_rated"] == 3 * 32
    assert out["moe_assignments_decode"] == 16 * 3 * 31
    assert 0 < out["kv_pages_dead_window"] < out["kv_pages_held"]
