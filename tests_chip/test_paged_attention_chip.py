"""Compiled (Mosaic, not interpret) paged decode-attention kernel on the
real chip — the CPU suite runs it only under the Pallas interpreter, which
proves semantics but not that Mosaic accepts the scalar-prefetch block-
table index maps, the (page, kv_heads, D) kv tiling, or the int8 load + f32
dequant-in-kernel path. Mirrors test_attention_chip.py: bf16 parity
against an XLA gather oracle, then a page-size sweep whose winner is
persisted and picked back up through the tuning table.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.ops.paged_attention import paged_attention, quantize_kv


def _gather_oracle(q, k_pool, v_pool, table, pos0, P, k_scale=None,
                   v_scale=None, window=None):
    """XLA reference: gather the horizon through the block table, masked
    softmax in f32 — the engine's paged gather path, standalone."""
    B, H, S, D = q.shape
    Hkv = k_pool.shape[1]                               # token-major pools
    G = H // Hkv
    W = table.shape[1] * P
    j = jnp.arange(W)
    flat = table[:, j // P] * P + j % P                 # (B, W)
    k = jnp.take(k_pool, flat.reshape(-1), axis=0)      # (B*W, Hkv, D)
    v = jnp.take(v_pool, flat.reshape(-1), axis=0)
    if k_scale is not None:                             # (Hkv, pool_tokens)
        k = k.astype(jnp.float32) * jnp.take(
            k_scale, flat.reshape(-1), axis=1).T[..., None]
        v = v.astype(jnp.float32) * jnp.take(
            v_scale, flat.reshape(-1), axis=1).T[..., None]
    k = k.reshape(B, W, Hkv, D).transpose(0, 2, 1, 3)   # (B, Hkv, W, D)
    v = v.reshape(B, W, Hkv, D).transpose(0, 2, 1, 3)
    qf = q.astype(jnp.float32).reshape(B, Hkv, G * S, D)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, k.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.float32(D))
    qpos = pos0[:, None] + jnp.arange(G * S)[None, :] % S   # (B, G*S)
    mask = j[None, None, None, :] <= qpos[:, None, :, None]
    if window is not None:
        mask &= j[None, None, None, :] > qpos[:, None, :, None] - window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.reshape(B, H, S, D).astype(q.dtype)


def _case(seed, *, B=4, Hkv=4, G=2, S=1, D=64, P=64, pages_per_row=8,
          dtype=jnp.bfloat16):
    rng = np.random.RandomState(seed)
    H = Hkv * G
    n_pages = 1 + B * pages_per_row
    T = n_pages * P
    q = jnp.asarray(rng.randn(B, H, S, D) / np.sqrt(D), dtype)
    kp = jnp.asarray(rng.randn(T, Hkv, D) / np.sqrt(D), dtype)
    vp = jnp.asarray(rng.randn(T, Hkv, D) / np.sqrt(D), dtype)
    perm = 1 + rng.permutation(B * pages_per_row).astype(np.int32)
    table = jnp.asarray(perm.reshape(B, pages_per_row))
    # staggered fills: every row ends at a different offset in its page
    pos0 = jnp.asarray(
        pages_per_row * P - S - np.arange(B, dtype=np.int32) * 7
    )
    return q, kp, vp, table, pos0


@pytest.mark.parametrize("span", [1, 5])
def test_paged_compiled_bf16_parity(span):
    """Compiled kernel vs the XLA gather oracle, decode and verify-span
    shapes, bf16 pools at a horizon (512 tokens/row) the engine actually
    serves."""
    q, kp, vp, table, pos0 = _case(0, S=span)
    out = paged_attention(q, kp, vp, table, pos0, page_size=64)
    ref = _gather_oracle(q, kp, vp, table, pos0, 64)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,  # bf16 operands
    )


def test_paged_compiled_int8_parity():
    """Compiled int8 load + dequant-in-kernel vs the same dequant done by
    XLA gather: both run the identical scale-multiply, so agreement is
    tight even in bf16 (the f32 dequant/softmax dominates)."""
    q, kp, vp, table, pos0 = _case(1)
    kq, ks = quantize_kv(kp.astype(jnp.float32))
    vq, vs = quantize_kv(vp.astype(jnp.float32))
    ks, vs = ks.T, vs.T                 # stored (kv_heads, pool_tokens)
    out = paged_attention(q, kq, vq, table, pos0, page_size=64,
                          k_scale=ks, v_scale=vs)
    ref = _gather_oracle(q, kq, vq, table, pos0, 64, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_paged_compiled_window():
    q, kp, vp, table, pos0 = _case(2, S=3)
    out = paged_attention(q, kp, vp, table, pos0, page_size=64, window=96)
    ref = _gather_oracle(q, kp, vp, table, pos0, 64, window=96)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_page_sweep_and_tuned_pickup(tmp_path):
    """Sweep candidate page sizes ON CHIP (compiled Mosaic timing, the
    thing interpret mode cannot measure), persist the winner, and show
    the selector picks it back up through KFT_FLASH_BLOCKS_FILE —
    mirroring test_block_sweep_and_tuned_s512_parity."""
    from kubeflow_tpu.ops import flash_tuning as ft

    res = ft.sweep_paged_pages(
        seq_tokens=512, candidates=(32, 64, 128), reps=2,
        table_path=str(tmp_path / "blocks.json"),
    )
    assert res["page_size"] in (32, 64, 128)
    assert res["all"], res

    os.environ["KFT_FLASH_BLOCKS_FILE"] = str(tmp_path / "blocks.json")
    ft.reset_table_cache()
    try:
        best = ft.select_paged_page_size(64)
        assert best == res["page_size"]
        # and the tuned page size runs compiled with correct numerics
        q, kp, vp, table, pos0 = _case(
            3, P=best, pages_per_row=512 // best
        )
        out = paged_attention(q, kp, vp, table, pos0, page_size=best)
        ref = _gather_oracle(q, kp, vp, table, pos0, best)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=2e-2,
        )
    finally:
        os.environ.pop("KFT_FLASH_BLOCKS_FILE", None)
        ft.reset_table_cache()
