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

from kubeflow_tpu.models.transformer import (
    paged_flash_attention,
    paged_gather_attention,
)
from kubeflow_tpu.ops.flash_tuning import PagedTile, select_paged_geometry
from kubeflow_tpu.ops.paged_attention import (
    paged_attention,
    paged_kernel_name,
    quantize_kv,
)


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


# ------------------------------------------- the geometry, at the cell's
# head shape (`mistral-7b_gen-closed`: 32 query heads over 8 kv heads of
# 128, 64-token pages), compiled, against the model's own gather read

#: keys each ragged row holds: one token; ending exactly on a page
#: boundary; one key into a new page; mid-page; the table's whole width
#: (None); -1 = a dead row on the scratch page (a free slot's position)
RAGGED = (1, 128, 129, 300, None, -1)


def _cell_case(table_pages, *, span=1, quant=False, seed=0, kv_heads=8):
    H, Hkv, D, P = 32, kv_heads, 128, 64
    rng = np.random.default_rng(seed)
    ctx = [
        min(table_pages * P if c is None else c, table_pages * P)
        for c in RAGGED
    ]
    B = len(ctx)
    T = (1 + B * table_pages) * P
    q = jnp.asarray(rng.normal(size=(B, H, span, D)) / np.sqrt(D), jnp.bfloat16)
    kp = jnp.asarray(rng.normal(size=(T, Hkv, D)), jnp.bfloat16)
    vp = jnp.asarray(rng.normal(size=(T, Hkv, D)), jnp.bfloat16)
    table = 1 + rng.permutation(B * table_pages).astype(np.int32).reshape(
        B, table_pages
    )
    live = np.asarray([c > 0 for c in ctx])
    table[~live] = 0
    pos0 = np.asarray(
        [max(c - span, 0) if c > 0 else -1 for c in ctx], np.int32
    )
    cache = {"k": kp, "v": vp}
    if quant:
        kq, ks = quantize_kv(kp)
        vq, vs = quantize_kv(vp)
        cache = {"k": kq, "v": vq, "k_scale": ks.T, "v_scale": vs.T}
    return q, cache, jnp.asarray(table), jnp.asarray(pos0), live


def _kernel_vs_gather(q, cache, table, pos0, live, *, tile, window=None):
    span = q.shape[2]
    got = paged_attention(
        q, cache["k"], cache["v"], table, pos0, page_size=64, window=window,
        k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"), tile=tile,
    )
    want = jax.jit(
        lambda q, cache: paged_gather_attention(
            q, cache, table, pos0[:, None] + jnp.arange(span)[None, :],
            page_size=64, window=window,
        )
    )(q, cache)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got[live], want[live], atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("table_pages", [1, 8, 16])
def test_selected_geometry_compiled_on_ragged_rows(table_pages, quant):
    """What the rule chooses at table widths 1, 8 and 16: a row of one
    token, a row ending on a page boundary, a dead row, a full row."""
    _kernel_vs_gather(*_cell_case(table_pages, quant=quant), tile=None)


@pytest.mark.parametrize("pages", [1, 2, 4, 8, 16])
def test_every_pages_per_step_compiled(pages):
    """Every pages-per-step the rule can return, as the rule folds them
    and a head at a time, with a window that leaves whole pages behind
    the longer rows."""
    fold = select_paged_geometry(
        table_pages=16, page_size=64, kv_heads=8, groups=4, span=1,
        head_dim=128,
    ).fold
    case = _cell_case(16, seed=pages)
    _kernel_vs_gather(
        *case, tile=PagedTile(pages, min(fold, pages)), window=200
    )
    _kernel_vs_gather(*case, tile=PagedTile(pages, 0), window=200)


@pytest.mark.parametrize("window", [2048, None], ids=["window2048", "global"])
def test_trinity_geometry_compiled_with_and_without_a_window(window):
    """`trinity-mini_mixed-closed`'s head shape — 32 query heads over 4 kv
    heads of 128, eight a group — at the widest table its engine sends
    (136 pages: max_seq 8,704, not a power of two), as a window layer
    (2,048: the full row's first 103 pages are dead) and as the global
    layer read it, against the gather."""
    tile = select_paged_geometry(
        table_pages=136, page_size=64, kv_heads=4, groups=8, span=1,
        head_dim=128,
    )
    assert paged_kernel_name(64, tile, 4) == "paged_decode_p64_n16_h4_f4"
    _kernel_vs_gather(*_cell_case(136, kv_heads=4, seed=7), tile=None, window=window)
    # and a narrower table, as shorter rows get
    _kernel_vs_gather(*_cell_case(32, kv_heads=4, seed=8), tile=None, window=window)


@pytest.mark.parametrize("window", [2048, None], ids=["window2048", "global"])
def test_trinity_work_list_compiled_with_and_without_a_window(window):
    """The grid as a work list at `trinity-mini_mixed-closed`'s decode
    shape: 96 rows of the mix's contexts (one key to the table's whole
    width, 30 % past the 2,048 window) over a 136-page table, each row
    holding the pages its context fills in a pool of those pages, beside
    a dead row and a freed one (its table all scratch, its position left
    where its request ended) — a step per live block, not every row
    across the table — against the gather."""
    P, Hkv, G, D, W = 64, 4, 8, 128, 136
    rng = np.random.default_rng(11)
    ctx = np.clip(
        1200 * np.exp(rng.normal(size=94)), 1, W * P
    ).astype(int).tolist() + [W * P, 1]
    rows = ctx + [-1, -2]
    B = len(rows)
    held = [-(-c // P) if c > 0 else 0 for c in rows]
    pool = 1 + sum(held)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(kq, (B, Hkv * G, 1, D), jnp.bfloat16)
    cache = {
        "k": jax.random.normal(kk, (pool * P, Hkv, D), jnp.bfloat16),
        "v": jax.random.normal(kv, (pool * P, Hkv, D), jnp.bfloat16),
    }
    pages = iter(1 + rng.permutation(pool - 1).astype(np.int32))
    table = np.zeros((B, W), np.int32)
    for b, h in enumerate(held):
        table[b, :h] = [next(pages) for _ in range(h)]
    pos0 = np.asarray(
        [c - 1 if c > 0 else -1 if c == -1 else W * P - 1 for c in rows],
        np.int32,
    )
    live = np.asarray([c > 0 for c in rows])
    _kernel_vs_gather(
        q, cache, jnp.asarray(table), jnp.asarray(pos0), live, tile=None,
        window=window,
    )


#: a prefill piece of each serving cell: (span, first position, table
#: pages, kv heads, groups, window)
PIECES = {
    # `trinity-mini_mixed-closed`: 1,024 tokens, 4 kv heads x 8
    "trinity-first-global": (1024, 0, 16, 4, 8, None),
    "trinity-first-window": (1024, 0, 16, 4, 8, 2048),
    "trinity-deep-global": (1024, 7680, 136, 4, 8, None),
    "trinity-deep-window": (1024, 7680, 136, 4, 8, 2048),
    # after a prefix hit whose base is no multiple of the piece
    "trinity-prefix-window": (1024, 4112, 128, 4, 8, 2048),
    # `mistral-7b_gen-closed`: 512 tokens against their own 512 keys; and
    # the same piece far into a row of the widest table (130 pages)
    "mistral-first": (512, 0, 8, 8, 4, 4096),
    "mistral-deep": (512, 7680, 130, 8, 4, 4096),
}


@pytest.mark.parametrize("piece", sorted(PIECES))
def test_piece_flash_read_compiled_against_the_gather(piece):
    """The prefill piece's read on the chip, bf16 at both serving cells'
    shapes: the row's window gathered and the flash forward kernel over
    it (the queries' offset a scalar-prefetch operand, grouped heads read
    in place, the width made up to whole kv blocks) against the gather's
    float32 score passes. The two round the probabilities against
    different maxima (running | the row's), so they agree to bf16."""
    span, offset, pages, kv_heads, groups, window = PIECES[piece]
    P, D = 64, 128
    rng = np.random.default_rng(pages)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(pages + offset), 3)
    pool_pages = 1 + pages + 32
    q = jax.random.normal(kq, (1, kv_heads * groups, span, D), jnp.bfloat16)
    cache = {
        "k": jax.random.normal(kk, (pool_pages * P, kv_heads, D), jnp.bfloat16),
        "v": jax.random.normal(kv, (pool_pages * P, kv_heads, D), jnp.bfloat16),
    }
    table = jnp.asarray(
        1 + rng.permutation(pool_pages - 1)[:pages].astype(np.int32)[None, :]
    )
    positions = offset + jnp.arange(span)[None, :]
    kw = dict(page_size=P, window=window)
    got = jax.jit(lambda *a: paged_flash_attention(*a, **kw))(
        q, cache, table, positions
    )
    want = jax.jit(lambda *a: paged_gather_attention(*a, **kw))(
        q, cache, table, positions
    )
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


def test_block_sweep_times_the_piece_forward(tmp_path):
    """`flash_tuning.sweep_blocks` with a query offset times the piece's
    forward alone — 1,024 queries deep among 8,704 keys of 4 kv heads —
    at the span tile (the keys made up to 9,216, 1,024 a grid step) and
    at the tile the raw width would get (2,176 rows staged in sub-tiles
    of 128), which it must beat."""
    from kubeflow_tpu.ops import flash_tuning as ft

    raw = ft.select_geometry(1024, 8704, 128, heads=32)
    rows = ft.sweep_blocks(
        batch=1, heads=32, seq=1024, head_dim=128, seq_kv=8704, kv_heads=4,
        q_offset=7680, candidates=(None, raw), steps=3, logdir=str(tmp_path),
    )
    assert not any("error" in r for r in rows), rows
    assert [r["seq_kv"] for r in rows] == [9216, 8704]
    assert rows[0]["geometry"][0] == [512, 1024, 1024, 1]
    assert rows[1]["geometry"][0] == [512, 2176, 128, 1]
    assert all(r["fwd_ms"] > 0 and r["dq_ms"] == 0 for r in rows), rows
    assert rows[0]["fwd_ms"] < rows[1]["fwd_ms"], rows


def test_verify_span_and_int8_pages_compiled():
    """The verify span (K + 1 = 4) through the rule's geometry, and int8
    pools through sixteen pages a step."""
    _kernel_vs_gather(*_cell_case(8, span=4, seed=4), tile=None)
    _kernel_vs_gather(
        *_cell_case(16, quant=True, seed=5), tile=PagedTile(16, 0), window=200
    )


@pytest.mark.parametrize("span", [1, 4])
def test_geometry_sweep_times_kernel_and_gather(tmp_path, span):
    """`flash_tuning.sweep_paged_geometry` — the tool PERF.md's span
    crossover and pages-a-step numbers were read with — times the rule's
    choice, a forced tile and the gather from a profile of the compiled
    calls, names the kernel it timed and says how far it lies from the
    gather (mirroring test_block_sweep_and_tuned_s512_parity)."""
    from kubeflow_tpu.ops import flash_tuning as ft

    res = ft.sweep_paged_geometry(
        contexts=(span, 64, 300, 512), table_pages=8, span=span,
        gather=paged_gather_attention,
        candidates=("gather", None, PagedTile(8, 0)), steps=3,
        logdir=str(tmp_path),
    )
    assert [r["read"] for r in res] == ["gather", "kernel", "kernel"]
    assert not [r for r in res if "error" in r], res
    assert all(r["ms"] > 0 for r in res), res
    rule = select_paged_geometry(
        table_pages=8, page_size=64, kv_heads=8, groups=4, span=span,
        head_dim=128,
    )
    assert res[1]["tile"] == list(rule) and res[2]["tile"] == [8, 0]
    for r, tile in ((res[1], rule), (res[2], PagedTile(8, 0))):
        assert paged_kernel_name(64, tile, 8) in r["ops"], r
        assert r["max_abs_err_vs_gather"] < 2e-2, r


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
