"""Attention kernels: Pallas flash (interpret mode), ring CP, Ulysses SP.

Numerics oracle is plain-XLA reference_attention; kernels run in interpret
mode on the virtual CPU mesh (compiled-mode parity is exercised on the real
chip by bench/serving paths).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.core.mesh import Axis, MeshSpec, build_mesh
from kubeflow_tpu.ops.flash_attention import flash_attention, reference_attention
from kubeflow_tpu.parallel.ring_attention import ring_attention
from kubeflow_tpu.parallel.ulysses import ulysses_attention

B, H, S, D = 2, 8, 256, 32

# what the rule returns at the two training cells' shapes (bf16; measured on
# the chip, PERF.md section 6 "PR 26") — pinned so a change of the rule is a
# decision, and shared with the compile test of those geometries
from kubeflow_tpu.ops.flash_tuning import Geometry, Tile  # noqa: E402

BERT_GEOMETRY = Geometry(
    Tile(512, 512, 512, 1), Tile(512, 512, 512, 4), Tile(512, 512, 512, 4)
)
X4_GEOMETRY = Geometry(
    Tile(512, 4096, 1024, 1), Tile(512, 4096, 512, 1), Tile(4096, 512, 512, 1)
)


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D) / np.sqrt(D), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(qkv, causal):
    q, k, v = qkv
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_segment_masking(qkv):
    q, k, v = qkv
    rng = np.random.RandomState(1)
    seg = jnp.asarray(np.sort(rng.randint(0, 3, (B, S)), axis=-1))
    out = flash_attention(
        q, k, v, q_segment_ids=seg, kv_segment_ids=seg, interpret=True
    )
    ref = reference_attention(q, k, v, q_segment_ids=seg, kv_segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_grad_matches_reference(qkv):
    q, k, v = qkv

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_flash_grad_with_segments_matches_reference(qkv):
    # the Pallas backward kernels must respect segment masking (packed
    # sequences): masked entries contribute exactly zero gradient
    q, k, v = qkv
    rng = np.random.RandomState(2)
    seg = jnp.asarray(np.sort(rng.randint(0, 3, (B, S)), axis=-1))

    def loss_flash(q, k, v):
        return (
            flash_attention(
                q, k, v, q_segment_ids=seg, kv_segment_ids=seg, interpret=True
            )
            ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (
            reference_attention(q, k, v, q_segment_ids=seg, kv_segment_ids=seg)
            ** 2
        ).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_flash_rejects_bad_shapes(qkv):
    q, k, v = qkv
    with pytest.raises(ValueError, match="heads"):
        flash_attention(q[:, :4], k, v, interpret=True)
    with pytest.raises(ValueError, match="segment"):
        flash_attention(q, k, v, q_segment_ids=jnp.zeros((B, S), jnp.int32))
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q[:, :, :150], k[:, :, :150], v[:, :, :150],
                        block_q=128, block_k=128, interpret=True)


# ------------------------- ring attention (CP) ------------------------- #

@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(qkv, causal, devices8):
    q, k, v = qkv
    mesh = build_mesh(MeshSpec(seq=8))
    out = ring_attention(q, k, v, mesh, causal=causal, interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_grad(qkv, causal, devices8):
    q, k, v = qkv
    mesh = build_mesh(MeshSpec(seq=4), devices=jax.devices()[:4])

    def loss_ring(q, k, v):
        return (ring_attention(q, k, v, mesh, causal=causal, interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (reference_attention(q, k, v, causal=causal) ** 2).sum()

    gf = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4,
            err_msg=f"d{name} mismatch (causal={causal})",
        )


def test_ring_attention_2d_mesh(qkv, devices8):
    """seq ring composed with data-parallel batch sharding."""
    q, k, v = qkv
    mesh = build_mesh(MeshSpec(data=2, seq=4))
    out = ring_attention(q, k, v, mesh, causal=True, interpret=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ------------------------- Ulysses (SP) -------------------------------- #

@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(qkv, causal, devices8):
    q, k, v = qkv
    mesh = build_mesh(MeshSpec(seq=8))
    out = ulysses_attention(q, k, v, mesh, causal=causal, interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_rejects_indivisible_heads(qkv, devices8):
    q, k, v = qkv
    mesh = build_mesh(MeshSpec(seq=8))
    with pytest.raises(Exception, match="divisible|Ulysses"):
        ulysses_attention(q[:, :6], k[:, :6], v[:, :6], mesh, interpret=True)


# ---------------- packed sequences (segment ids) over CP/SP ------------ #

@pytest.fixture(scope="module")
def packed_segs():
    """(B, S) segment labels: three packed documents per row."""
    rng = np.random.RandomState(3)
    seg = np.zeros((B, S), np.int32)
    for b in range(B):
        cuts = sorted(rng.choice(np.arange(8, S - 8), size=2, replace=False))
        seg[b, cuts[0]:cuts[1]] = 1
        seg[b, cuts[1]:] = 2
    return jnp.asarray(seg)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_segment_ids(qkv, packed_segs, causal, devices8):
    """Packed-sequence masking rides the ring: cross-document attention is
    blocked exactly as in the reference, across shard boundaries."""
    q, k, v = qkv
    mesh = build_mesh(MeshSpec(seq=8))
    out = ring_attention(
        q, k, v, mesh, causal=causal, segment_ids=packed_segs, interpret=True
    )
    ref = reference_attention(
        q, k, v, causal=causal,
        q_segment_ids=packed_segs, kv_segment_ids=packed_segs,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_segment_ids_grad(qkv, packed_segs, devices8):
    q, k, v = qkv
    mesh = build_mesh(MeshSpec(seq=4), devices=jax.devices()[:4])

    def loss_ring(q, k, v):
        return (
            ring_attention(
                q, k, v, mesh, causal=True, segment_ids=packed_segs,
                interpret=True,
            ) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (
            reference_attention(
                q, k, v, causal=True,
                q_segment_ids=packed_segs, kv_segment_ids=packed_segs,
            ) ** 2
        ).sum()

    gf = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4,
            err_msg=f"d{name} mismatch (segmented ring)",
        )


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_segment_ids(qkv, packed_segs, causal, devices8):
    q, k, v = qkv
    mesh = build_mesh(MeshSpec(seq=8))
    out = ulysses_attention(
        q, k, v, mesh, causal=causal, segment_ids=packed_segs, interpret=True
    )
    ref = reference_attention(
        q, k, v, causal=causal,
        q_segment_ids=packed_segs, kv_segment_ids=packed_segs,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ulysses_segment_ids_grad(qkv, packed_segs, devices8):
    q, k, v = qkv
    mesh = build_mesh(MeshSpec(seq=8))

    def loss_uly(q, k, v):
        return (
            ulysses_attention(
                q, k, v, mesh, causal=True, segment_ids=packed_segs,
                interpret=True,
            ) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (
            reference_attention(
                q, k, v, causal=True,
                q_segment_ids=packed_segs, kv_segment_ids=packed_segs,
            ) ** 2
        ).sum()

    gf = jax.grad(loss_uly, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4,
            err_msg=f"d{name} mismatch (segmented ulysses)",
        )


# ---------------------- sliding-window attention ----------------------- #

@pytest.mark.parametrize("window", [16, 40, 128])
def test_flash_sliding_window_matches_reference(qkv, window):
    q, k, v = qkv
    out = flash_attention(
        q, k, v, causal=True, window=window, block_q=16, block_k=16,
        interpret=True,
    )
    ref = reference_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_sliding_window_grad(qkv):
    q, k, v = qkv
    window = 24

    def loss_flash(q, k, v):
        return (
            flash_attention(
                q, k, v, causal=True, window=window, block_q=16,
                block_k=16, interpret=True,
            ) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (
            reference_attention(q, k, v, causal=True, window=window) ** 2
        ).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4,
            err_msg=f"d{name} mismatch (window={window})",
        )


def test_flash_window_with_segments(qkv, packed_segs):
    """Window and packed-sequence masks compose."""
    q, k, v = qkv
    out = flash_attention(
        q, k, v, causal=True, window=24,
        q_segment_ids=packed_segs, kv_segment_ids=packed_segs,
        block_q=16, block_k=16, interpret=True,
    )
    ref = reference_attention(
        q, k, v, causal=True, window=24,
        q_segment_ids=packed_segs, kv_segment_ids=packed_segs,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_window_requires_causal(qkv):
    q, k, v = qkv
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8, interpret=True)


# --------------------------------------------------------- block selection


@pytest.mark.parametrize("blocks", [(128, 256), (256, 128), (256, 256),
                                    (128, 512), (512, 512)])
def test_flash_nondefault_blocks_match_reference(blocks):
    """Every candidate block shape the S512 tuner sweeps must be
    numerically identical to reference — fwd AND grad — so the sweep can
    pick purely on speed (interpret mode exercises the same tile code)."""
    bq, bk = blocks
    B, H, S, D = 1, 2, 512, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, D), jnp.float32) for kk in ks)
    out = flash_attention(
        q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True
    )
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )

    def f_loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    g_flash = jax.grad(
        f_loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk, interpret=True
        ))
    )(q, k, v)
    g_ref = jax.grad(
        f_loss(lambda q, k, v: reference_attention(q, k, v, causal=True))
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(g_flash), np.asarray(g_ref), rtol=5e-3, atol=5e-3
    )


def test_block_selection_rule():
    from kubeflow_tpu.ops import flash_tuning as ft
    from kubeflow_tpu.ops.flash_tuning import Geometry, Tile

    # the two training cells (PERF.md section 6, PR 26). BERT: one head's
    # whole rows per step, heads sharing a step; x4's per-chip problem:
    # long causal rows, a staged kv block looped over in sub-tiles
    assert ft.select_geometry(512, 512, 64, heads=12) == BERT_GEOMETRY
    assert (
        ft.select_geometry(4096, 4096, 128, heads=16)
        == X4_GEOMETRY
    )
    # heads a backward step divide the head count
    assert ft.select_geometry(512, 512, 64, heads=3).dq.heads == 3
    assert ft.select_geometry(512, 512, 64, heads=7).dq.heads == 1
    assert ft.select_geometry(2048, 2048, 64, heads=12).dkv.heads == 1
    # short rows are staged whole; 128-class tiles where heads are wide or
    # the operands are f32 (tile bytes scale with both)
    assert ft.select_geometry(128, 128, 64).fwd[:3] == (128, 128, 128)
    wide = ft.select_geometry(512, 512, 256)
    assert wide == Geometry(
        Tile(128, 128, 128, 1), Tile(128, 128, 128, 1), Tile(128, 128, 128, 1)
    )
    f32 = ft.select_geometry(512, 512, 64, heads=12, itemsize=4)
    assert f32.fwd == Tile(128, 256, 256, 1) and f32.dkv == Tile(128, 256, 128, 1)
    # block sizes divide the sequence when a sane divisor exists
    assert ft.select_blocks(96, 96, 64) == (96, 96)
    assert ft.select_blocks(384, 384, 64) == (384, 384)
    assert ft.select_blocks(1536, 1536, 64) == (512, 512)
    assert ft.select_blocks(640, 640, 64) == (128, 128)
    # the pair the ring hops pass on explicitly: 512-class, which all
    # three kernels can stage as given
    assert ft.select_blocks(4096, 4096, 128) == (512, 512)
    assert ft.select_blocks(512, 512, 256) == (128, 128)
    # explicit blocks are staged as given; only the sub-tile is derived
    assert ft.geometry_from_blocks(128, 1024) == Geometry(
        Tile(128, 1024, 512, 1), Tile(128, 1024, 512, 1),
        Tile(128, 1024, 128, 1),
    )
    # prime-ish lengths must NOT degrade to block-1 grids — selection
    # keeps a non-dividing cap so the kernel's explicit 'pad inputs'
    # divisibility error fires instead
    bq, bk = ft.select_blocks(509, 509, 64)
    assert bq == bk == 509  # short: staged whole, one block
    bq, bk = ft.select_blocks(4099, 4099, 64)
    assert bq > 1 and bk > 1 and (4099 % bq and 4099 % bk)
    q = jnp.zeros((1, 1, 4099, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="pad inputs"):
        flash_attention(q, q, q, causal=True, block_q=None, block_k=None,
                        interpret=True)


def _geometry_cases():
    """Every kind of geometry the rule (or explicit blocks) can hand the
    kernels: (shape, causal, window, segments, geometry)."""
    from kubeflow_tpu.ops import flash_tuning as ft
    from kubeflow_tpu.ops.flash_tuning import Geometry, Tile

    g = lambda f, dq, dkv: Geometry(Tile(*f), Tile(*dq), Tile(*dkv))
    return {
        # BERT's case: non-causal, segment ids, whole rows, heads a step
        "bert-rule": ((1, 4, 512, 16), False, None, True,
                      ft.select_geometry(512, 512, 64, heads=4)),
        # x4's case cut in length: a staged kv block with an inner loop,
        # diagonal, interior and skipped sub-tiles; the window never bites
        "x4-rule-s1024": ((1, 2, 1024, 16), True, 1024, False,
                          ft.select_geometry(1024, 1024, 128)),
        "x4-rule-s2048-window": ((1, 1, 2048, 8), True, 600, False,
                                 ft.select_geometry(2048, 2048, 128)),
        # window < S at block_q != block_k: diagonal tiles, band-edge
        # tiles and skipped tiles all present, in all three kernels
        "window40-uneven": ((2, 4, 256, 32), True, 40, True,
                            g((64, 128, 32, 2), (32, 128, 64, 1),
                              (128, 32, 64, 2))),
        "window100-uneven": ((1, 2, 256, 16), True, 100, False,
                             g((32, 64, 32, 1), (64, 128, 32, 1),
                               (64, 32, 16, 1))),
        "causal-inner-loop": ((2, 4, 256, 32), True, None, False,
                              g((64, 128, 64, 1), (32, 256, 64, 2),
                                (128, 64, 32, 1))),
        "noncausal-heads4-inner-loop": ((2, 4, 256, 32), False, None, True,
                                        g((128, 256, 128, 2),
                                          (64, 128, 64, 4),
                                          (256, 64, 128, 2))),
        # lengths that are not a power of two
        "s96-rule": ((1, 2, 96, 64), False, None, True,
                     ft.select_geometry(96, 96, 64, heads=2)),
        "s384-rule-causal": ((1, 2, 384, 32), True, None, True,
                             ft.select_geometry(384, 384, 64, heads=2)),
        "s1536-rule": ((1, 1, 1536, 8), True, None, False,
                       ft.select_geometry(1536, 1536, 64)),
        # f32 operands / wide heads: the 128-class tiles
        "f32-rule": ((1, 2, 512, 32), True, None, False,
                     ft.select_geometry(512, 512, 32, heads=2, itemsize=4)),
        # explicit blocks wider than a sub-tile
        "explicit-128x1024": ((1, 1, 1024, 16), True, None, False,
                              ft.geometry_from_blocks(128, 1024)),
    }


@pytest.mark.parametrize("case", sorted(_geometry_cases()))
def test_flash_geometries_match_reference(case):
    """Forward AND gradients of every geometry against the plain-XLA
    reference, in interpret mode (the same tile code Mosaic compiles)."""
    import importlib

    fa = importlib.import_module("kubeflow_tpu.ops.flash_attention")
    shape, causal, window, segments, geometry = _geometry_cases()[case]
    b, _, s, d = shape
    q, k, v, w = (
        jax.random.normal(kk, shape, jnp.float32)
        for kk in jax.random.split(jax.random.PRNGKey(0), 4)
    )
    seg = None
    if segments:
        rng = np.random.RandomState(1)
        seg = jnp.asarray(np.sort(rng.randint(0, 3, (b, s)), axis=-1))

    def loss_flash(q, k, v):
        out = fa._flash(
            q, k, v, seg, seg, causal, d ** -0.5, geometry, (True, window)
        )
        return (out * w).sum(), out

    def loss_ref(q, k, v):
        out = reference_attention(
            q, k, v, causal=causal, window=window,
            q_segment_ids=seg, kv_segment_ids=seg,
        )
        return (out * w).sum(), out

    (_, out), grads = jax.value_and_grad(
        loss_flash, argnums=(0, 1, 2), has_aux=True
    )(q, k, v)
    (_, ref), grads_ref = jax.value_and_grad(
        loss_ref, argnums=(0, 1, 2), has_aux=True
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for name, a, r in zip("qkv", grads, grads_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), atol=5e-5,
            err_msg=f"d{name} mismatch ({case})",
        )


def test_flash_kernels_are_named_by_geometry():
    """The device trace tells forward, dq and dkv apart, and which
    geometry engaged: each pallas_call carries both in its name."""
    import re

    q = jnp.zeros((1, 4, 512, 64), jnp.bfloat16)
    seg = jnp.ones((1, 512), jnp.int32)

    def loss(q, k, v):
        return flash_attention(
            q, k, v, q_segment_ids=seg, kv_segment_ids=seg,
            block_q=None, block_k=None, interpret=True,
        ).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    names = set(re.findall(r"flash_[a-z]+_q\d+_k\d+_t\d+_h\d+", text))
    assert {n.split("_")[1] for n in names} == {"fwd", "dq", "dkv"}, names


def test_flash_fully_masked_rows_are_zero():
    """A q row whose segment matches no key: output and every gradient
    through it exactly zero (the ring's skipped partials rely on it)."""
    b, h, s, d = 1, 2, 64, 16
    q, k, v = (
        jax.random.normal(kk, (b, h, s, d), jnp.float32)
        for kk in jax.random.split(jax.random.PRNGKey(2), 3)
    )
    qseg = jnp.concatenate(
        [jnp.full((b, 16), 7, jnp.int32), jnp.ones((b, s - 16), jnp.int32)], 1
    )
    kseg = jnp.ones((b, s), jnp.int32)

    from kubeflow_tpu.ops.flash_attention import flash_attention_bwd

    out, lse = flash_attention(
        q, k, v, q_segment_ids=qseg, kv_segment_ids=kseg, block_q=32,
        block_k=32, interpret=True, return_residuals=True,
    )
    assert lse.shape == (b, h, s)
    dq, dk, dv = flash_attention_bwd(
        q, k, v, out, lse, jnp.ones_like(out), causal=False,
        q_segment_ids=qseg, kv_segment_ids=kseg, block_q=32, block_k=32,
        interpret=True,
    )
    assert not np.asarray(dq[:, :, :16]).any()
    ref_dk, ref_dv = jax.grad(
        lambda k, v: reference_attention(
            q[:, :, 16:], k, v, q_segment_ids=qseg[:, 16:],
            kv_segment_ids=kseg,
        ).sum(),
        argnums=(0, 1),
    )(k, v)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(ref_dk), atol=5e-5)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(ref_dv), atol=5e-5)


def test_flash_auto_blocks_parity():
    """block_q=None routes through select_blocks and stays exact."""
    B, H, S, D = 1, 2, 256, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, D), jnp.float32) for kk in ks)
    out = flash_attention(
        q, k, v, causal=True, block_q=None, block_k=None, interpret=True
    )
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )
