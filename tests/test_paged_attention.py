"""Pallas paged decode-attention kernel (ops/paged_attention.py) + int8
KV-cache quantization: the vLLM PagedAttention-kernel analog.

Two contracts pinned here, in two layers:

1. Kernel layer — ``paged_attention`` against a numpy oracle that walks
   the block table by hand: GQA folding, sliding window, the (K+1)-wide
   speculative verify span (in-span causal mask), int8 dequantize-in-
   kernel, and the pos0=0 first-token edge. ``interpret=True`` runs the
   Mosaic interpreter on CPU, so these are real kernel-semantics tests,
   not a shadow implementation.

2. Engine layer — the kernel read (what the model chooses for a short
   span when ``interpret_kernels`` asks for the interpreter; on a TPU,
   for itself) is a READ-PATH SWAP, NOT A NUMERICS CHANGE: byte-identical
   greedy streams vs the XLA gather (the same model without the
   interpreter, on this CPU) across the whole engine matrix (churn,
   chunked prefill, prefix hits, mid-stream cancellation, spec K=4,
   pipeline 0/1). int8 KV is lossy by
   design, so its contract is different: gather and kernel must agree
   with each other EXACTLY (same dequant arithmetic), the token stream
   must track the fp32 engine within a stated tolerance, the quant-error
   gauge must be small but nonzero, and prefix export/import must refuse
   to mix quantized and float payloads.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dataclasses

from kubeflow_tpu.models.transformer import (
    PAGED_FLASH_MIN_SPAN,
    PAGED_KERNEL_MAX_SPAN,
    PAGED_KERNEL_MAX_SPAN_ONE_ROW,
    TransformerConfig,
    TransformerLM,
    paged_flash_attention,
    paged_flash_read,
    paged_gather_attention,
    paged_kernel_read,
)
from kubeflow_tpu.ops.flash_tuning import (
    PagedTile,
    Tile,
    select_geometry,
    select_paged_geometry,
    select_span_tile,
    span_kv_block,
)
from kubeflow_tpu.ops.paged_attention import (
    _FIRST,
    _LAST,
    _work_list,
    dequantize_kv,
    paged_attention,
    paged_kernel_name,
    paged_work,
    quantize_kv,
)
from kubeflow_tpu.serve.engine import LMEngine
from kubeflow_tpu.serve.server import (
    decode_prefix_entries,
    encode_prefix_entries,
)

CFG = TransformerConfig(
    vocab_size=89, d_model=32, n_layers=2, n_heads=4, d_ff=64,
    causal=True, max_seq_len=256, attn_impl="reference", dtype=jnp.float32,
    interpret_kernels=True,
)
#: the same model on the gather read path: on this CPU the paged branch
#: takes the kernel only where the configuration asks for the interpreter
CFG_GATHER = dataclasses.replace(CFG, interpret_kernels=False)
EOS = 1


@pytest.fixture(scope="module")
def model_and_params():
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    return model, params


def _prompts(rng, n, lo=3, hi=25, vocab=89):
    return [
        [int(x) for x in rng.integers(2, vocab, size=rng.integers(lo, hi))]
        for _ in range(n)
    ]


# ------------------------------------------------------------ kernel unit


def _oracle(q, k_pool, v_pool, table, pos0, P, window=None,
            k_scale=None, v_scale=None):
    """Straight-line numpy paged attention: gather the whole horizon
    through the block table, mask, softmax in f64-free f32."""
    B, H, S, D = q.shape
    Hkv = k_pool.shape[1]                       # token-major pools
    G = H // Hkv
    W = table.shape[1] * P
    j = np.arange(W)
    out = np.zeros((B, H, S, D), np.float32)
    kf = np.asarray(k_pool, np.float32)
    vf = np.asarray(v_pool, np.float32)
    if k_scale is not None:
        kf = kf * np.asarray(k_scale).T[:, :, None]
        vf = vf * np.asarray(v_scale).T[:, :, None]
    for b in range(B):
        flat = np.asarray(table)[b, j // P] * P + j % P
        K = kf[flat].transpose(1, 0, 2)         # (Hkv, W, D)
        V = vf[flat].transpose(1, 0, 2)
        for h in range(H):
            hk = h // G
            for s in range(S):
                qpos = pos0[b] + s
                mask = j <= qpos
                if window is not None:
                    mask &= j > qpos - window
                sc = (np.asarray(q[b, h, s], np.float32) @ K[hk].T)
                sc = sc / np.sqrt(D)
                sc = np.where(mask, sc, -1e30)
                sc = sc - sc.max()
                p = np.exp(sc)
                p /= p.sum()
                out[b, h, s] = p @ V[hk]
    return out


@pytest.mark.parametrize(
    "name,kw",
    [
        ("decode", dict()),
        ("gqa_span", dict(S=5)),
        ("window", dict(S=3, window=24)),
        ("mha", dict(H=2, Hkv=2)),
        ("int8_span", dict(S=5, quant=True)),
        ("int8_window", dict(S=2, window=20, quant=True)),
    ],
)
def test_kernel_matches_oracle(name, kw):
    rng = np.random.default_rng(hash(name) % 2**31)
    B, H, Hkv, S, D, P = 2, kw.pop("H", 4), kw.pop("Hkv", 2), \
        kw.pop("S", 1), 64, 16
    window = kw.pop("window", None)
    quant = kw.pop("quant", False)
    n_pages, W_pages = 8, 4
    T = n_pages * P
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    kp = rng.normal(size=(T, Hkv, D)).astype(np.float32)
    vp = rng.normal(size=(T, Hkv, D)).astype(np.float32)
    # random distinct non-scratch pages per row; row 1 ends mid-page so
    # the partial-last-page mask is exercised every run
    table = np.zeros((B, W_pages), np.int32)
    perm = rng.permutation(np.arange(1, n_pages))
    table[0] = perm[:W_pages]
    table[1] = perm[:W_pages][::-1]
    pos0 = np.array([W_pages * P - S, (W_pages - 1) * P - S], np.int32)
    ks = vs = None
    if quant:
        kq, ks = quantize_kv(jnp.asarray(kp))    # scales (T, Hkv) ...
        vq, vs = quantize_kv(jnp.asarray(vp))
        kpo, vpo, ks, vs = kq, vq, ks.T, vs.T    # ... stored (Hkv, T)
    else:
        kpo, vpo = jnp.asarray(kp), jnp.asarray(vp)
    out = paged_attention(
        q, kpo, vpo, jnp.asarray(table), jnp.asarray(pos0),
        page_size=P, window=window, k_scale=ks, v_scale=vs, interpret=True,
    )
    ref = _oracle(q, kpo, vpo, table, pos0, P, window=window,
                  k_scale=ks, v_scale=vs)
    assert np.max(np.abs(np.asarray(out) - ref)) < 2e-5


def test_kernel_first_token_pos0_zero():
    """pos0=0: exactly one unmasked key; later pages fully masked must
    not poison the accumulator (the exp(0)=1 garbage-tile hazard)."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 2, 1, 32)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(64, 1, 32)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(64, 1, 32)), jnp.float32)
    tb = np.array([[1, 0]], np.int32)
    pos0 = np.array([0], np.int32)
    out = paged_attention(
        q, kp, vp, jnp.asarray(tb), jnp.asarray(pos0),
        page_size=32, interpret=True,
    )
    ref = _oracle(q, kp, vp, tb, pos0, 32)
    assert np.max(np.abs(np.asarray(out) - ref)) < 2e-5
    assert np.all(np.isfinite(np.asarray(out)))


# ------------------------------------------- the geometry, at the cell's
# head shape: `mistral-7b_gen-closed` reads 32 query heads over 8 kv heads
# of 128 through 64-token pages, table widths 1 to 16

CELL = dict(H=32, Hkv=8, D=128, P=64)
#: context (keys a row holds, the query's own among them) of each ragged
#: row: one token; ending exactly on a page boundary; one key into a new
#: page; mid-page; the table's whole width. -1 is a dead row (its table
#: points at the scratch page, its position is real_len + gen_count - 1
#: of a free slot)
RAGGED = (1, 128, 129, 300, None, -1)
#: the rows of a long table (72 or 70 pages): one key, one page, mid-page,
#: two rows whose 2,048-key window starts mid-table, the whole width, a
#: dead row, and -2: a freed row — its table all scratch, its position
#: left where its request ended (the engine's free slots)
LONG = (1, 64, 300, 2500, 3500, None, -1, -2)


def _cell_case(table_pages, *, span=1, quant=False, dtype=jnp.bfloat16,
               seed=0, rows=RAGGED, tight=False):
    """Queries, pools, table and positions at the cell's head shape with
    ``rows`` (None = the table's whole width). ``tight``: each row holds
    the pages its context fills and the pool those pages and the scratch
    page, as the engine's allocator leaves them — small enough that the
    pool bounds the kernel's grid."""
    H, Hkv, D, P = (CELL[k] for k in ("H", "Hkv", "D", "P"))
    rng = np.random.default_rng(seed)
    ctx = [table_pages * P if c is None else c for c in rows]
    ctx = [min(c, table_pages * P) for c in ctx]
    B = len(ctx)
    live = np.asarray([c > 0 for c in ctx])
    held = [-(-c // P) if c > 0 else 0 for c in ctx]
    n_pages = 1 + (sum(held) if tight else B * table_pages)
    T = n_pages * P
    q = jnp.asarray(rng.normal(size=(B, H, span, D)) / np.sqrt(D), dtype)
    kp = jnp.asarray(rng.normal(size=(T, Hkv, D)), dtype)
    vp = jnp.asarray(rng.normal(size=(T, Hkv, D)), dtype)
    if tight:
        pages = iter(1 + rng.permutation(n_pages - 1).astype(np.int32))
        table = np.zeros((B, table_pages), np.int32)
        for b, h in enumerate(held):
            table[b, :h] = [next(pages) for _ in range(h)]
    else:
        table = 1 + rng.permutation(B * table_pages).astype(np.int32).reshape(
            B, table_pages
        )
    table[~live] = 0                      # a dead row owns no page
    # the span's first query sits at context - span (its last at the
    # row's last key); a row shorter than the span starts at 0; a freed
    # row at the table's last key
    pos0 = np.asarray(
        [max(c - span, 0) if c > 0 else -1 if c == -1
         else table_pages * P - span for c in ctx],
        np.int32,
    )
    cache = {"k": kp, "v": vp}
    if quant:
        kq, ks = quantize_kv(kp)
        vq, vs = quantize_kv(vp)
        cache = {"k": kq, "v": vq, "k_scale": ks.T, "v_scale": vs.T}
    return q, cache, jnp.asarray(table), jnp.asarray(pos0), live


def _kernel_vs_gather(q, cache, table, pos0, live, *, tile, window=None):
    """The kernel (interpreter) against the model's own gather read on the
    live rows; a dead row's output is never used, only finite."""
    P = CELL["P"]
    span = q.shape[2]
    got = paged_attention(
        q, cache["k"], cache["v"], table, pos0, page_size=P, window=window,
        k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"),
        interpret=True, tile=tile,
    )
    want = paged_gather_attention(
        q, cache, table, pos0[:, None] + jnp.arange(span)[None, :],
        page_size=P, window=window,
    )
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.all(np.isfinite(got))
    # bf16: the result is rounded to it, and probabilities against a
    # running maximum (kernel) and the row's (gather) round differently
    tol = 2e-2 if q.dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(got[live], want[live], atol=tol, rtol=tol)


def _grid_within_the_pool(case, window=None):
    """The grid of a ``tight`` case: a step per live block and per dead
    row — what `paged_work` counts from the rows' positions and the pages
    they hold — which stays within what the pool can hold, ``B +
    ceil(pool / n)`` blocks, itself shorter here than every row across
    the whole table (the parent's grid)."""
    q, cache, table, pos0, _ = case
    B, W = table.shape
    pool = cache["k"].shape[0] // CELL["P"]
    n = select_paged_geometry(
        table_pages=W, page_size=CELL["P"], kv_heads=CELL["Hkv"],
        groups=CELL["H"] // CELL["Hkv"], span=q.shape[2],
        head_dim=CELL["D"], itemsize=cache["k"].dtype.itemsize,
        quant="k_scale" in cache,
    ).pages
    work = paged_work(
        np.asarray(pos0), table_pages=W, page_size=CELL["P"],
        span=q.shape[2], window=window, pages=n,
        held=(np.asarray(table) != 0).sum(1),
    )
    assert work.steps <= B + -(-pool // n) < B * -(-W // n)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("table_pages", [1, 8, 16, 72])
def test_selected_geometry_matches_gather_on_ragged_rows(table_pages, quant):
    """What the rule chooses for the cell's head shape at table widths 1, 8
    and 16, on ragged rows: a row of one token, a row ending exactly on a
    page boundary, a dead row on the scratch page, a full row. At 72
    pages, the LONG rows in a pool that bounds the grid, with no window
    and a 2,048-key one."""
    if table_pages < 64:
        case = _cell_case(table_pages, quant=quant, seed=table_pages)
        _kernel_vs_gather(*case, tile=None)
        return
    case = _cell_case(
        table_pages, quant=quant, seed=table_pages, rows=LONG, tight=True
    )
    _grid_within_the_pool(case)
    for window in (None, 2048):
        _kernel_vs_gather(*case, tile=None, window=window)


@pytest.mark.parametrize("fold", [0, 1, 2, 4], ids="fold{}".format)
@pytest.mark.parametrize("pages,table_pages", [
    *(pytest.param(p, 16, id=str(p)) for p in (1, 2, 4, 8, 16)),
    pytest.param(16, 72, id="16-table72"),
])
def test_every_pages_per_step_matches_gather(pages, table_pages, fold):
    """Every pages-per-step the rule can return (powers of two up to 16),
    a head at a time and folded in groups of 1, 2 and 4 pages, on a table
    of 16 pages — with a window that leaves whole pages of the longer
    rows behind it (a row past the window) — and sixteen a step on the
    LONG rows of a 72-page table in a pool that bounds the grid."""
    if table_pages == 16:
        case = _cell_case(16, seed=pages)
    else:
        case = _cell_case(table_pages, seed=fold, rows=LONG, tight=True)
    _kernel_vs_gather(
        *case, tile=PagedTile(pages, min(fold, pages)), window=200
    )


@pytest.mark.parametrize("pages,table_pages", [
    pytest.param(2, 6, id="2"), pytest.param(16, 6, id="16"),
    pytest.param(16, 70, id="16-table70"),
])
def test_pages_per_step_int8_and_a_table_it_does_not_divide(pages,
                                                            table_pages):
    """int8 pools through more than one page a step, and a table width
    (6; 70 with the LONG rows, in a pool that bounds the grid) that the
    step's pages do not divide: the pages past the table are neither
    fetched nor computed."""
    rows = RAGGED if table_pages < 64 else LONG
    tight = table_pages >= 64
    case = _cell_case(
        table_pages, quant=True, seed=pages, rows=rows, tight=tight
    )
    _kernel_vs_gather(*case, tile=PagedTile(pages, 0), window=200)
    case = _cell_case(
        table_pages, dtype=jnp.float32, seed=pages + 1, rows=rows,
        tight=tight,
    )
    _kernel_vs_gather(*case, tile=PagedTile(pages, 2))


@pytest.mark.parametrize("span,table_pages", [
    pytest.param(4, 8, id="4"), pytest.param(5, 8, id="5"),
    pytest.param(5, 72, id="5-table72"),
])
def test_verify_span_matches_gather_at_the_cell_shape(span, table_pages):
    """The speculative verify span (K + 1 queries a row) through the
    rule's geometry: in-span causality, rows shorter than the span; on a
    72-page table the LONG rows in a pool that bounds the grid, with no
    window and a 2,048-key one."""
    if table_pages < 64:
        _kernel_vs_gather(*_cell_case(8, span=span, seed=span), tile=None)
        return
    case = _cell_case(table_pages, span=span, seed=span, rows=LONG, tight=True)
    _grid_within_the_pool(case)
    for window in (None, 2048):
        _kernel_vs_gather(*case, tile=None, window=window)


def test_geometry_pinned_at_the_cell_shape():
    """`mistral-7b_gen-closed`'s decode step: 32 rows, table 16 pages of
    64, 8 kv heads x 4 x 128, bf16 — sixteen pages a grid step, the kv
    heads folded into one product, four pages a softmax update: 32 steps
    a layer, 512 over 16 layers (a page a step: 8,192). Narrower tables
    take what they have; a longer span folds two pages an update; int8
    and spans past 16 go a head at a time; the trace names the call."""
    shape = dict(page_size=64, kv_heads=8, groups=4, head_dim=128)
    cell = select_paged_geometry(table_pages=16, span=1, **shape)
    assert cell == PagedTile(16, 4)
    assert paged_kernel_name(64, cell, 8) == "paged_decode_p64_n16_h8_f4"
    assert 32 * -(-16 // cell.pages) * 16 <= 1000
    for w in (1, 2, 4, 8):
        assert select_paged_geometry(
            table_pages=w, span=1, **shape
        ) == PagedTile(w, min(w, 4))
    # the widest table of the deployment (8,320 tokens: 130 pages)
    assert select_paged_geometry(table_pages=130, span=1, **shape).pages == 16
    # the verify span and every other span the engine sends: folded, four
    # pages an update up to four queries a row, two up to sixteen; longer
    # (a direct call with a piece) a head at a time
    assert [
        select_paged_geometry(table_pages=16, span=s, **shape).fold
        for s in (2, 4, 5, 8, 9, 16, 17, 512)
    ] == [4, 4, 2, 2, 2, 2, 0, 0]
    assert select_paged_geometry(
        table_pages=1, span=8, **shape
    ) == PagedTile(1, 1)
    # a head at a time: sixteen products a step, two pages of eight heads
    assert select_paged_geometry(
        table_pages=16, span=1, quant=True, itemsize=1, **shape
    ) == PagedTile(2, 0)
    assert select_paged_geometry(
        table_pages=16, span=512, **shape
    ) == PagedTile(2, 0)
    # f32 pools stage half the pages in the same VMEM
    assert select_paged_geometry(
        table_pages=16, span=1, itemsize=4, **shape
    ).pages == 8


def test_work_list_counts_orders_and_flags_the_grid():
    """`paged_work`, the kernel's grid in numbers, and `_work_list`, the
    grid itself. At both serving cells' decode shapes, on rows as the
    allocator leaves them: Trinity's 96 rows holding all of a 4,096-page
    pool over a 136-page table, 16 pages a step, walk at most 288 steps
    on a window layer (three blocks a row) and 352 on the global one (the
    pool's 4,096 pages in blocks of 16, plus a block a row), where every
    row across the table is 864; `mistral-7b_gen-closed`'s 32 rows over
    16 or 32 pages (contexts up to 1,024 or 2,048) walk 32 and at most
    64, as every row across the table did. Then, on drawn rows: the steps
    ordered by row and by block, one step for a dead row, the live steps
    those of the rows' live blocks, and each row's blocks those a brute
    force finds live."""
    rng = np.random.default_rng(0)
    trinity = dict(page_size=64, span=1, pages=16, table_pages=136)
    for _ in range(20):
        held = rng.multinomial(4095 - 96, np.full(96, 1 / 96)) + 1
        held = held.clip(max=136)
        pos0 = held * 64 - 1 - rng.integers(0, 64, 96)
        assert paged_work(pos0, window=2048, held=held, **trinity).steps <= 288
        assert paged_work(pos0, window=None, held=held, **trinity).steps <= 352
    assert 96 * -(-136 // 16) == 864
    mistral = dict(page_size=64, span=1, window=4096, pages=16)
    assert paged_work(
        rng.integers(0, 1024, 32), table_pages=16, **mistral
    ).steps == 32
    assert paged_work(
        rng.integers(0, 2048, 32), table_pages=32, **mistral
    ).steps <= 64
    P, n, W = 16, 4, 40
    for window, span in ((None, 1), (100, 1), (None, 5), (37, 5)):
        for _ in range(20):
            B = int(rng.integers(1, 9))
            held = rng.integers(0, W + 1, B)
            # a row's span lies inside what it holds; a row that holds
            # nothing keeps a stale position (or -1)
            pos0 = np.where(
                held > 0,
                (held * P - span - rng.integers(0, P, B)).clip(min=0),
                rng.choice([-1, W * P - span], B),
            )
            work = paged_work(
                pos0, table_pages=W, page_size=P, span=span, window=window,
                pages=n, held=held,
            )
            for b in range(B):
                keys = np.arange(pos0[b], pos0[b] + span)
                live = {
                    page for page in range(held[b]) if any(
                        page * P <= k and (
                            window is None or page * P + P - 1 > k - window
                        ) for k in keys
                    )
                }
                assert work.blocks[b] == len({p // n for p in live})
            length = B * -(-W // n)
            row, block, flags, steps = map(
                np.asarray, _work_list(work, n, length)
            )
            assert steps == work.steps == np.maximum(work.blocks, 1).sum()
            assert steps <= length == len(row)
            real = slice(0, int(steps))
            assert (np.diff(row[real]) >= 0).all()
            assert np.bincount(row[real], minlength=B).tolist() == (
                np.maximum(work.blocks, 1).tolist()
            )
            for b in range(B):
                mine = row[real] == b
                assert (np.diff(block[real][mine]) == 1).all()
                assert flags[real][mine][0] & _FIRST
                assert flags[real][mine][-1] & _LAST
                assert (flags[real][mine][1:] & _FIRST == 0).all()
                assert (flags[real][mine][:-1] & _LAST == 0).all()
                if work.blocks[b]:
                    assert block[real][mine][0] == work.lo[b] // n
            assert work.live == work.blocks.sum() == (
                work.steps - (work.blocks == 0).sum()
            )
            # entries past the grid repeat its last step and do nothing
            assert (row[int(steps):] == row[int(steps) - 1]).all()
            assert (block[int(steps):] == block[int(steps) - 1]).all()
            assert not flags[int(steps):].any()


def test_read_path_is_chosen_by_span_interpreter_and_mesh():
    """`paged_kernel_read`: on this CPU the kernel only where the
    configuration asks for the interpreter, only for decode and verify
    shapes — up to 16 queries of several rows, up to 8 of one row (a
    prefill piece's window is its own: a 16-token piece gathers) — and
    not with a mesh in force."""
    rows = 32
    assert paged_kernel_read(CFG, rows, 1) and paged_kernel_read(CFG, rows, 5)
    assert paged_kernel_read(CFG, rows, PAGED_KERNEL_MAX_SPAN)
    assert not paged_kernel_read(CFG, rows, PAGED_KERNEL_MAX_SPAN + 1)
    assert not paged_kernel_read(CFG, rows, 512)
    # one row: an engine of one row decodes through the kernel, a piece
    # of 16 tokens (the smallest suffix bucket) or 512 gathers
    assert (PAGED_KERNEL_MAX_SPAN, PAGED_KERNEL_MAX_SPAN_ONE_ROW) == (16, 8)
    assert paged_kernel_read(CFG, 1, 1) and paged_kernel_read(CFG, 1, 8)
    assert not paged_kernel_read(CFG, 1, 9)
    assert not paged_kernel_read(CFG, 1, 16)
    assert not paged_kernel_read(CFG, 1, 512)
    assert not paged_kernel_read(CFG_GATHER, rows, 1)
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:2]).reshape(1, 2), ("data", "model")
    )
    with jax.set_mesh(mesh):
        assert not paged_kernel_read(CFG, rows, 1)


@pytest.mark.parametrize("rows", [1, 32], ids="rows{}".format)
@pytest.mark.parametrize("span", [1, 8, 16, 48, 512, 1024, 1000])
def test_read_path_follows_span_rows_backend_and_mesh(span, rows):
    """The paged branch's three read paths, by what the call can observe:
    the paged kernel for a decode or verify shape (up to 16 queries of
    several rows, up to 8 of one), the gathered window through the flash
    forward kernel for a span of whole 128-row q blocks (a prefill
    piece: 512, 1,024), the XLA gather for what lies between (17 to 127)
    or off the block (1,000) — and for everything on a plain CPU or with
    a mesh in force."""
    kernel = span <= (PAGED_KERNEL_MAX_SPAN if rows > 1
                      else PAGED_KERNEL_MAX_SPAN_ONE_ROW)
    flash = span >= PAGED_FLASH_MIN_SPAN and span % PAGED_FLASH_MIN_SPAN == 0
    assert PAGED_FLASH_MIN_SPAN == 128 and not (kernel and flash)
    assert paged_kernel_read(CFG, rows, span) == kernel
    assert paged_flash_read(CFG, span) == flash
    assert flash == (span in (512, 1024))
    # a plain CPU: no kernel of either kind
    assert not paged_kernel_read(CFG_GATHER, rows, span)
    assert not paged_flash_read(CFG_GATHER, span)
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:2]).reshape(1, 2), ("data", "model")
    )
    with jax.set_mesh(mesh):
        assert not paged_kernel_read(CFG, rows, span)
        assert not paged_flash_read(CFG, span)


#: a prefill piece's place in its row, as the engine sends them: (span,
#: its first position, the table's pages, the pages the row holds — None
#: = all, the queries counted — None = all)
PIECES = {
    # a prompt's first piece: the table is the piece's own two pages
    "offset0": (128, 0, 2, None, None),
    # far into a long prompt, two q blocks: a window layer gathers the
    # 35 pages its windows reach (made up to 48), a global one all 48
    "deep": (256, 2816, 48, None, None),
    # after a prefix hit whose base (16 x 129) is no multiple of the piece
    "prefix-base": (128, 2064, 48, None, None),
    # a table wider than the row holds: the pages past the piece are the
    # scratch page, as are the pages the gathered width is made up with
    # (40 pages of 64 -> 3,072 keys)
    "wide-table": (128, 128, 40, 4, None),
    # the widest table is no power of two (the engine caps it at the
    # row's pages): 52 pages, made up to 64
    "capped-table": (128, 3200, 52, None, None),
    # a prompt's last piece: 37 tokens and 91 pad positions, whose writes
    # went to the scratch page and whose outputs nobody reads
    "last-piece-pads": (128, 2048, 34, 33, 37),
}


@pytest.mark.parametrize("groups,kv_heads", [(8, 1), (4, 2)],
                         ids=["groups8", "groups4"])
@pytest.mark.parametrize("window", [2048, None], ids=["window2048", "global"])
@pytest.mark.parametrize("piece", sorted(PIECES))
def test_flash_read_matches_gather_on_a_prefill_piece(piece, window, groups,
                                                      kv_heads):
    """`paged_flash_attention` — the row's window gathered out of the
    pool, then the flash forward kernel (interpreter) with the queries'
    offset as a scalar and grouped heads read in place — against
    `paged_gather_attention`, which masks by position over a float32
    score array: the same keys, the same masks, another order of
    summation."""
    span, offset, pages, held, valid = PIECES[piece]
    P, D = 64, 32
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, kv_heads * groups, span, D)), jnp.float32)
    pool = lambda: jnp.asarray(
        rng.normal(size=((1 + pages) * P, kv_heads, D)), jnp.float32
    )
    cache = {"k": pool(), "v": pool()}
    table = 1 + rng.permutation(pages).astype(np.int32)[None, :]
    table[:, held:] = 0
    positions = offset + jnp.arange(span)[None, :]
    kw = dict(page_size=P, window=window)
    got = paged_flash_attention(
        q, cache, jnp.asarray(table), positions, interpret=True, **kw
    )
    want = paged_gather_attention(q, cache, jnp.asarray(table), positions, **kw)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(
        np.asarray(got)[:, :, :valid], np.asarray(want)[:, :, :valid],
        atol=2e-6, rtol=2e-6,
    )


def test_flash_read_two_rows_int8_pool_and_the_widths_it_pads():
    """Several rows, each with an offset of its own, over an int8 pool
    (dequantized after the gather, as the gather path does); and the
    widths the engine's tables give, made up to whole kv blocks so that
    the kernel runs in 1,024-key sub-tiles at every one of them."""
    P, D, Hkv, G, span = 64, 32, 2, 4, 128
    rng = np.random.default_rng(3)
    pages = 40
    q = jnp.asarray(rng.normal(size=(2, Hkv * G, span, D)), jnp.float32)
    kq, ks = quantize_kv(jnp.asarray(
        rng.normal(size=((1 + 2 * pages) * P, Hkv, D)), jnp.float32))
    vq, vs = quantize_kv(jnp.asarray(
        rng.normal(size=((1 + 2 * pages) * P, Hkv, D)), jnp.float32))
    cache = {"k": kq, "v": vq, "k_scale": ks.T, "v_scale": vs.T}
    table = jnp.asarray(
        1 + rng.permutation(2 * pages).astype(np.int32).reshape(2, pages)
    )
    positions = jnp.asarray([2064, 0])[:, None] + jnp.arange(span)[None, :]
    kw = dict(page_size=P, window=2048)
    got = paged_flash_attention(q, cache, table, positions, interpret=True, **kw)
    want = paged_gather_attention(q, cache, table, positions, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)
    # table widths of both serving cells (pages of 64) and a window
    # layer's reach: the width handed to the kernel, its tile — the rule's
    # q block and sub-tile, one sub-tile staged a step
    for keys, padded, block_k in [
        (512, 512, 512), (1024, 1024, 1024), (2048, 2048, 1024),
        (4096, 4096, 1024), (8192, 8192, 1024), (8704, 9216, 1024),
        (3136, 4096, 1024), (8320, 9216, 1024),
    ]:
        block = span_kv_block(keys)
        assert -(-keys // block) * block == padded
        assert select_span_tile(1024, padded, 128) == Tile(
            512, block_k, block_k, 1
        )
    # the raw widths: sub-tiles of 128 under 2,176 staged rows; a block
    # that is the whole 3,136
    assert select_geometry(1024, 8704, 128).fwd == Tile(512, 2176, 128, 1)
    assert select_geometry(1024, 3136, 128).fwd == Tile(512, 3136, 3136, 1)


def test_act_constraint_gives_no_hint_on_a_mesh_without_its_axes():
    """The model's activation hint under the meshes it can be traced
    with: `build_mesh`'s (every axis: the constraint the trainer has
    always had), none, and a serving engine's ("data", "model"), which
    lacks fsdp and seq — no hint there (the constraint would name axes the
    mesh does not have)."""
    from kubeflow_tpu.core import MeshSpec, build_mesh
    from kubeflow_tpu.models.transformer import _act_constraint

    x = jnp.zeros((2, 8, 4))
    plain = jax.make_jaxpr(_act_constraint)(x)
    assert "sharding_constraint" not in str(plain)
    with jax.set_mesh(jax.sharding.Mesh(
        np.asarray(jax.devices()[:2]).reshape(1, 2), ("data", "model")
    )):
        assert str(jax.make_jaxpr(_act_constraint)(x)) == str(plain)
    with jax.set_mesh(build_mesh(MeshSpec(data=2), devices=jax.devices()[:2])):
        hinted = str(jax.make_jaxpr(_act_constraint)(x))
    assert "sharding_constraint" in hinted
    assert "data" in hinted and "fsdp" in hinted and "seq" in hinted


def test_engine_under_a_mesh_keeps_the_gather_read():
    """An engine under a 2-device ("data", "model") mesh traces and runs
    its programs with the mesh in force (`jax.set_mesh`), so the model
    sees it: the configuration asks for the kernel (the interpreter is
    on) and the engine still gathers — a Mosaic kernel is not partitioned
    automatically. Same streams as the engine with no mesh, which reads
    through the kernel; entering the scope at every dispatch compiles
    nothing again."""
    from kubeflow_tpu.parallel.sharding import transformer_rules

    cfg = dataclasses.replace(CFG, vocab_size=96)  # divides over "model"
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    prompts = _prompts(np.random.default_rng(41), 3)
    want = _run_engine(params, prompts, cfg=cfg)  # no mesh: the kernel
    mesh = jax.sharding.Mesh(
        np.asarray(jax.devices()[:2]).reshape(1, 2), ("data", "model")
    )
    eng = LMEngine(
        model, cfg, params, max_batch=4, max_seq=96,
        chunk_steps=4, prefill_buckets=(32,), eos_id=EOS,
        kv_pool_tokens=16 * 24, page_size=16,
        mesh=mesh, rules=transformer_rules(fsdp=False),
    ).start()
    try:
        assert eng.kernel_read is False
        k0 = next(iter(eng.cache.values()))["k"]
        assert tuple(k0.sharding.spec) == (None, "model", None)
        got = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
        compiled = (eng._chunk._cache_size(), eng._suffix_prefill._cache_size())
        again = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
        assert (
            eng._chunk._cache_size(), eng._suffix_prefill._cache_size()
        ) == compiled
        assert eng.stats["chunks"] > 0
        assert eng.stats["decode_chunks_kernel_read"] == 0
    finally:
        eng.stop()
    assert got == want and again == want


def test_quantize_roundtrip_bound():
    """Per-token-per-head symmetric int8: roundtrip error is bounded by
    half a quantization step of that token's own scale, and the scale
    floor keeps all-zero tokens representable."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, 48, 64)) * 3.0, jnp.float32)
    codes, scale = quantize_kv(x)
    assert codes.dtype == jnp.int8 and scale.shape == (2, 48)
    back = dequantize_kv(codes, scale)
    step = np.asarray(scale)[:, :, None]
    assert np.all(np.abs(np.asarray(back) - np.asarray(x)) <= step * 0.5 + 1e-7)
    # zero token: scale floors, codes stay zero, roundtrip exact
    z_codes, z_scale = quantize_kv(jnp.zeros((1, 4, 8), jnp.float32))
    assert np.all(np.asarray(z_codes) == 0) and np.all(np.asarray(z_scale) > 0)


# ------------------------------------------------- engine: kernel parity

MAX_NEW = 12


def _run_engine(params, prompts, *, max_new=MAX_NEW, cfg=CFG, **kw):
    """Serve ``prompts``; the read path is ``cfg``'s (CFG: the kernel under
    the interpreter for decode and verify spans, CFG_GATHER: the gather).
    The engine's counter must say the same."""
    kw.setdefault("kv_pool_tokens", 16 * 24)
    kw.setdefault("page_size", 16)
    eng = LMEngine(
        TransformerLM(cfg), cfg, params, max_batch=4, max_seq=96,
        chunk_steps=4, prefill_buckets=(32,), eos_id=EOS, **kw,
    ).start()
    assert eng.kernel_read == cfg.interpret_kernels
    try:
        # concurrent submits → requests batch up to max_batch, so the
        # parity matrix also exercises batched decode (streams are
        # row-independent, so results don't depend on batch packing)
        with ThreadPoolExecutor(len(prompts)) as ex:
            futs = [
                ex.submit(eng.submit, p, max_new_tokens=max_new)
                for p in prompts
            ]
            outs = [f.result() for f in futs]
        assert eng.stats["decode_chunks_kernel_read"] == (
            eng.stats["chunks"] if cfg.interpret_kernels else 0
        )
        return outs
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def shared_prompts():
    return _prompts(np.random.default_rng(7), 5)


@pytest.fixture(scope="module")
def gather_streams(model_and_params, shared_prompts):
    """The gather/fp32 baseline every parity test compares against —
    computed once; both the kernel matrix and the int8 contract measure
    relative to these streams."""
    model, params = model_and_params
    return _run_engine(params, shared_prompts, cfg=CFG_GATHER)


@pytest.mark.slow
def test_kernel_byte_parity_matrix(model_and_params, shared_prompts,
                                   gather_streams):
    """The read-path swap across the engine matrix: plain decode,
    pipeline_depth=0, and spec K=4 all emit byte-identical streams under
    gather and kernel."""
    model, params = model_and_params
    for name, kw in [
        ("kernel", dict()),
        ("kernel_pipe0", dict(pipeline_depth=0)),
        ("kernel_spec4", dict(spec_draft_tokens=4)),
    ]:
        got = _run_engine(params, shared_prompts, **kw)
        assert got == gather_streams, name


def test_kernel_parity_churn_chunked_prefix_cancel(model_and_params):
    """Gather vs kernel under the full serving shape at once: staggered
    concurrent arrivals (admission churn), chunked prefill, prefix-cache
    hits (same long prompt resubmitted), and a mid-stream cancellation
    walking away after one chunk."""
    model, params = model_and_params
    rng = np.random.default_rng(23)
    prompts = _prompts(rng, 3, lo=3, hi=14) + [
        [int(x) for x in rng.integers(2, 89, size=n)] for n in (34, 41)
    ]

    def run(cfg):
        eng = LMEngine(
            TransformerLM(cfg), cfg, params, max_batch=3, max_seq=96,
            chunk_steps=4, prefill_buckets=(48,), eos_id=EOS,
            prefill_chunk=16, prefix_cache_entries=4,
            kv_pool_tokens=16 * 24, page_size=16,
        ).start()
        assert eng.kernel_read == cfg.interpret_kernels
        outs: dict[int, list[int]] = {}
        errors: list[Exception] = []

        def worker(i):
            try:
                time.sleep(0.02 * i)
                outs[i] = eng.submit(prompts[i], max_new_tokens=8)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        try:
            threads = [
                threading.Thread(target=worker, args=(i,))
                for i in range(len(prompts))
            ]
            for t in threads:
                t.start()
            stream = eng.stream(prompts[0], max_new_tokens=12)
            next(iter(stream))
            stream.close()
            for t in threads:
                t.join(180)
            # exact resubmit of the long prompt after its first run
            # completed → a deterministic prefix-cache hit
            outs["resub"] = eng.submit(prompts[-1], max_new_tokens=8)
            stats = dict(eng.stats)
        finally:
            eng.stop()
        assert not errors, errors
        return outs, stats

    want, want_stats = run(CFG_GATHER)
    got, got_stats = run(CFG)
    assert got == want
    assert want_stats["decode_chunks_kernel_read"] == 0
    assert got_stats["decode_chunks_kernel_read"] == got_stats["chunks"] > 0
    assert got_stats["max_concurrent"] >= 2  # churn really happened
    assert got_stats["prefix_hits"] >= 1  # the resubmit hit the cache


def test_kernel_and_int8_need_no_pool_size_named(model_and_params):
    """One cache layout: the kernel read path and the int8 pool run on the
    pool the engine derives when no ``kv_pool_tokens`` is given (they
    used to be refused there); the read path is no setting any more, and
    unknown values of what is one are still refused."""
    model, params = model_and_params
    kernel = LMEngine(
        model, CFG, params, max_batch=2, max_seq=64, chunk_steps=4,
        eos_id=EOS,
    )
    assert kernel.kernel_read
    int8 = LMEngine(
        model, CFG, params, max_batch=2, max_seq=64, chunk_steps=4,
        eos_id=EOS, kv_quant="int8",
    )
    layer = next(iter(int8.cache.values()))
    assert layer["k"].dtype == jnp.int8 and "k_scale" in layer
    for eng in (kernel, int8):
        assert eng.pager.stats()["pages_total"] == 2  # one 64-token page a row
    with pytest.raises(TypeError, match="paged_attn_impl"):
        LMEngine(
            model, CFG, params, max_batch=2, max_seq=64, chunk_steps=4,
            eos_id=EOS, kv_pool_tokens=16 * 8, page_size=16,
            paged_attn_impl="kernel",
        )
    with pytest.raises(ValueError, match="kv_quant"):
        LMEngine(
            model, CFG, params, max_batch=2, max_seq=64, chunk_steps=4,
            eos_id=EOS, kv_pool_tokens=16 * 8, page_size=16, kv_quant="fp8",
        )


# ---------------------------------------------------- engine: int8 KV


@pytest.fixture(scope="module")
def int8_gather(model_and_params, shared_prompts):
    """One int8 gather engine run shared by the parity and gauge tests:
    (streams, kv_quant_error observed after serving the prompts)."""
    model, params = model_and_params
    eng = LMEngine(
        TransformerLM(CFG_GATHER), CFG_GATHER, params, max_batch=4,
        max_seq=96, chunk_steps=4, prefill_buckets=(32,), eos_id=EOS,
        kv_pool_tokens=16 * 24, page_size=16, kv_quant="int8",
    ).start()
    assert not eng.kernel_read
    try:
        with ThreadPoolExecutor(len(shared_prompts)) as ex:
            futs = [
                ex.submit(eng.submit, p, max_new_tokens=MAX_NEW)
                for p in shared_prompts
            ]
            outs = [f.result() for f in futs]
        err = eng.overlap["kv_quant_error"]
    finally:
        eng.stop()
    return outs, err


def test_int8_gather_kernel_agree_and_track_fp32(model_and_params,
                                                 shared_prompts,
                                                 gather_streams,
                                                 int8_gather):
    """int8's two-sided contract: gather and kernel dequantize with the
    SAME arithmetic (exact agreement), and the quantized stream tracks
    the fp32 engine closely — on this tiny model (d_model=32, vocab 89,
    near-tie logits) a handful of flips is expected, so the tolerance is
    a match fraction, not equality. Spec decode on the same quantized
    pool must not introduce further drift vs its own non-spec run."""
    model, params = model_and_params
    g8, _ = int8_gather
    k8 = _run_engine(params, shared_prompts, kv_quant="int8")
    assert g8 == k8  # same dequant arithmetic → bitwise same streams
    pairs = [
        (a, b) for p, q in zip(gather_streams, g8) for a, b in zip(p, q)
    ]
    match = float(np.mean([a == b for a, b in pairs]))
    assert match >= 0.85, match
    s8 = _run_engine(params, shared_prompts, kv_quant="int8",
                     spec_draft_tokens=4)
    assert s8 == k8  # verify-span reads the same quantized pool


def test_int8_quant_error_gauge(int8_gather):
    """The EWMA gauge is live (nonzero — quantization really is lossy)
    and small (int8 per-token scales keep relative error well under 5%),
    and it shows up in engine_stats for /metrics exposition."""
    _, err = int8_gather
    assert 0.0 < err < 0.05, err


def test_prefix_transfer_rejects_mixed_quantization(model_and_params):
    """Cross-replica prefix-KV transfer: a float engine must skip int8
    payloads (it would attend to raw codes) and an int8 engine must skip
    float payloads (no scales to dequantize with) — in both directions,
    through the real wire encode/decode. Like-to-like int8 transfer
    still works."""
    model, params = model_and_params
    rng = np.random.default_rng(13)
    prompt = [int(x) for x in rng.integers(2, 89, size=40)]

    def engine(quant):
        return LMEngine(
            model, CFG, params, max_batch=2, max_seq=96, chunk_steps=4,
            prefill_buckets=(48,), eos_id=EOS, prefix_cache_entries=4,
            kv_pool_tokens=16 * 24, page_size=16, kv_quant=quant,
        ).start()

    fp_eng, q_eng = engine("none"), engine("int8")
    try:
        fp_eng.submit(prompt, max_new_tokens=4)
        q_eng.submit(prompt, max_new_tokens=4)
        fp_entries = fp_eng.export_prefix_entries()
        q_entries = q_eng.export_prefix_entries()
        assert fp_entries and q_entries
        # int8 entries carry scales on the wire; float entries don't
        layer0 = next(iter(q_entries[0][1].values()))
        assert set(layer0) == {"k", "v", "k_scale", "v_scale"}
        assert layer0["k"].dtype == np.int8
        # wire roundtrip preserves the key-set discriminator
        fp_wire = decode_prefix_entries(encode_prefix_entries(fp_entries))
        q_wire = decode_prefix_entries(encode_prefix_entries(q_entries))
        assert fp_eng.import_prefix_entries(q_wire) == 0
        assert q_eng.import_prefix_entries(fp_wire) == 0
        # like-to-like works end to end
        peer = engine("int8")
        try:
            assert peer.import_prefix_entries(q_wire) == len(q_wire)
        finally:
            peer.stop()
    finally:
        fp_eng.stop()
        q_eng.stop()


def test_int8_pool_bytes_quartered(model_and_params):
    """The density claim, measured on the live cache: int8 k/v pools
    bill 1 byte/elem vs f32's 4 (half of a bf16 pool), with the f32
    per-token scale side arrays a ~1/D overhead on top."""
    model, params = model_and_params

    def pool_bytes(quant):
        eng = LMEngine(
            model, CFG, params, max_batch=2, max_seq=64, chunk_steps=4,
            eos_id=EOS, kv_pool_tokens=16 * 8, page_size=16, kv_quant=quant,
        )
        kv = sum(
            int(lc[w].nbytes) for lc in eng.cache.values() for w in ("k", "v")
        )
        sc = sum(
            int(a.nbytes)
            for lc in eng.cache.values()
            for w, a in lc.items() if w.endswith("_scale")
        )
        return kv, sc

    fp_kv, fp_sc = pool_bytes("none")
    q_kv, q_sc = pool_bytes("int8")
    assert fp_sc == 0
    assert q_kv * 4 == fp_kv
    head_dim = CFG.d_model // CFG.n_heads
    assert q_sc == q_kv * 4 // head_dim  # one f32 scale per token per head
