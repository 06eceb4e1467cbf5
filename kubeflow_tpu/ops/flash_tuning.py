"""Flash-attention tile geometry (a rule on the observed shape) and the
sweep tool that measured it; the paged kernel's geometry, its sweep tool
and its page-size table.

The three flash kernels (forward, dq, dkv) each take a :class:`Tile`: how
many q and kv rows one grid step stages, the width of the sub-tile its
in-kernel loop computes at a time, and how many heads share the step.
:func:`select_geometry` chooses all three from what a call can observe at
trace time — sequence lengths, head size, element size, head count — so
BERT's short non-causal rows with small heads and a decoder's long causal
rows get different parameters of one algorithm, with no option
and no file to keep in step. The rule's numbers were measured on a TPU v5e
with :func:`sweep_blocks` at the two training shapes of the benchmark
(PERF.md section 6, PR 26): a grid step costs about 0.35 us whatever it
computes, so a step stages whole rows where they fit and loops over score
tiles of a quarter to half a million elements, and only then do the MXU
and the VPU, not the step, set the time.

The serving engine's prefill piece runs the forward kernel alone over
its row's gathered window (``flash_attention_span``); :func:`span_kv_block`
says what width the gather makes the window up to, so that the same rule
tiles every page-table width (PERF.md section 6, PR 35).

``flash_attention(block_q=None)`` (and TransformerConfig
``attn_block_q=None``) routes through the rule; explicit ``block_q`` /
``block_k`` are honoured as the staged block of every kernel
(:func:`geometry_from_blocks`), which is what the ring hops pass.

The paged decode kernel (`ops/paged_attention.py`) takes a
:class:`PagedTile` from :func:`select_paged_geometry` the same way — pages
a grid step, pages a softmax update — measured with
:func:`sweep_paged_geometry` at the serving cell's shape (PERF.md section
6, PR 33). The table file (``ops/flash_blocks_v5e.json``, override
``KFT_FLASH_BLOCKS_FILE``) serves the paged kernel's page size only.
"""

from __future__ import annotations

import json
import os
from typing import NamedTuple

_TABLE: dict | None = None
_TABLE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "flash_blocks_v5e.json"
)


def _table() -> dict:
    global _TABLE
    if _TABLE is None:
        path = os.environ.get("KFT_FLASH_BLOCKS_FILE", _TABLE_PATH)
        try:
            with open(path) as f:
                _TABLE = json.load(f)
        except (OSError, ValueError):
            _TABLE = {}
    return _TABLE


def reset_table_cache() -> None:
    global _TABLE
    _TABLE = None


# --------------------------------------------------------------------- #
# flash kernels: tile geometry
# --------------------------------------------------------------------- #

LANES = 128  # a block's last dim tiles in 128s; so do in-kernel slices


class Tile(NamedTuple):
    """One kernel's work per grid step."""

    block_q: int  #: q rows staged per step
    block_k: int  #: kv rows staged per step
    #: rows of the looped operand per in-kernel sub-tile: kv rows for the
    #: forward and dq (score tile ``block_q x sub``), q rows for dkv
    #: (score tile ``block_k x sub``, computed transposed)
    sub: int
    heads: int = 1  #: heads sharing one step (and its segment blocks)


class Geometry(NamedTuple):
    fwd: Tile
    dq: Tile
    dkv: Tile


def _fit(seq: int, cap: int) -> int:
    """The largest block <= cap that tiles ``seq``: the whole sequence if
    it is short, else a multiple of 128 that divides it, else any divisor
    of 64 or more (the interpreter takes those; Mosaic wants the 128s).
    Never DEGENERATE: a prime-ish length keeps the non-dividing cap so the
    kernel's explicit 'pad inputs' error fires instead of a block-1 grid."""
    if seq <= cap:
        return seq
    for c in range(cap - cap % LANES, 0, -LANES):
        if seq % c == 0:
            return c
    for c in range(cap, 63, -1):
        if seq % c == 0:
            return c
    return cap


def _sub(block: int, cap: int) -> int:
    """Sub-tile width for a staged block: the block itself when it is
    small, else a multiple of 128 that divides it (in-kernel slices must
    sit on lane tiles)."""
    if block <= cap:
        return block
    for c in range(cap - cap % LANES, 0, -LANES):
        if block % c == 0:
            return c
    return block


def _heads_per_step(heads: int, tile_elems: int) -> int:
    """How many heads share a backward step: as many as keep the step's
    score elements within ``_STEP_ELEMS``, a divisor of the head count, at
    most four (each head is unrolled into the kernel's body; six and
    twelve measured no better)."""
    best = 1
    for h in range(2, min(heads, 4) + 1):
        if heads % h == 0 and h * tile_elems <= _STEP_ELEMS:
            best = h
    return best


#: score elements a backward step takes on before more heads stop paying:
#: four heads of BERT's 512 x 512
_STEP_ELEMS = 4 * 512 * 512
#: rows of the looped operand (kv for forward and dq, q for dkv) one grid
#: step stages: x4's whole rows. A step costs about 0.35 us whatever it
#: computes and refetches its blocks, so the fewer the better until VMEM
#: says no (4096 x 128 bf16 is 1 MB a block, two blocks, two buffers)
_STAGED_ROWS = 4096


def span_kv_block(seq_kv: int) -> int:
    """The kv rows a caller who can pad its keys (`flash_attention_span`'s:
    the serving engine's gathered window) makes them up to whole blocks
    of, so that :func:`select_span_tile` tiles every page-table width
    alike: 8,704 keys (136 pages of 64) would get sub-tiles of 128 (6.78
    ms a layer against 1.19 at 9,216: PERF.md section 6, PR 35) and
    3,136 (49 pages) has no block over 64 rows; 9,216 and 4,096 run in
    sub-tiles of 1,024. A width of one block or less is only brought
    onto the lane tile: keys added are skipped a sub-tile at a time, and
    a wider sub-tile would compute them."""
    return _SPAN_KV_BLOCK if seq_kv > _SPAN_KV_BLOCK else LANES


_SPAN_KV_BLOCK = 1024


def select_geometry(
    seq_q: int,
    seq_kv: int,
    head_dim: int,
    *,
    heads: int = 1,
    itemsize: int = 2,
) -> Geometry:
    """Tile geometry of the three kernels for a call's shape (measured on
    a v5e at (32, 12, 512, 64) non-causal with segment ids and at
    (2, 16, 4096, 128) causal: PERF.md section 6, PR 26). Causality and
    the window do not enter: dead sub-tiles are skipped inside a step
    whatever its size."""
    if head_dim > 128 or itemsize > 2:
        # operand tiles and f32 temporaries scale with D and the element
        # size: stay at 128-class tiles (what every shape ran before PR 26)
        bq, bk = _fit(seq_q, 128), _fit(seq_kv, 256 if head_dim <= 128 else 128)
        t = Tile(bq, bk, bk, 1)
        return Geometry(t, t, Tile(bq, bk, bq, 1))
    block_q, block_k = _fit(seq_q, 512), _fit(seq_kv, 512)
    staged_q, staged_kv = _fit(seq_q, _STAGED_ROWS), _fit(seq_kv, _STAGED_ROWS)
    # the forward pays per sub-tile for its running max / denominator
    # (columns of block_q/8 vregs each) and the accumulator's rescale, so
    # it takes the wider sub-tile; the backward kernels have no such state
    # and measured faster at 512
    return Geometry(
        fwd=Tile(block_q, staged_kv, _sub(staged_kv, 1024), 1),
        dq=Tile(
            block_q, staged_kv, _sub(staged_kv, 512),
            _heads_per_step(heads, block_q * staged_kv),
        ),
        dkv=Tile(
            staged_q, block_k, _sub(staged_q, 512),
            _heads_per_step(heads, staged_q * block_k),
        ),
    )


def select_span_tile(
    seq_q: int, seq_kv: int, head_dim: int, *, itemsize: int = 2
) -> Tile:
    """The forward tile of a span call (`flash_attention_span`: a serving
    engine's prefill piece over its row's gathered window): the rule's q
    block and sub-tile, one sub-tile staged a step. The kernel's body
    holds every sub-tile of a staged block twice (with and without the
    positional mask), and an engine traces and lowers one such kernel
    per table width and window at every start, compile cache or not:
    staging 3,072 or 4,096 keys read 3 to 11 % faster in the kernel
    (1.095 against 1.188 ms at 9,216 keys, 0.489 against 0.552 over a
    window layer's 4,096) and cost `trinity-mini_mixed-closed` 6 s of a
    71 s start (PERF.md section 6, PR 35)."""
    fwd = select_geometry(seq_q, seq_kv, head_dim, itemsize=itemsize).fwd
    return Tile(fwd.block_q, fwd.sub, fwd.sub, 1)


def geometry_from_blocks(block_q: int, block_k: int) -> Geometry:
    """Explicit ``block_q`` / ``block_k``: every kernel stages exactly
    those blocks, one head a step; only the sub-tile is derived."""
    t = Tile(block_q, block_k, _sub(block_k, 512), 1)
    return Geometry(t, t, Tile(block_q, block_k, _sub(block_q, 512), 1))


def select_blocks(seq_q: int, seq_kv: int, head_dim: int) -> tuple[int, int]:
    """(block_q, block_k) for a caller that resolves blocks once and passes
    them on explicitly to several calls (the ring hops): the rule's
    512-class pair, which every kernel can stage as given."""
    geometry = select_geometry(seq_q, seq_kv, head_dim)
    return geometry.dq.block_q, geometry.dkv.block_k


def resolve_blocks(q, k, block_q, block_k) -> tuple[int, int]:
    """None → selected; shared by flash_attention and the ring entry so
    the resolution logic cannot drift between them."""
    if block_q is None or block_k is None:
        auto_q, auto_k = select_blocks(q.shape[2], k.shape[2], q.shape[3])
        block_q = auto_q if block_q is None else block_q
        block_k = auto_k if block_k is None else block_k
    return block_q, block_k


def device_ms_from_trace(xplane_path: str, steps: int) -> dict[str, float]:
    """Device milliseconds per step in a profile, by operation: the
    durations of the first TPU's ``XLA Ops`` events, summed over the
    trace and divided by ``steps``, keyed by each instruction's own name
    (not its operands': a copy of a kernel's result names the kernel too)
    with its trailing ``.N`` dropped."""
    import re

    from jax.profiler import ProfileData

    total: dict[str, float] = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                name = re.sub(
                    r"\.\d+$", "", e.name.partition(" = ")[0].lstrip("%")
                )
                total[name] = total.get(name, 0.0) + e.duration_ns * 1e-6
    return {k: v / steps for k, v in sorted(total.items())}


def kernel_ms_from_trace(xplane_path: str, steps: int) -> dict[str, float]:
    """Device milliseconds per step of each flash kernel in a profile:
    ``{"flash_fwd_q512_...": ms, ...}``."""
    import re

    name = re.compile(r"flash_(?:fwd|dq|dkv)_q\d+_k\d+_t\d+_h\d+")
    total: dict[str, float] = {}
    for op, ms in device_ms_from_trace(xplane_path, steps).items():
        m = name.search(op)
        if m:
            total[m.group(0)] = total.get(m.group(0), 0.0) + ms
    return dict(sorted(total.items()))


def sweep_blocks(
    *,
    batch: int,
    heads: int,
    seq: int,
    head_dim: int,
    causal: bool = False,
    window: int | None = None,
    segments: bool = False,
    seq_kv: int | None = None,
    kv_heads: int | None = None,
    q_offset: int | None = None,
    candidates: tuple[Geometry | None, ...] = (None,),
    steps: int = 5,
    logdir: str,
) -> list[dict]:
    """Time what the trainer runs — ``value_and_grad`` of the flash call,
    bf16, at the given shape — for each candidate geometry (``None`` = the
    rule's choice) on the LIVE backend, and return per candidate the
    device milliseconds of its forward, dq and dkv kernels, read from a
    profile (host timers cannot split the three, and include dispatch).
    ``segments`` passes all-ones segment ids, as BERT's unpadded batches
    do. With ``q_offset`` it times what the serving engine's prefill
    piece runs instead — the forward alone (``flash_attention_span``) of
    ``seq`` queries that start at ``q_offset`` among ``seq_kv`` keys of
    ``kv_heads`` heads, the keys made up to whole blocks of the
    candidate's (the rule's: :func:`span_kv_block`). Run this on the
    chip — CPU-interpret timings are meaningless. A candidate the
    compiler refuses is reported with its error."""
    import glob
    import importlib

    import jax
    import jax.numpy as jnp

    # the package re-exports the function under the module's name
    fa = importlib.import_module("kubeflow_tpu.ops.flash_attention")
    seq_kv, kv_heads = seq_kv or seq, kv_heads or heads
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, w = (
        jax.random.normal(key, (batch, heads, seq, head_dim), jnp.bfloat16)
        for key in keys[:2]
    )
    seg = jnp.ones((batch, seq), jnp.int32) if segments else None
    scale = head_dim ** -0.5
    results = []
    for i, cand in enumerate(candidates):
        block = cand.fwd.block_k if cand else span_kv_block(seq_kv)
        width = seq_kv if q_offset is None else -(-seq_kv // block) * block
        geometry = cand or select_geometry(seq, width, head_dim, heads=heads)
        if q_offset is not None and not cand:
            geometry = geometry._replace(
                fwd=select_span_tile(seq, width, head_dim)
            )
        k, v = (
            jax.random.normal(
                key, (batch, kv_heads, width, head_dim), jnp.bfloat16
            )
            for key in keys[2:]
        )

        def loss(q, k, v, geometry=geometry):
            out = fa._flash(
                q, k, v, seg, seg, causal, scale, geometry, (False, window)
            )
            return (out.astype(jnp.float32) * w.astype(jnp.float32)).sum()

        def piece(q, k, v, tile=geometry.fwd):
            return fa.flash_attention_span(
                q, k, v, jnp.full((batch,), q_offset, jnp.int32),
                window=window, tile=tile,
            )

        step = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2))
            if q_offset is None else piece
        )
        row = {"geometry": [list(t) for t in geometry], "seq_kv": width}
        try:
            jax.block_until_ready(step(q, k, v))  # compile + warm
            run_dir = os.path.join(logdir, f"cand{i}")
            with jax.profiler.trace(run_dir):
                for _ in range(steps):
                    out = step(q, k, v)
                jax.block_until_ready(out)
            (path,) = glob.glob(
                os.path.join(run_dir, "plugins", "profile", "*", "*.xplane.pb")
            )
            ms = kernel_ms_from_trace(path, steps)
            for kind in ("fwd", "dq", "dkv"):
                row[f"{kind}_ms"] = sum(
                    t for n, t in ms.items() if n.startswith(f"flash_{kind}_")
                )
            row["total_ms"] = sum(ms.values())
        except Exception as e:  # noqa: BLE001 — a refused tile is a result
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        results.append(row)
    return results


# --------------------------------------------------------------------- #
# paged decode-attention (ops/paged_attention.py): geometry, page size
# --------------------------------------------------------------------- #

class PagedTile(NamedTuple):
    """The paged kernel's work per grid step."""

    pages: int  #: pool pages (of every kv head) one step stages and computes
    #: pages that share one softmax update, each read in one product over
    #: all kv heads (the page as (page * kv_heads, D) rows, scores masked
    #: to the query's own head); 0 = a product per head, page by page.
    #: Divides ``pages``. The group is computed if any of its pages is
    #: live, so it is also the granularity at which dead pages are skipped
    fold: int


#: K and V pages a step may stage, in bytes, both double-buffered: half
#: of the 16 MiB a kernel gets without asking
_PAGED_STAGED_BYTES = 8 << 20
#: page operands a step: each is a block of the pool with an index map of
#: its own, traced on its own when the program is built (sixteen pages a
#: step cost a v5e's host about a second of every start)
_PAGED_MAX_PAGES = 16
#: score elements one softmax update of the folded body takes on by
#: choice: query rows x pages x (page x kv_heads) keys. Four pages an
#: update while they fit, else two (ms a layer at the cell's shape, 32
#: rows, pages an update 1 | 2 | 4: one query 0.123 | 0.098 | 0.088, two
#: 0.125 | 0.105 | 0.098, four 0.156 | 0.131 | 0.125, six 0.184 | 0.162 |
#: 0.168, eight 0.200 | 0.173 | 0.185, sixteen 0.324 | 0.280 | 0.309; a
#: product per head: one 0.259, four 0.295, eight 0.261, sixteen 0.336)
_PAGED_SCORE_ELEMS = 1 << 18
#: and the most a two-page update takes on before the body goes a head at
#: a time: 2 MB of f32 scores — a span of 16 at the cell's heads, the
#: longest measured and the longest the engine sends here
#: (`transformer.paged_kernel_read`); a direct call with a longer one (a
#: 512-token piece) still compiles, a head at a time
_PAGED_FOLD_MAX_ELEMS = 1 << 19
#: products a step of the per-head body unrolls (pages x kv heads): each is
#: traced and compiled on its own — sixteen pages of eight heads of a
#: 512-token piece took the compiler seven minutes — and more buy little
#: (ms a layer, 16 pages against 1: decode 0.259 | 0.288, a 512-token
#: piece 0.407 | 0.346)
_PAGED_HEAD_PRODUCTS = 16


def select_paged_geometry(
    *,
    table_pages: int,
    page_size: int,
    kv_heads: int,
    groups: int,
    span: int,
    head_dim: int,
    itemsize: int = 2,
    quant: bool = False,
) -> PagedTile:
    """Geometry of the paged kernel for a call's shape (measured on a v5e
    at 32 rows x 16 pages of 64 x 8 kv heads x 4 x 128, bf16, with
    :func:`sweep_paged_geometry`: PERF.md section 6, PR 33). A step takes
    as many pages as fit: the table's width, a power of two, up to
    ``_PAGED_MAX_PAGES`` operands and ``_PAGED_STAGED_BYTES`` of VMEM
    (sixteen a step 0.094 ms a layer, eight 0.127, a page a step 0.176).
    The kv heads are folded into one product a page, four pages sharing a
    softmax update while their score tile stays within
    ``_PAGED_SCORE_ELEMS`` and two beyond — every span the engine reads
    through the kernel. A product per head, ``_PAGED_HEAD_PRODUCTS`` of
    them a step, is for int8 pools (the scale planes lie heads-major, so
    a page is dequantized a head at a time), a single kv head (nothing to
    fold) and spans whose folded scores would not fit. The rows do not
    enter: the grid takes a step per block of n pages a row reads
    (`paged_attention.paged_work`), so n sets how many steps a row of a
    given length takes."""
    page_bytes = 4 * page_size * kv_heads * head_dim * itemsize
    cap = max(1, min(_PAGED_MAX_PAGES, _PAGED_STAGED_BYTES // page_bytes))
    pages = 1
    while pages * 2 <= min(cap, table_pages):
        pages *= 2
    page_scores = kv_heads * groups * span * page_size * kv_heads
    if quant or kv_heads == 1 or 2 * page_scores > _PAGED_FOLD_MAX_ELEMS:
        return PagedTile(
            min(pages, max(1, _PAGED_HEAD_PRODUCTS // kv_heads)), 0
        )
    fold = 4 if 4 * page_scores <= _PAGED_SCORE_ELEMS else 2
    return PagedTile(pages, min(pages, fold))


def sweep_paged_geometry(
    *,
    contexts: tuple[int, ...],
    table_pages: int,
    gather,
    page_size: int = 64,
    kv_heads: int = 8,
    groups: int = 4,
    head_dim: int = 128,
    span: int = 1,
    window: int | None = None,
    pool_pages: int | None = None,
    candidates: tuple[PagedTile | None | str, ...] = (None, "gather"),
    steps: int = 20,
    logdir: str,
) -> list[dict]:
    """Time one layer's paged read — a bare jitted call, bf16 — on the
    LIVE backend for each candidate: a :class:`PagedTile`, ``None`` (the
    rule's choice) or ``"gather"``, the XLA read path, handed in as
    ``gather`` (`models/transformer.py::paged_gather_attention`: this
    package does not import the models). One row per entry of ``contexts``
    (the keys the row holds, the span's own among them), pages drawn
    without replacement from a pool of ``pool_pages``. Device
    milliseconds per call come from a profile (a host timer would add the
    dispatch); a kernel candidate after a ``"gather"`` one also reports
    how far its result lies from the gather's. Run this on the chip; a
    candidate the compiler refuses is reported with its error."""
    import glob
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.ops.paged_attention import paged_attention

    rows = len(contexts)
    pool_pages = pool_pages or 1 + rows * table_pages
    pool_tokens = pool_pages * page_size
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(
        kq, (rows, kv_heads * groups, span, head_dim), jnp.bfloat16
    )
    cache = {
        "k": jax.random.normal(kk, (pool_tokens, kv_heads, head_dim), jnp.bfloat16),
        "v": jax.random.normal(kv, (pool_tokens, kv_heads, head_dim), jnp.bfloat16),
    }
    table = jnp.asarray(
        1 + np.random.default_rng(0).permutation(pool_pages - 1)[
            : rows * table_pages
        ].reshape(rows, table_pages).astype(np.int32)
    )
    pos0 = jnp.asarray(np.asarray(contexts, np.int32) - span)
    positions = pos0[:, None] + jnp.arange(span)[None, :]
    results, want = [], None
    for i, cand in enumerate(candidates):
        if cand == "gather":
            fn = jax.jit(lambda q, cache: gather(
                q, cache, table, positions, page_size=page_size, window=window,
            ))
            row = {"read": "gather"}
        else:
            tile = cand or select_paged_geometry(
                table_pages=table_pages, page_size=page_size,
                kv_heads=kv_heads, groups=groups, span=span,
                head_dim=head_dim,
            )
            fn = jax.jit(lambda q, cache, tile=tile: paged_attention(
                q, cache["k"], cache["v"], table, pos0, page_size=page_size,
                window=window, tile=tile,
            ))
            row = {"read": "kernel", "tile": list(tile)}
        try:
            out = jax.block_until_ready(fn(q, cache))  # compile + warm
            if cand == "gather":
                want = out
            elif want is not None:
                row["max_abs_err_vs_gather"] = float(jnp.max(jnp.abs(
                    out.astype(jnp.float32) - want.astype(jnp.float32)
                )))
            os.makedirs(logdir, exist_ok=True)
            run_dir = tempfile.mkdtemp(prefix=f"cand{i}_", dir=logdir)
            with jax.profiler.trace(run_dir):
                for _ in range(steps):
                    out = fn(q, cache)
                jax.block_until_ready(out)
            (path,) = glob.glob(
                os.path.join(run_dir, "plugins", "profile", "*", "*.xplane.pb")
            )
            ms = device_ms_from_trace(path, steps)
            row["ms"] = sum(ms.values())
            row["ops"] = dict(
                sorted(ms.items(), key=lambda kv: -kv[1])[:4]
            )
        except Exception as e:  # noqa: BLE001 — a refused tile is a result
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        results.append(row)
    return results


def select_paged_page_size(head_dim: int, default: int = 64) -> int:
    """Measured page size for the paged decode-attention kernel. One kv
    grid step stages one pool page HBM→VMEM, so the sweepable "block
    size" IS the engine's ``page_size``. Table section ``paged:{head_dim}``
    (the kv tile is (page, head_dim) — sequence length doesn't change its
    VMEM footprint). Falls back to the engine's historical 64-token
    default when no sweep has landed."""
    entry = _table().get(f"paged:{head_dim}")
    if entry:
        return int(entry[0]) if isinstance(entry, (list, tuple)) else int(entry)
    return default


def sweep_paged_pages(
    *,
    batch: int = 8,
    kv_heads: int = 4,
    groups: int = 2,
    head_dim: int = 64,
    seq_tokens: int = 1024,
    span: int = 1,
    candidates: tuple[int, ...] = (32, 64, 128, 256),
    reps: int = 3,
    write: bool = True,
    table_path: str | None = None,
) -> dict:
    """Time the paged decode kernel per candidate page size on the LIVE
    backend (host clock, chained two-point: 20 calls minus 5, over 15);
    returns {"page_size": best, "ms": ..., "all": {...}} and (optionally)
    writes the winner to the ``paged:{head_dim}`` table entry. Each
    candidate gets its own synthetic pool + block table covering
    ``seq_tokens`` resident tokens per row."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.ops.paged_attention import paged_attention

    heads = kv_heads * groups
    per: dict[str, float] = {}
    for P in candidates:
        if seq_tokens % P:
            continue
        w = seq_tokens // P                      # pages per row
        n_pages = 1 + batch * w                  # + scratch page 0
        pool_tokens = n_pages * P
        rng = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(rng, 3)
        q = jax.random.normal(
            kq, (batch, heads, span, head_dim), jnp.bfloat16
        )
        k_pool = jax.random.normal(
            kk, (pool_tokens, kv_heads, head_dim), jnp.bfloat16
        )
        v_pool = jax.random.normal(
            kv, (pool_tokens, kv_heads, head_dim), jnp.bfloat16
        )
        table_np = (
            1 + np.arange(batch * w, dtype=np.int32).reshape(batch, w)
        )
        tbl = jnp.asarray(table_np)
        pos0 = jnp.full((batch,), seq_tokens - span, jnp.int32)

        fn = jax.jit(
            lambda q, kp, vp, t, p0, _P=P: paged_attention(
                q, kp, vp, t, p0, page_size=_P
            )
        )
        out = fn(q, k_pool, v_pool, tbl, pos0)  # compile
        np.asarray(out[0, 0, 0])                # host-transfer sync

        def run(n):
            t0 = time.perf_counter()
            o = None
            for _ in range(n):
                o = fn(q, k_pool, v_pool, tbl, pos0)
            np.asarray(o[0, 0, 0])
            return time.perf_counter() - t0

        est = []
        for _ in range(reps):
            t_small, t_large = run(5), run(20)
            est.append((t_large - t_small) / 15)
        med = sorted(est)[len(est) // 2]
        if med <= 0:
            continue  # timing noise won — never crown an invalid sample
        per[str(P)] = round(med * 1e3, 4)
    if not per:
        return {}
    best = min(per, key=per.get)
    result = {"page_size": int(best), "ms": per[best], "all": per}
    if write:
        path = table_path or os.environ.get(
            "KFT_FLASH_BLOCKS_FILE", _TABLE_PATH
        )
        try:
            with open(path) as f:
                table = json.load(f)
        except (OSError, ValueError):
            table = {}
        table[f"paged:{head_dim}"] = [int(best)]
        with open(path, "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)
        reset_table_cache()
    return result
