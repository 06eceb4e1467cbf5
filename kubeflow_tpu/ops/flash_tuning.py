"""Flash-attention tile geometry (a rule on the observed shape) and the
sweep tool that measured it; plus the paged kernel's page-size table.

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

``flash_attention(block_q=None)`` (and TransformerConfig
``attn_block_q=None``) routes through the rule; explicit ``block_q`` /
``block_k`` are honoured as the staged block of every kernel
(:func:`geometry_from_blocks`), which is what the ring hops pass.

The table file (``ops/flash_blocks_v5e.json``, override
``KFT_FLASH_BLOCKS_FILE``) now serves the paged kernel's page size only.
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


def kernel_ms_from_trace(xplane_path: str, steps: int) -> dict[str, float]:
    """Device milliseconds per step of each flash kernel in a profile:
    ``{"flash_fwd_q512_...": ms, ...}`` — the durations of the device's
    ``XLA Ops`` events whose text names a flash kernel, summed over the
    trace and divided by ``steps``."""
    import re

    from jax.profiler import ProfileData

    name = re.compile(r"flash_(?:fwd|dq|dkv)_q\d+_k\d+_t\d+_h\d+")
    total: dict[str, float] = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                # the instruction's own name, not its operands' (a copy
                # of a kernel's result names the kernel too)
                m = name.search(e.name.partition(" = ")[0])
                if m:
                    total[m.group(0)] = (
                        total.get(m.group(0), 0.0) + e.duration_ns * 1e-6
                    )
    return {k: v / steps for k, v in sorted(total.items())}


def sweep_blocks(
    *,
    batch: int,
    heads: int,
    seq: int,
    head_dim: int,
    causal: bool = False,
    window: int | None = None,
    segments: bool = False,
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
    do. Run this on the chip — CPU-interpret timings are meaningless. A
    candidate the compiler refuses is reported with its error."""
    import glob
    import importlib

    import jax
    import jax.numpy as jnp

    # the package re-exports the function under the module's name
    fa = importlib.import_module("kubeflow_tpu.ops.flash_attention")
    shape = (batch, heads, seq, head_dim)
    q, k, v, w = (
        jax.random.normal(key, shape, jnp.bfloat16)
        for key in jax.random.split(jax.random.PRNGKey(0), 4)
    )
    seg = jnp.ones((batch, seq), jnp.int32) if segments else None
    scale = head_dim ** -0.5
    results = []
    for i, cand in enumerate(candidates):
        geometry = cand or select_geometry(seq, seq, head_dim, heads=heads)

        def loss(q, k, v, geometry=geometry):
            out = fa._flash(
                q, k, v, seg, seg, causal, scale, geometry, (False, window)
            )
            return (out.astype(jnp.float32) * w.astype(jnp.float32)).sum()

        step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        row = {"geometry": [list(t) for t in geometry]}
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
# paged decode-attention page size (ops/paged_attention.py)
# --------------------------------------------------------------------- #

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
