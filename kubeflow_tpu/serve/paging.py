"""Paged KV-cache allocator: the vLLM block-table analog, TPU-first.

Reference analog (SURVEY.md §2.2 HF-runtime row, "optional vLLM backend"
— UNVERIFIED, mount empty, §0): vLLM bills HBM per TOKEN via fixed-size
pages instead of per ROW via a (max_batch, max_seq) rectangle, so
concurrent mixed-length requests fit in the memory the rectangle wastes
on short rows.

TPU-first shape of the idea: the pool is ONE flat token axis per layer,
token-major — ``(pool_tokens, kv_heads, head_dim)`` — and a row's logical
token ``j`` lives at flat slot ``table[row, j // P] * P + j % P`` of
axis 0. Reads/writes are XLA gathers/scatters computed in-graph from the
table operand (static shapes, no host round-trips); the allocator below
is pure host-side bookkeeping. The token axis comes first because it is
the one those gathers and scatters index, and the TPU compiler keeps an
indexed axis outermost: with the heads first, every program re-laid
every layer's K and V out on entry and again on exit (rehearsed at
`mistral-7b-l16`: 64 pool-sized copies in the decode chunk and in the
prefill piece, and a second pool held meanwhile; token-major: none). A
page of all kv heads is also one contiguous block this way. Divergence from vLLM, documented: pages are allocated AT
ADMISSION for the request's full worst case (prompt + max_new_tokens)
rather than grown on demand per step — admission control then happens in
one place and a row can never OOM mid-decode; the cost is that a request
ending early holds its tail pages until completion. Page 0 is a scratch
page: writes that must go nowhere (pad positions, dead rows still
stepping in the SPMD batch, speculative span positions past a row's
budgeted region) are routed there and nothing ever reads it — which is
why speculative decoding needs no extra pages: rejected-draft overflow
simply scratches. The engine's pipelined read-window (``device_table``)
widens by up to chunk_steps × (spec_draft_tokens + 1) tokens per
in-flight chunk to cover the span's reach.
"""

from __future__ import annotations

import numpy as np


class PageAllocator:
    """Host-side page bookkeeping + the block table device operand.

    ``table`` maps (row, page-ordinal) → pool page index; unallocated
    entries point at the scratch page 0 (always in-bounds for gathers,
    never read because the token mask stops at each row's length).
    """

    #: pages of the pool no row can own (page 0, the scratch page): what a
    #: caller sizing a pool adds to the pages its rows need
    RESERVED_PAGES = 1

    def __init__(
        self, *, pool_tokens: int, page_size: int, max_batch: int,
        max_pages_per_row: int,
    ):
        if page_size < 16 or page_size % 16:
            # the prefix cache quantizes at 16 tokens; a finer page would
            # split a quantum across pages for no density gain
            raise ValueError(f"page_size must be a 16-multiple, got {page_size}")
        if pool_tokens % page_size:
            raise ValueError(
                f"pool_tokens {pool_tokens} must be a multiple of "
                f"page_size {page_size}"
            )
        self.page_size = page_size
        self.num_pages = pool_tokens // page_size
        if self.num_pages <= self.RESERVED_PAGES:
            raise ValueError("pool must hold at least 2 pages (1 is scratch)")
        self.max_pages_per_row = max_pages_per_row
        #: pages 1..N-1 allocatable; 0 is the scratch page
        self._free: list[int] = list(range(self.num_pages - 1, 0, -1))
        self._owned: dict[int, list[int]] = {}  # row → pages
        self.table = np.zeros((max_batch, max_pages_per_row), np.int32)
        #: device mirror bookkeeping: ``version`` bumps on every alloc/free,
        #: and ``device_table`` memoizes one upload per (version, width) so
        #: the pipelined decode loop pays H2D only on real table changes or
        #: horizon widenings — never per chunk.
        self.version = 0
        self.device_uploads = 0
        self._dev: dict[int, tuple[int, object]] = {}  # width → (ver, arr)

    # ------------------------------------------------------------------ #

    def pages_for(self, tokens: int) -> int:
        return -(-tokens // self.page_size)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def usable_pages(self) -> int:
        return self.num_pages - self.RESERVED_PAGES

    @property
    def used_pages(self) -> int:
        return self.usable_pages - len(self._free)

    def can_alloc(self, n_pages: int) -> bool:
        return n_pages <= len(self._free)

    def alloc(self, row: int, n_pages: int) -> None:
        if row in self._owned:
            raise RuntimeError(f"row {row} already holds pages")
        if n_pages > self.max_pages_per_row:
            raise ValueError(
                f"{n_pages} pages exceeds max_pages_per_row "
                f"{self.max_pages_per_row}"
            )
        if n_pages > len(self._free):
            raise RuntimeError(
                f"pool exhausted: need {n_pages}, have {len(self._free)}"
            )
        pages = [self._free.pop() for _ in range(n_pages)]
        self._owned[row] = pages
        self.table[row, :] = 0
        self.table[row, : len(pages)] = pages
        self.version += 1

    def free(self, row: int) -> None:
        pages = self._owned.pop(row, None)
        if pages:
            self._free.extend(pages)
            self.table[row, :] = 0
            self.version += 1

    def device_table(self, width: int):
        """Device-resident ``table[:, :width]``, re-uploaded only when the
        host table changed since the last upload at this width. The width
        set is pow2-bucketed by the engine, so the memo stays small; on a
        miss, entries from older table versions are evicted first — a
        long-lived engine with churning horizons would otherwise pin one
        stale int32 slab per width it ever touched, forever."""
        import jax.numpy as jnp  # deferred: the allocator itself is host-only

        ver, arr = self._dev.get(width, (-1, None))
        if ver != self.version or arr is None:
            self._dev = {
                w: va for w, va in self._dev.items() if va[0] == self.version
            }
            # snapshot, don't view: jnp.asarray of an aligned numpy
            # buffer is ZERO-COPY on the CPU backend, so the "device"
            # mirror would alias the live table and a later alloc/free
            # would rewrite what an in-flight chunk reads
            arr = jnp.asarray(self.table[:, :width].copy())
            self._dev[width] = (self.version, arr)
            self.device_uploads += 1
        return arr

    def stats(self) -> dict:
        return {
            "page_size": self.page_size,
            "pages_total": self.usable_pages,
            "pages_used": self.used_pages,
            "rows_resident": len(self._owned),
        }
