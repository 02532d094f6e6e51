"""Paged cache slot pool — the decode lane's memory allocator.

vLLM-style paged memory for a model's cache on the executor's scope
model: the pool is one persistable program var per (layer, declared
cache row) shaped ``[num_pages, page_size, width]`` — a K and a V row
with the heads side by side for dense attention, a latent row and an
indexer key for latent sparse attention (serving/lane.py ``CacheRow``);
every row tensor lives under ONE page table.  The executor donates the
vars every step so they update in place; a sequence's cache is a LIST
of page ids (its page table), not a contiguous slab.  Admission, growth
and eviction therefore move ZERO cache memory — they edit host-side
page lists — and the decode step stays one fixed-shape executable no
matter how sequences come and go.

Page 0 is the TRASH page: never allocated, the write target of inactive
decode slots and padded prefill tails.  Readers can't observe it —
every attention masks positions past a row's own length.

This module is the pure allocator (page lists, free-list reuse,
accounting); scheduling policy — WHO gets evicted under pressure — lives
in `serving/decode.py`.  Freed pages are reused LIFO so the hot pages of
a churning slot stay the same physical pages across steps (cross-step
slot reuse: the steady-state working set stops growing once warm, which
`reused_allocs` makes visible).
"""

from __future__ import annotations

import collections

import numpy as np

from .errors import PoolExhaustedError
from .lane import POOL_PREFIX, pool_var_names

__all__ = ["KVPool", "PoolExhaustedError"]

TRASH_PAGE = 0


class KVPool:
    """Host-side page allocator + the device-resident pool vars.

    ``rows`` is the model's declaration of what a token leaves in each
    layer (``lane.CacheRow`` name, width, dtype); ``num_pages`` INCLUDES
    the trash page, so ``num_pages - 1`` pages are allocatable; a single
    sequence needs up to ``max_pages_per_seq`` of them (the constructor
    enforces one sequence always fits — otherwise eviction could never
    unblock the allocator)."""

    def __init__(self, num_layers, rows, num_pages, page_size,
                 max_pages_per_seq, prefix=None):
        if num_pages - 1 < max_pages_per_seq:
            raise ValueError(
                f"KV pool of {num_pages} pages (1 reserved for trash) "
                f"cannot hold one full sequence of {max_pages_per_seq} "
                f"pages — raise num_pages or lower max_len")
        self.num_layers = int(num_layers)
        self.rows = list(rows)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_pages_per_seq = int(max_pages_per_seq)
        self.prefix = POOL_PREFIX if prefix is None else prefix
        self.var_names = pool_var_names(self.rows, self.num_layers,
                                        self.prefix)
        # LIFO free list: a just-freed page is the next one handed out,
        # so a churning slot's working set stays the same physical pages
        self._free = collections.deque(range(1, self.num_pages))
        self._tables = {}           # seq_id -> [page ids]
        self._ever_used = set()     # pages that have ever been allocated
        self.alloc_total = 0
        self.free_total = 0
        self.reused_allocs = 0      # allocations served by a reused page

    # -- device arrays ------------------------------------------------------

    def install(self, scope):
        """Zero the pool vars into `scope` (idempotent on shape AND
        dtype match — an engine rebuild over a live scope keeps the
        resident pool; a rebuild with a different pool dtype must NOT,
        or every later write trips the dtype guard blaming the
        payload).  The zeros are made on the device: a pool is
        gigabytes, and host zeros would cross to the chip."""
        import jax.numpy as jnp

        for names in self.var_names:
            for name, row in zip(names, self.rows):
                shape = (self.num_pages, self.page_size, row.width)
                cur = scope.get(name)
                if (cur is None or tuple(np.shape(cur)) != shape
                        or str(getattr(cur, "dtype", "")) != row.dtype):
                    scope.set(name, jnp.zeros(shape, dtype=row.dtype))

    # -- modeled bytes ------------------------------------------------------

    def row_bytes(self, row, pages=None):
        """Device bytes of one declared row tensor over every layer:
        resident (``pages`` None) or of ``pages`` pages."""
        import jax.numpy as jnp  # its dtypes know bfloat16

        pages = self.num_pages if pages is None else pages
        return (pages * self.page_size * row.width
                * jnp.dtype(row.dtype).itemsize * self.num_layers)

    def modeled_bytes(self):
        """Device bytes of the resident pool: every declared row tensor
        of every layer (for the dual-int8 rows that is kernels/
        primitives/int8.py's ``dual_int8_bytes``)."""
        return sum(self.row_bytes(row) for row in self.rows)

    # -- allocation ---------------------------------------------------------

    def open_seq(self, seq_id):
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already open")
        self._tables[seq_id] = []

    def ensure_capacity(self, seq_id, n_tokens):
        """Grow `seq_id`'s page table to cover `n_tokens` positions.
        Raises PoolExhaustedError — with the shortfall named — when the
        free list runs dry; the caller (the scheduler) evicts and
        retries."""
        table = self._tables[seq_id]
        need = -(-int(n_tokens) // self.page_size)  # ceil
        if need > self.max_pages_per_seq:
            raise ValueError(
                f"sequence {seq_id!r} needs {need} pages for "
                f"{n_tokens} tokens, above max_pages_per_seq="
                f"{self.max_pages_per_seq}")
        while len(table) < need:
            if not self._free:
                raise PoolExhaustedError(
                    f"KV pool out of pages: sequence {seq_id!r} needs "
                    f"{need - len(table)} more (of {need}) but 0 of "
                    f"{self.num_pages - 1} allocatable pages are free "
                    f"— evict a sequence or grow the pool")
            page = self._free.pop()
            if page in self._ever_used:
                self.reused_allocs += 1
            self._ever_used.add(page)
            self.alloc_total += 1
            table.append(page)
        return table

    def free_seq(self, seq_id):
        """Return every page of `seq_id` to the free list (LIFO)."""
        pages = self._tables.pop(seq_id, [])
        for p in reversed(pages):
            self._free.append(p)
        self.free_total += len(pages)
        return len(pages)

    # -- views --------------------------------------------------------------

    def table(self, seq_id):
        return list(self._tables[seq_id])

    def live_seqs(self):
        return list(self._tables)

    def pages_in_use(self):
        return (self.num_pages - 1) - len(self._free)

    def padded_table(self, seq_id=None):
        """One row of the decode feed: the sequence's page table padded
        with the trash page to max_pages_per_seq (all-trash when
        seq_id is None — the inactive-slot row)."""
        row = np.full(self.max_pages_per_seq, TRASH_PAGE, np.int32)
        if seq_id is not None:
            pages = self._tables[seq_id]
            row[:len(pages)] = pages
        return row

    def stats(self):
        return {
            "pages_total": self.num_pages - 1,
            "pages_in_use": self.pages_in_use(),
            "page_size": self.page_size,
            "live_seqs": len(self._tables),
            "alloc_total": self.alloc_total,
            "free_total": self.free_total,
            "reused_allocs": self.reused_allocs,
        }
