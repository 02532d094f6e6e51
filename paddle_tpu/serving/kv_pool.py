"""Paged KV-cache slot pool — the decode lane's memory allocator.

vLLM-style paged memory for KV caches on the executor's scope model:
the pool is one persistable program var per (layer, K/V) shaped
``[num_pages, page_size, n_heads * head_dim]`` (the heads side by side
in the last dimension: the shape whose default TPU layout the paged
kernel reads, kernels/primitives/paged.py "Shapes"), donated by the
executor every step so it updates in place; a sequence's cache is a
LIST of page ids (its page table), not a contiguous slab.  Admission,
growth and eviction therefore move ZERO cache memory — they edit
host-side page lists — and the decode step stays one fixed-shape
executable (models/gpt.py build_gpt_decode_step) no matter how
sequences come and go.

Page 0 is the TRASH page: never allocated, the write target of inactive
decode slots and padded prefill tails.  Readers can't observe it —
paged attention masks every position past a row's own length.

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

__all__ = ["KVPool", "PoolExhaustedError"]

TRASH_PAGE = 0


class KVPool:
    """Host-side page allocator + the device-resident pool vars.

    ``num_pages`` INCLUDES the trash page, so ``num_pages - 1`` pages
    are allocatable; a single sequence needs up to ``max_pages_per_seq``
    of them (the constructor enforces one sequence always fits —
    otherwise eviction could never unblock the allocator)."""

    def __init__(self, num_layers, num_heads, head_dim, num_pages,
                 page_size, max_pages_per_seq, dtype="float32",
                 prefix=None):
        from paddle_tpu.models.gpt import KV_POOL_PREFIX, kv_pool_var_names

        if num_pages - 1 < max_pages_per_seq:
            raise ValueError(
                f"KV pool of {num_pages} pages (1 reserved for trash) "
                f"cannot hold one full sequence of {max_pages_per_seq} "
                f"pages — raise num_pages or lower max_len")
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_pages_per_seq = int(max_pages_per_seq)
        self.dtype = dtype
        self.prefix = KV_POOL_PREFIX if prefix is None else prefix
        self.var_names = kv_pool_var_names(self.num_layers, self.prefix)
        if dtype == "int8":
            from paddle_tpu.models.gpt import kv_pool_quant_var_names
            self.quant_var_names = kv_pool_quant_var_names(
                self.num_layers, self.prefix)
        else:
            self.quant_var_names = None
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
        resident pool; a rebuild with a different pool_dtype must NOT,
        or every later write trips the dtype guard blaming the
        payload)."""
        # the one place that knows the heads and their width apart: the
        # pool itself holds them flattened into its last dimension
        shape = (self.num_pages, self.page_size,
                 self.num_heads * self.head_dim)
        if self.dtype == "int8":
            # dual-int8 pool: hi/lo int8 + one fp32 scale a head_dim
            # vector per K/V (docs/KERNELS.md "int8 KV")
            sc_shape = shape[:-1] + (self.num_heads,)
            for k_names, v_names in self.quant_var_names:
                for hi_n, lo_n, sc_n in (k_names, v_names):
                    for name, shp, dt in ((hi_n, shape, "int8"),
                                          (lo_n, shape, "int8"),
                                          (sc_n, sc_shape, "float32")):
                        cur = scope.get(name)
                        if (cur is None
                                or tuple(np.shape(cur)) != shp
                                or np.asarray(cur).dtype != np.dtype(dt)):
                            scope.set(name, np.zeros(shp, dtype=dt))
            return
        want = np.dtype(self.dtype)
        for kn, vn in self.var_names:
            for name in (kn, vn):
                cur = scope.get(name)
                if (cur is None or tuple(np.shape(cur)) != shape
                        or np.asarray(cur).dtype != want):
                    scope.set(name, np.zeros(shape, dtype=self.dtype))

    # -- modeled bytes ------------------------------------------------------

    def modeled_bytes(self):
        """Modeled device bytes of the resident pool across all layers
        and both K/V — dual-int8 accounting when dtype == 'int8'
        (kernels/primitives/int8.py dual_int8_bytes with a per-head_dim
        scale block), plain dtype-width bytes otherwise."""
        n_vec = self.num_pages * self.page_size * self.num_heads
        n_elems = n_vec * self.head_dim
        per_var = (self._dual_int8_bytes(n_elems)
                   if self.dtype == "int8"
                   else n_elems * np.dtype(self.dtype).itemsize)
        return per_var * 2 * self.num_layers

    def modeled_bytes_fp32(self):
        """The same pool's modeled bytes at fp32 — the denominator of
        the int8 saving claim (bench.py PT_BENCH_RAGGED rung)."""
        n_elems = (self.num_pages * self.page_size * self.num_heads
                   * self.head_dim)
        return n_elems * 4 * 2 * self.num_layers

    def _dual_int8_bytes(self, n_elems):
        from paddle_tpu.kernels import primitives as _prims
        return _prims.dual_int8_bytes(n_elems, self.head_dim)

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
