"""Paged cache slot pool — the decode lane's memory allocator.

vLLM-style paged memory for a model's cache on the executor's scope
model: the pool is one persistable program var per (layer, declared
cache row) shaped ``[num_pages, page_size, width]`` — a K and a V row
with the heads side by side for dense attention, a latent row and an
indexer key for latent sparse attention (serving/lane.py ``CacheRow``).
The executor donates the vars every step so they update in place; a
sequence's cache is a LIST of page ids (its page table), not a
contiguous slab.  Admission, growth and eviction therefore move ZERO
cache memory — they edit host-side page lists — and the decode step
stays one fixed-shape executable no matter how sequences come and go.

**Kinds.**  Every layer leaves one KIND of cache (serving/lane.py
``layer_windows``): ``full`` — a token's rows stay until its request
ends — or ``window<W>`` — the layer attends the last W tokens only.  The
pool keeps one page list a kind a sequence (every row tensor of every
layer of a kind lives under that kind's page table), its own free list
a kind, and sizes a kind's tensors by that kind's worst case: every
slot at full length for ``full``, ``lane.window_pages_per_seq`` pages a
slot for a window kind.  A model that declares nothing has the one kind
``full`` and this allocator does what it always did.  A lane may
declare its rows BY KIND (``{kind: [CacheRow]}``: a window layer that
leaves wider rows than a full one): a layer's tensors then have its
kind's rows, and a page of a kind counts its own rows' bytes.

**When a window page is freed.**  ``release(seq_id, length)`` — called
by the scheduler after a prefill chunk or a decode step has moved the
sequence on to ``length`` tokens — gives back every logical page of a
window kind that lies WHOLLY below ``length - W`` (no later query can
see a key of it) while the request lives; its table entry becomes the
trash page.  A page index stays logical: a table is as wide for a
window kind as for ``full``, with trash below the window.

**Per-sequence state.**  A lane may also declare state a SEQUENCE owns
in some layers (serving/lane.py ``SeqState``: a linear-attention layer's
recurrent state).  That is the kind ``state``: its "pages" are whole
blocks, a sequence holds exactly ONE whatever its length, handed out by
``open_seq`` (which raises what ``ensure_capacity`` raises when none is
free: the scheduler evicts) and given back by ``free_seq``.  Its tensors
are ``[blocks, *shape]`` a state layer, block 0 the trash block; it has
no page table (``kinds`` lists the page kinds only) and is counted by
the same ``kind_stats``.

Page 0 of every kind is the TRASH page: never allocated, the write
target of inactive decode slots and padded prefill tails, and what a
released entry of a window kind's table points at.  Readers can't
observe it — every attention masks positions past a row's own length
and, in a window layer, positions below its window (the kernel does
not even fetch those pages).

This module is the pure allocator (page lists, free-list reuse,
accounting); scheduling policy — WHO gets evicted under pressure — lives
in `serving/decode.py`.  Freed pages are reused LIFO so the hot pages of
a churning slot stay the same physical pages across steps (cross-step
slot reuse: the steady-state working set stops growing once warm, which
`reused_allocs` makes visible), and a page a window gave back is the
next one handed out.
"""

from __future__ import annotations

import collections

import numpy as np

from .errors import PoolExhaustedError
from .lane import (POOL_PREFIX, STATE, kind_name, kinds_of, pool_var_names,
                   rows_of_layers, state_var_names)

__all__ = ["KVPool", "PoolExhaustedError"]

TRASH_PAGE = 0
FREED_WHY = ("window", "end", "evict")


class _Kind:
    """One cache kind's allocator state: its pages, its free list, a
    page list a sequence."""

    def __init__(self, name, window, num_pages, layers):
        self.name = name
        self.window = window
        self.num_pages = int(num_pages)
        self.layers = layers            # how many layers leave this kind
        # LIFO free list: a just-freed page is the next one handed out,
        # so a churning slot's working set stays the same physical pages
        self.free = collections.deque(range(1, self.num_pages))
        self.tables = {}                # seq_id -> [page ids], logical order
        self.first_live = {}            # seq_id -> first page not released
        self.ever_used = set()          # pages that have ever been allocated
        self.alloc_total = 0
        self.reused_allocs = 0          # allocations served by a reused page
        self.freed = dict.fromkeys(FREED_WHY, 0)

    def in_use(self):
        return (self.num_pages - 1) - len(self.free)

    def take(self):
        """Hand out the free list's last page (the one freed last)."""
        page = self.free.pop()
        if page in self.ever_used:
            self.reused_allocs += 1
        self.ever_used.add(page)
        self.alloc_total += 1
        return page

    def give_back(self, pages, why):
        for p in reversed(pages):
            self.free.append(p)
        self.freed[why] += len(pages)


class KVPool:
    """Host-side page allocator + the device-resident pool vars.

    ``rows`` is the model's declaration of what a token leaves in each
    layer (``lane.CacheRow`` name, width, dtype), one list for every
    layer or ``{kind: [CacheRow]}``; ``num_pages`` INCLUDES
    the trash page, so ``num_pages - 1`` pages are allocatable; a single
    sequence needs up to ``max_pages_per_seq`` of them (the constructor
    enforces one sequence always fits — otherwise eviction could never
    unblock the allocator).

    ``layer_windows`` (``lane.DecodeLane.layer_windows``: per layer None
    or W) declares the layers' cache kinds; ``num_pages`` is then the
    ``full`` kind's, and a window kind gets ``window_pages[kind]``
    pages (trash included; never more than ``num_pages``, and
    ``num_pages`` where none is given).

    ``seq_state`` / ``state_layers`` (``lane.DecodeLane``'s) declare the
    per-sequence state tensors and the layers that own them;
    ``state_blocks`` is how many blocks each has, the trash block
    included."""

    def __init__(self, num_layers, rows, num_pages, page_size,
                 max_pages_per_seq, prefix=None, layer_windows=None,
                 window_pages=None, seq_state=(), state_layers=(),
                 state_blocks=0):
        if num_pages - 1 < max_pages_per_seq:
            raise ValueError(
                f"KV pool of {num_pages} pages (1 reserved for trash) "
                f"cannot hold one full sequence of {max_pages_per_seq} "
                f"pages — raise num_pages or lower max_len")
        self.num_layers = int(num_layers)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_pages_per_seq = int(max_pages_per_seq)
        self.prefix = POOL_PREFIX if prefix is None else prefix
        windows = ([None] * self.num_layers if layer_windows is None
                   else list(layer_windows))
        self.layer_kinds = [kind_name(w) for w in windows]
        # per layer, the rows it leaves (its kind's, where the lane
        # declares them by kind); ``rows``: every distinct row, once
        self.layer_rows = rows_of_layers(rows, self.num_layers, windows)
        self.rows = list(dict.fromkeys(
            row for layer in self.layer_rows for row in layer))
        self.var_names = pool_var_names(rows, self.num_layers, self.prefix,
                                        windows)
        self._kinds = {}
        for w in kinds_of(windows):
            name = kind_name(w)
            pages = self.num_pages if w is None else min(
                self.num_pages,
                int((window_pages or {}).get(name, self.num_pages)))
            self._kinds[name] = _Kind(name, w, pages,
                                      self.layer_kinds.count(name))
        self.kinds = list(self._kinds)
        self.seq_state = list(seq_state)
        self.state_layers = list(state_layers)
        self.state_var_names = state_var_names(
            self.seq_state, self.state_layers, self.prefix)
        self._state = None
        if self.seq_state:
            if int(state_blocks) < 2:
                raise ValueError(
                    f"KV pool with per-sequence state needs at least 2 "
                    f"state blocks (1 is the trash block), got "
                    f"{state_blocks}")
            self._state = _Kind(STATE, None, state_blocks,
                                len(self.state_layers))
        # the page kinds and, where declared, the state kind, by name
        self._every = dict(self._kinds)
        if self._state is not None:
            self._every[STATE] = self._state

    def _kind(self, kind=None):
        return self._kinds[self.kinds[0] if kind is None else kind]

    @property
    def state_blocks(self):
        """Blocks of each state tensor, the trash block included (0: no
        state declared)."""
        return 0 if self._state is None else self._state.num_pages

    def pages_by_kind(self):
        """{kind: pages of its tensors, trash included}."""
        return {k.name: k.num_pages for k in self._kinds.values()}

    # one-kind views of the counters, as they always read
    alloc_total = property(lambda self: sum(
        k.alloc_total for k in self._kinds.values()))
    free_total = property(lambda self: sum(
        sum(k.freed.values()) for k in self._kinds.values()))
    reused_allocs = property(lambda self: sum(
        k.reused_allocs for k in self._kinds.values()))

    # -- device arrays ------------------------------------------------------

    def install(self, scope):
        """Zero the pool vars into `scope` (idempotent on shape AND
        dtype match — an engine rebuild over a live scope keeps the
        resident pool; a rebuild with a different pool dtype must NOT,
        or every later write trips the dtype guard blaming the
        payload).  The zeros are made on the device: a pool is
        gigabytes, and host zeros would cross to the chip."""
        import jax.numpy as jnp

        def zeros(name, shape, dtype):
            cur = scope.get(name)
            if (cur is None or tuple(np.shape(cur)) != shape
                    or str(getattr(cur, "dtype", "")) != dtype):
                scope.set(name, jnp.zeros(shape, dtype=dtype))

        for names, kind, rows in zip(self.var_names, self.layer_kinds,
                                     self.layer_rows):
            for name, row in zip(names, rows):
                zeros(name, (self._kinds[kind].num_pages, self.page_size,
                             row.width), row.dtype)
        for names in self.state_var_names:
            for name, st in zip(names, self.seq_state):
                zeros(name, (self.state_blocks, *map(int, st.shape)),
                      st.dtype)

    # -- modeled bytes ------------------------------------------------------

    def _page_bytes(self, row):
        """Bytes one page of one layer holds of ``row``."""
        import jax.numpy as jnp  # its dtypes know bfloat16

        return self.page_size * row.width * jnp.dtype(row.dtype).itemsize

    def row_bytes(self, row, pages=None):
        """Device bytes of one declared row tensor over every layer that
        leaves it: resident (``pages`` None), or of ``pages`` pages — a
        number (of every layer) or ``{kind: pages}``."""
        if pages is None:
            pages = self.pages_by_kind()
        elif not isinstance(pages, dict):
            pages = dict.fromkeys(self.kinds, pages)
        return self._page_bytes(row) * sum(
            pages[kind] for kind, rows in zip(self.layer_kinds,
                                              self.layer_rows) if row in rows)

    def kind_bytes(self, kind, pages=None):
        """Device bytes of cache kind ``kind`` over its layers, each at
        its own rows: resident (``pages`` None), or of ``pages`` pages
        (blocks, for the ``state`` kind)."""
        if kind == STATE:
            return sum(self.state_bytes(st, pages) for st in self.seq_state)
        if pages is None:
            pages = self._kinds[kind].num_pages
        return pages * sum(
            self._page_bytes(row) for k, rows in zip(self.layer_kinds,
                                                     self.layer_rows)
            if k == kind for row in rows)

    def state_bytes(self, st, blocks=None):
        """Device bytes of one declared state tensor over every state
        layer: resident (``blocks`` None, the trash block included), or
        of ``blocks`` blocks."""
        import jax.numpy as jnp

        if blocks is None:
            blocks = self.state_blocks
        return (int(np.prod(st.shape)) * jnp.dtype(st.dtype).itemsize
                * blocks * len(self.state_layers))

    def modeled_bytes(self):
        """Device bytes of the resident pool: every declared row tensor
        of every layer (for the dual-int8 rows that is kernels/
        primitives/int8.py's ``dual_int8_bytes``) and every declared
        state tensor of every state layer."""
        return (sum(self.row_bytes(row) for row in self.rows)
                + sum(self.state_bytes(st) for st in self.seq_state))

    # -- allocation ---------------------------------------------------------

    def open_seq(self, seq_id):
        """Open `seq_id`: empty page lists and, where the lane declares
        per-sequence state, its ONE state block — raises
        PoolExhaustedError (nothing opened) when none is free; the
        caller evicts and retries."""
        if seq_id in self._kind().tables:
            raise ValueError(f"sequence {seq_id!r} already open")
        st = self._state
        if st is not None:
            if not st.free:
                raise PoolExhaustedError(
                    f"KV pool out of blocks of kind {st.name!r}: sequence "
                    f"{seq_id!r} needs 1 but 0 of {st.num_pages - 1} "
                    f"allocatable blocks are free — evict a sequence or "
                    f"grow state_blocks")
            st.tables[seq_id] = [st.take()]
        for k in self._kinds.values():
            k.tables[seq_id] = []
            k.first_live[seq_id] = 0

    def state_block(self, seq_id=None):
        """The state block `seq_id` holds (the trash block for None: an
        inactive slot, a warm-up chunk)."""
        if seq_id is None or self._state is None:
            return TRASH_PAGE
        return self._state.tables[seq_id][0]

    def ensure_capacity(self, seq_id, n_tokens):
        """Grow `seq_id`'s page table, of every kind, to cover `n_tokens`
        positions (a window kind allocates nothing below what it has
        released).  Raises PoolExhaustedError — with the kind and the
        shortfall named — when a free list runs dry; the caller (the
        scheduler) evicts and retries.  Returns the first kind's
        table."""
        need = -(-int(n_tokens) // self.page_size)  # ceil
        if need > self.max_pages_per_seq:
            raise ValueError(
                f"sequence {seq_id!r} needs {need} pages for "
                f"{n_tokens} tokens, above max_pages_per_seq="
                f"{self.max_pages_per_seq}")
        for k in self._kinds.values():
            table = k.tables[seq_id]
            while len(table) < need:
                if len(table) < k.first_live[seq_id]:
                    table.append(TRASH_PAGE)    # below the window already
                    continue
                if not k.free:
                    raise PoolExhaustedError(
                        f"KV pool out of pages of kind {k.name!r}: "
                        f"sequence {seq_id!r} needs "
                        f"{need - len(table)} more (of {need}) but 0 of "
                        f"{k.num_pages - 1} allocatable pages are free "
                        f"— evict a sequence or grow the pool")
                table.append(k.take())
        return self._kind().tables[seq_id]

    def release(self, seq_id, length):
        """`seq_id` now holds `length` tokens: give back, in every window
        kind, the logical pages WHOLLY below ``length - W`` — no later
        query sees a key of them — and point their table entries at the
        trash page.  The freed pages are reusable at once.  Returns how
        many pages went back."""
        n = 0
        for k in self._kinds.values():
            if k.window is None or seq_id not in k.tables:
                continue
            table = k.tables[seq_id]
            first = k.first_live[seq_id]
            upto = max(first, (int(length) - k.window) // self.page_size)
            dead = [table[lp] for lp in range(first, min(upto, len(table)))]
            table[first:first + len(dead)] = [TRASH_PAGE] * len(dead)
            k.first_live[seq_id] = upto
            k.give_back(dead, "window")
            n += len(dead)
        return n

    def free_seq(self, seq_id, why="end"):
        """Return every page of `seq_id`, of every kind, to the free
        lists (LIFO).  ``why``: ``end`` (the request finished) or
        ``evict``."""
        n = 0
        for k in self._every.values():
            pages = [p for p in k.tables.pop(seq_id, [])
                     if p != TRASH_PAGE]
            k.first_live.pop(seq_id, None)
            k.give_back(pages, why)
            n += len(pages)
        return n

    # -- views --------------------------------------------------------------

    def table(self, seq_id, kind=None):
        return list(self._kind(kind).tables[seq_id])

    def live_seqs(self):
        return list(self._kind().tables)

    def pages_in_use(self, kind=None):
        """Pages allocated to live sequences: of ``kind``, or of every
        kind together."""
        if kind is not None:
            return self._every[kind].in_use()
        return sum(k.in_use() for k in self._kinds.values())

    def padded_table(self, seq_id=None, kind=None):
        """One row of the decode feed: the sequence's page table of
        ``kind`` (the first kind if None) padded with the trash page to
        max_pages_per_seq (all-trash when seq_id is None — the
        inactive-slot row)."""
        row = np.full(self.max_pages_per_seq, TRASH_PAGE, np.int32)
        if seq_id is not None:
            pages = self._kind(kind).tables[seq_id]
            row[:len(pages)] = pages
        return row

    def kind_stats(self):
        """Per kind: pages, pages in use, and the running totals of pages
        allocated and of pages freed by reason.  The ``state`` kind,
        where declared, counts its blocks under the same keys."""
        return {k.name: {"pages_total": k.num_pages - 1,
                         "pages_in_use": k.in_use(),
                         "alloc_total": k.alloc_total,
                         "freed": dict(k.freed)}
                for k in self._every.values()}

    def stats(self):
        return {
            "pages_total": sum(k.num_pages - 1
                               for k in self._kinds.values()),
            "pages_in_use": self.pages_in_use(),
            "page_size": self.page_size,
            "live_seqs": len(self._kind().tables),
            "alloc_total": self.alloc_total,
            "free_total": self.free_total,
            "reused_allocs": self.reused_allocs,
            "kinds": self.kind_stats(),
        }
