"""Paged attention — K/V read through a per-sequence page table.

Migrated from the ad-hoc ``kernels/paged_attention.py`` (which now
re-exports from here) onto the primitives contract, and extended with
the **int8-pool form**: ``paged_attention_quant`` reads a dual-int8
block-scaled pool (hi/lo int8 + per-vector fp32 scale, the
quantized_collectives wire format applied to storage) and dequantizes
INSIDE the kernel — the pool lives in HBM at ~half the fp32 bytes and
fp32 never exists outside VMEM blocks (docs/KERNELS.md "int8 KV").

This primitive is also the decode lane's RAGGED form: ``q_start`` is a
per-sequence length vector, so each row attends exactly its own prefix
— pages wholly past ``q_start[b] + t - 1`` are skipped via ``pl.when``
and no padded key is ever scored (primitives/ragged.py holds the dense
prefill form of the same contract).

The decode serving lane (docs/SERVING.md "Decode lane") stores K/V in a
pool of fixed-size pages (`serving/kv_pool.py`): a sequence's cache is a
LIST of page ids, not a contiguous slab, so admission/eviction moves no
memory and the decode step is one fixed-shape executable regardless of
how many sequences are live or how long each one is.

Two implementations (the shared resolve_mode dispatch):

- **XLA reference** (CPU fallback + numerics oracle): gather the pages
  (`k_pages[page_table]`), mask positions past each query's length with
  the same -1e9 the fused causal softmax op uses, `jax.nn.softmax`.
- **Pallas kernel**: the page table and per-row start offsets ride as
  scalar prefetch, so the kernel resolves PHYSICAL page ids itself and
  never sees a gathered copy of the pool.  It reads G pages at a time
  (256 keys at page 32), concatenated on the key axis before ONE score
  / online-softmax / value update in float32, and touches no page past
  a row's length.  Two bodies, chosen from ``q``'s shape at trace time
  and booked on ``pt_paged_attention_form_total``:
  **heads-batched** for a decode row (T == 1) — grid (B,), the pools
  left in HBM, the row's live pages copied G at a time into two VMEM
  slots (the next group, or the next row's first, in flight while this
  one is scored), all heads in one block-diagonal product;
  **per-head** for a prefill chunk (T > 1) and for the int8 pool —
  grid (B, pages / G), the pool passed G times with one BlockSpec a
  page, heads as d-wide lane slices.  The comment block over the
  kernels says what each buys.

**Grouped-query heads and window layers** (PR 31).  The pools may hold
fewer K/V heads than ``q`` has query heads (``n_q = g x n_kv``: query
head j reads K/V head ``j // g``), and ``window=W`` bounds the keys from
below: query i of row b sees ``max(0, q_start[b] + i - W + 1) ..
q_start[b] + i``.  Either turns the launch into the grouped form of the
two bodies — the decode row still copies its live pages itself, now
starting at the first page its window reaches, with the g query heads of
a K/V head as g rows on that head's lanes; the chunk gets a grid axis a
K/V head (a (page, d) lane block of the pool as stored, so d must be a
multiple of 128 on the chip) and scores the group's g x tq query rows of
a tile at once, over the steps between its first query's window and its
last query, and no others.  **The chunk's grid step** (PR 43): the query
tile and the keys a step are chosen TOGETHER from the shapes
(``_chunk_geometry``: as many of the group's rows as one score product
takes, a tile no longer than half a window, then as many whole pages as
the float32 score tiles and the layer's span allow — 256 queries x 1024
keys for Trinity's 6 heads, 512 x 1024 for one head a K/V head, 64 x
1024 for MiMo's 16 heads and 64 x 256 under its window of 128), the
grid's step axis ends at the last step the call's chunk reaches (a
traced extent), and a step that every query of the tile sees whole is
scored without a mask.  The decode row's G is not the chunk's: it stays
what ``_pages_per_step`` gives.  Pages below the bound are neither fetched nor
scored: **a table entry wholly below a row's window may be anything**
(the allocator points it at the trash page once the page is given
back, serving/kv_pool.py); what the kernel assumes of the trash page
is only that it holds finite numbers.  The kernel's ``name=`` says
which kind it serves (``paged_attention``, ``.._grouped``,
``.._window``, ``.._grouped_window``), in the HLO and on the dispatch
and form counters.  Plain multi-head attention over the whole context
(g = 1, no window) traces exactly what it did.

**Value heads narrower than key heads, and a sink** (PR 41).  The V
pool may hold its heads at another width than the K pool
(``n_kv x d_v`` lanes beside ``n_kv x d``; the output is d_v wide), and
``sinks`` [n_heads] float32 joins every query's softmax as one more
column that carries no value: ``p_t = exp(s_t - m) / (sum_u exp(s_u -
m) + exp(b_h - m))`` with ``m`` the largest of the scores and ``b_h``.
Either takes the asymmetric form of the grouped bodies
(``.._asym``, ``.._sink`` in the kernel's name).  The decode row reads
its pages as the grouped row does; its queries arrive laid out on their
K/V head's lanes already, so no lane of the row is cut at a head's
edge.  The chunk reads the K pool in LANE BLOCKS of whole 128-lane
tiles — two heads of 192 as one block of 384 — and scores each head of
the block against the aligned tiles that cover it (lanes 0..255 for the
first, 128..383 for the second, the query zero outside its own lanes:
the products are those of a 192-wide head, at the two MXU passes a
192-wide contraction takes anyway), the V pool in blocks of the same
heads.  The sink is the state the online softmax STARTS from (m = b_h,
l = 1), so a step scores nothing more.  The pool is read as stored:
nothing pads a head to 256 lanes.

Shapes:
  q           [B, n_heads, T, d]   T = 1 (decode step) or the prefill
                                   chunk length
  k/v_pages   [num_pages, page_size, n_kv_heads * d] — heads side by
              side in the lane dimension (head h is lanes h*d..(h+1)*d);
              n_kv_heads divides n_heads.  v_pages may be
              [.., n_kv_heads * d_v] with d_v != d.
              The pool is stored, written and read in THIS shape and
              no other: its default TPU layout is row-major with
              (8, 128) tiles of (page_size, n_heads*d), which is what
              the kernel's blocks address.  Declared with the heads
              apart, [.., n_heads, 64], XLA:TPU puts the page index
              minor-most (a 64-wide minor dimension would pad to 128
              lanes) and every executable copies the whole pool into
              the kernel's layout and back (PERF.md finding 4).
  page_table  [B, max_pages] int32 — physical page of each logical page
  q_start     [B] int32 — tokens already in the cache BEFORE this q
              block; query i of row b attends keys at global positions
              j <= q_start[b] + i (its own K/V must already be written)
              and, with ``window``, j > q_start[b] + i - window

Page 0 of the pool is the allocator's trash page (writes of inactive
slots land there); a row's mask only ever exposes positions below its
own length, so trash content is never attended.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import autotune, contract
from .contract import Block, DmaSem, Smem, Vmem
from .int8 import RESID_DIV, dequantize_lastdim

NEG_INF = -1e9  # the fused causal softmax op's mask constant — shared so
# the decode lane's masked softmax matches the composed path's spelling

__all__ = ["paged_attention", "paged_attention_reference",
           "paged_attention_quant", "paged_attention_quant_reference"]


def paged_attention_reference(q, k_pages, v_pages, page_table, q_start,
                              sm_scale=None, window=None, sinks=None):
    """Materializing XLA implementation: CPU fallback + numerics oracle.

    Mirrors the composed attention path's op spelling (matmul — scale —
    -1e9 mask — jax.nn.softmax — matmul) so greedy decode through the
    pool is comparable with the whole-sequence program token for
    token.  Grouped-query pools, ``window``, V heads of another width
    and ``sinks`` as ``paged_attention``."""
    b, n, t, d = q.shape
    n_kv, d_v = _kv_heads("paged_attention", q, k_pages, v_pages)
    page_size = k_pages.shape[1]
    max_pages = page_table.shape[1]
    l_max = max_pages * page_size
    scale = float(sm_scale if sm_scale is not None else 1.0 / np.sqrt(d))

    def gathered(pages, width):
        # the heads come apart on the gathered pages, never on the pool
        g = pages[page_table]                      # [B, MAXP, PGS, n_kv*w]
        g = g.reshape(b, l_max, n_kv, width)
        g = jnp.transpose(g, (0, 2, 1, 3))         # [B, n_kv, L, w]
        # query head j reads K/V head j // (n / n_kv)
        return g if n_kv == n else jnp.repeat(g, n // n_kv, axis=1)

    k = gathered(k_pages, d)
    v = gathered(v_pages, d_v)
    s = jnp.matmul(q.astype(jnp.float32),
                   jnp.swapaxes(k.astype(jnp.float32), -1, -2)) * scale
    kpos = jax.lax.broadcasted_iota(jnp.int32, (b, n, t, l_max), 3)
    qpos = (q_start.astype(jnp.int32)[:, None, None, None]
            + jax.lax.broadcasted_iota(jnp.int32, (b, n, t, l_max), 2))
    visible = kpos <= qpos
    if window is not None:
        visible &= kpos > qpos - int(window)
    s = jnp.where(visible, s, jnp.asarray(NEG_INF, s.dtype))
    if sinks is None:
        p = jax.nn.softmax(s, axis=-1)
    else:   # one more column a head, which carries no value
        sink = jnp.broadcast_to(
            sinks.astype(jnp.float32)[None, :, None, None], (b, n, t, 1))
        p = jax.nn.softmax(jnp.concatenate([s, sink], axis=-1),
                           axis=-1)[..., :l_max]
    return jnp.matmul(p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels.  The page table + q_start ride as scalar prefetch so
# that physical page ids are resolved on the chip — the pool is never
# gathered into a copy.
#
# Mosaic tiling: a block's last two dims must be (8, 128)-divisible or
# span the array's.  d = 64 on the real models, so a per-head block
# (.., 1, d) cannot lower: what is read is one whole page of every head,
# (page, n*d), of the pool AS IT IS STORED.  Nothing here reshapes the
# pool — a reshape of [P, page, n, d] to this shape is "free" only in
# row-major order, which is not the layout XLA:TPU gives the 4-D array
# (tests/test_mosaic_aot.py holds the compiled executables to zero
# pool-shaped copies).
#
# What a grid step carries (PERF.md section 6, PR 30; GPT-2-large's
# decode step, 16 rows of ~350 tokens, one page = 164 KB of K and of V =
# 0.4 us of HBM time).  Until PR 30 a step was ONE page and a Python loop
# over the heads: 20 x ([8,64] x [64,32] dot, where, max, two exp, sum,
# [8,32] x [32,64] dot) on sub-vreg tiles, ~2 us a live page and a dead
# step for every page of the table past a row's length — 600 us a call,
# 11.8% of the pool's bandwidth.  Now G pages (256 keys) meet ONE score /
# softmax / value update, in one of two bodies:
#
#   heads-batched  (T == 1, the decode step; _paged_heads_kernel)
#       The n query rows ride as a block-diagonal [n, n*d] operand (row
#       h holds q_h in lanes h*d..(h+1)*d, zeros elsewhere), so
#       S = Q . K^T is all heads' scores in one product with the pages
#       as stored — no d-wide lane slices — the softmax runs once over
#       [n, G*page] and the diagonal d-wide blocks of P . V are taken
#       once a row.  The zeros add exact zeros: the products are those
#       of the per-head form.  Grid (B,): the pools stay in HBM
#       (Block(None)) and the kernel copies a row's LIVE pages itself,
#       G at a time, into one of two VMEM slots while the other slot is
#       scored; a row's last group starts the next row's first.  Nothing
#       is paid for a page past a row's length, and a copy is waited for
#       only where the bytes are what takes the time: 118 us a call, 60%
#       of the pool's bandwidth (76% at 1024-token rows).  With a
#       BlockSpec a page instead (the per-head body's launch) the same
#       body took 160 us: a dead step costs ~1 us at 19 operands, and a
#       row's first fetch is exposed behind the dead steps of the row
#       before.
#   per-head       (T > 1, the prefill chunk; the int8 pool at any T;
#                   _paged_body)
#       Grid (B, pages / G); the pool is passed G times, the j-th copy's
#       index map resolving the step's j-th page (dsa.py's idiom), heads
#       as static d-wide lane slices of the G pages.  A chunk is one
#       row: what it gains is 4 grid steps instead of 32 and 256-key
#       dots (64 -> 35 us a call).  The int8 pool's scales are one a
#       head, not one a lane, so its decode row stays here too.
#   grouped        (n_q = g x n_kv heads and/or a window; PR 31)
#       T == 1: the heads-batched body with the g query heads of K/V
#       head j as g rows on lanes j*d..(j+1)*d, the first group copied
#       starting at the page the row's window reaches.  T > 1
#       (_paged_kv_head_kernel): grid (B, n_kv, query tiles, steps), a
#       K/V head's (page, d) lane block a page, the group's g x tq query
#       rows stacked into one [g*tq, d] operand, so that a tile of 256
#       queries of 6 heads meets 1024 keys as a [1536, 128] x [128, 1024]
#       product (_chunk_geometry, PR 43); the steps start at the tile's
#       first query's window, the grid has only as many as a window and
#       a tile span and ends at the last one the chunk reaches.  A bf16
#       pool feeds the MXU in bf16 (float32 accumulation, float32
#       softmax state).
# ---------------------------------------------------------------------------

_SUBLANES = 8
# keys scored at once by a DECODE ROW and by the per-head chunk body, and
# the VMEM the two slots (or the double-buffered blocks) of one pool form
# may take: G = pages_per_step is the most pages inside both — 8 at page
# 32.  It is the decode row's measurement: on the chip G = 4 / 8 / 16
# read 123 / 118 / 113 us a decode call at GPT-2-large's mixed contexts
# and 300 / 270 / 259 at 1024 tokens (PERF.md section 6, PR 30): flat, so
# the smaller VMEM footprint wins.  No chunk was in it: the grouped and
# lane-block chunk bodies take their step from ``_chunk_geometry``
_KEYS_PER_STEP = 256
_BLOCK_VMEM_BYTES = 8 << 20


def _check_pool_shapes(op, q, scales=(), grouped=False, **pools):
    """The pool has ONE shape, [num_pages, page_size, n_heads*head_dim]
    (an int8 pool's scales [num_pages, page_size, n_heads]); a caller
    that holds the heads apart is refused, not reshaped.  ``grouped``
    also takes n_kv*head_dim lanes for an n_kv that divides n_heads;
    returns the pools' K/V heads."""
    n, d = q.shape[1], q.shape[3]
    n_kv = n
    for what, x in pools.items():
        lanes = n if what in scales else n * d
        if (grouped and x.ndim == 3 and x.shape[2] and x.shape[2] % d == 0
                and n % (x.shape[2] // d) == 0):
            n_kv = x.shape[2] // d
            continue
        if x.ndim != 3 or x.shape[2] != lanes:
            raise ValueError(
                f"{op}: {what} has shape {tuple(x.shape)}, but the KV "
                f"pool is stored [num_pages, page_size, {lanes}] — "
                f"{n} heads side by side in the last dimension, so that "
                f"no executable copies the pool between layouts; "
                f"reshape a gathered page if the heads are needed "
                f"apart, never the pool (docs/SERVING.md 'Decode lane')")
    return n_kv


def _kv_heads(op, q, k_pages, v_pages):
    """(K/V heads, width of a V head) of the grouped pools: the V pool
    may hold its heads at another width than the K pool (and q)."""
    n, d = q.shape[1], q.shape[3]
    if (k_pages.ndim != 3 or v_pages.ndim != 3
            or k_pages.shape[2] == v_pages.shape[2]):
        return _check_pool_shapes(op, q, grouped=True, k_pages=k_pages,
                                  v_pages=v_pages), d
    n_kv = _check_pool_shapes(op, q, grouped=True, k_pages=k_pages)
    if v_pages.shape[2] % n_kv or k_pages.shape[:2] != v_pages.shape[:2]:
        raise ValueError(
            f"{op}: K pool {tuple(k_pages.shape)} holds {n_kv} heads of "
            f"{d}, but V pool {tuple(v_pages.shape)} is not as many "
            f"pages of as many whole heads")
    return n_kv, v_pages.shape[2] // n_kv


def _online_softmax_step(s, v, acc_ref, m_ref, l_ref, p_dtype=None):
    """One kv-block update of the running (max, sum, acc) state — the
    shared online-softmax spelling of every attention primitive.
    ``p_dtype`` rounds the probabilities to the values' storage dtype
    for the MXU (a bf16 cache); None keeps them float32."""
    m_prev, l_prev = m_ref[...], l_ref[...]
    s_max = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(s_max, m_prev.shape))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    m_ref[...] = m_new
    l_ref[...] = l_prev * alpha + jnp.broadcast_to(
        jnp.sum(p, axis=1, keepdims=True), l_prev.shape)
    acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
        p if p_dtype is None else p.astype(p_dtype), v,
        preferred_element_type=jnp.float32)


def _step_pages(refs):
    """One pool's pages of the current grid step, concatenated on the
    key axis: [G * page, width].  Loaded whole, once a step: a head's
    lanes are sliced from the value (sliced from the refs, the 2 x G
    loads a head made a 36-layer program 8 s slower to trace)."""
    tiles = [r[0] for r in refs]
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=0)


def _init_state(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _init_sink(sink, m_ref, l_ref):
    """The state a softmax with a sink starts from: the sink logit
    ``sink`` (the state's shape) is a column already summed, so m = b_h
    and l = exp(b_h - m) = 1.  A sink of -inf leaves the plain start."""
    m = jnp.maximum(sink, NEG_INF)
    m_ref[...] = m
    l_ref[...] = jnp.exp(sink - m)


def _paged_body(page_table_ref, q_start_ref, q_ref, o_ref, acc_ref, m_ref,
                l_ref, load_step, *, keys, t, n, n_steps, sm_scale):
    """The per-head grid-step body both pool forms share;
    ``load_step()`` reads the step's pages and returns ``load_kv``, whose
    ``load_kv(h)`` is head h's fp32 (K, V) [keys, d] tiles of them."""
    from jax.experimental import pallas as pl

    bi = pl.program_id(0)
    pi = pl.program_id(1)
    tp = q_ref.shape[2]  # t rounded up to a sublane multiple

    pl.when(pi == 0)(lambda: _init_state(acc_ref, m_ref, l_ref))
    start = q_start_ref[bi]

    # the step is live iff its first key position is attendable by the
    # LAST query of the block (global key limit = start + t - 1)
    @pl.when(pi * keys <= start + t - 1)
    def _step():
        kpos = pi * keys + jax.lax.broadcasted_iota(
            jnp.int32, (tp, keys), 1)
        qpos = start + jax.lax.broadcasted_iota(jnp.int32, (tp, keys), 0)
        visible = kpos <= qpos
        load_kv = load_step()
        for h in range(n):
            q = q_ref[0, h].astype(jnp.float32)                 # [tp, d]
            k, v = load_kv(h)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
            s = jnp.where(visible, s * sm_scale, NEG_INF)
            _online_softmax_step(s, v, acc_ref.at[h], m_ref.at[h],
                                 l_ref.at[h])

    @pl.when(pi == n_steps - 1)
    def _finish():
        for h in range(n):
            l = l_ref[h]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, h] = (acc_ref[h] / l_safe[:, :1]).astype(o_ref.dtype)


def _paged_kernel(page_table_ref, q_start_ref, q_ref, *refs, d, n_sub,
                  **kw):
    def load_step():
        k, v = _step_pages(refs[:n_sub]), _step_pages(refs[n_sub:2 * n_sub])

        def load_kv(h):
            lanes = slice(h * d, (h + 1) * d)
            return (k[:, lanes].astype(jnp.float32),
                    v[:, lanes].astype(jnp.float32))

        return load_kv

    _paged_body(page_table_ref, q_start_ref, q_ref, *refs[2 * n_sub:],
                load_step, **kw)


def _mxu(x):
    """A pool tile as the MXU takes it: bfloat16 as stored, anything
    else in float32."""
    return x if x.dtype == jnp.bfloat16 else x.astype(jnp.float32)


def _p_dtype(pool_dtype):
    return jnp.bfloat16 if pool_dtype == jnp.bfloat16 else None


def _paged_heads_kernel(page_table_ref, q_start_ref, q_ref, k_hbm, v_hbm,
                        o_ref, k_buf, v_buf, sem, slot_ref, acc_ref, m_ref,
                        l_ref, *, d, n_sub, page_size, n_rows, sm_scale,
                        heads_per_kv=None, window=None, d_v=None,
                        sink_ref=None):
    """The heads-batched body of a decode row (T == 1), one grid step a
    row: q_ref is the row [1, n*d] of all heads' queries, the state
    [rows, ...] holds head h in row h (rows = n rounded up to a sublane
    multiple; a padding row scores zeros against every key and is
    dropped at the end).  The pools stay in HBM; the row's LIVE pages,
    and no others, are copied ``n_sub`` at a time into one of two VMEM
    slots while the other slot's keys are scored, and the row's last
    group starts the next row's first, so that a copy is waited for only
    where the pool's bytes are what takes the time.

    The grouped form (``heads_per_kv`` = g, not None): q_ref and o_ref
    are [rows, d], query head h a row of its own on the lanes of K/V
    head h // g.  With ``window`` a row's groups count from the first
    page its window reaches, and keys below the window are masked.

    The asymmetric form (``d_v`` not None): the V pool's heads are d_v
    wide, so the state is [rows, n_kv*d_v] and o_ref [rows, d_v]; q_ref
    is [rows, n_kv*d], each query laid on its K/V head's lanes by the
    launch (zeros elsewhere), in the pool's dtype.  ``sink_ref``
    [rows, 128] float32 (every lane the row's sink logit) is the state
    the softmax starts from: m = b_h, l = exp(b_h - m) = 1."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bi = pl.program_id(0)
    rows, width = acc_ref.shape
    keys = n_sub * page_size
    dv = d if d_v is None else d_v

    def first_page(row):
        """The first page ``row``'s window reaches."""
        return jax.lax.div(
            jnp.maximum(q_start_ref[row] - (window - 1), 0), page_size)

    def each_page(row, group, slot, act):
        """``act`` on the (K, V) copies of every LIVE page of one group
        of ``row``: a start and its wait walk the same pages."""
        first = group * n_sub
        if window is not None:
            first = first + first_page(row)
        live = jnp.clip(jax.lax.div(q_start_ref[row], page_size) + 1 - first,
                        0, n_sub)

        def page(j, carry):
            src = page_table_ref[row, first + j]
            dst = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            for which, (pool, buf) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf))):
                act(pltpu.make_async_copy(
                    pool.at[src], buf.at[slot, dst], sem.at[slot, which]))
            return carry

        jax.lax.fori_loop(0, live, page, 0)

    def start(row, group, slot):
        each_page(row, group, slot, lambda copy: copy.start())

    def wait(row, group, slot):
        each_page(row, group, slot, lambda copy: copy.wait())

    @pl.when(bi == 0)
    def _first():
        # a page never copied is scored too (masked): it may hold stale
        # keys, never a NaN, which an exact 0 of P would not silence
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        start(0, 0, 0)

    q_start = q_start_ref[bi]
    if window is None:
        groups = jax.lax.div(q_start, keys) + 1
        first_key = 0
    else:
        groups = jax.lax.div(
            jax.lax.div(q_start, page_size) - first_page(bi), n_sub) + 1
        first_key = first_page(bi) * page_size
    first_slot = slot_ref[0]
    _init_state(acc_ref, m_ref, l_ref)
    if sink_ref is not None:
        _init_sink(sink_ref[...], m_ref, l_ref)

    if d_v is not None:
        q = q_ref[0]
    else:
        # [rows, n*d] bool: the d lanes of row h's own head
        first_lane = d * jax.lax.broadcasted_iota(
            jnp.int32, (rows, width), 0)
        if heads_per_kv is not None:  # row h on the lanes of head h // g
            first_lane = d * jax.lax.div(jax.lax.broadcasted_iota(
                jnp.int32, (rows, width), 0), heads_per_kv)
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
        own_lanes = (lane >= first_lane) & (lane < first_lane + d)
        if heads_per_kv is None:
            q = jnp.where(own_lanes, jnp.broadcast_to(
                q_ref[0].astype(jnp.float32), (rows, width)), 0.0)
        else:
            q = jnp.where(own_lanes, jnp.tile(
                q_ref[0].astype(jnp.float32), (1, width // d)), 0.0)
        if k_buf.dtype == jnp.bfloat16:
            q = q.astype(jnp.bfloat16)

    def score_group(g, carry):
        slot = jax.lax.rem(first_slot + g, 2)
        wait(bi, g, slot)

        # into the other slot: this row's next group or, behind its
        # last, the next row's first
        more = g + 1 < groups

        @pl.when(more | (bi + 1 < n_rows))
        def _next():
            start(jnp.where(more, bi, jnp.minimum(bi + 1, n_rows - 1)),
                  jnp.where(more, g + 1, 0), 1 - slot)

        s = jax.lax.dot_general(
            q, _mxu(k_buf[slot]), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [rows, keys]
        kpos = g * keys + jax.lax.broadcasted_iota(
            jnp.int32, (rows, keys), 1)
        if window is None:
            visible = kpos <= q_start
        else:
            kpos = kpos + first_key
            visible = (kpos <= q_start) & (kpos > q_start - window)
        s = jnp.where(visible, s * sm_scale, NEG_INF)
        _online_softmax_step(s, _mxu(v_buf[slot]), acc_ref, m_ref, l_ref,
                             p_dtype=_p_dtype(v_buf.dtype))
        return carry

    jax.lax.fori_loop(0, groups, score_group, 0)
    slot_ref[0] = jax.lax.rem(first_slot + groups, 2)

    l = l_ref[...]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    if heads_per_kv is None:
        out = jnp.where(own_lanes, acc_ref[...] / l_safe[:, :1], 0.0)
        o_ref[0] = jnp.sum(out, axis=0, keepdims=True).astype(o_ref.dtype)
    else:   # row h's d lanes, from under its K/V head
        normed = acc_ref[...] / l_safe[:, :1]
        kv_head = jax.lax.div(jax.lax.broadcasted_iota(
            jnp.int32, (rows, dv), 0), heads_per_kv)
        out = jnp.zeros((rows, dv), jnp.float32)
        for j in range(width // dv):
            out = out + jnp.where(kv_head == j,
                                  normed[:, j * dv:(j + 1) * dv], 0.0)
        o_ref[0] = out.astype(o_ref.dtype)


def _paged_heads_sink_kernel(page_table_ref, q_start_ref, q_ref, sink_ref,
                             *refs, **kw):
    """``_paged_heads_kernel`` with the sinks as one operand more."""
    _paged_heads_kernel(page_table_ref, q_start_ref, q_ref, *refs,
                        sink_ref=sink_ref, **kw)


def _first_step(start, window, keys):
    """The first step of ``keys`` keys that a query at ``start`` with
    ``window`` reaches."""
    return jax.lax.div(jnp.maximum(start - (window - 1), 0), keys)


def _chunk_step(q_start_ref, bi, qi, pi, *, keys, tq, window):
    """Where one grid step of a chunk body stands: (the position of its
    query tile's first query, the step's first key, whether a query of
    the tile sees a key of the step, whether EVERY query of the tile
    sees every key of it).  The tile's steps count from the one its
    first query's window reaches."""
    start = q_start_ref[bi] + qi * tq
    step = pi if window is None else _first_step(start, window, keys) + pi
    first_key = step * keys
    live = first_key <= start + tq - 1
    # not on the causal edge: the step's last key is the first query's
    # own or older; not on the window's lower edge: its first key is
    # inside the last query's window
    whole = first_key + keys - 1 <= start
    if window is not None:
        whole &= first_key > start + tq - 1 - window
    return start, first_key, live, whole


def _chunk_mask(start, first_key, rows, keys, tq, window):
    """[rows, keys] bool: the keys of a step that row r*tq + i, query i
    of a tile that starts at position ``start``, sees."""
    kpos = first_key + jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
    qpos = start + jax.lax.rem(jax.lax.broadcasted_iota(
        jnp.int32, (rows, keys), 0), tq)
    visible = kpos <= qpos
    if window is not None:
        visible &= kpos > qpos - window
    return visible


def _paged_kv_head_kernel(page_table_ref, q_start_ref, run_ref, q_ref, *refs,
                          n_sub, keys, tq, heads_per_kv, window, sm_scale):
    """The grouped body of a chunk (T > 1): one grid step is one K/V
    head (its d lanes of ``n_sub`` pages), one tile of ``tq`` queries
    and one step of ``keys`` keys.  q_ref / o_ref [g*tq, d]: row
    r*tq + i is query i of the group's r-th head.  A step that every
    query of the tile sees whole is scored without a mask; only the
    steps on the causal and the window's edges are masked.  The grid's
    step axis ends at ``run_ref[0]`` steps."""
    from jax.experimental import pallas as pl

    k_refs, v_refs = refs[:n_sub], refs[n_sub:2 * n_sub]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * n_sub:]
    bi, qi, pi = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    rows = heads_per_kv * tq

    pl.when(pi == 0)(lambda: _init_state(acc_ref, m_ref, l_ref))
    start, first_key, live, whole = _chunk_step(
        q_start_ref, bi, qi, pi, keys=keys, tq=tq, window=window)

    def score(masked):
        k, v = _mxu(_step_pages(k_refs)), _mxu(_step_pages(v_refs))
        s = jax.lax.dot_general(
            q_ref[0, 0, 0].astype(k.dtype), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [rows, keys]
        s = s * sm_scale
        if masked:
            s = jnp.where(_chunk_mask(start, first_key, rows, keys, tq,
                                      window), s, NEG_INF)
        _online_softmax_step(s, v, acc_ref, m_ref, l_ref,
                             p_dtype=_p_dtype(v.dtype))

    pl.when(live & whole)(lambda: score(False))
    pl.when(live & jnp.logical_not(whole))(lambda: score(True))

    @pl.when(pi == run_ref[0] - 1)
    def _finish():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, 0] = (acc_ref[...] / l_safe[:, :1]).astype(o_ref.dtype)


# What a grid step of a chunk body (T > 1: the grouped and the lane-block
# launch) may hold (PR 43): the rows of one score product (the heads of
# a group x the queries of a tile), the keys it scores at once, and the
# float32 bytes of the [rows, keys] score tiles of the K/V heads a step
# scores (one; a lane block's P).  Every geometry they admit at the
# served widths compiles under Mosaic's default 16 MiB of scoped VMEM
# (tests/test_mosaic_aot.py); the readings behind them are in
# docs/KERNELS.md ("The chunk's grid step"): on a v5e more rows at equal
# keys read faster up to 1536 (Trinity's 6 heads x 256), more keys than
# 1024 read no faster, and 1024 rows x 1536 keys of a lane block of two
# heads no longer fit
_CHUNK_ROWS_PER_STEP = 1536
_CHUNK_KEYS_PER_STEP = 1024
_CHUNK_SCORE_BYTES = 8 << 20


def _chunk_geometry(tp, heads_per_kv, kv_heads, page_size, max_pages,
                    window):
    """(query tile, pages a step) of a chunk's launch, chosen together
    from the shapes.  The tile: the largest sublane-multiple divisor of
    the padded chunk ``tp`` whose group's rows fit one score product (as
    few tiles as may be stream K and V again) and that is no longer than
    half a window (a tile scores window + tq keys a query, of which each
    query sees window).  The step: the most whole pages within the keys
    a step may score, the score tiles' bytes of the ``kv_heads`` heads
    it scores, and what the layer can use: the table, or what a window
    and a tile span."""
    tq = _SUBLANES
    for tiles in range(1, tp // _SUBLANES + 1):
        tile = tp // tiles
        if (tp % tiles == 0 and tile % _SUBLANES == 0
                and heads_per_kv * tile <= _CHUNK_ROWS_PER_STEP
                and (window is None or 2 * tile <= window)):
            tq = tile
            break
    keys = min(_CHUNK_KEYS_PER_STEP,
               _CHUNK_SCORE_BYTES // (4 * kv_heads * heads_per_kv * tq))
    usable = max_pages
    if window is not None:
        usable = min(usable, -(-(int(window) + tq) // page_size))
    return tq, max(1, min(keys // page_size, usable))


# most bytes the per-head chunk body's whole-chunk blocks may take: its
# q and o blocks (double-buffered) and its accumulator hold every head's
# queries at once
_PER_HEAD_VMEM_BYTES = 8 << 20


def _per_head_block_bytes(q):
    _, n, t, d = q.shape
    tp = -(-t // _SUBLANES) * _SUBLANES
    return n * tp * (4 * d * q.dtype.itemsize + 4 * d + 2 * 4 * 128)


def _pages_per_step(name, q, page_size, max_pages, pools):
    """G: the pages one grid step reads — from the shapes, or from a
    pinned entry of the tile table (autotune.py) for this signature."""
    b, n, t, d = q.shape
    page_bytes = sum(page_size * x.shape[2] * x.dtype.itemsize
                     for x in pools)
    g = max(1, _KEYS_PER_STEP // page_size)
    while g > 1 and 2 * g * page_bytes > _BLOCK_VMEM_BYTES:
        g //= 2
    tile = autotune.tile_for(
        name, autotune.shape_signature(b=b, n=n, t=t, d=d, page=page_size,
                                       pages=max_pages),
        {"pages_per_step": g})
    return max(1, min(int(tile["pages_per_step"]), max_pages))


def _book_form(primitive, form, pages_per_step):
    """Count one trace-time choice of kernel body on
    ``pt_paged_attention_form_total{primitive, form, pages_per_step}``."""
    from paddle_tpu.observability import metrics as obs

    obs.counter(
        "pt_paged_attention_form_total",
        "Trace-time choices of the paged-attention Pallas kernel's body "
        "(heads_batched = all heads in one product, a decode row; "
        "per_head = heads as lane slices, a prefill chunk or the int8 "
        "pool; kv_head_tq<tq> = a grid axis a K/V head or lane block, a "
        "grouped chunk in tiles of tq queries) and the pages one grid "
        "step reads",
        labels=("primitive", "form", "pages_per_step"),
    ).labels(primitive=primitive, form=form,
             pages_per_step=str(pages_per_step)).inc()


def _heads_batched_call(name, q, pools, page_table, q_start, scale,
                        interpret, g, heads_per_kv=None, window=None,
                        d_v=None, sinks=None):
    """A decode row's launch (T == 1): grid (B,), the pools unblocked.
    ``heads_per_kv`` (not None) is the grouped form: q and the output
    ride [rows, d], a query head a row.  ``d_v`` (not None) is the
    asymmetric form: q rides [rows, n_kv*d], laid on its K/V head's
    lanes here, the output [rows, d_v]; ``sinks`` [n] one operand
    more."""
    b, n, _, d = q.shape
    page_size = pools[0].shape[1]
    rows = -(-n // _SUBLANES) * _SUBLANES

    def row_map(bi, pt, qs):
        return (bi, 0, 0)

    kernel, extra, extra_specs = _paged_heads_kernel, (), []
    if heads_per_kv is None:
        q_block, q_rows = (1, 1, n * d), q.reshape(b, 1, n * d)
        o_block = q_block
        kw = {}
    else:
        q_block = o_block = (1, rows, d)
        q_rows = jnp.pad(q.reshape(b, n, d), ((0, 0), (0, rows - n), (0, 0)))
        kw = {"heads_per_kv": heads_per_kv, "window": window}
    if d_v is not None:
        n_kv = n // heads_per_kv
        # row h on the lanes of K/V head h // g, zeros elsewhere
        own = (jnp.arange(rows)[:, None] // heads_per_kv
               == jnp.arange(n_kv)[None, :])
        q_rows = jnp.where(own[None, :, :, None], q_rows[:, :, None, :],
                           0.0).reshape(b, rows, n_kv * d)
        if pools[0].dtype == jnp.bfloat16:
            q_rows = q_rows.astype(jnp.bfloat16)
        q_block, o_block = (1, rows, n_kv * d), (1, rows, d_v)
        kw["d_v"] = d_v
    if sinks is not None:
        kernel = _paged_heads_sink_kernel
        extra = (jnp.broadcast_to(jnp.pad(
            sinks.astype(jnp.float32), (0, rows - n),
            constant_values=NEG_INF)[:, None], (rows, 128)),)
        extra_specs = [Block((rows, 128), lambda bi, pt, qs: (0, 0))]
    spec = contract.make_spec(
        name,
        grid=(b,),
        in_specs=[Block(q_block, row_map)] + extra_specs
        + [Block(None, None) for _ in pools],
        out_specs=[Block(o_block, row_map)],
        out_shape=[((b,) + o_block[1:], q.dtype)],
        scratch=[Vmem((2, g * page_size, x.shape[2]), x.dtype)
                 for x in pools]
        + [DmaSem((2, len(pools))), Smem((1,), jnp.int32),
           Vmem((rows, pools[-1].shape[2]), jnp.float32),
           Vmem((rows, 128), jnp.float32),
           Vmem((rows, 128), jnp.float32)],
        num_scalar_prefetch=2,
        interpret=interpret,
    )
    out = contract.primitive_call(
        functools.partial(kernel, d=d, n_sub=g,
                          page_size=page_size, n_rows=b, sm_scale=scale,
                          **kw),
        spec, page_table.astype(jnp.int32), q_start.astype(jnp.int32),
        q_rows, *extra, *pools)             # T == 1: the same bytes
    if heads_per_kv is not None:
        out = out[:, :n]
    return out.reshape(b, n, 1, d if d_v is None else d_v)


def _chunk_grid(name, t, heads_per_kv, kv_heads, page_table, q_start,
                page_size, window):
    """The query and step axes of a chunk's launch: (the chunk padded to
    sublanes, the query tile, the pages and the keys a step, the steps
    THIS call runs, the page table padded with the trash page, q_start
    as int32), the geometry booked as ``form="kv_head_tq<tq>"``.  The grid's step axis ends at the last
    step a row's chunk reaches (a traced extent [1], as grouped.py's
    visits): a step past every row's last query moves nothing, and costs
    its block specs' bookkeeping all the same (Trinity's full layer at
    4k of context under a 33k table, 256 queries x 512 keys a step:
    1.17 -> 0.76 ms a call on a v5e, docs/KERNELS.md)."""
    max_pages = page_table.shape[1]
    tp = -(-t // _SUBLANES) * _SUBLANES
    tq, g = _chunk_geometry(tp, heads_per_kv, kv_heads, page_size,
                            max_pages, window)
    _book_form(name, f"kv_head_tq{tq}", g)
    keys = g * page_size
    steps = -(-max_pages // g)
    if window is not None:  # a window and a tile span so many steps
        steps = min(steps, -(-(int(window) + tq - 1) // keys) + 1)
    q_start = q_start.astype(jnp.int32)
    run = jnp.clip((jnp.max(q_start) + tp - 1) // keys + 1, 1, steps)
    # whole steps, and the steps a window's first may run past the
    # table: pad with the trash page
    pad = (-(-max_pages // g) + steps) * g - max_pages
    page_table = jnp.pad(page_table.astype(jnp.int32), ((0, 0), (0, pad)))
    return tp, tq, g, keys, run.reshape(1), page_table, q_start


def _chunk_kv_map(j, g, keys, tq, window):
    """The index map of a chunk step's j-th page: lane block ``hj`` of
    the page the table names, the tile's steps counted from the one its
    first query's window reaches."""
    def index(bi, hj, qi, pi, pt, qs, run):
        step = pi if window is None else _first_step(
            qs[bi] + qi * tq, window, keys) + pi
        return (pt[bi, step * g + j], 0, hj)
    return index


def _kv_head_call(name, q, pools, page_table, q_start, scale, interpret,
                  heads_per_kv, window):
    """The grouped chunk's launch (T > 1): grid (B, n_kv, query tiles,
    steps), a K/V head's lane block a page."""
    b, n, t, d = q.shape
    page_size = pools[0].shape[1]
    n_kv = n // heads_per_kv
    tp, tq, g, keys, run, page_table, q_start = _chunk_grid(
        name, t, heads_per_kv, 1, page_table, q_start, page_size, window)
    tiles = tp // tq
    if tp != t:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    # [B, n_kv, tiles, g*tq, d]: row r*tq + i of a tile is query i of
    # the group's r-th head
    rows = heads_per_kv * tq
    q = q.reshape(b, n_kv, heads_per_kv, tiles, tq, d).transpose(
        0, 1, 3, 2, 4, 5).reshape(b, n_kv, tiles, rows, d)

    def q_map(bi, hj, qi, pi, pt, qs, run):
        return (bi, hj, qi, 0, 0)

    spec = contract.make_spec(
        name,
        grid=(b, n_kv, tiles, run[0]),
        in_specs=[Block((1, 1, 1, rows, d), q_map)]
        + [Block((1, page_size, d), _chunk_kv_map(j, g, keys, tq, window))
           for _ in pools for j in range(g)],
        out_specs=[Block((1, 1, 1, rows, d), q_map)],
        out_shape=[((b, n_kv, tiles, rows, d), q.dtype)],
        scratch=[Vmem((rows, d), jnp.float32),
                 Vmem((rows, 128), jnp.float32),
                 Vmem((rows, 128), jnp.float32)],
        num_scalar_prefetch=3,
        interpret=interpret,
    )
    out = contract.primitive_call(
        functools.partial(_paged_kv_head_kernel, n_sub=g, keys=keys, tq=tq,
                          heads_per_kv=heads_per_kv, window=window,
                          sm_scale=scale),
        spec, page_table, q_start, run, q,
        *[x for x in pools for _ in range(g)])
    out = out.reshape(b, n_kv, tiles, heads_per_kv, tq, d).transpose(
        0, 1, 3, 2, 4, 5).reshape(b, n, tp, d)
    return out[:, :, :t, :]


def _lane_block_heads(d, d_v):
    """How the asymmetric chunk body reads K/V heads of width ``d`` /
    ``d_v`` from pools stored with the heads side by side: P heads make
    one LANE BLOCK of whole 128-lane tiles (two heads of 192: 384
    lanes), and head h of a block is scored against the aligned tiles
    that cover it.  Returns (P, query width, [(first K lane of the
    tiles, their width, where the head starts inside them, first V
    lane)] a head)."""
    p = 128 // int(np.gcd(d, 128))
    heads = []
    for h in range(p):
        first = h * d // 128 * 128
        inner = h * d - first
        heads.append((first, -(-(inner + d) // 128) * 128, inner, h * d_v))
    return p, max(w for _, w, _, _ in heads), heads


def _paged_lane_block_kernel(page_table_ref, q_start_ref, run_ref, q_ref,
                             *refs, n_sub, keys, tq, heads_per_kv, heads,
                             d_v, window, sm_scale, sink):
    """The asymmetric body of a chunk (T > 1): one grid step is one lane
    block of K/V heads (``heads``, ``_lane_block_heads``), one tile of
    ``tq`` queries and one step of ``keys`` keys.  q_ref
    [P, g*tq, width]: row r*tq + i of head h is query i of the r-th
    query head of the block's h-th K/V head, laid where the head lies
    inside its tiles, zeros elsewhere; o_ref [P, g*tq, d_v].  With
    ``sink`` the first operand behind q is [P, g8, 128] float32: row r
    of head h holds that query head's sink logit."""
    from jax.experimental import pallas as pl

    if sink:
        sink_ref, refs = refs[0], refs[1:]
    k_refs, v_refs = refs[:n_sub], refs[n_sub:2 * n_sub]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * n_sub:]
    bi, qi, pi = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    rows = heads_per_kv * tq

    @pl.when(pi == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)
        if sink:
            for h in range(len(heads)):
                for r in range(heads_per_kv):
                    at = pl.ds(r * tq, tq)
                    _init_sink(jnp.broadcast_to(
                        sink_ref[0, h, r:r + 1, :], (tq, 128)),
                        m_ref.at[h, at], l_ref.at[h, at])

    start, first_key, live, whole = _chunk_step(
        q_start_ref, bi, qi, pi, keys=keys, tq=tq, window=window)

    def score(masked):
        k, v = _mxu(_step_pages(k_refs)), _mxu(_step_pages(v_refs))
        if masked:
            visible = _chunk_mask(start, first_key, rows, keys, tq, window)
        for h, (first, width, _, v_first) in enumerate(heads):
            s = jax.lax.dot_general(
                q_ref[0, 0, 0, h, :, :width].astype(k.dtype),
                k[:, first:first + width], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # [rows, keys]
            s = s * sm_scale
            if masked:
                s = jnp.where(visible, s, NEG_INF)
            _online_softmax_step(s, v[:, v_first:v_first + d_v],
                                 acc_ref.at[h], m_ref.at[h], l_ref.at[h],
                                 p_dtype=_p_dtype(v.dtype))

    pl.when(live & whole)(lambda: score(False))
    pl.when(live & jnp.logical_not(whole))(lambda: score(True))

    @pl.when(pi == run_ref[0] - 1)
    def _finish():
        for h in range(len(heads)):
            l = l_ref[h]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, 0, 0, h] = (acc_ref[h] / l_safe[:, :1]).astype(
                o_ref.dtype)


def _lane_block_call(name, q, pools, page_table, q_start, scale, interpret,
                     heads_per_kv, window, d_v, sinks):
    """The asymmetric chunk's launch (T > 1): grid (B, lane blocks of
    K/V heads, query tiles, steps), ``_kv_head_call``'s with a lane
    block of P heads where that has one head."""
    b, n, t, d = q.shape
    page_size = pools[0].shape[1]
    n_kv = n // heads_per_kv
    p, q_width, heads = _lane_block_heads(d, d_v)
    if n_kv % p or d_v % 128:
        raise ValueError(
            f"{name}: the Pallas form reads {p} K heads of {d} as one "
            f"lane block and V heads in whole 128-lane tiles; {n_kv} K/V "
            f"heads with V heads of {d_v} fit neither (the XLA reference "
            f"form takes any widths)")
    blocks = n_kv // p
    tp, tq, g, keys, run, page_table, q_start = _chunk_grid(
        name, t, heads_per_kv, p, page_table, q_start, page_size, window)
    tiles = tp // tq
    if tp != t:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    if pools[0].dtype == jnp.bfloat16:
        q = q.astype(jnp.bfloat16)
    # [B, blocks, tiles, P, g*tq, d]: row r*tq + i of head h of a block
    # is query i of that K/V head's r-th query head ...
    rows = heads_per_kv * tq
    q = q.reshape(b, blocks, p, heads_per_kv, tiles, tq, d).transpose(
        0, 1, 4, 2, 3, 5, 6).reshape(b, blocks, tiles, p, rows, d)
    # ... laid where the head lies inside the tiles it is scored against
    q = jnp.stack([jnp.pad(q[:, :, :, h], ((0, 0),) * 4 + (
        (inner, q_width - inner - d),))
        for h, (_, _, inner, _) in enumerate(heads)], axis=3)

    def q_map(bi, hj, qi, pi, pt, qs, run):
        return (bi, hj, qi, 0, 0, 0)

    extra, extra_specs = (), []
    if sinks is not None:
        g8 = -(-heads_per_kv // _SUBLANES) * _SUBLANES
        rows_of = jnp.pad(
            sinks.astype(jnp.float32).reshape(blocks, p, heads_per_kv),
            ((0, 0), (0, 0), (0, g8 - heads_per_kv)),
            constant_values=NEG_INF)
        extra = (jnp.broadcast_to(rows_of[..., None],
                                  (blocks, p, g8, 128)),)
        extra_specs = [Block((1, p, g8, 128),
                             lambda bi, hj, qi, pi, pt, qs, run: (
                                 hj, 0, 0, 0))]
    spec = contract.make_spec(
        name,
        grid=(b, blocks, tiles, run[0]),
        in_specs=[Block((1, 1, 1, p, rows, q_width), q_map)] + extra_specs
        + [Block((1, page_size, p * w),
                 _chunk_kv_map(j, g, keys, tq, window))
           for w in (d, d_v) for j in range(g)],
        out_specs=[Block((1, 1, 1, p, rows, d_v), q_map)],
        out_shape=[((b, blocks, tiles, p, rows, d_v), q.dtype)],
        scratch=[Vmem((p, rows, d_v), jnp.float32),
                 Vmem((p, rows, 128), jnp.float32),
                 Vmem((p, rows, 128), jnp.float32)],
        num_scalar_prefetch=3,
        interpret=interpret,
    )
    out = contract.primitive_call(
        functools.partial(_paged_lane_block_kernel, n_sub=g, keys=keys,
                          tq=tq, heads_per_kv=heads_per_kv,
                          heads=tuple(heads), d_v=d_v, window=window,
                          sm_scale=scale, sink=sinks is not None),
        spec, page_table, q_start, run, q, *extra,
        *[x for x in pools for _ in range(g)])
    out = out.reshape(b, blocks, tiles, p, heads_per_kv, tq, d_v).transpose(
        0, 1, 3, 4, 2, 5, 6).reshape(b, n, tp, d_v)
    return out[:, :, :t, :]


def _pallas_paged_asym(q, k_pages, v_pages, page_table, q_start, scale,
                       interpret, name, heads_per_kv, window, d_v, sinks):
    """The asymmetric form's launches: a decode row through the
    heads-batched body, a chunk through the lane-block body."""
    pools = (k_pages, v_pages)
    if q.shape[2] == 1:
        g = _pages_per_step(name, q, k_pages.shape[1], page_table.shape[1],
                            pools)
        _book_form(name, "heads_batched", g)
        return _heads_batched_call(name, q, pools, page_table, q_start,
                                   scale, interpret, g, heads_per_kv,
                                   window, d_v, sinks)
    return _lane_block_call(name, q, pools, page_table, q_start, scale,
                            interpret, heads_per_kv, window, d_v, sinks)


def _paged_call(kernel, name, q, pools, page_table, q_start, scale,
                interpret, heads_batched=False, heads_per_kv=None,
                window=None):
    """Launch over q [B, n, T, d] and ``pools`` — each a
    [P, page, n*w] array (w = d for K/V payloads, 1 for the int8
    scales), taken as stored.  ``kernel`` is the pool form's per-head
    body; with ``heads_batched`` a decode row (T == 1) takes the
    heads-batched one instead.  ``heads_per_kv`` (not None) asks for
    the grouped form of either."""
    b, n, t, d = q.shape
    page_size = pools[0].shape[1]
    max_pages = page_table.shape[1]
    g = _pages_per_step(name, q, page_size, max_pages, pools)
    if heads_batched and t == 1:
        _book_form(name, "heads_batched", g)
        return _heads_batched_call(name, q, pools, page_table, q_start,
                                   scale, interpret, g, heads_per_kv,
                                   window)
    if (heads_per_kv is None and heads_batched and d % 128 == 0
            and _per_head_block_bytes(q) > _PER_HEAD_VMEM_BYTES):
        # a chunk whose every head's queries do not fit VMEM at once (30
        # heads x 512 queries x 128: 39 MB) takes the K/V-head body with
        # one query head a K/V head: a grid axis a head, the queries in
        # tiles.  What fits keeps the launch it had
        heads_per_kv = 1
    if heads_per_kv is not None:
        return _kv_head_call(name, q, pools, page_table, q_start, scale,
                             interpret, heads_per_kv, window)
    _book_form(name, "per_head", g)
    steps = -(-max_pages // g)
    page_table = page_table.astype(jnp.int32)
    if steps * g != max_pages:  # whole steps: pad with the trash page
        page_table = jnp.pad(page_table,
                             ((0, 0), (0, steps * g - max_pages)))
    tp = -(-t // _SUBLANES) * _SUBLANES
    if tp != t:  # a short block rides zero-padded to a sublane tile
        q = jnp.pad(q, ((0, 0), (0, 0), (0, tp - t), (0, 0)))

    # index_map signature under scalar prefetch: grid indices first,
    # then one ref per prefetched operand
    def q_map(bi, pi, pt, qs):
        return (bi, 0, 0, 0)

    def kv_map(j):
        # read THROUGH the table: the physical page of the step's j-th
        # logical page — the pool is never gathered.  Past a row's length
        # the table holds the trash page, so consecutive dead blocks
        # repeat one block index and are not fetched again
        return lambda bi, pi, pt, qs: (pt[bi, pi * g + j], 0, 0)

    spec = contract.make_spec(
        name,
        grid=(b, steps),
        in_specs=[Block((1, n, tp, d), q_map)]
        + [Block((1, page_size, x.shape[2]), kv_map(j))
           for x in pools for j in range(g)],
        out_specs=[Block((1, n, tp, d), q_map)],
        out_shape=[((b, n, tp, d), q.dtype)],
        scratch=[
            Vmem((n, tp, d), jnp.float32),
            Vmem((n, tp, 128), jnp.float32),
            Vmem((n, tp, 128), jnp.float32),
        ],
        num_scalar_prefetch=2,
        interpret=interpret,
    )
    out = contract.primitive_call(
        functools.partial(kernel, d=d, n_sub=g, keys=g * page_size, t=t,
                          n=n, n_steps=steps, sm_scale=scale),
        spec, page_table, q_start.astype(jnp.int32), q,
        *[x for x in pools for _ in range(g)])
    return out[:, :, :t, :]


def _pallas_paged(q, k_pages, v_pages, page_table, q_start, scale,
                  interpret, name="paged_attention", heads_per_kv=None,
                  window=None):
    return _paged_call(_paged_kernel, name, q, (k_pages, v_pages),
                       page_table, q_start, scale, interpret,
                       heads_batched=True, heads_per_kv=heads_per_kv,
                       window=window)


def kernel_name(heads_per_kv, window, asym=False, sink=False):
    """What the kernel that serves this kind of layer is called, in the
    HLO and on the dispatch and form counters."""
    return ("paged_attention" + ("_grouped" if heads_per_kv > 1 else "")
            + ("" if window is None else "_window")
            + ("_asym" if asym else "") + ("_sink" if sink else ""))


def paged_attention(q, k_pages, v_pages, page_table, q_start, *,
                    sm_scale=None, force=None, window=None, sinks=None):
    """Attention of q [B, n, T, d] against pool K/V read through
    `page_table` [B, max_pages]; query i of row b attends global key
    positions j <= q_start[b] + i and, with a static ``window``,
    j > q_start[b] + i - window.  The pools hold n or any divisor of n
    K/V heads (grouped-query attention); the V pool's heads may be of
    another width d_v than the K pool's (the output is then d_v wide).
    ``sinks`` [n] float32: one logit a query head that joins its softmax
    as a column without a value.

    force: None → Pallas on TPU, XLA reference elsewhere; "pallas" →
    Pallas (interpret mode off-TPU, for tests); "reference" → XLA."""
    n, d = q.shape[1], q.shape[-1]
    scale = float(sm_scale if sm_scale is not None else 1.0 / np.sqrt(d))
    n_kv, d_v = _kv_heads("paged_attention", q, k_pages, v_pages)
    if k_pages.dtype != v_pages.dtype or (
            d_v == d and k_pages.shape != v_pages.shape):
        raise ValueError(
            f"paged_attention: K pool {k_pages.dtype}{k_pages.shape} != V "
            f"pool {v_pages.dtype}{v_pages.shape} — the pool must be one "
            f"dtype and one shape")
    if sinks is not None and sinks.shape != (n,):
        raise ValueError(f"paged_attention: sinks {sinks.shape} is not one "
                         f"logit for each of {n} query heads")
    window = None if window is None else int(window)
    name = kernel_name(n // n_kv, window, d_v != d, sinks is not None)
    mode, interpret = contract.resolve_mode(name, force)
    if mode == "pallas" and (d_v != d or sinks is not None):
        return _pallas_paged_asym(q, k_pages, v_pages, page_table, q_start,
                                  scale, interpret, name, n // n_kv, window,
                                  d_v, sinks)
    if mode == "pallas":
        # plain multi-head attention over the whole context keeps the
        # launch it had; anything else takes the grouped form
        plain = n_kv == n and window is None
        return _pallas_paged(q, k_pages, v_pages, page_table, q_start,
                             scale, interpret, name,
                             None if plain else n // n_kv, window)
    return paged_attention_reference(q, k_pages, v_pages, page_table,
                                     q_start, sm_scale=scale, window=window,
                                     sinks=sinks)


# ---------------------------------------------------------------------------
# int8-pool form: the pool rides as (hi int8, lo int8, scale fp32) —
# the dual-int8 block-scale wire format with one scale per (page, slot,
# head) head_dim vector — and dequantizes inside the kernel.
# ---------------------------------------------------------------------------


def _paged_quant_kernel(page_table_ref, q_start_ref, q_ref, *refs, d,
                        n_sub, **kw):
    def load_step():
        khi, klo, ksc, vhi, vlo, vsc = (
            _step_pages(refs[i * n_sub:(i + 1) * n_sub]) for i in range(6))

        def load_kv(h):
            lanes = slice(h * d, (h + 1) * d)

            def deq(hi, lo, sc):
                # dequant in VMEM: fp32 K/V exists only block-at-a-time
                return ((hi[:, lanes].astype(jnp.float32)
                         + lo[:, lanes].astype(jnp.float32)
                         * (1.0 / RESID_DIV)) * sc[:, h:h + 1])

            return deq(khi, klo, ksc), deq(vhi, vlo, vsc)

        return load_kv

    _paged_body(page_table_ref, q_start_ref, q_ref, *refs[6 * n_sub:],
                load_step, **kw)


def paged_attention_quant_reference(q, k_hi, k_lo, k_scale, v_hi, v_lo,
                                    v_scale, page_table, q_start,
                                    sm_scale=None):
    """Numerics oracle: dequantize the whole pool, then the fp32
    reference (fine on the CPU rung; the kernel never does this)."""
    d = q.shape[-1]

    def deq(hi, lo, scale):
        # one scale a head: repeat it over the head's d lanes
        return dequantize_lastdim(hi, lo, jnp.repeat(scale, d, axis=-1))

    k_pages = deq(k_hi, k_lo, k_scale)
    v_pages = deq(v_hi, v_lo, v_scale)
    return paged_attention_reference(q, k_pages, v_pages, page_table,
                                     q_start, sm_scale=sm_scale)


def _pallas_paged_quant(q, k_hi, k_lo, k_scale, v_hi, v_lo, v_scale,
                        page_table, q_start, scale, interpret):
    return _paged_call(_paged_quant_kernel, "paged_attention_quant", q,
                       (k_hi, k_lo, k_scale, v_hi, v_lo, v_scale),
                       page_table, q_start, scale, interpret)


def paged_attention_quant(q, k_hi, k_lo, k_scale, v_hi, v_lo, v_scale,
                          page_table, q_start, *, sm_scale=None,
                          force=None):
    """paged_attention over a dual-int8 pool: hi/lo int8
    [P, page_size, n*d] + one fp32 scale per (page, slot, head)
    head_dim vector [P, page_size, n] (primitives/int8.py
    quantize_lastdim, the heads flattened into the lane dimension like
    the fp pool's).  Dequant happens inside the kernel — fp32 K/V never
    materializes outside VMEM."""
    d = q.shape[-1]
    scale = float(sm_scale if sm_scale is not None else 1.0 / np.sqrt(d))
    _check_pool_shapes("paged_attention_quant", q,
                       scales=("k_scale", "v_scale"), k_hi=k_hi, k_lo=k_lo,
                       k_scale=k_scale, v_hi=v_hi, v_lo=v_lo,
                       v_scale=v_scale)
    for nm, arr in (("k_hi", k_hi), ("k_lo", k_lo), ("v_hi", v_hi),
                    ("v_lo", v_lo)):
        if arr.dtype != jnp.int8:
            raise ValueError(
                f"paged_attention_quant: {nm} dtype {arr.dtype} != int8 "
                f"— the quant pool stores the dual-int8 wire format "
                f"(serving/kv_pool.py KVPool(dtype='int8'))")
    mode, interpret = contract.resolve_mode("paged_attention_quant", force)
    if mode == "pallas":
        return _pallas_paged_quant(q, k_hi, k_lo, k_scale, v_hi, v_lo,
                                   v_scale, page_table, q_start, scale,
                                   interpret)
    return paged_attention_quant_reference(
        q, k_hi, k_lo, k_scale, v_hi, v_lo, v_scale, page_table, q_start,
        sm_scale=scale)
