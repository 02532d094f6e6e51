"""Learned sparse attention over a paged latent cache (DeepSeek sparse
attention on multi-head latent attention): the three device operations
of the decode lane's sparse path.

  dsa_indexer_scores    I[b,t,s] = sum_j w[b,t,j] * relu(qI[b,t,j] . kI[s])
                        over the paged INDEXER cache (one key row a
                        token, shared by the indexer's heads), reduced
                        over the heads before anything is stored: the
                        head-wise tensor [B, T, heads, context] never
                        exists outside VMEM
  dsa_topk_select       the ``k`` positions with the largest score a
                        query, as an additive mask — exact
                        (``approx_max_k`` would change the model): the
                        k-th largest value by bisection over the
                        scores' bits, ties to the lower position, one
                        kernel over a query tile's whole score row
  sparse_mla_attention  attention of the absorbed queries (latent-space
                        form: q_nope already multiplied into the
                        compressed-KV space) over the SELECTED rows of
                        the paged latent cache only

Why the selection is a mask and not a list of rows: on a v5e a gather of
cache rows costs 21-80 ns a row (PERF.md, PR 27: 2.6 ms for the 32k
rows a 16-slot decode step selects, 22 ms for a 512-query chunk's 1M),
more than streaming every visible row of the context through VMEM once
(0.6 GB at 16 x 33k tokens: 0.75 ms at the HBM roofline).  So the
attention kernel walks the sequence's pages like paged.py and the mask
drops the rows that were not selected; at contexts far past 33k a row
gather would win and this file is where it would go.

A grid step of the attention (PR 37): the step's G pages are
concatenated on the key axis (``paged._step_pages``, as mla.py does) and
a tile of tq queries x all heads makes ONE score product
``[tq * heads, W] x [W, G * page]``, ONE application of the visibility
rule and of the ``selected`` block ``[tq, G * page]``, ONE online-softmax
update and ONE value product ``[tq * heads, G * page] x [G * page, C]``
into the float32 accumulator; a step whose first key lies past the
tile's last query is skipped, a partly dead one is made right by the
mask.  ``_attention_tile`` picks (tq, G) from the shapes: the largest
tile of 8, 16, 32 .. queries within 1024 rows, then the most pages that
divide the selection's within 1024 keys and a 2-MB float32 score tile:
(16, 4) for GLM-5's chunk (T = 512, 64 heads, page 128), (1, 8) for its
decode row.  On a v5e, a call at the chunk's shape and 4k / 9k / 16k /
33k of context (ms; the two products alone need 1.6 / 3.5 / 6.3 / 12.6):
one update a page, four a step (the parent) 8.78 / 13.66 / 20.49 /
36.37; one a step at (8, 4) 3.44 / 5.70 / 8.86 / 16.22, (8, 8) 3.27 /
5.41 / 8.39 / 15.32, (16, 4) 2.78 / 4.92 / 7.91 / 14.88; (32, 4) 2.54 /
4.64 / 7.54 / 14.29 with the scoped VMEM raised to hold it.  The decode
row over 16 slots, 5 live: 0.78 -> 0.46-0.47 (PERF.md section 6, PR 37).
The choice is booked at trace time on
``pt_paged_attention_form_total{primitive="sparse_mla_attention",
form="chunk_tq<tq>" | "row_tq1", pages_per_step}``.

Shapes (B sequences, T queries each: T = 1 in a decode step with B the
pool's slots; B = 1 in a prefill chunk with T the chunk):
  q_idx        [B, T, Hi, Di]   indexer queries (RoPE applied)
  w_idx        [B, T, Hi]       per-head weights, float32
  index_pages  [num_pages, page_size, Di]   the indexer's cache rows
  latent_pages [num_pages, page_size, W], W >= C + R: a token's row is
               [c_kv (C) | k_rope (R) | zeros], shared by all heads
               (stored at whole lane tiles, serving/lane.py lane_padded)
  page_table   [B, max_pages] int32; q_start [B] int32 — as in paged.py:
               query t of row b sees positions s <= q_start[b] + t
  scores       [B, T, Lp] float32, Lp >= max_pages * page_size (the
               page table is padded to a whole number of grid steps);
               positions a query may not see read -inf
  selected     [B, T, Lp] float32 additive mask (Lp a whole number of
               pages, at least the page table's): 0 on the k positions
               with the largest scores, -1e9 elsewhere; where a query
               sees fewer than k positions the rest of the k are
               positions it may not see, which the attention masks by
               the visibility rule

Both pools are read as stored, [num_pages, page_size, width]: widths
640 (576 padded) and 128 keep the default row-major TPU layout, so no
executable copies a pool (tests/test_mosaic_aot.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import contract
from .contract import Block, Vmem
from .paged import NEG_INF, _book_form, _init_state, _mxu, \
    _online_softmax_step, _p_dtype, _step_pages

MASKED = float("-inf")

__all__ = ["dsa_indexer_scores", "dsa_indexer_scores_reference",
           "dsa_topk_select", "dsa_topk_select_reference",
           "sparse_mla_attention", "sparse_mla_attention_reference"]

# pages of the indexer cache one grid step reads (the page table is
# padded to a multiple): a step costs ~0.35 us whatever it moves, and a
# 128-row page of 128-wide keys is only 32 KB
PAGES_PER_STEP = 8
# queries a grid step scores: 32 x 32 heads fill the MXU's rows, and a
# larger tile's [tile * heads, page] float32 scores outgrow scoped VMEM
_QUERY_TILES = (32, 16, 8)


def _query_tile(t):
    for tile in _QUERY_TILES:
        if t % tile == 0:
            return tile
    return t


def _row_map(bi, qi, pi, pt, qs):
    """Block index of a [B, T, ...] operand tiled over its queries."""
    return (bi, qi, 0, 0)


def _page_map(n_sub, j):
    """Block index of the j-th of the ``n_sub`` pool pages a grid step
    reads: the physical page, through the prefetched page table."""
    return lambda bi, qi, pi, pt, qs: (pt[bi, pi * n_sub + j], 0, 0)


def _padded_table(page_table, pages):
    """The page table padded to ``pages`` entries with the trash page,
    whose positions lie past every length."""
    return jnp.pad(page_table.astype(jnp.int32),
                   ((0, 0), (0, pages - page_table.shape[1])))


def _visible(q_start, t, length):
    """[B, T, length] bool: position s is visible to query t of row b."""
    qpos = q_start.astype(jnp.int32)[:, None, None] + jnp.arange(
        t, dtype=jnp.int32)[None, :, None]
    return jnp.arange(length, dtype=jnp.int32)[None, None, :] <= qpos


def dsa_indexer_scores_reference(q_idx, w_idx, index_pages, page_table,
                                 q_start):
    """Materialising XLA form: CPU fallback and numerics oracle."""
    b, t, _, d = q_idx.shape
    length = page_table.shape[1] * index_pages.shape[1]
    keys = index_pages[page_table].reshape(b, length, d)
    s = jnp.einsum("bthd,bld->bthl", q_idx.astype(keys.dtype), keys,
                   preferred_element_type=jnp.float32)
    s = jnp.sum(jnp.maximum(s, 0.0)
                * w_idx.astype(jnp.float32)[..., None], axis=2)
    return jnp.where(_visible(q_start, t, length), s, MASKED)


def _indexer_kernel(pt_ref, qs_ref, q_ref, w_ref, *refs, page, tq, heads,
                    n_sub):
    from jax.experimental import pallas as pl

    k_refs, o_ref = refs[:n_sub], refs[n_sub]
    bi, qi, pi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    first_q = qs_ref[bi] + qi * tq
    q2 = q_ref[0].reshape(tq * heads, q_ref.shape[-1])
    w3 = w_ref[0]                                          # [tq, heads, 1]
    for j in range(n_sub):
        base = (pi * n_sub + j) * page
        lanes = slice(j * page, (j + 1) * page)

        # live iff the block's last query sees the page's first position
        @pl.when(base <= first_q + tq - 1)
        def _live(j=j, base=base, lanes=lanes):
            s = jax.lax.dot_general(
                q2, k_refs[j][0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # [tq*heads, page]
            s = jnp.maximum(s, 0.0).reshape(tq, heads, page) * w3
            s = jnp.sum(s, axis=1)                         # [tq, page]
            kpos = base + jax.lax.broadcasted_iota(
                jnp.int32, (tq, page), 1)
            qpos = first_q + jax.lax.broadcasted_iota(
                jnp.int32, (tq, page), 0)
            o_ref[0, :, lanes] = jnp.where(kpos <= qpos, s, MASKED)

        @pl.when(base > first_q + tq - 1)
        def _dead(lanes=lanes):
            o_ref[0, :, lanes] = jnp.full((tq, page), MASKED, jnp.float32)


def _pallas_indexer(q_idx, w_idx, index_pages, page_table, q_start,
                    interpret):
    b, t, heads, d = q_idx.shape
    page = index_pages.shape[1]
    n_sub = PAGES_PER_STEP
    steps = -(-page_table.shape[1] // n_sub)
    page_table = _padded_table(page_table, steps * n_sub)
    tq = _query_tile(t)

    spec = contract.make_spec(
        "dsa_indexer_scores",
        grid=(b, t // tq, steps),
        in_specs=[Block((1, tq, heads, d), _row_map),
                  Block((1, tq, heads, 1), _row_map)]
        + [Block((1, page, d), _page_map(n_sub, j)) for j in range(n_sub)],
        out_specs=[Block((1, tq, n_sub * page),
                         lambda bi, qi, pi, pt, qs: (bi, qi, pi))],
        out_shape=[((b, t, steps * n_sub * page), jnp.float32)],
        num_scalar_prefetch=2,
        interpret=interpret,
    )
    return contract.primitive_call(
        functools.partial(_indexer_kernel, page=page, tq=tq, heads=heads,
                          n_sub=n_sub),
        spec, page_table, q_start.astype(jnp.int32),
        q_idx.astype(index_pages.dtype),
        w_idx.astype(jnp.float32)[..., None],
        *([index_pages] * n_sub))


def dsa_indexer_scores(q_idx, w_idx, index_pages, page_table, q_start, *,
                       force=None):
    """Indexer scores of every query against its sequence's paged
    indexer cache, [B, T, Lp] float32 (see the module's Shapes).

    force: None → Pallas on TPU, XLA reference elsewhere; "pallas" →
    Pallas (interpret mode off-TPU); "reference" → XLA."""
    if index_pages.ndim != 3 or index_pages.shape[2] != q_idx.shape[3]:
        raise ValueError(
            f"dsa_indexer_scores: the indexer cache has shape "
            f"{tuple(index_pages.shape)}, wanted [num_pages, page_size, "
            f"{q_idx.shape[3]}] (one key row a token)")
    mode, interpret = contract.resolve_mode("dsa_indexer_scores", force)
    if mode == "pallas":
        return _pallas_indexer(q_idx, w_idx, index_pages, page_table,
                               q_start, interpret)
    return dsa_indexer_scores_reference(q_idx, w_idx, index_pages,
                                        page_table, q_start)


def _float_order_keys(x):
    """int32 keys whose signed order is the float32 order of ``x``."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def dsa_topk_select_reference(scores, k):
    """XLA form: CPU fallback and oracle.  The k-th largest value by
    bisection over the bits of the scores' keys (32 counting passes),
    values above it are taken and, of those equal to it, the lowest
    positions up to k — what a stable descending sort's first k would
    be."""
    u = jax.lax.bitcast_convert_type(
        _float_order_keys(scores.astype(jnp.float32)),
        jnp.uint32) ^ jnp.uint32(0x80000000)

    def raise_bit(i, kth):
        cand = kth | (jnp.uint32(1) << (jnp.uint32(31)
                                        - i.astype(jnp.uint32)))
        count = jnp.sum((u >= cand[..., None]).astype(jnp.int32), axis=-1)
        return jnp.where(count >= k, cand, kth)

    kth = jax.lax.fori_loop(0, 32, raise_bit,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = u > kth[..., None]
    tie = u == kth[..., None]
    wanted = k - jnp.sum(above.astype(jnp.int32), axis=-1)
    tie_rank = jnp.cumsum(tie.astype(jnp.int32), axis=-1) - 1
    chosen = above | (tie & (tie_rank < wanted[..., None]))
    return jnp.where(chosen, 0.0, NEG_INF).astype(jnp.float32)


def _topk_kernel(s_ref, o_ref, key_ref, *, k, lanes, n_tiles, pos_bits):
    """One tile of query rows, its whole score row in VMEM: every pass
    below streams the row's keys through the vector unit once.  State a
    row is kept [rows, lanes] with all lanes equal (contract.py)."""
    from jax.experimental import pallas as pl

    rows = s_ref.shape[0]
    int_min = jnp.int32(-2 ** 31)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)

    def tile(c):
        return pl.ds(pl.multiple_of(c * lanes, lanes), lanes)

    # lane tiles a loop trip handles (Mosaic unrolls a whole loop or none)
    group = next(g for g in (8, 4, 2, 1) if n_tiles % g == 0)

    def sweep(fn, init):
        def trip(i, carry):
            for j in range(group):
                carry = fn(i * group + j, carry)
            return carry

        return jax.lax.fori_loop(0, n_tiles // group, trip, init)

    def to_keys(c, carry):
        key_ref[:, tile(c)] = _float_order_keys(s_ref[:, tile(c)])
        return carry

    sweep(to_keys, 0)

    def count(pred):
        """[rows, lanes], every lane the row's number of positions whose
        (key tile, position tile) satisfy ``pred``."""
        def add(c, acc):
            hit = pred(key_ref[:, tile(c)], c * lanes + lane)
            return acc + jnp.where(hit, 1, 0).astype(jnp.int32)

        acc = sweep(add, jnp.zeros((rows, lanes), jnp.int32))
        return jnp.broadcast_to(jnp.sum(acc, axis=1, keepdims=True),
                                (rows, lanes))

    # the k-th largest key, bit by bit from the top, in the order of the
    # keys read as unsigned after their sign bit is flipped
    def raise_bit(i, kth):
        cand = kth | jax.lax.shift_left(jnp.int32(1), jnp.int32(31) - i)
        n = count(lambda x, pos: x >= (cand ^ int_min))
        return jnp.where(n >= k, cand, kth)

    kth = jax.lax.fori_loop(0, 32, raise_bit,
                            jnp.zeros((rows, lanes), jnp.int32)) ^ int_min
    wanted = k - count(lambda x, pos: x > kth)        # >= 1 ties to take
    ties = count(lambda x, pos: x == kth)

    # of the keys equal to the k-th, the ``wanted`` lowest positions: the
    # position of the wanted-th tie, bit by bit (the largest q with fewer
    # than ``wanted`` ties below it).  Skipped where every tie is taken,
    # which is every row whose k-th score is no one else's.
    def last_tie():
        def raise_pos(i, q):
            cand = q | jax.lax.shift_left(jnp.int32(1),
                                          jnp.int32(pos_bits - 1) - i)
            n = count(lambda x, pos: (x == kth) & (pos < cand))
            return jnp.where(n < wanted, cand, q)

        return jax.lax.fori_loop(0, pos_bits, raise_pos,
                                 jnp.zeros((rows, lanes), jnp.int32))

    last = jax.lax.cond(
        jnp.max(ties - wanted) > 0, last_tie,
        lambda: jnp.full((rows, lanes), 2 ** 31 - 1, jnp.int32))

    def write(c, carry):
        x, pos = key_ref[:, tile(c)], c * lanes + lane
        chosen = (x > kth) | ((x == kth) & (pos <= last))
        o_ref[:, tile(c)] = jnp.where(chosen, 0.0, NEG_INF)
        return carry

    sweep(write, 0)


def _pallas_topk(scores, k, interpret):
    length = scores.shape[-1]
    rows = 1
    for n in scores.shape[:-1]:
        rows *= n
    tr = 8 if rows % 8 == 0 else rows
    lanes = 128 if length % 128 == 0 else length
    spec = contract.make_spec(
        "dsa_topk_select",
        grid=(rows // tr,),
        in_specs=[Block((tr, length), lambda r: (r, 0))],
        out_specs=[Block((tr, length), lambda r: (r, 0))],
        out_shape=[((rows, length), jnp.float32)],
        scratch=[Vmem((tr, length), jnp.int32)],
        interpret=interpret,
    )
    out = contract.primitive_call(
        functools.partial(_topk_kernel, k=k, lanes=lanes,
                          n_tiles=length // lanes,
                          pos_bits=max(1, (length - 1).bit_length())),
        spec, scores.reshape(rows, length).astype(jnp.float32))
    return out.reshape(scores.shape)


def dsa_topk_select(scores, k, *, force=None):
    """Additive mask [B, T, Lp] float32 of the ``k`` largest scores of
    every query: 0 on them, -1e9 elsewhere.  Exact, ties to the lower
    position: what a stable descending sort's first k would be.  The
    whole selection (the bisection for the k-th value, the ties, the
    mask) is ONE kernel, ``dsa_topk_select`` in a trace.

    force: None → Pallas on TPU, XLA reference elsewhere; "pallas" →
    Pallas (interpret mode off-TPU); "reference" → XLA."""
    k = int(k)
    if k >= scores.shape[-1]:
        return jnp.zeros(scores.shape, jnp.float32)
    mode, interpret = contract.resolve_mode("dsa_topk_select", force)
    if mode == "pallas":
        return _pallas_topk(scores, k, interpret)
    return dsa_topk_select_reference(scores, k)


def _padded_queries(q_lat, q_rope, width):
    """[q_lat | q_rope | zeros] at the stored row's width."""
    pad = width - q_lat.shape[-1] - q_rope.shape[-1]
    parts = [q_lat, q_rope]
    if pad:
        parts.append(jnp.zeros(q_lat.shape[:-1] + (pad,), q_lat.dtype))
    return jnp.concatenate(parts, axis=-1)


def sparse_mla_attention_reference(q_lat, q_rope, latent_pages, page_table,
                                   selected, q_start, sm_scale):
    """Materialising XLA form: CPU fallback and numerics oracle."""
    b, t, _, c = q_lat.shape
    length = page_table.shape[1] * latent_pages.shape[1]
    rows = latent_pages[page_table].reshape(b, length, -1)
    q = _padded_queries(q_lat, q_rope, rows.shape[-1]).astype(rows.dtype)
    s = jnp.einsum("bthc,blc->bthl", q, rows,
                   preferred_element_type=jnp.float32) * sm_scale
    mask = jnp.where(_visible(q_start, t, length),
                     selected[..., :length], NEG_INF)
    p = jax.nn.softmax(s + mask[:, :, None, :], axis=-1)
    return jnp.einsum("bthl,blc->bthc", p.astype(rows.dtype),
                      rows[..., :c], preferred_element_type=jnp.float32)


def _mask_rows(s, mask, tq, heads):
    """``s`` [tq * heads, keys] plus the [tq, keys] additive mask of its
    queries, a query's row for each of its heads.  The reshape is free
    (64 heads are whole sublane tiles); a broadcast of the mask or a
    slice a query read the same on the chip (PERF.md section 6, PR 37)."""
    if tq == 1:
        return s + mask
    keys = s.shape[-1]
    return (s.reshape(tq, heads, keys)
            + mask[:, None, :]).reshape(tq * heads, keys)


def _sparse_mla_kernel(pt_ref, qs_ref, q_ref, sel_ref, *refs, page, tq,
                       heads, c, n_sub, n_steps, sm_scale):
    from jax.experimental import pallas as pl

    k_refs, o_ref = refs[:n_sub], refs[n_sub]
    acc_ref, m_ref, l_ref = refs[n_sub + 1:]
    bi, qi, pi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    rows, keys = tq * heads, n_sub * page

    @pl.when(pi == 0)
    def _init():
        _init_state(acc_ref, m_ref, l_ref)

    first_q = qs_ref[bi] + qi * tq
    base = pi * keys

    # live iff the tile's last query sees the step's first key; a partly
    # dead step is made right by the mask
    @pl.when(base <= first_q + tq - 1)
    def _live():
        kv = _mxu(_step_pages(k_refs))                     # [keys, W]
        s = jax.lax.dot_general(
            q_ref[0].reshape(rows, q_ref.shape[-1]), kv,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        kpos = base + jax.lax.broadcasted_iota(jnp.int32, (tq, keys), 1)
        qpos = first_q + jax.lax.broadcasted_iota(jnp.int32, (tq, keys), 0)
        mask = jnp.where(kpos <= qpos, sel_ref[0], NEG_INF)   # [tq, keys]
        # a masked score reads -1e9 exactly (its own value is lost in the
        # rounding), as in paged.py
        s = jnp.maximum(_mask_rows(s, mask, tq, heads), NEG_INF)
        _online_softmax_step(s, kv[:, :c], acc_ref, m_ref, l_ref,
                             p_dtype=_p_dtype(kv.dtype))

    @pl.when(pi == n_steps - 1)
    def _finish():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe[:, :1]).reshape(tq, heads, c)


# What a grid step of the attention may hold: the rows of its query tile
# (queries x heads), the keys it scores at once, and the float32 bytes of
# the [rows, keys] score tile; the readings behind them are in the
# module's docstring.  2048 rows read 6% less again but want 22 MB of
# scoped VMEM where Mosaic's default gives 16; a decode row (64 rows) is
# flat from 768 to 1408 keys a step and loses at 3072.
_ATTN_ROWS_PER_STEP = 1024
_ATTN_KEYS_PER_STEP = 1024
_ATTN_SCORE_BYTES = 2 << 20


def _attention_tile(t, heads, page, sel_pages):
    """(query tile, pages a step) of the attention launch, from the
    shapes: the largest tile of 8, 16, 32 .. queries that divides T
    within the rows a step may hold (T itself where 8 does not divide
    it: a decode row), then the most pages that divide the selection's
    within the keys and the score bytes.  GLM-5's chunk (T = 512, 64
    heads, page 128) gets (16, 4), its decode row (1, 8)."""
    tq = t
    if t % 8 == 0:
        tq = 8
        while (t % (2 * tq) == 0
               and 2 * tq * heads <= _ATTN_ROWS_PER_STEP):
            tq *= 2
    keys = min(_ATTN_KEYS_PER_STEP, _ATTN_SCORE_BYTES // (4 * tq * heads))
    return tq, max(d for d in range(1, sel_pages + 1)
                   if sel_pages % d == 0 and (d == 1 or d * page <= keys))


def _pallas_sparse_mla(q_lat, q_rope, latent_pages, page_table, selected,
                       q_start, sm_scale, interpret):
    b, t, heads, c = q_lat.shape
    page, width = latent_pages.shape[1], latent_pages.shape[2]
    sel_pages, odd = divmod(selected.shape[-1], page)
    if odd or sel_pages < page_table.shape[1]:
        raise ValueError(
            f"sparse_mla_attention: the selection covers "
            f"{selected.shape[-1]} positions for a page table of "
            f"{page_table.shape[1]} pages of {page}: take it from "
            f"dsa_topk_select of dsa_indexer_scores over the same page "
            f"table")
    tq, n_sub = _attention_tile(t, heads, page, sel_pages)
    steps = sel_pages // n_sub
    _book_form("sparse_mla_attention",
               f"{'row' if t == 1 else 'chunk'}_tq{tq}", n_sub)
    page_table = _padded_table(page_table, sel_pages)
    q = _padded_queries(q_lat, q_rope, width).astype(latent_pages.dtype)

    spec = contract.make_spec(
        "sparse_mla_attention",
        grid=(b, t // tq, steps),
        in_specs=[Block((1, tq, heads, width), _row_map),
                  Block((1, tq, n_sub * page),
                        lambda bi, qi, pi, pt, qs: (bi, qi, pi))]
        + [Block((1, page, width), _page_map(n_sub, j))
           for j in range(n_sub)],
        out_specs=[Block((1, tq, heads, c), _row_map)],
        out_shape=[((b, t, heads, c), jnp.float32)],
        scratch=[Vmem((tq * heads, c), jnp.float32),
                 Vmem((tq * heads, 128), jnp.float32),
                 Vmem((tq * heads, 128), jnp.float32)],
        num_scalar_prefetch=2,
        interpret=interpret,
    )
    return contract.primitive_call(
        functools.partial(_sparse_mla_kernel, page=page, tq=tq,
                          heads=heads, c=c, n_sub=n_sub, n_steps=steps,
                          sm_scale=sm_scale),
        spec, page_table, q_start.astype(jnp.int32), q,
        selected.astype(jnp.float32), *([latent_pages] * n_sub))


def sparse_mla_attention(q_lat, q_rope, latent_pages, page_table, selected,
                         q_start, *, sm_scale, force=None):
    """Latent-space attention over the selected rows of the paged latent
    cache.

    q_lat [B, T, H, C] (q_nope absorbed into the compressed-KV space),
    q_rope [B, T, H, R], ``selected`` the additive mask of
    ``dsa_topk_select`` → [B, T, H, C] float32, still in the latent
    space (the caller applies the value half of the up-projection).

    force: None → Pallas on TPU, XLA reference elsewhere; "pallas" →
    Pallas (interpret mode off-TPU); "reference" → XLA."""
    c = q_lat.shape[3]
    if (latent_pages.ndim != 3
            or latent_pages.shape[2] < c + q_rope.shape[3]):
        raise ValueError(
            f"sparse_mla_attention: the latent cache has shape "
            f"{tuple(latent_pages.shape)}, wanted [num_pages, page_size, "
            f">= {c + q_rope.shape[3]}] (a row is [c_kv | k_rope | pad])")
    mode, interpret = contract.resolve_mode("sparse_mla_attention", force)
    if mode == "pallas":
        return _pallas_sparse_mla(q_lat, q_rope, latent_pages, page_table,
                                  selected, q_start, float(sm_scale),
                                  interpret)
    return sparse_mla_attention_reference(
        q_lat, q_rope, latent_pages, page_table, selected, q_start,
        float(sm_scale))
