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
- **Pallas kernel**: grid (B, logical pages) with the page dimension
  innermost, one whole page of every head per step; the page table and
  per-row start offsets ride as scalar prefetch so each K/V block's
  index_map resolves the PHYSICAL page id — the kernel never sees a
  gathered copy of the pool.  Online softmax (running max/sum in VMEM
  scratch, per head) over the pages, blocks past the row's length
  skipped entirely (`pl.when`), fp32 accumulation.

Shapes:
  q           [B, n_heads, T, d]   T = 1 (decode step) or the prefill
                                   chunk length
  k/v_pages   [num_pages, page_size, n_heads * d] — heads side by side
              in the lane dimension (head h is lanes h*d..(h+1)*d).
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

Page 0 of the pool is the allocator's trash page (writes of inactive
slots land there); a row's mask only ever exposes positions below its
own length, so trash content is never attended.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import contract
from .contract import Block, Vmem
from .int8 import RESID_DIV, dequantize_lastdim

NEG_INF = -1e9  # the fused causal softmax op's mask constant — shared so
# the decode lane's masked softmax matches the composed path's spelling

__all__ = ["paged_attention", "paged_attention_reference",
           "paged_attention_quant", "paged_attention_quant_reference"]


def paged_attention_reference(q, k_pages, v_pages, page_table, q_start,
                              sm_scale=None):
    """Materializing XLA implementation: CPU fallback + numerics oracle.

    Mirrors the composed attention path's op spelling (matmul — scale —
    -1e9 mask — jax.nn.softmax — matmul) so greedy decode through the
    pool is comparable with the whole-sequence program token for
    token."""
    b, n, t, d = q.shape
    _check_pool_shapes("paged_attention", q, k_pages=k_pages,
                       v_pages=v_pages)
    page_size = k_pages.shape[1]
    max_pages = page_table.shape[1]
    l_max = max_pages * page_size
    scale = float(sm_scale if sm_scale is not None else 1.0 / np.sqrt(d))

    def gathered(pages):
        # the heads come apart on the gathered pages, never on the pool
        g = pages[page_table]                      # [B, MAXP, PGS, n*d]
        g = g.reshape(b, l_max, n, d)
        return jnp.transpose(g, (0, 2, 1, 3))      # [B, n, L, d]

    k = gathered(k_pages)
    v = gathered(v_pages)
    s = jnp.matmul(q.astype(jnp.float32),
                   jnp.swapaxes(k.astype(jnp.float32), -1, -2)) * scale
    kpos = jax.lax.broadcasted_iota(jnp.int32, (b, n, t, l_max), 3)
    qpos = (q_start.astype(jnp.int32)[:, None, None, None]
            + jax.lax.broadcasted_iota(jnp.int32, (b, n, t, l_max), 2))
    s = jnp.where(kpos <= qpos, s, jnp.asarray(NEG_INF, s.dtype))
    p = jax.nn.softmax(s, axis=-1)
    return jnp.matmul(p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel: grid (B, logical pages), pages innermost; the page
# table + q_start ride as scalar prefetch so the K/V BlockSpecs resolve
# physical page ids — the pool is never gathered into a copy.
#
# Mosaic tiling: a block's last two dims must be (8, 128)-divisible or
# span the array's.  d = 64 on the real models, so a per-head block
# (.., 1, d) cannot lower: each grid step takes one whole page of every
# head, (1, page, n*d), of the pool AS IT IS STORED, and the kernel
# walks the heads as static d-wide lane slices of that block.  Nothing
# here reshapes the pool — a reshape of [P, page, n, d] to this shape
# is "free" only in row-major order, which is not the layout XLA:TPU
# gives the 4-D array (tests/test_mosaic_aot.py holds the compiled
# executables to zero pool-shaped copies).
# ---------------------------------------------------------------------------

_SUBLANES = 8


def _check_pool_shapes(op, q, scales=(), **pools):
    """The pool has ONE shape, [num_pages, page_size, n_heads*head_dim]
    (an int8 pool's scales [num_pages, page_size, n_heads]); a caller
    that holds the heads apart is refused, not reshaped."""
    n, d = q.shape[1], q.shape[3]
    for what, x in pools.items():
        lanes = n if what in scales else n * d
        if x.ndim != 3 or x.shape[2] != lanes:
            raise ValueError(
                f"{op}: {what} has shape {tuple(x.shape)}, but the KV "
                f"pool is stored [num_pages, page_size, {lanes}] — "
                f"{n} heads side by side in the last dimension, so that "
                f"no executable copies the pool between layouts; "
                f"reshape a gathered page if the heads are needed "
                f"apart, never the pool (docs/SERVING.md 'Decode lane')")


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


def _paged_body(page_table_ref, q_start_ref, q_ref, o_ref, acc_ref, m_ref,
                l_ref, load_kv, *, page_size, t, n, n_blocks, sm_scale):
    """The grid-step body both pool forms share; ``load_kv(h)`` returns
    head h's fp32 (K, V) [page, d] tiles of the current page."""
    from jax.experimental import pallas as pl

    bi = pl.program_id(0)
    pi = pl.program_id(1)
    tp = q_ref.shape[2]  # t rounded up to a sublane multiple

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    start = q_start_ref[bi]

    # the block is live iff its first key position is attendable by the
    # LAST query of the block (global key limit = start + t - 1)
    @pl.when(pi * page_size <= start + t - 1)
    def _step():
        kpos = pi * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (tp, page_size), 1)
        qpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (tp, page_size), 0)
        visible = kpos <= qpos
        for h in range(n):
            q = q_ref[0, h].astype(jnp.float32)                 # [tp, d]
            k, v = load_kv(h)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
            s = jnp.where(visible, s * sm_scale, NEG_INF)
            _online_softmax_step(s, v, acc_ref.at[h], m_ref.at[h],
                                 l_ref.at[h])

    @pl.when(pi == n_blocks - 1)
    def _finish():
        for h in range(n):
            l = l_ref[h]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, h] = (acc_ref[h] / l_safe[:, :1]).astype(o_ref.dtype)


def _paged_kernel(page_table_ref, q_start_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, d, **kw):
    def load_kv(h):
        lanes = slice(h * d, (h + 1) * d)
        return (k_ref[0, :, lanes].astype(jnp.float32),
                v_ref[0, :, lanes].astype(jnp.float32))

    _paged_body(page_table_ref, q_start_ref, q_ref, o_ref, acc_ref, m_ref,
                l_ref, load_kv, **kw)


def _paged_call(kernel, name, q, pools, page_table, q_start, scale,
                interpret):
    """Launch ``kernel`` over q [B, n, T, d] and ``pools`` — each a
    [P, page, n*w] array (w = d for K/V payloads, 1 for the int8
    scales), taken as stored."""
    b, n, t, d = q.shape
    page_size = pools[0].shape[1]
    max_pages = page_table.shape[1]
    tp = -(-t // _SUBLANES) * _SUBLANES
    if tp != t:  # a T=1 decode row rides as one zero-padded sublane tile
        q = jnp.pad(q, ((0, 0), (0, 0), (0, tp - t), (0, 0)))

    # index_map signature under scalar prefetch: grid indices first,
    # then one ref per prefetched operand
    def q_map(bi, pi, pt, qs):
        return (bi, 0, 0, 0)

    def kv_map(bi, pi, pt, qs):
        # read THROUGH the table: the physical page this (row, logical
        # page) pair maps to — the pool is never gathered
        return (pt[bi, pi], 0, 0)

    spec = contract.make_spec(
        name,
        grid=(b, max_pages),
        in_specs=[Block((1, n, tp, d), q_map)]
        + [Block((1, page_size, x.shape[2]), kv_map) for x in pools],
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
        functools.partial(kernel, page_size=page_size, t=t, n=n, d=d,
                          n_blocks=max_pages, sm_scale=scale),
        spec, page_table.astype(jnp.int32), q_start.astype(jnp.int32), q,
        *pools)
    return out[:, :, :t, :]


def _pallas_paged(q, k_pages, v_pages, page_table, q_start, scale,
                  interpret):
    return _paged_call(_paged_kernel, "paged_attention", q,
                       (k_pages, v_pages), page_table, q_start, scale,
                       interpret)


def paged_attention(q, k_pages, v_pages, page_table, q_start, *,
                    sm_scale=None, force=None):
    """Attention of q [B, n, T, d] against pool K/V read through
    `page_table` [B, max_pages]; query i of row b attends global key
    positions j <= q_start[b] + i.

    force: None → Pallas on TPU, XLA reference elsewhere; "pallas" →
    Pallas (interpret mode off-TPU, for tests); "reference" → XLA."""
    d = q.shape[-1]
    scale = float(sm_scale if sm_scale is not None else 1.0 / np.sqrt(d))
    _check_pool_shapes("paged_attention", q, k_pages=k_pages,
                       v_pages=v_pages)
    if k_pages.dtype != v_pages.dtype:
        raise ValueError(
            f"paged_attention: K pool dtype {k_pages.dtype} != V pool "
            f"dtype {v_pages.dtype} — the pool must be one dtype")
    mode, interpret = contract.resolve_mode("paged_attention", force)
    if mode == "pallas":
        return _pallas_paged(q, k_pages, v_pages, page_table, q_start,
                             scale, interpret)
    return paged_attention_reference(q, k_pages, v_pages, page_table,
                                     q_start, sm_scale=scale)


# ---------------------------------------------------------------------------
# int8-pool form: the pool rides as (hi int8, lo int8, scale fp32) —
# the dual-int8 block-scale wire format with one scale per (page, slot,
# head) head_dim vector — and dequantizes inside the kernel.
# ---------------------------------------------------------------------------


def _paged_quant_kernel(page_table_ref, q_start_ref, q_ref,
                        khi_ref, klo_ref, ksc_ref,
                        vhi_ref, vlo_ref, vsc_ref, o_ref,
                        acc_ref, m_ref, l_ref, *, d, **kw):
    def load_kv(h):
        lanes = slice(h * d, (h + 1) * d)

        def deq(hi_ref, lo_ref, sc_ref):
            # dequant in VMEM: fp32 K/V exists only block-at-a-time
            hi = hi_ref[0, :, lanes].astype(jnp.float32)
            lo = lo_ref[0, :, lanes].astype(jnp.float32)
            return (hi + lo * (1.0 / RESID_DIV)) * sc_ref[0, :, h:h + 1]

        return (deq(khi_ref, klo_ref, ksc_ref),
                deq(vhi_ref, vlo_ref, vsc_ref))

    _paged_body(page_table_ref, q_start_ref, q_ref, o_ref, acc_ref, m_ref,
                l_ref, load_kv, **kw)


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
