"""Decode-lane ops: paged KV-cache writes + paged attention.

The decode serving lane (docs/SERVING.md "Decode lane",
serving/decode.py) runs ONE fixed-shape executable per decode step over
a pool of KV pages (serving/kv_pool.py).  These ops are its program
surface:

  kv_cache_write        scatter ONE new token's K or V rows into the
                        pool at per-slot (page, offset) coordinates —
                        the decode step's write side
  kv_cache_write_pages  scatter a prefill CHUNK's K or V (whole pages)
                        into the pool — the chunked-prefill write side
  paged_attention       read the pool through a per-sequence page table
                        (kernels/paged_attention.py: Pallas on TPU, lax
                        gather reference on CPU)

All three are inference-only (grad=None — generation programs are never
differentiated) and the writes alias their pool input (XLA buffer
donation: the pool updates in place, never doubled).

Shape contract: a pool var is [num_pages, page_size, n_heads*head_dim]
— the heads side by side in the lane dimension, the one shape the pool
is stored, written and read in (kernels/primitives/paged.py "Shapes"
says why: it is the shape whose default TPU layout the paged kernel's
blocks address, so no executable copies the pool between layouts).  The
writes take their payload with the heads apart, [.., n, d], as the
model's projections hand it over, and flatten the PAYLOAD.

Dtype contract: the pool's dtype is stamped at creation
(KVPool(dtype=...)) and the write lowerings REFUSE a mismatched payload
at trace time — a bf16-AMP prefill feeding an fp32 pool fails loudly
with both dtypes named instead of silently mixing precisions in the
cache (the models/gpt.py KVSink stamps the cast on the program side).
"""

from __future__ import annotations

import jax.numpy as jnp

from paddle_tpu.fluid.registry import simple_op


def _rows(x):
    """A payload [R, n, w] as the pool's rows [R, n*w]."""
    return x.reshape(x.shape[0], -1)


def _page_blocks(op, x, page_size):
    """A chunk's payload [C, n, w] as whole pool pages
    [C/page_size, page_size, n*w]."""
    c = x.shape[0]
    if c % page_size:
        raise ValueError(
            f"{op}: chunk length {c} is not a multiple of the pool page "
            f"size {page_size} — the prefill chunk must cover whole "
            f"pages")
    return x.reshape(c // page_size, page_size, -1)


def _check_pool_dtype(op, pages, new):
    if pages.dtype != new.dtype:
        raise ValueError(
            f"{op}: payload dtype {new.dtype} does not match the KV "
            f"pool dtype {pages.dtype} — a mixed-precision prefill must "
            f"cast its K/V to the pool dtype before the write (the "
            f"gpt.KVSink(dtype=...) prefill sink stamps this cast; see "
            f"docs/SERVING.md 'Decode lane')")


@simple_op("kv_cache_write", ["Pages", "New", "PageIdx", "Offset"],
           ["PagesOut"], grad=None, inplace={"PagesOut": "Pages"})
def _kv_cache_write(ctx, pages, new, page_idx, offset, attrs):
    """One decode step's write: new [B, n, d] lands, flattened to
    [B, n*d], at pages[page_idx[b], offset[b]] per slot b.  Inactive
    slots point at the pool's trash page (page 0); duplicate trash
    coordinates are benign — nothing ever attends them."""
    _check_pool_dtype("kv_cache_write", pages, new)
    return pages.at[page_idx.astype(jnp.int32),
                    offset.astype(jnp.int32)].set(_rows(new))


@simple_op("kv_cache_write_pages", ["Pages", "New", "PageIdx"],
           ["PagesOut"], grad=None, inplace={"PagesOut": "Pages"})
def _kv_cache_write_pages(ctx, pages, new, page_idx, attrs):
    """One prefill chunk's write: new [C, n, d] (C a multiple of the
    page size) is viewed as C/page_size whole pages and scattered to
    pages[page_idx].  Pages past the chunk's valid tail carry the trash
    page id; rows past a sequence's length inside a REAL page are
    masked by every reader (attention masks j <= q_start + i)."""
    _check_pool_dtype("kv_cache_write_pages", pages, new)
    blocks = _page_blocks("kv_cache_write_pages", new, pages.shape[1])
    return pages.at[page_idx.astype(jnp.int32)].set(blocks)


@simple_op("paged_attention",
           ["Q", "KPages", "VPages", "PageTable", "QStart", "Sinks"],
           ["Out"], optional=("Sinks",), grad=None)
def _paged_attention(ctx, q, k_pages, v_pages, page_table, q_start, sinks,
                     attrs):
    """Attention of q [B, n, T, d] against the pool through the page
    table — kernels/primitives/paged.py (Pallas on TPU, lax gather
    reference on CPU; attrs["force"] pins an implementation,
    attrs["window"] bounds the keys from below; ``Sinks`` [n] joins
    each head's softmax as a column without a value)."""
    from paddle_tpu.kernels import primitives as _prims

    return _prims.paged_attention(
        q, k_pages, v_pages, page_table, q_start,
        sm_scale=attrs.get("sm_scale"), force=attrs.get("force"),
        window=attrs.get("window"), sinks=sinks)


# ---------------------------------------------------------------------------
# int8-pool forms (docs/KERNELS.md "int8 KV"): the pool rides as three
# vars per K/V — hi/lo int8 [P, pgs, n*d] + one fp32 scale per head_dim
# vector [P, pgs, n] (primitives/int8.py quantize_lastdim, taken on the
# payload while its heads are still apart).  Quantization happens ONCE
# here at append; readers dequantize inside the kernel.
# ---------------------------------------------------------------------------


def _quantize_payload(op, hi, new):
    from paddle_tpu.kernels import primitives as _prims

    if hi.dtype != jnp.int8:
        raise ValueError(
            f"{op}: Hi pool dtype {hi.dtype} != int8 — the quant write "
            f"ops only serve an int8 pool (KVPool(dtype='int8'))")
    return _prims.quantize_lastdim(new.astype(jnp.float32))


@simple_op("kv_cache_write_quant",
           ["Hi", "Lo", "Scale", "New", "PageIdx", "Offset"],
           ["HiOut", "LoOut", "ScaleOut"], grad=None,
           inplace={"HiOut": "Hi", "LoOut": "Lo", "ScaleOut": "Scale"})
def _kv_cache_write_quant(ctx, hi, lo, scale, new, page_idx, offset,
                          attrs):
    """kv_cache_write for the int8 pool: quantize new [B, n, d] per
    (slot, head) head_dim vector, scatter hi/lo/scale at
    (page_idx[b], offset[b]).  Same trash-page semantics as the fp
    write."""
    q_hi, q_lo, q_sc = _quantize_payload("kv_cache_write_quant", hi, new)
    pi = page_idx.astype(jnp.int32)
    off = offset.astype(jnp.int32)
    return (hi.at[pi, off].set(_rows(q_hi)),
            lo.at[pi, off].set(_rows(q_lo)),
            scale.at[pi, off].set(_rows(q_sc)))


@simple_op("kv_cache_write_pages_quant",
           ["Hi", "Lo", "Scale", "New", "PageIdx"],
           ["HiOut", "LoOut", "ScaleOut"], grad=None,
           inplace={"HiOut": "Hi", "LoOut": "Lo", "ScaleOut": "Scale"})
def _kv_cache_write_pages_quant(ctx, hi, lo, scale, new, page_idx,
                                attrs):
    """kv_cache_write_pages for the int8 pool: quantize the chunk
    [C, n, d] per vector, scatter whole pages of hi/lo/scale."""
    op = "kv_cache_write_pages_quant"
    q_hi, q_lo, q_sc = _quantize_payload(op, hi, new)
    pi = page_idx.astype(jnp.int32)
    return tuple(pool.at[pi].set(_page_blocks(op, x, hi.shape[1]))
                 for pool, x in ((hi, q_hi), (lo, q_lo), (scale, q_sc)))


@simple_op("paged_attention_quant",
           ["Q", "KHi", "KLo", "KScale", "VHi", "VLo", "VScale",
            "PageTable", "QStart"], ["Out"], grad=None)
def _paged_attention_quant(ctx, q, k_hi, k_lo, k_scale, v_hi, v_lo,
                           v_scale, page_table, q_start, attrs):
    """paged_attention over the dual-int8 pool — dequant inside the
    kernel (kernels/primitives/paged.py paged_attention_quant)."""
    from paddle_tpu.kernels import primitives as _prims

    return _prims.paged_attention_quant(
        q, k_hi, k_lo, k_scale, v_hi, v_lo, v_scale, page_table, q_start,
        sm_scale=attrs.get("sm_scale"), force=attrs.get("force"))
