"""Ragged (variable-length) attention — the dense prefill form.

What the primitives layer opens that the ad-hoc kernels couldn't: a
batch of sequences with DIFFERENT lengths attends in one launch, driven
by a per-sequence length vector instead of per-sequence padding masks +
one compiled executable per padded length.  The serving lane's use
(docs/SERVING.md "Ragged serving"): every batch pads dim 1 to ONE fixed
length, rows carry their true length in ``lengths``, and no padded key
position is ever scored — the seq-bucket cross-product warmup collapses
to one executable per batch bucket.

Two forms share the contract:

- **prefill (this module)** — dense q/k/v ``[B, H, S, D]`` + ``lengths
  [B]``; row b attends keys ``j < lengths[b]`` (and ``j <= i`` when
  causal).  Grid (bh, q_blocks, kv_blocks), kv innermost; ``lengths``
  rides as scalar prefetch and kv blocks wholly past a row's length are
  skipped via ``pl.when`` — short rows cost their OWN length in kv
  steps, not the batch max.
- **paged decode** — primitives/paged.py: ``q_start`` IS the length
  vector, pages past it are skipped the same way.

Output rows at positions ``i >= lengths[b]`` are computed under the
same key mask (finite, deterministic) but carry no contract — callers
slice ``[:lengths[b]]`` (the engine's seq slice-back does exactly
that).  Forward-only: the decode/serving lanes never differentiate
ragged attention (grad=None at the op layer).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import autotune, contract
from .contract import Block, Vmem
from .flash import BLOCK_CANDIDATES, DEFAULT_BLOCK, NEG_INF, _ceil_to

__all__ = ["ragged_attention", "ragged_attention_reference"]


def ragged_attention_reference(q, k, v, lengths, causal=False,
                               sm_scale=None):
    """Materializing XLA oracle over [BH, S, D] + lengths [BH]: key
    positions past a row's length masked with -1e30 (flash's constant),
    then the standard softmax spelling."""
    d = q.shape[-1]
    scale = float(sm_scale if sm_scale is not None else 1.0 / np.sqrt(d))
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    ki = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(ki < lengths.astype(jnp.int32)[:, None, None], s,
                  NEG_INF)
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qi >= ki, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # a fully-masked row (length 0) softmaxes to uniform garbage — zero
    # it so both implementations agree on the degenerate case
    p = jnp.where(lengths.astype(jnp.int32)[:, None, None] > 0, p, 0.0)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# kernel: flash's online-softmax grid with lengths as scalar prefetch —
# kv blocks wholly past a row's length never run
# ---------------------------------------------------------------------------


def _ragged_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, block_q, block_k, sm_scale,
                   causal, n_k):
    from jax.experimental import pallas as pl

    bi = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[bi]

    run = ki * block_k < length
    if causal:
        run = jnp.logical_and(run, ki <= qi)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_pos < length, s, NEG_INF)
        if causal:
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        s_max = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(s_max, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_prev.shape)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finish():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe[:, :1]).astype(o_ref.dtype)


def _pallas_ragged(q, k, v, lengths, causal, scale, interpret, block):
    bh, s, d = q.shape
    bq = bk = block
    n_q, n_k = s // bq, s // bk
    kernel = functools.partial(_ragged_kernel, block_q=bq, block_k=bk,
                               sm_scale=scale, causal=causal, n_k=n_k)

    # index maps under scalar prefetch take the lengths ref last
    spec = contract.make_spec(
        "ragged_fwd",
        grid=(bh, n_q, n_k),
        in_specs=[
            Block((1, bq, d), lambda b, i, j, ln: (b, i, 0)),
            Block((1, bk, d), lambda b, i, j, ln: (b, j, 0)),
            Block((1, bk, d), lambda b, i, j, ln: (b, j, 0)),
        ],
        out_specs=[Block((1, bq, d), lambda b, i, j, ln: (b, i, 0))],
        out_shape=[((bh, s, d), q.dtype)],
        scratch=[
            Vmem((bq, d), jnp.float32),
            Vmem((bq, 128), jnp.float32),
            Vmem((bq, 128), jnp.float32),
        ],
        num_scalar_prefetch=1,
        interpret=interpret,
    )
    return contract.primitive_call(kernel, spec,
                                   lengths.astype(jnp.int32), q, k, v)


def _select_block(q, k, v, lengths, causal, scale, interpret):
    bh, s, d = q.shape

    def measure(tile):
        blk = int(tile["block"])
        s_pad = _ceil_to(s, blk)
        qq = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0)))
        kk = jnp.pad(k, ((0, 0), (0, s_pad - s), (0, 0)))
        vv = jnp.pad(v, ((0, 0), (0, s_pad - s), (0, 0)))
        jax.block_until_ready(
            _pallas_ragged(qq, kk, vv, lengths, causal, scale, interpret,
                           blk))

    tracing = isinstance(q, jax.core.Tracer)
    tile = autotune.tile_for(
        "ragged_fwd",
        autotune.shape_signature(bh=bh, s=s, d=d, causal=int(causal)),
        {"block": DEFAULT_BLOCK},
        candidates=BLOCK_CANDIDATES,
        measure=None if tracing else measure,
    )
    return int(tile["block"])


def ragged_attention(q, k, v, lengths, causal=False, sm_scale=None,
                     force=None):
    """Variable-length attention over [B, H, S, D] (or [BH, S, D]):
    row b attends key positions j < lengths[b] (and j <= i when
    causal); rows past a row's length carry no output contract.

    lengths: [B] (4-D q, broadcast over heads) or [BH] int32.
    force: None → Pallas on TPU, XLA reference elsewhere; "pallas" →
    Pallas (interpret mode off-TPU, for tests); "reference" → XLA."""
    squeeze = False
    if q.ndim == 4:
        b, h, s, d = q.shape
        q = q.reshape(b * h, s, d)
        k = k.reshape(b * h, s, d)
        v = v.reshape(b * h, s, d)
        lengths = jnp.broadcast_to(
            jnp.reshape(lengths, (b, 1)), (b, h)).reshape(b * h)
        squeeze = (b, h)
    bh, s, d = q.shape
    scale = float(sm_scale if sm_scale is not None else 1.0 / np.sqrt(d))
    lengths = jnp.reshape(lengths, (bh,)).astype(jnp.int32)

    mode, interpret = contract.resolve_mode(
        "ragged_attention", force, force_env="PT_FLASH_FORCE_PALLAS")
    if mode == "pallas":
        block = _select_block(q, k, v, lengths, causal, scale, interpret)
        s_pad = _ceil_to(s, block)
        if s_pad != s:
            pad = s_pad - s
            q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        out = _pallas_ragged(q, k, v, lengths, causal, scale, interpret,
                             block)
        out = out[:, :s, :]
    else:
        out = ragged_attention_reference(q, k, v, lengths, causal, scale)
    if squeeze:
        b, h = squeeze
        out = out.reshape(b, h, s, d)
    return out
