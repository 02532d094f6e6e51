"""Flash attention — the dense-attention primitive on the contract.

Migrated from the ad-hoc ``kernels/flash_attention.py`` (which now
re-exports from here): same kernels, same custom VJP, same numerics —
the pallas_call plumbing now rides :func:`contract.primitive_call`
(specs as data, one audited launch site) and the block size resolves
through :mod:`autotune` instead of a baked-in constant.

The reference has no attention op at all (SURVEY.md §5: its Transformer
is composed from matmul/softmax layers, materializing the [B,H,S,S]
score matrix).  On TPU that materialization is the HBM-bandwidth
bottleneck and caps sequence length; this kernel computes attention
block-wise in VMEM with an online softmax (never writing S×S to HBM),
the standard flash-attention scheme.

Grid layout: (batch*heads, q_blocks, kv_blocks) with the kv dimension
innermost; running max/sum/accumulator live in VMEM scratch that
persists across the sequential kv steps, so resident VMEM is
O(block·D) — long sequences stream K/V block-by-block from HBM instead
of staging [S, D].  fp32 accumulation regardless of input dtype;
additive bias per (bh, key) position; optional causal mask.  Backward =
standard flash bwd: saved logsumexp + delta = rowsum(dO·O); one kernel
accumulating dQ over kv blocks, one accumulating dK/dV over q blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import autotune, contract
from .contract import Block, Vmem

DEFAULT_BLOCK = 128
NEG_INF = -1e30

# candidate q/kv blocks the measured autotune hook may try (the pinned
# table can set anything; candidates are what FLAGS_kernel_autotune
# times) — 128 is the MXU/lane width, 256 trades grid steps for VMEM
BLOCK_CANDIDATES = ({"block": 128}, {"block": 256})


def _ceil_to(x, m):
    return (x + m - 1) // m * m


def attention_reference(q, k, v, bias=None, causal=False, sm_scale=None):
    """Materializing XLA implementation: CPU fallback + numerics oracle."""
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(d)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias[:, None, :].astype(jnp.float32)
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ki = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(qi >= ki, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _causal_mask(s, qi, ki, bq, bk):
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward kernel: grid (bh, n_q, n_k), kv innermost; scratch carries the
# online-softmax state across kv steps
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, block_q, block_k, sm_scale, causal,
                n_k):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    # m/l scratch are (bq, 128) with all lanes equal — 2-D keeps Mosaic's
    # tile constraints happy (same layout as jax's fused attention kernels)
    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = (ki <= qi) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        b = bias_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        s = s + b[None, :]
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        m_prev, l_prev = m_ref[...], l_ref[...]
        s_max = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(s_max, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)            # all-lanes-equal
        p = jnp.exp(s - m_new[:, :1])
        p_sum = jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.broadcast_to(p_sum, l_prev.shape)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finish():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe[:, :1]).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l_safe))[:, 0]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc_ref, *, block_q, block_k, sm_scale, causal,
                   n_k):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    run = (ki <= qi) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        b = bias_ref[0, 0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        s = s + b[None, :]
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        p = jnp.exp(s - lse[:, None])
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dq_acc_ref[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, db_ref, dk_acc_ref, dv_acc_ref,
                    db_acc_ref, *, block_q, block_k, sm_scale, causal, n_q):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)
        db_acc_ref[...] = jnp.zeros_like(db_acc_ref)

    run = (qi >= ki) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        b = bias_ref[0, 0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        s = s + b[None, :]
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        p = jnp.exp(s - lse[:, None])  # [bq, bk]
        dv_acc_ref[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        dl = p * (dp - delta[:, None])   # d loss / d logits (pre-scale)
        ds = dl * sm_scale               # chain through the qk scale for dq/dk
        dk_acc_ref[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        # bias enters the logits unscaled → dbias[k] = Σ_q dl; all rows of
        # the (8, bk) scratch carry the same value to satisfy tile layout
        db_acc_ref[...] += jnp.broadcast_to(
            jnp.sum(dl, axis=0, keepdims=True), db_acc_ref.shape)

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)
        db_ref[0, 0] = db_acc_ref[0]


# ---------------------------------------------------------------------------
# launch plumbing on the contract — rank-2 (bh, s) operands ride as
# (bh, 1, s): Mosaic requires the block's second-minor dim to divide 8
# or equal the array's — a literal 1 does
# ---------------------------------------------------------------------------


def _pallas_fwd(q, k, v, bias, causal, sm_scale, interpret, block):
    bh, s, d = q.shape
    bq = bk = block
    n_q, n_k = s // bq, s // bk
    kernel = functools.partial(_fwd_kernel, block_q=bq, block_k=bk,
                               sm_scale=sm_scale, causal=causal, n_k=n_k)
    spec = contract.make_spec(
        "flash_fwd",
        grid=(bh, n_q, n_k),
        in_specs=[
            Block((1, bq, d), lambda b, i, j: (b, i, 0)),
            Block((1, bk, d), lambda b, i, j: (b, j, 0)),
            Block((1, bk, d), lambda b, i, j: (b, j, 0)),
            Block((1, 1, bk), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=[
            Block((1, bq, d), lambda b, i, j: (b, i, 0)),
            Block((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[((bh, s, d), q.dtype), ((bh, 1, s), jnp.float32)],
        scratch=[
            Vmem((bq, d), jnp.float32),
            Vmem((bq, 128), jnp.float32),
            Vmem((bq, 128), jnp.float32),
        ],
        interpret=interpret,
    )
    out, lse = contract.primitive_call(kernel, spec, q, k, v,
                                       bias[:, None, :])
    return out, lse[:, 0, :]


def _pallas_bwd(q, k, v, bias, o, lse, do, causal, sm_scale, interpret,
                block):
    bh, s, d = q.shape
    bq = bk = block
    n_q, n_k = s // bq, s // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    bias3 = bias[:, None, :]
    lse3 = lse[:, None, :]
    delta3 = delta[:, None, :]

    dq_spec = contract.make_spec(
        "flash_bwd_dq",
        grid=(bh, n_q, n_k),
        in_specs=[
            Block((1, bq, d), lambda b, i, j: (b, i, 0)),
            Block((1, bk, d), lambda b, i, j: (b, j, 0)),
            Block((1, bk, d), lambda b, i, j: (b, j, 0)),
            Block((1, 1, bk), lambda b, i, j: (b, 0, j)),
            Block((1, bq, d), lambda b, i, j: (b, i, 0)),
            Block((1, 1, bq), lambda b, i, j: (b, 0, i)),
            Block((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=[Block((1, bq, d), lambda b, i, j: (b, i, 0))],
        out_shape=[((bh, s, d), q.dtype)],
        scratch=[Vmem((bq, d), jnp.float32)],
        interpret=interpret,
    )
    dq = contract.primitive_call(
        functools.partial(_bwd_dq_kernel, block_q=bq, block_k=bk,
                          sm_scale=sm_scale, causal=causal, n_k=n_k),
        dq_spec, q, k, v, bias3, do, lse3, delta3)

    dkv_spec = contract.make_spec(
        "flash_bwd_dkv",
        grid=(bh, n_k, n_q),
        in_specs=[
            Block((1, bq, d), lambda b, j, i: (b, i, 0)),
            Block((1, bk, d), lambda b, j, i: (b, j, 0)),
            Block((1, bk, d), lambda b, j, i: (b, j, 0)),
            Block((1, 1, bk), lambda b, j, i: (b, 0, j)),
            Block((1, bq, d), lambda b, j, i: (b, i, 0)),
            Block((1, 1, bq), lambda b, j, i: (b, 0, i)),
            Block((1, 1, bq), lambda b, j, i: (b, 0, i)),
        ],
        out_specs=[
            Block((1, bk, d), lambda b, j, i: (b, j, 0)),
            Block((1, bk, d), lambda b, j, i: (b, j, 0)),
            Block((1, 1, bk), lambda b, j, i: (b, 0, j)),
        ],
        out_shape=[
            ((bh, s, d), q.dtype),
            ((bh, s, d), q.dtype),
            ((bh, 1, s), jnp.float32),
        ],
        scratch=[
            Vmem((bk, d), jnp.float32),
            Vmem((bk, d), jnp.float32),
            Vmem((8, bk), jnp.float32),
        ],
        interpret=interpret,
    )
    dk, dv, db = contract.primitive_call(
        functools.partial(_bwd_dkv_kernel, block_q=bq, block_k=bk,
                          sm_scale=sm_scale, causal=causal, n_q=n_q),
        dkv_spec, q, k, v, bias3, do, lse3, delta3)
    return dq, dk, dv, db[:, 0, :]


# ---------------------------------------------------------------------------
# public entry: custom_vjp over [BH, S, D]
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(q, k, v, bias, causal, sm_scale, interpret, block):
    out, _ = _pallas_fwd(q, k, v, bias, causal, sm_scale, interpret, block)
    return out


def _flash_fwd(q, k, v, bias, causal, sm_scale, interpret, block):
    out, lse = _pallas_fwd(q, k, v, bias, causal, sm_scale, interpret, block)
    return out, (q, k, v, bias, out, lse)


def _flash_bwd(causal, sm_scale, interpret, block, res, do):
    q, k, v, bias, o, lse = res
    dq, dk, dv, db = _pallas_bwd(q, k, v, bias, o, lse, do, causal, sm_scale,
                                 interpret, block)
    return dq, dk, dv, db.astype(bias.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _pad_to_block(q, k, v, bias, block):
    """Pad S up to a block multiple; padded keys carry -inf bias."""
    s = q.shape[1]
    s_pad = _ceil_to(s, block)
    if s_pad != s:
        pad = s_pad - s
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, pad)), constant_values=NEG_INF)
    return q, k, v, bias


def _select_block(q, k, v, bias, causal, scale, interpret):
    """Resolve the q/kv block through the tile table; the measure hook
    runs the real padded forward per candidate (FLAGS_kernel_autotune
    opt-in — candidate compiles are not free)."""
    bh, s, d = q.shape

    def measure(tile):
        blk = int(tile["block"])
        qq, kk, vv, bb = _pad_to_block(q, k, v, bias, blk)
        jax.block_until_ready(
            _flash(qq, kk, vv, bb, causal, scale, interpret, blk))

    # measured autotune needs concrete operands — under jit/abstract
    # tracing only the pinned table / measured cache / defaults apply
    tracing = isinstance(q, jax.core.Tracer)
    tile = autotune.tile_for(
        "flash_fwd",
        autotune.shape_signature(bh=bh, s=s, d=d, causal=int(causal)),
        {"block": DEFAULT_BLOCK},
        candidates=BLOCK_CANDIDATES,
        measure=None if tracing else measure,
    )
    return int(tile["block"])


def flash_attention(q, k, v, bias=None, causal=False, sm_scale=None,
                    force=None):
    """Attention over [B, H, S, D] (or [BH, S, D]) without materializing the
    S×S score matrix.

    bias: optional additive [B, 1, 1, S] / [B, S] / [BH, S] key bias
    (e.g. padding mask: 0 for real tokens, -1e4 for pads).
    force: None → pallas on TPU, XLA reference elsewhere;
           "pallas" → pallas (interpret-mode off-TPU, for tests);
           "reference" → XLA reference.
    """
    squeeze = False
    if q.ndim == 4:
        b, h, s, d = q.shape
        q = q.reshape(b * h, s, d)
        k = k.reshape(b * h, s, d)
        v = v.reshape(b * h, s, d)
        if bias is not None:
            bias = jnp.broadcast_to(
                bias.reshape(b, 1, -1), (b, h, bias.shape[-1])
            ).reshape(b * h, -1)
        squeeze = (b, h)
    bh, s, d = q.shape
    scale = float(sm_scale if sm_scale is not None else 1.0 / np.sqrt(d))
    if bias is None:
        bias = jnp.zeros((bh, s), jnp.float32)
    else:
        bias = jnp.broadcast_to(bias.reshape(bh, -1), (bh, s)).astype(jnp.float32)

    mode, interpret = contract.resolve_mode(
        "flash_attention", force, force_env="PT_FLASH_FORCE_PALLAS")
    if mode == "pallas":
        block = _select_block(q, k, v, bias, causal, scale, interpret)
        q, k, v, bias = _pad_to_block(q, k, v, bias, block)
        out = _flash(q, k, v, bias, causal, scale, interpret, block)
        out = out[:, :s, :]
    else:
        out = attention_reference(q, k, v, bias, causal, scale)
    if squeeze:
        b, h = squeeze
        out = out.reshape(b, h, s, d)
    return out
