"""Fused dequant→optimizer-update→requant step kernels.

The motivating cost (modeled, never measured on the chip — PERF.md) is
the optimizer leg's fp32 HBM round-trip: the quantized all-reduce
dequantizes the gradient bucket into a full fp32 buffer, the optimizer
op reads it back,
writes the fp32 updated parameter, and (under ZeRO-1 + zero_gather_quant)
the gather wrapper reads THAT back to requantize it for the wire.  This
module fuses the chain so neither fp32 image materializes
(Operator Fusion in XLA, arXiv:2301.13062):

  int8 grad bucket + scales ──dequant──► fp32 registers ──Adam/SGD──►
  fp32 registers ──requant──► int8 updated-param payload + scales

- **dequant leg** (data-parallel path): ``c_allreduce_quant_keep`` keeps
  the reduced bucket in the wire format and the fused optimizer ops
  (`ops/optimizer_ops.py` ``fused_adam_quant_grad`` /
  ``fused_sgd_quant_grad``) consume int8 + scales directly —
  :func:`dequant_slice` pulls one block-aligned member out of the bucket
  and dequantizes inline with the update math.
- **requant leg** (hybrid ZeRO-1 path): ``fused_adam_quant_gather`` /
  ``fused_sgd_quant_gather`` emit the quantized gather payload beside
  the exact fp32 ``ParamOut`` — under
  ``HybridParallelRunner(zero_gather_quant=...)`` the payload rides the
  ZeRO-1 weight-update gather
  (`kernels.ring_collectives.gather_quantized_shards`) and the fp32
  updated parameter between update and requant exists only inside the
  XLA fusion (pinned by the HLO assertion in tests/test_fused_update.py).

Two implementations, selected by :func:`impl` (env
``PT_FUSED_UPDATE_IMPL`` = ``auto`` | ``xla`` | ``pallas`` |
``interpret``):

- **pure-XLA** (default off-TPU): one jitted jnp chain; XLA's fusion
  keeps the intermediates in registers.
- **Pallas** (default on TPU; ``interpret`` runs the same kernel through
  the interpreter for CPU tests): a blockwise VMEM kernel over the
  ``(n_blocks, block_size)`` view — dequant, update and requant of each
  tile never leave VMEM.

Reachability (be precise about which leg gets which impl): the Pallas
kernel serves the QUANTIZED-GRADIENT chains — the DP ``*_quant_grad``
ops (``_pallas_able`` keys on the wire-tuple gradient).  The hybrid
``*_quant_gather`` ops pass an fp32 gradient, so their update→requant
chain intentionally rides the XLA path: it must return the EXACT fp32
``ParamOut`` (the plain-Executor contract), which the Pallas requant
form — whose ``p_new`` is the dequantized payload image — cannot
provide without re-writing the fp32 update to HBM and forfeiting the
saving.  The Pallas ``requant=True`` branch is the kernel-level
full-chain capability (dequant→update→requant in one VMEM pass, pinned
by the jaxpr boundary test) for the future DP+ZeRO combination.

Numerics contract: the update math mirrors ``ops/optimizer_ops.py``
``_adam``/``_sgd`` term for term, so on an fp32 gradient the fused update
matches the reference op to float-associativity (≤ 1e-6 gate); on a
quantized gradient the only divergence is the gradient's own dual-int8
error (≤ ``block_max/64516`` per element — the documented wire bound).
``bytes_saved`` models the avoided fp32 HBM round-trip (one write + one
read of the full buffer) booked on
``pt_fused_update_bytes_saved_total``.
"""

from __future__ import annotations

import os

import numpy as np

import jax
import jax.numpy as jnp

from .quantized_collectives import (DEFAULT_BLOCK_SIZE, _QMAX, _RESID_DIV,
                                    dequantize_block_scaled,
                                    quantize_block_scaled)

__all__ = [
    "impl",
    "bytes_saved",
    "dequant_slice",
    "adam_math",
    "adamw_math",
    "lamb_math",
    "sgd_math",
    "momentum_math",
    "quantize_for_gather",
    "fused_adam_update",
    "fused_adamw_update",
    "fused_lamb_update",
    "fused_sgd_update",
    "fused_momentum_update",
]


def impl():
    """Resolve the kernel implementation: ``PT_FUSED_UPDATE_IMPL`` =
    ``xla`` | ``pallas`` | ``interpret`` | ``auto`` (default).  ``auto``
    picks Pallas where traces lower for a TPU and pure XLA elsewhere."""
    mode = os.environ.get("PT_FUSED_UPDATE_IMPL", "auto").strip().lower()
    if mode in ("xla", "pallas", "interpret"):
        return mode
    from .primitives.contract import is_tpu_platform

    return "pallas" if is_tpu_platform() else "xla"


def bytes_saved(n_elements):
    """Modeled HBM bytes one fused update avoids per step: the unfused
    chain writes the full fp32 intermediate (dequantized bucket on the
    grad side, updated parameter on the gather side) and reads it back —
    2 passes x 4 bytes per element."""
    return 8 * int(n_elements)


def dequant_slice(q_hi, q_lo, scales, offset_blocks, numel, block_size,
                  shape=None):
    """Dequantize one block-aligned member out of a quantized bucket:
    blocks ``[offset_blocks, offset_blocks + ceil(numel/block))`` of the
    flat wire image, trimmed to ``numel`` and reshaped.  Static offsets —
    the slice is a view XLA folds into the consuming fusion, so the fp32
    member never materializes outside it."""
    bs = int(block_size)
    off = int(offset_blocks) * bs
    nb = -(-int(numel) // bs)  # ceil
    hi = jax.lax.slice_in_dim(q_hi, off, off + nb * bs)
    lo = (jax.lax.slice_in_dim(q_lo, off, off + nb * bs)
          if q_lo is not None else None)
    sc = jax.lax.slice_in_dim(scales, int(offset_blocks),
                              int(offset_blocks) + nb)
    g = dequantize_block_scaled(hi, lo, sc, bs)[: int(numel)]
    return g.reshape(shape) if shape is not None else g


def adam_math(p, g32, m1, m2, lr, b1p, b2p, beta1, beta2, epsilon):
    """The Adam update in fp32 — term-for-term the math of
    ``ops/optimizer_ops.py`` ``_adam`` (exactness is the fused-vs-
    reference gate in tests/test_fused_update.py).  Returns
    ``(p_new32, m1n, m2n, b1pn, b2pn)``; moment/pow outputs keep their
    input dtypes, ``p_new32`` stays fp32 for the requant leg."""
    g32 = g32.astype(jnp.float32)
    p32 = p.astype(jnp.float32)
    m1n = beta1 * m1.astype(jnp.float32) + (1 - beta1) * g32
    m2n = beta2 * m2.astype(jnp.float32) + (1 - beta2) * jnp.square(g32)
    b1pf = jnp.reshape(b1p, ()).astype(jnp.float32)
    b2pf = jnp.reshape(b2p, ()).astype(jnp.float32)
    lr_t = (jnp.reshape(lr, ()).astype(jnp.float32)
            * jnp.sqrt(1 - b2pf) / (1 - b1pf))
    p_new = p32 - lr_t * m1n / (jnp.sqrt(m2n) + epsilon)
    return (p_new, m1n.astype(m1.dtype), m2n.astype(m2.dtype),
            jnp.reshape(b1pf * beta1, jnp.shape(b1p)).astype(b1p.dtype),
            jnp.reshape(b2pf * beta2, jnp.shape(b2p)).astype(b2p.dtype))


def adamw_math(p, g32, m1, m2, lr, b1p, b2p, beta1, beta2, epsilon,
               coeff):
    """The AdamW update in fp32 — the base Adam step plus the decoupled
    decay ``p -= lr_raw * coeff * p`` applied to the PRE-update
    parameter, term-for-term ``ops/optimizer_ops.py`` ``_adamw`` (the
    decay uses the RAW learning rate, not the bias-corrected step)."""
    outs = adam_math(p, g32, m1, m2, lr, b1p, b2p, beta1, beta2, epsilon)
    lr_raw = jnp.reshape(lr, ()).astype(jnp.float32)
    p_new = outs[0] - lr_raw * coeff * p.astype(jnp.float32)
    return (p_new,) + outs[1:]


def lamb_math(p, g32, m1, m2, lr, b1p, b2p, beta1, beta2, epsilon,
              weight_decay):
    """The LAMB update in fp32 — term-for-term ``ops/optimizer_ops.py``
    ``_lamb``: Adam moments, bias correction, ``r = mhat/(sqrt(vhat)+
    eps) + wd*p``, and the layer-wise trust ratio ``|p| / |r|`` scaling
    the step.  Returns ``(p_new32, m1n, m2n, b1pn, b2pn)``."""
    g32 = g32.astype(jnp.float32)
    p32 = p.astype(jnp.float32)
    m1n = beta1 * m1.astype(jnp.float32) + (1 - beta1) * g32
    m2n = beta2 * m2.astype(jnp.float32) + (1 - beta2) * jnp.square(g32)
    b1pf = jnp.reshape(b1p, ()).astype(jnp.float32)
    b2pf = jnp.reshape(b2p, ()).astype(jnp.float32)
    mhat = m1n / (1 - b1pf)
    vhat = m2n / (1 - b2pf)
    r = mhat / (jnp.sqrt(vhat) + epsilon) + weight_decay * p32
    pn = jnp.sqrt(jnp.sum(jnp.square(p32)))
    rn = jnp.sqrt(jnp.sum(jnp.square(r)))
    trust = jnp.where((pn > 0) & (rn > 0), pn / rn, 1.0)
    p_new = p32 - jnp.reshape(lr, ()).astype(jnp.float32) * trust * r
    return (p_new, m1n.astype(m1.dtype), m2n.astype(m2.dtype),
            jnp.reshape(b1pf * beta1, jnp.shape(b1p)).astype(b1p.dtype),
            jnp.reshape(b2pf * beta2, jnp.shape(b2p)).astype(b2p.dtype))


def sgd_math(p, g32, lr):
    """The SGD update in fp32 (mirrors ``_sgd``)."""
    return (p.astype(jnp.float32)
            - jnp.reshape(lr, ()).astype(jnp.float32)
            * g32.astype(jnp.float32))


def momentum_math(p, g32, v, lr, mu, use_nesterov=False):
    """The momentum update in fp32 — term-for-term
    ``ops/optimizer_ops.py`` ``_momentum`` (heavy-ball by default,
    Nesterov under the op's ``use_nesterov`` attr).  Returns
    ``(p_new32, v_new)`` with the velocity in its input dtype."""
    g32 = g32.astype(jnp.float32)
    p32 = p.astype(jnp.float32)
    lr_ = jnp.reshape(lr, ()).astype(jnp.float32)
    v_new = mu * v.astype(jnp.float32) + g32
    if use_nesterov:
        p_new = p32 - (g32 + mu * v_new) * lr_
    else:
        p_new = p32 - lr_ * v_new
    return p_new, v_new.astype(v.dtype)


def quantize_for_gather(p_new32, block_size, dual_int8=True,
                        pad_multiple=None):
    """Requantize the fp32 updated parameter into the ZeRO-gather wire
    format: flat, zero-padded to ``pad_multiple`` (the gather caller's
    ``dp * block_size``, so per-shard blocks never straddle a shard
    boundary), block-scaled dual int8.  Returns ``(q_hi, q_lo, scales)``."""
    bs = int(block_size)
    mult = int(pad_multiple) if pad_multiple else bs
    flat = jnp.ravel(p_new32).astype(jnp.float32)
    pad = (-flat.size) % mult
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return quantize_block_scaled(flat, bs, dual_int8=dual_int8)


# ---------------------------------------------------------------------------
# Pallas kernels: blockwise dequant→update→requant over the
# (n_blocks, block_size) view.  Tiles of _TILE_ROWS blocks live in VMEM;
# the fp32 gradient and updated parameter exist only inside the tile.
# ---------------------------------------------------------------------------

_TILE_ROWS = 32  # int8 min sublane tile; f32 tiles (8) divide it


def _dequant_tile(hi_ref, lo_ref, sc_ref):
    sc = sc_ref[:].astype(jnp.float32)  # [R, 1]
    g = hi_ref[:].astype(jnp.float32) * sc
    if lo_ref is not None:
        g = g + lo_ref[:].astype(jnp.float32) * (sc / _RESID_DIV)
    return g


def _requant_tile(pn):
    amax = jnp.max(jnp.abs(pn), axis=1, keepdims=True)
    # jnp.maximum, not where(amax > 0): a NaN/Inf block must propagate
    # into its scale, not launder to finite garbage (the health
    # sentinel's detection surface — see quantize_block_scaled)
    scale = jnp.maximum(amax / _QMAX, jnp.float32(1e-30))
    q_hi = jnp.clip(jnp.round(pn / scale), -_QMAX, _QMAX)
    resid = pn - q_hi * scale
    q_lo = jnp.clip(jnp.round(resid * (_RESID_DIV / scale)), -_QMAX, _QMAX)
    return q_hi.astype(jnp.int8), q_lo.astype(jnp.int8), scale


def _tile_rows(n_rows, block_size):
    """Row tile through the primitives tile table (pinned-table hook;
    _TILE_ROWS stays the default — it is the int8 minimum sublane
    tile).  A pinned value that does not divide the padded row count
    falls back to the default rather than mislaunching."""
    from .primitives import autotune

    tile = autotune.tile_for(
        "fused_update",
        autotune.shape_signature(rows=n_rows, block=block_size),
        {"rows": _TILE_ROWS})
    rows = int(tile["rows"])
    return rows if rows > 0 and n_rows % rows == 0 else _TILE_ROWS


def _pallas_call(kernel, n_rows, block_size, in_structs, out_structs,
                 interpret):
    """Shared launch builder on the primitives contract: 1-D grid over
    row tiles of the (n_rows, block_size) view; every ref is an
    [R_tile, ...] VMEM block."""
    from .primitives import contract
    from .primitives.contract import Block

    rows = _tile_rows(n_rows, block_size)
    grid = (n_rows // rows,)

    def spec(s):
        if len(s.shape) == 2 and s.shape[0] == n_rows:
            return Block((rows, s.shape[1]), lambda i: (i, 0))
        # whole-array operand (the scalar lr carrier)
        return Block(tuple(s.shape), lambda i: (0,) * len(s.shape))

    launch = contract.make_spec(
        "fused_update",
        grid=grid,
        in_specs=[spec(s) for s in in_structs],
        out_specs=[spec(s) for s in out_structs],
        out_shape=[(tuple(s.shape), s.dtype) for s in out_structs],
        interpret=interpret,
    )
    def call(*ops):
        out = contract.primitive_call(kernel, launch, *ops)
        # historical contract: always a tuple, even for one output
        return out if isinstance(out, (tuple, list)) else (out,)

    return call


def _pallas_fused(kind, p2, ghi2, glo2, gsc2, m1_2, m2_2, lr_t, hyper,
                  requant, interpret, lr_decay=0.0):
    """Run the fused chain as a Pallas kernel over [R, B] views.
    ``lr_t`` is the precomputed scalar step size (bias-corrected for
    Adam); returns (p_new or (q_hi, q_lo, sc), m1n, m2n).  ``kind`` is
    "sgd" (stateless), "momentum" (one velocity slot in m1_2, hyper =
    (mu, use_nesterov, _)), "adam" (two moment slots, hyper =
    (beta1, beta2, epsilon)), or "adamw" (adam plus the decoupled decay
    ``p -= lr_decay * p`` — ``lr_decay`` = raw lr × coeff rides the
    second lane of the scalar carrier)."""
    dual = glo2 is not None
    beta1, beta2, eps = hyper
    R, B = p2.shape
    lr_arr = jnp.stack(
        [jnp.reshape(lr_t, ()).astype(jnp.float32),
         jnp.reshape(lr_decay, ()).astype(jnp.float32)]).reshape(1, 2)

    def kernel(*refs):
        i = 0
        p_ref = refs[i]; i += 1
        hi_ref = refs[i]; i += 1
        lo_ref = None
        if dual:
            lo_ref = refs[i]; i += 1
        sc_ref = refs[i]; i += 1
        m1_ref = m2_ref = None
        if kind in ("adam", "adamw", "momentum"):
            m1_ref = refs[i]; i += 1
        if kind in ("adam", "adamw"):
            m2_ref = refs[i]; i += 1
        lr_ref = refs[i]; i += 1
        outs = refs[i:]
        g = _dequant_tile(hi_ref, lo_ref, sc_ref)
        p = p_ref[:].astype(jnp.float32)
        lr = lr_ref[0, 0]
        o = 0
        if kind in ("adam", "adamw"):
            m1n = beta1 * m1_ref[:].astype(jnp.float32) + (1 - beta1) * g
            m2n = (beta2 * m2_ref[:].astype(jnp.float32)
                   + (1 - beta2) * jnp.square(g))
            pn = p - lr * m1n / (jnp.sqrt(m2n) + eps)
            if kind == "adamw":
                pn = pn - lr_ref[0, 1] * p
        elif kind == "momentum":
            mu, nesterov = beta1, bool(beta2)
            m1n = mu * m1_ref[:].astype(jnp.float32) + g
            pn = (p - (g + mu * m1n) * lr if nesterov
                  else p - lr * m1n)
        else:
            pn = p - lr * g
        if requant:
            q_hi, q_lo, scale = _requant_tile(pn)
            outs[o][:] = q_hi; o += 1
            outs[o][:] = q_lo; o += 1
            outs[o][:] = scale; o += 1
        else:
            outs[o][:] = pn; o += 1
        if kind in ("adam", "adamw", "momentum"):
            outs[o][:] = m1n; o += 1
        if kind in ("adam", "adamw"):
            outs[o][:] = m2n; o += 1

    sds = jax.ShapeDtypeStruct
    ins = [p2, ghi2] + ([glo2] if dual else []) + [gsc2]
    if kind in ("adam", "adamw", "momentum"):
        ins += [m1_2]
    if kind in ("adam", "adamw"):
        ins += [m2_2]
    ins += [lr_arr]
    out_structs = []
    if requant:
        out_structs += [sds((R, B), jnp.int8), sds((R, B), jnp.int8),
                        sds((R, 1), jnp.float32)]
    else:
        out_structs += [sds((R, B), jnp.float32)]
    if kind in ("adam", "adamw", "momentum"):
        out_structs += [sds((R, B), jnp.float32)]
    if kind in ("adam", "adamw"):
        out_structs += [sds((R, B), jnp.float32)]
    call = _pallas_call(kernel, R, B,
                        [sds(x.shape, x.dtype) for x in ins],
                        out_structs, interpret)
    outs = call(*ins)
    o = 0
    if requant:
        result = (outs[0], outs[1], outs[2][:, 0])
        o = 3
    else:
        result = outs[0]
        o = 1
    m1n = m2n = None
    if kind in ("adam", "adamw", "momentum"):
        m1n = outs[o]; o += 1
    if kind in ("adam", "adamw"):
        m2n = outs[o]
    return result, m1n, m2n


def _rows_pad(flat2d_rows, block_size):
    """Pad the row (block) count to the Pallas tile multiple."""
    return (-flat2d_rows) % _TILE_ROWS


def _as_blocks(x, n_rows, block_size):
    """Flatten + zero-pad ``x`` to ``n_rows * block_size`` elements and
    view it as [n_rows, block_size]."""
    flat = jnp.ravel(x).astype(jnp.float32)
    pad = n_rows * block_size - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(n_rows, block_size)


# ---------------------------------------------------------------------------
# Fused entries (XLA fallback + Pallas dispatch)
# ---------------------------------------------------------------------------


def _grad_value(grad, block_size, shape):
    """Resolve the gradient leg: an fp32-ish array passes through; a
    ``(q_hi, q_lo, scales, offset_blocks, numel)`` bucket slice
    dequantizes inline."""
    if isinstance(grad, tuple):
        q_hi, q_lo, scales, offset_blocks, numel = grad
        return dequant_slice(q_hi, q_lo, scales, offset_blocks, numel,
                             block_size, shape)
    return grad


def _pallas_able(grad, requant_pad, block_size):
    """The Pallas kernel covers the quantized-gradient chain with
    whole-tensor updates (the DP bucket path): grad as a wire-format
    tuple, block_size a lane multiple.  Everything else (fp32 grads —
    a 3-op elementwise chain XLA fuses by itself) takes the XLA path."""
    return (isinstance(grad, tuple) and int(block_size) % 128 == 0
            and impl() in ("pallas", "interpret"))


def _pallas_grad_blocks(grad, block_size, numel_padded):
    """Slice the bucket member's quantized blocks for the Pallas kernel
    (int8 view — never dequantized outside VMEM), row-padded to the tile
    multiple with zero blocks (scale 1.0 dequantizes them to 0)."""
    q_hi, q_lo, scales, offset_blocks, _numel = grad
    bs = int(block_size)
    nb = numel_padded // bs
    off = int(offset_blocks)
    hi = jax.lax.slice_in_dim(q_hi, off * bs, (off + nb) * bs)
    lo = (jax.lax.slice_in_dim(q_lo, off * bs, (off + nb) * bs)
          if q_lo is not None else None)
    sc = jax.lax.slice_in_dim(scales, off, off + nb)
    rpad = _rows_pad(nb, bs)
    hi2 = hi.reshape(nb, bs)
    lo2 = lo.reshape(nb, bs) if lo is not None else None
    sc2 = sc.reshape(nb, 1)
    if rpad:
        hi2 = jnp.pad(hi2, ((0, rpad), (0, 0)))
        if lo2 is not None:
            lo2 = jnp.pad(lo2, ((0, rpad), (0, 0)))
        sc2 = jnp.pad(sc2, ((0, rpad), (0, 0)), constant_values=1.0)
    return hi2, lo2, sc2, nb + rpad


def fused_adam_update(p, grad, m1, m2, lr, b1p, b2p, *, beta1=0.9,
                      beta2=0.999, epsilon=1e-8,
                      block_size=DEFAULT_BLOCK_SIZE, requant_pad=None,
                      _wd_coeff=None):
    """The fused Adam step.  ``grad`` is an fp32 array shaped like ``p``
    OR a wire-format bucket slice ``(q_hi, q_lo, scales, offset_blocks,
    numel)`` (dequant leg).  ``requant_pad`` non-None additionally emits
    the quantized-gather payload of the updated parameter, padded to that
    multiple (requant leg).  Returns
    ``(p_new, m1n, m2n, b1pn, b2pn[, q_hi, q_lo, q_sc])`` with ``p_new``
    in ``p``'s dtype.

    ``p_new`` semantics on the requant chain: the XLA fallback returns
    the EXACT fp32 update (its quantize reads the same registers), while
    the Pallas kernel returns the dequantized payload image — the update
    never leaves VMEM in fp32, which is the point of the kernel; the two
    agree within one dual-int8 quantization.  Wired callers never see the
    difference: the hybrid gather wrapper replaces ``p_new`` with the
    gathered payload (the same image), and the DP grad-side ops don't
    requant."""
    shape, bs = jnp.shape(p), int(block_size)
    if _pallas_able(grad, requant_pad, bs):
        numel = int(np.prod(shape, dtype=np.int64)) if shape else 1
        numel_padded = numel + (-numel) % bs
        hi2, lo2, sc2, rows = _pallas_grad_blocks(grad, bs, numel_padded)
        p2 = _as_blocks(p, rows, bs)
        m1_2 = _as_blocks(m1, rows, bs)
        m2_2 = _as_blocks(m2, rows, bs)
        b1pf = jnp.reshape(b1p, ()).astype(jnp.float32)
        b2pf = jnp.reshape(b2p, ()).astype(jnp.float32)
        lr_t = (jnp.reshape(lr, ()).astype(jnp.float32)
                * jnp.sqrt(1 - b2pf) / (1 - b1pf))
        lr_decay = (jnp.reshape(lr, ()).astype(jnp.float32) * _wd_coeff
                    if _wd_coeff is not None else 0.0)
        out, m1n2, m2n2 = _pallas_fused(
            "adamw" if _wd_coeff is not None else "adam",
            p2, hi2, lo2, sc2, m1_2, m2_2, lr_t,
            (beta1, beta2, epsilon), requant=requant_pad is not None,
            interpret=impl() == "interpret", lr_decay=lr_decay)

        def unblk(x2, dtype):
            return x2.reshape(-1)[:numel].reshape(shape).astype(dtype)

        m1n, m2n = unblk(m1n2, m1.dtype), unblk(m2n2, m2.dtype)
        b1pn = jnp.reshape(b1pf * beta1, jnp.shape(b1p)).astype(b1p.dtype)
        b2pn = jnp.reshape(b2pf * beta2, jnp.shape(b2p)).astype(b2p.dtype)
        if requant_pad is not None:
            q_hi2, q_lo2, q_sc2 = out
            p_new = dequantize_block_scaled(
                q_hi2.reshape(-1), q_lo2.reshape(-1),
                q_sc2.reshape(-1), bs)  # only for ParamOut parity
            # re-pad the payload to the gather multiple
            q_hi, q_lo, q_sc = _repad_payload(
                q_hi2, q_lo2, q_sc2, numel, bs, requant_pad)
            return (unblk(p_new.reshape(rows, bs), p.dtype), m1n, m2n,
                    b1pn, b2pn, q_hi, q_lo, q_sc)
        return unblk(out, p.dtype), m1n, m2n, b1pn, b2pn
    g = _grad_value(grad, bs, shape)
    if _wd_coeff is not None:
        p_new32, m1n, m2n, b1pn, b2pn = adamw_math(
            p, g, m1, m2, lr, b1p, b2p, beta1, beta2, epsilon, _wd_coeff)
    else:
        p_new32, m1n, m2n, b1pn, b2pn = adam_math(
            p, g, m1, m2, lr, b1p, b2p, beta1, beta2, epsilon)
    if requant_pad is not None:
        q_hi, q_lo, q_sc = quantize_for_gather(p_new32, bs,
                                               pad_multiple=requant_pad)
        return (p_new32.astype(p.dtype), m1n, m2n, b1pn, b2pn,
                q_hi, q_lo, q_sc)
    return p_new32.astype(p.dtype), m1n, m2n, b1pn, b2pn


def fused_adamw_update(p, grad, m1, m2, lr, b1p, b2p, *, beta1=0.9,
                       beta2=0.999, epsilon=1e-8, coeff=0.01,
                       block_size=DEFAULT_BLOCK_SIZE, requant_pad=None):
    """The fused AdamW step — :func:`fused_adam_update` plus the
    decoupled decay (``adamw_math``; Pallas kind "adamw" keeps the whole
    chain in one VMEM pass).  Same return contract as the Adam form."""
    return fused_adam_update(
        p, grad, m1, m2, lr, b1p, b2p, beta1=beta1, beta2=beta2,
        epsilon=epsilon, block_size=block_size, requant_pad=requant_pad,
        _wd_coeff=float(coeff))


def fused_lamb_update(p, grad, m1, m2, lr, b1p, b2p, *, beta1=0.9,
                      beta2=0.999, epsilon=1e-6, weight_decay=0.01,
                      block_size=DEFAULT_BLOCK_SIZE, requant_pad=None):
    """The fused LAMB step — same contract as :func:`fused_adam_update`
    (wire-format bucket slice OR fp32 gradient; optional requant leg).

    LAMB intentionally rides the XLA path only — no Pallas kind: the
    trust ratio needs GLOBAL ``|p|``/``|r|`` norms over the whole
    parameter, a cross-tile reduction the one-pass blockwise VMEM
    kernel cannot produce (it would need a second pass over every tile
    after the norms close, forfeiting the stay-in-VMEM point).  XLA
    still fuses the dequant into the update chain, so the fp32 gradient
    slice never persists as its own HBM buffer."""
    shape, bs = jnp.shape(p), int(block_size)
    g = _grad_value(grad, bs, shape)
    p_new32, m1n, m2n, b1pn, b2pn = lamb_math(
        p, g, m1, m2, lr, b1p, b2p, beta1, beta2, epsilon, weight_decay)
    if requant_pad is not None:
        q_hi, q_lo, q_sc = quantize_for_gather(p_new32, bs,
                                               pad_multiple=requant_pad)
        return (p_new32.astype(p.dtype), m1n, m2n, b1pn, b2pn,
                q_hi, q_lo, q_sc)
    return p_new32.astype(p.dtype), m1n, m2n, b1pn, b2pn


def fused_sgd_update(p, grad, lr, *, block_size=DEFAULT_BLOCK_SIZE,
                     requant_pad=None):
    """The fused SGD step — same contract as :func:`fused_adam_update`
    minus the moments.  Returns ``p_new`` or
    ``(p_new, q_hi, q_lo, q_sc)``."""
    shape, bs = jnp.shape(p), int(block_size)
    if _pallas_able(grad, requant_pad, bs):
        numel = int(np.prod(shape, dtype=np.int64)) if shape else 1
        numel_padded = numel + (-numel) % bs
        hi2, lo2, sc2, rows = _pallas_grad_blocks(grad, bs, numel_padded)
        p2 = _as_blocks(p, rows, bs)
        lr_t = jnp.reshape(lr, ()).astype(jnp.float32)
        out, _, _ = _pallas_fused(
            "sgd", p2, hi2, lo2, sc2, None, None, lr_t, (0, 0, 0),
            requant=requant_pad is not None,
            interpret=impl() == "interpret")

        def unblk(x2, dtype):
            return x2.reshape(-1)[:numel].reshape(shape).astype(dtype)

        if requant_pad is not None:
            q_hi2, q_lo2, q_sc2 = out
            p_new = dequantize_block_scaled(
                q_hi2.reshape(-1), q_lo2.reshape(-1),
                q_sc2.reshape(-1), bs)
            q_hi, q_lo, q_sc = _repad_payload(
                q_hi2, q_lo2, q_sc2, numel, bs, requant_pad)
            return unblk(p_new.reshape(rows, bs), p.dtype), q_hi, q_lo, q_sc
        return unblk(out, p.dtype)
    g = _grad_value(grad, bs, shape)
    p_new32 = sgd_math(p, g, lr)
    if requant_pad is not None:
        q_hi, q_lo, q_sc = quantize_for_gather(p_new32, bs,
                                               pad_multiple=requant_pad)
        return p_new32.astype(p.dtype), q_hi, q_lo, q_sc
    return p_new32.astype(p.dtype)


def fused_momentum_update(p, grad, v, lr, *, mu=0.9, use_nesterov=False,
                          block_size=DEFAULT_BLOCK_SIZE, requant_pad=None):
    """The fused momentum step — same contract as
    :func:`fused_adam_update` with one velocity slot instead of the two
    moments (the mechanical extension the comms-lane ROADMAP item names).
    Returns ``(p_new, v_new)`` or ``(p_new, v_new, q_hi, q_lo, q_sc)``."""
    shape, bs = jnp.shape(p), int(block_size)
    if _pallas_able(grad, requant_pad, bs):
        numel = int(np.prod(shape, dtype=np.int64)) if shape else 1
        numel_padded = numel + (-numel) % bs
        hi2, lo2, sc2, rows = _pallas_grad_blocks(grad, bs, numel_padded)
        p2 = _as_blocks(p, rows, bs)
        v2 = _as_blocks(v, rows, bs)
        lr_t = jnp.reshape(lr, ()).astype(jnp.float32)
        out, vn2, _ = _pallas_fused(
            "momentum", p2, hi2, lo2, sc2, v2, None, lr_t,
            (mu, 1.0 if use_nesterov else 0.0, 0.0),
            requant=requant_pad is not None,
            interpret=impl() == "interpret")

        def unblk(x2, dtype):
            return x2.reshape(-1)[:numel].reshape(shape).astype(dtype)

        v_new = unblk(vn2, v.dtype)
        if requant_pad is not None:
            q_hi2, q_lo2, q_sc2 = out
            p_new = dequantize_block_scaled(
                q_hi2.reshape(-1), q_lo2.reshape(-1),
                q_sc2.reshape(-1), bs)
            q_hi, q_lo, q_sc = _repad_payload(
                q_hi2, q_lo2, q_sc2, numel, bs, requant_pad)
            return (unblk(p_new.reshape(rows, bs), p.dtype), v_new,
                    q_hi, q_lo, q_sc)
        return unblk(out, p.dtype), v_new
    g = _grad_value(grad, bs, shape)
    p_new32, v_new = momentum_math(p, g, v, lr, mu,
                                   use_nesterov=use_nesterov)
    if requant_pad is not None:
        q_hi, q_lo, q_sc = quantize_for_gather(p_new32, bs,
                                               pad_multiple=requant_pad)
        return p_new32.astype(p.dtype), v_new, q_hi, q_lo, q_sc
    return p_new32.astype(p.dtype), v_new


def _repad_payload(q_hi2, q_lo2, q_sc2, numel, block_size, pad_multiple):
    """Trim the Pallas kernel's row-tile padding back to ``numel`` worth
    of blocks and zero-pad to the gather ``pad_multiple`` (blocks past
    ``numel`` quantize the zero padding: hi/lo 0, scale 1)."""
    bs = int(block_size)
    target = int(numel) + (-int(numel)) % int(pad_multiple)
    nb_keep = -(-int(numel) // bs)
    nb_target = target // bs
    hi = q_hi2.reshape(-1)[: nb_keep * bs]
    lo = q_lo2.reshape(-1)[: nb_keep * bs]
    sc = q_sc2.reshape(-1)[:nb_keep]
    extra = nb_target - nb_keep
    if extra > 0:
        hi = jnp.pad(hi, (0, extra * bs))
        lo = jnp.pad(lo, (0, extra * bs))
        sc = jnp.pad(sc, (0, extra), constant_values=1.0)
    return hi, lo, sc
