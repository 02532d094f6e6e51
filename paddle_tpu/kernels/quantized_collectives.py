"""Quantized gradient all-reduce — EQuARX-style block-scaled int8 collectives.

Reference analog: the reference's SparseAllReduceOpHandle (DGC) and
fuse_all_reduce_op_pass shrink/fuse gradient traffic on NCCL rings.
TPU-native redesign following EQuARX (arXiv:2506.17615): the all-reduce is
decomposed into its scatter and gather phases and the payload crossing ICI
is block-scaled int8 instead of fp32.

Pipeline (under shard_map over the dp axis, n devices):

  1. flatten + pad the tensor to a multiple of ``n * block_size`` and view
     it as n equal shards (blocks never straddle a shard boundary);
  2. quantize each shard block-scaled (int8 payload + one fp32 scale per
     ``block_size`` elements);
  3. scatter phase: ``lax.all_to_all`` moves shard i of every device's
     quantized payload to device i — int8 on the wire (this is
     ``lax.psum_scatter`` with the reduction peeled off, which is what
     makes a quantized wire format possible: int8 blocks with
     heterogeneous per-device scales cannot be summed by the fabric);
  4. dequant-reduce: dequantize the n received shards and sum in fp32;
  5. requant: block-quantize the reduced shard;
  6. gather phase: ``lax.all_gather`` the quantized reduced shard — int8
     on the wire again — then dequantize, unpad, and restore shape/dtype.

Precision: the default wire format is DUAL int8 — a hi int8 plus a second
int8 carrying the quantization residual at 1/254 of the block scale
(together an int16-grade representation at half the bytes of fp32).  Worst
case per-element error is ``block_max / 64516`` per quantization, so a
4-device sum of N(0,1) gradients lands well under 1e-2 max abs error.
``dual_int8=False`` selects the aggressive single-int8 format (quarter
bytes, EQuARX's headline mode) for workloads that tolerate ~1e-1 error on
the summed gradient.

The backward rule is the straight-through estimator: the cotangent takes
the exact fp32 ``lax.psum`` path (quantization is forward-only noise), so
``c_allreduce_quant`` differentiates exactly like ``c_allreduce_sum``.

This module is the ONE-SHOT form: two O(1)-launch phase boundaries, full
payload on the wire at each.  Its phase-2 sibling —
``kernels.ring_collectives`` — requantizes inside the hops of an explicit
``lax.ppermute`` ring so EVERY hop moves int8 at 2*(n-1)/n of the
payload bytes; ``ring_collectives.select_allreduce_algo`` picks between
the two per tensor size, and :func:`wire_bytes` models both.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "quantize_block_scaled",
    "dequantize_block_scaled",
    "quantized_all_reduce",
    "wire_bytes",
    "gather_wire_bytes",
    "quant_padded_elems",
    "DEFAULT_BLOCK_SIZE",
]

DEFAULT_BLOCK_SIZE = 256


def quant_padded_elems(n_elements, n_devices, block_size=DEFAULT_BLOCK_SIZE,
                       algo="oneshot"):
    """Padded element count of one quantized all-reduce payload — the
    static shape of the kept wire-format image
    (``adaptive_quantized_all_reduce_keep``): oneshot/ring pad to a
    multiple of ``n_devices * block_size`` (blocks never straddle a shard
    boundary), the bidirectional ring to ``2 * n_devices * block_size``
    (each half-ring pads independently).  The DP transpiler sizes the
    fused-update q-vars with this, so the declared shapes match the
    lowering exactly."""
    n, d, bs = int(n_elements), max(1, int(n_devices)), int(block_size)
    mult = (2 * d * bs) if algo == "ring_bidir" else (d * bs)
    if d <= 1:
        mult = bs  # dp=1 keep-quant fallback pads to one block
    return n + (-n) % mult


def wire_bytes(n_elements, block_size=DEFAULT_BLOCK_SIZE, dual_int8=True,
               n_devices=2, algo="oneshot"):
    """Per-device ICI payload of one quantized all-reduce of
    ``n_elements`` fp values — the standing collective-bytes metric
    (pure python; used by the data-parallel transpiler to report
    ``pt_collective_payload_bytes_total`` and every algorithm's modeled
    bytes).

    ``algo="oneshot"``: both phase boundaries (scatter all_to_all, gather
    all_gather) move the full padded tensor once — int8 hi (+ int8
    residual when dual) plus one fp32 scale per ``block_size`` block.

    ``algo="ring"`` (kernels.ring_collectives): each phase ships n-1
    one-hop chunks of 1/n of the payload, so per-device bytes are
    ``2*(n-1)/n`` of one quantized payload image — the large-tensor win
    the size-adaptive selector exploits.

    ``algo="ring_bidir"``: the bidir term — the payload pads to a
    multiple of ``2*d*block_size`` and splits into two half-images that
    ride opposite ring directions; per-device bytes are the SAME
    ``2*(d-1)/d`` fraction (summed over both halves, modulo the larger
    padding) — the bidirectional win is concurrent use of both ICI link
    directions (~2x bisection bandwidth), not fewer bytes.  BOTH of the
    selector's demotions are mirrored (d<=2 and sub-block payloads fall
    back to the unidirectional formula — the same arithmetic as
    ``ring_collectives.bidir_eligible``), so modeling a pinned
    "ring_bidir" can never book bytes for a form that would not lower.

    n_devices=1 is the exact fallback — nothing crosses the wire.
    """
    n = int(n_elements)
    d = int(n_devices)
    bs = int(block_size)
    if n <= 0 or d <= 1:
        return 0
    per_elem = 2 if dual_int8 else 1

    def payload_of(elems):
        return elems * per_elem + (elems // bs) * 4

    # bidir_eligible's arithmetic, inlined (importing ring_collectives
    # here would be circular): >2 devices AND at least one block per
    # direction per device
    if algo == "ring_bidir" and (d <= 2 or n < 2 * d * bs):
        algo = "ring"
    if algo == "ring_bidir":
        half = quant_padded_elems(n, d, bs, algo="ring_bidir") // 2
        # per direction: 2 phases x (d-1) hops of a 1/d chunk of the half
        return 2 * (2 * (d - 1) * (payload_of(half) // d))
    padded = n + (-n) % (d * bs)
    payload = payload_of(padded)
    if algo == "oneshot":
        return 2 * payload
    if algo == "ring":
        # padded is a multiple of d*block_size, so payload divides evenly
        # into d per-hop chunks; 2 phases x (d-1) hops each
        return 2 * (d - 1) * (payload // d)
    raise ValueError(f"wire_bytes: unknown algo {algo!r} "
                     f"(expected 'oneshot', 'ring' or 'ring_bidir')")


def gather_wire_bytes(n_elements, block_size=DEFAULT_BLOCK_SIZE,
                      dual_int8=True, n_devices=2):
    """Per-device ICI payload of one quantized all-gather where each
    device contributes a shard of ``n_elements`` fp values (the ZeRO-1
    weight-update gather of ``ring_collectives.quantized_all_gather``):
    every device receives n-1 foreign quantized shard images — int8 hi
    (+ lo when dual) plus one fp32 scale per block, shard padded to a
    block multiple."""
    n = int(n_elements)
    d = int(n_devices)
    if n <= 0 or d <= 1:
        return 0
    padded = n + (-n) % int(block_size)
    per_elem = 2 if dual_int8 else 1
    n_blocks = padded // int(block_size)
    return (d - 1) * (padded * per_elem + n_blocks * 4)


# int8 symmetric range: +-127 (never -128, keeping the scale symmetric —
# the convention of every block-scaled training format)
_QMAX = 127.0
# the residual is bounded by scale/2, so its own scale is scale/(2*127)
_RESID_DIV = 2.0 * _QMAX


def quantize_block_scaled(x, block_size=DEFAULT_BLOCK_SIZE, dual_int8=True):
    """Block-scaled symmetric int8 quantization of a flat fp array.

    ``x.size`` must be a multiple of ``block_size`` (callers pad).
    Returns ``(q_hi, q_lo, scales)`` where ``q_hi``/``q_lo`` are int8 of
    x's shape and ``scales`` holds one fp32 scale per block.  ``q_lo``
    carries the quantization residual at ``scales / 254`` resolution
    (``None`` when ``dual_int8=False``).
    """
    xf = jnp.reshape(x.astype(jnp.float32), (-1, block_size))
    amax = jnp.max(jnp.abs(xf), axis=1, keepdims=True)
    # all-zero block: a tiny positive scale quantizes it to exact zeros
    # (0/0 guard).  jnp.maximum — NOT a `where(amax > 0)` — so a
    # NaN/Inf block PROPAGATES into its fp32 scale and rides the wire:
    # `NaN > 0` is False, and the old where() silently laundered a NaN
    # gradient block into finite garbage at scale 1.0, which is exactly
    # the poisoned-collective class the health sentinel's QScale check
    # (docs/DISTRIBUTED.md §6) exists to catch.
    scale = jnp.maximum(amax / _QMAX, jnp.float32(1e-30))
    q_hi = jnp.clip(jnp.round(xf / scale), -_QMAX, _QMAX)
    if not dual_int8:
        return (q_hi.astype(jnp.int8).reshape(x.shape), None,
                scale[:, 0])
    resid = xf - q_hi * scale
    q_lo = jnp.clip(jnp.round(resid * (_RESID_DIV / scale)), -_QMAX, _QMAX)
    return (q_hi.astype(jnp.int8).reshape(x.shape),
            q_lo.astype(jnp.int8).reshape(x.shape), scale[:, 0])


def dequantize_block_scaled(q_hi, q_lo, scales, block_size=DEFAULT_BLOCK_SIZE):
    """Inverse of :func:`quantize_block_scaled` (fp32, flat-block view)."""
    hi = jnp.reshape(q_hi.astype(jnp.float32), (-1, block_size))
    s = scales.reshape(-1, 1)
    out = hi * s
    if q_lo is not None:
        lo = jnp.reshape(q_lo.astype(jnp.float32), (-1, block_size))
        out = out + lo * (s / _RESID_DIV)
    return out.reshape(q_hi.shape)


def _quantized_all_reduce_impl(x, axis_name, block_size, dual_int8,
                               keep_quant=False):
    n = lax.psum(1, axis_name)  # static axis size under shard_map
    if n == 1:
        # dp=1 fallback: the sum over one device is the identity — stay
        # EXACT (and skip the quantize/collective machinery entirely).
        # keep_quant callers route through ring_collectives'
        # _local_keep_quant before reaching here.
        return x
    orig_shape, orig_dtype = jnp.shape(x), x.dtype
    flat = jnp.ravel(x).astype(jnp.float32)
    size = flat.size
    pad = (-size) % (n * block_size)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    per_shard = flat.size // n
    shards = flat.reshape(n, per_shard)

    # (2) quantize per shard — blocks are within-row so all_to_all keeps
    # each block with its own scale
    q_hi, q_lo, scales = quantize_block_scaled(
        shards, block_size, dual_int8=dual_int8)
    scales = scales.reshape(n, per_shard // block_size)

    # (3) scatter phase: int8 (+ per-block fp32 scales) on the wire.
    # Row i of each operand goes to device i; afterwards row j holds what
    # device j contributed to OUR shard.
    a2a = partial(lax.all_to_all, axis_name=axis_name, split_axis=0,
                  concat_axis=0, tiled=False)
    q_hi = a2a(q_hi)
    q_lo = a2a(q_lo) if dual_int8 else None
    scales = a2a(scales)

    # (4) dequant-reduce: fp32 accumulation of the n contributions
    parts = dequantize_block_scaled(q_hi, q_lo, scales, block_size)
    reduced = jnp.sum(parts, axis=0)  # [per_shard]

    # (5) requant the reduced shard, (6) gather phase: int8 on the wire
    r_hi, r_lo, r_scales = quantize_block_scaled(
        reduced, block_size, dual_int8=dual_int8)
    g_hi = lax.all_gather(r_hi, axis_name)
    g_lo = lax.all_gather(r_lo, axis_name) if dual_int8 else None
    g_scales = lax.all_gather(r_scales, axis_name)

    if keep_quant:
        # fused-update consumers take the assembled wire-format image
        # (flat, padded to n*block_size) — no final dequantization
        return (g_hi.reshape(-1),
                g_lo.reshape(-1) if dual_int8 else None,
                g_scales.reshape(-1))
    out = dequantize_block_scaled(g_hi, g_lo, g_scales.reshape(-1),
                                  block_size)
    out = out.reshape(-1)
    if pad:
        out = out[:size]
    return out.reshape(orig_shape).astype(orig_dtype)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def quantized_all_reduce(x, axis_name, block_size=DEFAULT_BLOCK_SIZE,
                         dual_int8=True):
    """Block-scaled int8 all-reduce-sum of ``x`` over mesh axis
    ``axis_name``.  Must be called under shard_map; exact identity when
    the axis has a single device."""
    return _quantized_all_reduce_impl(x, axis_name, block_size, dual_int8)


def _qar_fwd(x, axis_name, block_size, dual_int8):
    return _quantized_all_reduce_impl(x, axis_name, block_size,
                                      dual_int8), None


def _qar_bwd(axis_name, block_size, dual_int8, _res, g):
    # straight-through: the gradient of sum_i x_i w.r.t. each x_i is the
    # identity, and under the global-loss convention the cotangent is
    # psum'd across devices — exactly c_allreduce_sum's derived grad
    # (tests/test_collective_grads.py pins that convention).  Quantization
    # noise is forward-only.
    return (lax.psum(g, axis_name),)


quantized_all_reduce.defvjp(_qar_fwd, _qar_bwd)
