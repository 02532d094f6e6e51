"""int8 storage quantization for inference — weights and KV cache.

Rides the EXISTING dual-int8 block-scale machinery
(kernels/quantized_collectives.py: hi int8 + residual lo int8 at
scale/254 resolution, one fp32 scale per block, symmetric ±127) and
applies it to STORAGE instead of the collective wire:

- **KV cache** — :func:`quantize_lastdim` treats each ``head_dim``
  vector as one block (scale per (page, slot, head)); the write ops
  flatten the heads of what it returns into the pool's lane dimension,
  so the pool vars are hi/lo int8 ``[P, pgs, n*d]`` + scale fp32
  ``[P, pgs, n]`` and the paged kernel dequantizes per-block in VMEM
  (primitives/paged.py paged_attention_quant).  Quantization happens
  ONCE at KV append (ops/decode_ops.py kv_cache_write_quant).
- **Weights** — :func:`quantize_weight` keeps the flat
  ``DEFAULT_BLOCK_SIZE`` block layout of the collectives wire format;
  quantization happens once at model load
  (passes/int8_weights.py).

Distinct from the int8 COMPUTE path (fluid/contrib/ptq — real int8
MXU contraction after calibration): here the matmul still runs fp32/bf16, int8 only halves
the BYTES AT REST.  fp32→dual-int8 is 4n → 2n + 4n/block bytes, i.e.
~2× for block ≥ 32; the realized saving books on
``pt_int8_bytes_saved_total{kind}``.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..quantized_collectives import (
    _QMAX, _RESID_DIV, DEFAULT_BLOCK_SIZE, dequantize_block_scaled,
    quantize_block_scaled,
)

QMAX = _QMAX
RESID_DIV = _RESID_DIV

__all__ = ["QMAX", "RESID_DIV", "quantize_lastdim", "dequantize_lastdim",
           "quantize_weight", "dequantize_weight", "dual_int8_bytes",
           "bytes_saved", "book_bytes_saved"]


def quantize_lastdim(x):
    """Dual-int8 quantization with one block PER LAST-AXIS VECTOR
    (block_size = x.shape[-1]): returns ``(hi, lo, scale)`` with
    hi/lo int8 of x's shape and scale fp32 ``x.shape[:-1] + (1,)``.
    The KV-cache layout — every (token, head) head_dim vector carries
    its own scale, so one outlier head cannot flatten its neighbors'
    resolution."""
    d = int(x.shape[-1])
    hi, lo, scales = quantize_block_scaled(
        jnp.reshape(x, (-1, d)), block_size=d)
    shape = tuple(x.shape)
    return (hi.reshape(shape), lo.reshape(shape),
            scales.reshape(shape[:-1] + (1,)).astype(jnp.float32))


def dequantize_lastdim(hi, lo, scale):
    """Inverse of :func:`quantize_lastdim` (fp32)."""
    return ((hi.astype(jnp.float32)
             + lo.astype(jnp.float32) * (1.0 / RESID_DIV))
            * scale.astype(jnp.float32))


def quantize_weight(w, block_size=DEFAULT_BLOCK_SIZE):
    """Flat block-scale dual-int8 of a weight array (any shape): returns
    ``(hi, lo, scales, pad)`` where hi/lo are int8 ``[padded_numel]``,
    scales fp32 ``[padded_numel / block_size]`` and ``pad`` is the
    zero-padding appended to reach a block multiple.  The collectives
    wire format, applied at rest (docs/KERNELS.md "int8 weights")."""
    flat = jnp.ravel(w).astype(jnp.float32)
    pad = (-flat.size) % block_size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    hi, lo, scales = quantize_block_scaled(flat, block_size=block_size)
    return hi, lo, scales, int(pad)


def dequantize_weight(hi, lo, scales, shape, block_size=DEFAULT_BLOCK_SIZE):
    """Inverse of :func:`quantize_weight` back to fp32 ``shape``."""
    flat = dequantize_block_scaled(hi, lo, scales, block_size=block_size)
    n = int(np.prod(shape))
    return flat[:n].reshape(shape)


def dual_int8_bytes(n_elements, block_size):
    """Bytes at rest for ``n_elements`` in the dual-int8 format: 2 per
    element (hi + lo) + 4 per block (the fp32 scale)."""
    n = int(n_elements)
    blocks = -(-n // int(block_size))
    return 2 * n + 4 * blocks


def bytes_saved(n_elements, block_size, fp_bytes=4):
    """Modeled HBM saving of storing ``n_elements`` dual-int8 instead of
    ``fp_bytes``-wide floats (≥ 0; the counter's unit of account)."""
    return max(0, int(n_elements) * int(fp_bytes)
               - dual_int8_bytes(n_elements, block_size))


def book_bytes_saved(kind, n_bytes):
    """Book a realized storage saving on
    ``pt_int8_bytes_saved_total{kind}`` (kind: "kv_cache" |
    "weights")."""
    from paddle_tpu.observability import metrics as obs

    obs.counter(
        "pt_int8_bytes_saved_total",
        "Modeled HBM bytes saved by int8 storage quantization vs the "
        "fp32 layout it replaced (dual-int8: 2 bytes/elem + 4/block "
        "scale), booked once per quantized artifact",
        labels=("kind",),
    ).labels(kind=kind).inc(float(n_bytes))
