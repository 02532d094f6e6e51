"""Fused bias + GeLU + dropout — the FFN elementwise chain as one op.

Operator Fusion in XLA (arXiv:2301.13062) names the bias+activation+
dropout chain as a pattern XLA's automatic fusion usually gets right
INSIDE one computation but cannot fuse across the op boundaries our
program layer emits (three ops, two HBM-materialized intermediates: the
biased pre-activation and the activation output).  The
``fuse_bias_act_dropout`` program pass (paddle_tpu/passes/) rewrites the
``elementwise_add -> gelu -> [dropout]`` chain to ONE
``fused_bias_act_dropout`` op whose lowering lands here: one jnp chain,
which XLA fuses into a single loop (and into the producing matmul's
epilogue where it can) — the intermediates live in registers.

There is no Pallas form.  The one that existed could not lower the
exact GeLU BERT and GPT use (Mosaic has no ``erf``), and a hand kernel
for a pure elementwise chain only adds a fusion barrier plus an fp32
round-trip XLA does not need; the pass report names the form (``xla``).

The dropout MASK is drawn with ``jax.random.bernoulli`` on the op's
per-op/per-step key and materializes as the op's ``Mask`` output (the
backward op reapplies it, exactly like the standalone dropout op).

Numerics contract: ``gelu(x + bias) [* mask * 1/(1-p)]`` term-for-term
the composed ops' math (``jax.nn.gelu`` with the same ``approximate``
flag, upscale_in_train dropout semantics) — the program pass's 20-step
parity gate runs against the unfused chain.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["KERNEL_FORM", "fused_bias_gelu_dropout"]

# the implementation every site takes, as the pass report and
# chip_smoke.py name it
KERNEL_FORM = "xla"


def _gelu(x, approximate):
    return jax.nn.gelu(x, approximate=bool(approximate))


def fused_bias_gelu_dropout(x, bias, *, dropout_prob=0.0, is_test=False,
                            approximate=False, rng_key=None):
    """The fused forward: ``gelu(x + bias)`` with optional UPSCALED
    dropout (the only semantics the op accepts — the mask-replay
    backward bakes the 1/(1-p) factor in).  ``bias``
    broadcasts on the LAST axis (the fc bias convention).  Returns
    ``(out, mask_uint8)``; the mask is all-ones when dropout is
    off/test-mode (the standalone dropout op's convention), and ``None``
    when ``dropout_prob == 0`` so callers that never declared a Mask
    output pay nothing."""
    shape = jnp.shape(x)
    p = float(dropout_prob)
    scale = 1.0 / max(1.0 - p, 1e-8)
    live = p > 0.0 and not is_test
    mask = None
    if live:
        if rng_key is None:
            raise ValueError("dropout_prob > 0 in train mode needs rng_key")
        mask = jax.random.bernoulli(rng_key, 1.0 - p, shape)

    y = _gelu(x.astype(jnp.float32) + bias.astype(jnp.float32), approximate)
    if live:
        y = y * mask.astype(jnp.float32) * scale
    out = y.astype(x.dtype)
    if p <= 0.0:
        return out, None
    if mask is None:  # test mode: the identity mask the dropout op saves
        mask_u8 = jnp.ones(shape, jnp.uint8)
    else:
        mask_u8 = mask.astype(jnp.uint8)
    return out, mask_u8


def fused_bias_gelu_dropout_grad(x, bias, mask, dy, *, dropout_prob=0.0,
                                 is_test=False, approximate=False):
    """Backward of the fused chain through the SAVED mask (the standalone
    ``dropout_grad``'s contract — forward and backward agree exactly):
    ``d_pre = gelu'(x + bias) · (dy · mask · 1/(1-p))``; ``dX = d_pre``;
    ``dBias = Σ_leading d_pre``.  Returns ``(dx, dbias)``."""
    p = float(dropout_prob)
    pre = x.astype(jnp.float32) + bias.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    if p > 0.0 and not is_test and mask is not None:
        dyf = dyf * mask.astype(jnp.float32) / max(1.0 - p, 1e-8)
    _, vjp = jax.vjp(lambda t: _gelu(t, approximate), pre)
    (dpre,) = vjp(dyf)
    axes = tuple(range(dpre.ndim - 1))
    dbias = jnp.sum(dpre, axis=axes)
    return dpre.astype(x.dtype), dbias.astype(bias.dtype)
