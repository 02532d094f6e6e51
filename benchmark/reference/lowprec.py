"""The control's arithmetic: a matmul in the nearest precision below the
one a configuration states.  Used by the control test and the control
script only; no benchmark run computes with it.

``fp8_matmul``: both operands rounded to float8 e4m3 with one scale per
tensor (the absolute maximum mapped to the format's largest value), the
product accumulated in float32 — what an fp8 matmul unit with per-tensor
scaling computes.  The rounding is straight-through for gradients, so the
backward matmuls see the rounded operands and float32 incoming gradients.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0


def fake_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def fp8_matmul(a, b):
    return jnp.matmul(fake_fp8(a), fake_fp8(b),
                      precision=jax.lax.Precision.HIGHEST)


def bf16_matmul(a, b):
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
