"""Ops of a grouped-query, gated-attention decoder served through the
decode lane (models/trinity.py), beside ops/mla_ops.py (whose
``weight_matmul``, ``rms_norm`` — over a head's entries when handed
[B, T, H, d] —, ``swiglu`` and ``moe_ffn_held`` it shares):

  rope_half      rotary embedding in the ``rotate_half`` form over the
                 whole head: entry i turns with entry i + d/2; or, with
                 ``rotary_dim``, over the head's first ``rotary_dim``
                 entries alone (the others pass unchanged)
  sigmoid_gate   x * sigmoid(gate), elementwise — the gate on
                 attention's output before its output projection

Both inference-only (grad=None), float32 in and out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.fluid.registry import simple_op


@simple_op("rope_half", ["X", "Pos"], ["Out"], grad=None)
def _rope_half(ctx, x, pos, attrs):
    """x [B, T, H, d], pos [B, T]: (x[i], x[i + d/2]) turn by
    pos * theta^(-2i/d), i < d/2 — ``x cos + rotate_half(x) sin`` with
    the angles repeated over both halves.  With attrs["rotary_dim"] = r
    the first r entries turn so (d = r above) and the rest pass."""
    x = x.astype(jnp.float32)
    d = int(attrs.get("rotary_dim") or x.shape[-1])
    inv = 1.0 / (float(attrs["theta"]) ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None] * inv         # [B, T, d/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:d]
    turned = [a * cos - b * sin, b * cos + a * sin]
    if d < x.shape[-1]:
        turned.append(x[..., d:])
    return jnp.concatenate(turned, axis=-1)


@simple_op("sigmoid_gate", ["X", "Gate"], ["Out"], grad=None)
def _sigmoid_gate(ctx, x, gate, attrs):
    return x.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
