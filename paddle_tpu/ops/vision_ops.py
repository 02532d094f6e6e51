"""Ops of a native-resolution vision tower that feeds image rows into a
decoder's prefill chunks (models/kimi_vl.py): what the older zoo had no
op for.

  bicubic_resize_table    a learned [h0, w0, D] position table resized to
                          an image's patch grid (run once a shape, when
                          the encoder for that shape is built)
  rope_2d_interleaved     rotary embedding by a patch's (row, column)
  vit_attention           bidirectional attention inside one image
                          (kernels/primitives/vit.py)
  select_embedding_rows   a prefill chunk's input rows: the token's
                          embedding, or, where ``Idx`` >= 0, that row of
                          the staged image rows

All inference-only (grad=None); results are float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.fluid.registry import simple_op

# Keys' cubic convolution kernel with a = -0.75 (what
# torch.nn.functional.interpolate(mode="bicubic") uses), half-pixel
# centres (align_corners=False), taps past an edge read the edge
CUBIC_A = -0.75


def bicubic_matrix(n_in, n_out):
    """[n_out, n_in] float32: row i holds the four tap weights of output
    position i (taps clamped to the table's edge add up there)."""
    a = CUBIC_A
    out = np.zeros((n_out, n_in), np.float64)
    for i in range(n_out):
        src = (i + 0.5) * n_in / n_out - 0.5
        x0 = int(np.floor(src))
        t = src - x0
        taps = (
            ((a * (t + 1) - 5 * a) * (t + 1) + 8 * a) * (t + 1) - 4 * a,
            ((a + 2) * t - (a + 3)) * t * t + 1,
            ((a + 2) * (1 - t) - (a + 3)) * (1 - t) * (1 - t) + 1,
            ((a * (2 - t) - 5 * a) * (2 - t) + 8 * a) * (2 - t) - 4 * a)
        for k, w in enumerate(taps):
            out[i, min(max(x0 - 1 + k, 0), n_in - 1)] += w
    return out.astype(np.float32)


@simple_op("bicubic_resize_table", ["X"], ["Out"], grad=None)
def _bicubic_resize_table(ctx, x, attrs):
    """x [h0, w0, D] -> [out_h * out_w, D] float32, rows in row-major
    order of the (out_h, out_w) grid."""
    gh, gw = int(attrs["out_h"]), int(attrs["out_w"])
    out = jnp.einsum("ih,hwd,jw->ijd", bicubic_matrix(x.shape[0], gh),
                     x.astype(jnp.float32), bicubic_matrix(x.shape[1], gw),
                     precision=jax.lax.Precision.HIGHEST)
    return out.reshape(gh * gw, x.shape[2])


def rope_2d_angles(grid_h, grid_w, head_dim, theta):
    """[grid_h * grid_w, head_dim / 2] float32: the angle of every
    interleaved pair of a head at every patch (row-major).  Pair 2k
    turns by column x theta^(-4k / head_dim), pair 2k + 1 by row x the
    same frequency."""
    freqs = 1.0 / (float(theta) ** (
        np.arange(0, head_dim, 4, dtype=np.float64) / head_dim))
    rows, cols = np.divmod(np.arange(grid_h * grid_w), grid_w)
    ang = np.stack([cols[:, None] * freqs, rows[:, None] * freqs], axis=-1)
    return ang.reshape(grid_h * grid_w, -1).astype(np.float32)


@simple_op("rope_2d_interleaved", ["X"], ["Out"], grad=None)
def _rope_2d_interleaved(ctx, x, attrs):
    """x [N, H, d], N = grid_h * grid_w patches in row-major order."""
    n, _, d = x.shape
    ang = rope_2d_angles(int(attrs["grid_h"]), int(attrs["grid_w"]), d,
                         attrs["theta"])
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    pairs = x.astype(jnp.float32).reshape(n, x.shape[1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


@simple_op("vit_attention", ["Q", "K", "V"], ["Out"], grad=None)
def _vit_attention(ctx, q, k, v, attrs):
    """q, k, v [N, H, d] -> [N, H, d] float32; every patch of the image
    attends every patch.  Heads narrower than a lane tile are padded
    with zeros to one (the scale stays the model's)."""
    from paddle_tpu.kernels import primitives as _prims

    d = q.shape[-1]
    dtype = jnp.dtype(attrs.get("dtype", "float32"))
    pad = -d % 128 if _prims.is_tpu_platform() else 0

    def heads_first(x):
        x = jnp.swapaxes(x.astype(dtype), 0, 1)
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad))) if pad else x

    out = _prims.vit_attention(heads_first(q), heads_first(k),
                               heads_first(v), sm_scale=attrs["sm_scale"],
                               force=attrs.get("force"))
    return jnp.swapaxes(out[..., :d], 0, 1)


@simple_op("select_embedding_rows", ["Emb", "Rows", "Idx"], ["Out"],
           grad=None)
def _select_embedding_rows(ctx, emb, rows, idx, attrs):
    """emb [B, T, D]; rows [R, 1, D] (the staged image rows); idx [B, T]
    int32: -1 keeps the token's embedding, r >= 0 takes rows[r]."""
    idx = idx.astype(jnp.int32)
    picked = rows.reshape(rows.shape[0], -1)[jnp.maximum(idx, 0)]
    return jnp.where((idx >= 0)[..., None], picked.astype(jnp.float32),
                     emb.astype(jnp.float32))
