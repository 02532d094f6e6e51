"""Ops of a linear-attention (gated delta rule) layer served through the
decode lane (models/olmo_hybrid.py, models/kimi_linear.py,
models/qwen3_next.py), beside
ops/mla_ops.py (whose
``weight_matmul``, ``rms_norm`` and ``swiglu`` it shares):

  short_conv_chunk   depthwise causal convolution over time, then SiLU,
  short_conv_step    whose last K - 1 pre-activation inputs are carried
                     from call to call in a per-sequence state tensor
                     (serving/lane.py ``SeqState``), read and written by
                     block index
  gdn_inputs         the conv's output and the two gate projections as
                     the rule's operands: L2-normalised q (scaled) and k,
                     v, g = log alpha, beta.  ``g`` is one number a
                     head, [B, T, H], or, where the gate projection is
                     H d_k wide, one a key channel, [B, T, H, d_k].
                     With ``key_heads`` < ``heads`` q and k have that
                     many heads and value head h reads key head
                     h // (heads / key_heads)
  gated_delta_chunk  the gated delta rule over a per-sequence state: a
  gated_delta_step   prefill chunk's form and a decode step's, the state
                     tensor updated in place.  ``g`` [.., H] runs
                     kernels/primitives/gdn.py's kernels, ``g`` [.., H,
                     d_k] kda.py's
  gated_rms_norm     RMSNorm over each head's entries times silu(gate)
                     or sigmoid(gate)

All inference-only (grad=None), float32 in and out.  The state tensors
are persistable vars of the pool (serving/kv_pool.py): ``*Out`` IS the
input (XLA buffer donation), block 0 the trash block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu.fluid.registry import simple_op

from .mla_ops import _f32


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _taps(ext, w, length):
    """out_t = sum_j w[j] ext[t + j]: ext [.., length + K - 1, ch] holds
    the K - 1 inputs before the first position; w [K, ch]."""
    return sum(w[j] * ext[..., j:j + length, :] for j in range(w.shape[0]))


@simple_op("short_conv_chunk",
           ["X", "W", "Tail", "Block", "QStart", "LastIdx"],
           ["Out", "TailOut"], grad=None, inplace={"TailOut": "Tail"})
def _short_conv_chunk(ctx, x, w, tails, block, q_start, last_idx, attrs):
    """x [1, C, ch] one sequence's chunk, w [K, ch]; ``tails`` [blocks,
    (K - 1) * ch] holds, for the sequence's ``block`` [1], the K - 1
    inputs before the chunk (read as zeros where ``q_start`` [1] is 0:
    the sequence's first chunk).  The tail written back is the K - 1
    inputs up to position ``last_idx`` [1], the chunk's last real one."""
    x, w = _f32(x[0]), _f32(w)
    c, ch = x.shape
    km1 = w.shape[0] - 1
    blk = block.reshape(()).astype(jnp.int32)
    tail = jnp.where(q_start.reshape(()) == 0, 0.0,
                     tails[blk].reshape(km1, ch))
    ext = jnp.concatenate([tail, x])                    # [km1 + C, ch]
    out = _silu(_taps(ext, w, c))
    first = last_idx.reshape(()).astype(jnp.int32) + 1  # ext's row of
    new = jax.lax.dynamic_slice(ext, (first, 0), (km1, ch))  # x[last-km1+1]
    return out[None], tails.at[blk].set(new.reshape(-1))


@simple_op("short_conv_step", ["X", "W", "Tail", "Block"],
           ["Out", "TailOut"], grad=None, inplace={"TailOut": "Tail"})
def _short_conv_step(ctx, x, w, tails, blocks, attrs):
    """x [B, 1, ch] one token a slot; ``blocks`` [B] each slot's block
    (inactive slots name the trash block 0)."""
    x, w = _f32(x), _f32(w)
    b, _, ch = x.shape
    km1 = w.shape[0] - 1
    blocks = blocks.astype(jnp.int32)
    ext = jnp.concatenate([tails[blocks].reshape(b, km1, ch), x], axis=1)
    return (_silu(_taps(ext, w, 1)),
            tails.at[blocks].set(ext[:, 1:].reshape(b, -1)))


@simple_op("gdn_inputs", ["QKV", "A", "B", "ALog", "DtBias", "RowValid"],
           ["Q", "K", "V", "G", "Beta"], optional=("RowValid",), grad=None)
def _gdn_inputs(ctx, qkv, a, b, a_log, dt_bias, row_valid, attrs):
    """qkv [B, T, 2 H_k d_k + H d_v] (after the convolution and its SiLU;
    H_k = ``key_heads``, H where the attr is absent), a, b [B, T, H] the
    two gate projections -> q [B, T, H_k, d_k] =
    l2norm(q') / sqrt(d_k), k = l2norm(k'), v [B, T, H, d_v], g = -exp(
    ALog) softplus(a + DtBias), beta = beta_scale sigmoid(b).  l2norm(x) =
    x rsqrt(sum x^2 + eps).  With a [B, T, H d_k] and DtBias [H d_k] (a
    decay a key channel; ALog stays [H]) g is [B, T, H, d_k].  Rows that
    ``row_valid`` [T] marks 0 get beta = 0 and g = 0: the rule leaves
    the state alone there."""
    heads, dk, dv = (int(attrs[k]) for k in ("heads", "key_dim",
                                             "value_dim"))
    hk = int(attrs.get("key_heads", heads))
    eps = float(attrs["epsilon"])
    qkv = _f32(qkv)
    lead = qkv.shape[:2]

    def l2norm(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + eps)

    q = l2norm(qkv[..., :hk * dk].reshape(*lead, hk, dk)) * dk ** -0.5
    k = l2norm(qkv[..., hk * dk:2 * hk * dk].reshape(*lead, hk, dk))
    v = qkv[..., 2 * hk * dk:].reshape(*lead, heads, dv)
    if a.shape[-1] == heads:
        g = -jnp.exp(_f32(a_log)) * jax.nn.softplus(_f32(a) + _f32(dt_bias))
    else:                             # a decay a key channel
        g = -jnp.exp(_f32(a_log))[:, None] * jax.nn.softplus(
            (_f32(a) + _f32(dt_bias)).reshape(*lead, heads, dk))
    beta = float(attrs["beta_scale"]) * jax.nn.sigmoid(_f32(b))
    if row_valid is not None:
        live = (row_valid.reshape(1, -1, 1) > 0).astype(jnp.float32)
        g = g * (live if g.ndim == 3 else live[..., None])
        beta = beta * live
    return q, k, v, g, beta


@simple_op("gated_delta_chunk",
           ["Q", "K", "V", "G", "Beta", "State", "Block", "QStart"],
           ["Out", "StateOut"], grad=None, inplace={"StateOut": "State"})
def _gated_delta_chunk(ctx, q, k, v, g, beta, state, block, q_start, attrs):
    """One sequence's chunk [1, C, H, .] (q and k [1, C, H_k, d_k]); its
    state block ``block`` [1] is read as zeros where ``q_start`` [1] is
    0.  g [1, C, H], or [1, C, H, d_k] for a decay a key channel."""
    from paddle_tpu.kernels import primitives as _prims

    rule = _prims.gated_delta_chunk if g.ndim == 3 else _prims.kda_chunk
    out, state = rule(
        q[0], k[0], v[0], g[0], beta[0], state,
        block.reshape(()).astype(jnp.int32), q_start.reshape(()) == 0,
        force=attrs.get("force"))
    return out[None], state


@simple_op("gated_delta_step",
           ["Q", "K", "V", "G", "Beta", "State", "Block"],
           ["Out", "StateOut"], grad=None, inplace={"StateOut": "State"})
def _gated_delta_step(ctx, q, k, v, g, beta, state, blocks, attrs):
    """One token a slot [B, 1, H, .] (q and k [B, 1, H_k, d_k]);
    ``blocks`` [B].  g [B, 1, H], or [B, 1, H, d_k] for a decay a key
    channel."""
    from paddle_tpu.kernels import primitives as _prims

    rule = _prims.gated_delta_step if g.ndim == 3 else _prims.kda_step
    out, state = rule(
        q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state, blocks,
        force=attrs.get("force"))
    return out[:, None], state


@simple_op("gated_rms_norm", ["X", "Gate", "Scale"], ["Out"], grad=None)
def _gated_rms_norm(ctx, x, gate, scale, attrs):
    """x [B, T, H, d] -> [B, T, H d]: RMSNorm over each head's d entries
    with one gain [d], times ``activation``(gate [B, T, H d]): ``silu``
    (the default) or ``sigmoid``."""
    act = {"silu": _silu, "sigmoid": jax.nn.sigmoid}[
        attrs.get("activation", "silu")]
    x = _f32(x)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + float(attrs["epsilon"])) * _f32(scale)
    return y.reshape(gate.shape) * act(_f32(gate))
