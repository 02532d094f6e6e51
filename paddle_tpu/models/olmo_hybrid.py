"""Olmo-Hybrid-style decoder LM (``model_type: olmo_hybrid``), Fluid
graph-building style: gated-delta-rule linear-attention layers beside
full-attention layers (3 : 1 as published), a SwiGLU in every block.

  x0 = E[tok]
  linear_attention block (H heads, d_k, d_v; ops/gdn_ops.py)
           u = RMS_in(x)
           [q~ | k~ | v~] = u [W_q | W_k | W_v]            (H d_k, H d_k, H d_v)
           each channel through a depthwise causal convolution over
           time of ``linear_conv_kernel_dim`` taps, then SiLU: q', k', v'
           q = l2norm(q') / sqrt(d_k), k = l2norm(k'), v = v'
           beta = 2 sigmoid(u W_b)   (``linear_allow_neg_eigval``; else 1 x)
           g = -exp(A_log) softplus(u W_a + dt_bias), alpha = exp(g)
           S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T,
           o_t = S_t^T q_t, S_0 = 0                         (per head)
           a = x + [RMS_head(o; gain d_v) * silu(u W_g)] W_o
  full_attention block (H heads of ``head_dim``, no bias)
           q = RMS_q(x W_q), k = RMS_k(x W_k) over the WHOLE projection,
           v = x W_v; RoPE (``rotate_half`` form, theta) on q and k;
           causal softmax(q k^T / sqrt(d)) v; a = x + RMS_post_attn(o W_o)
  both     y = a + RMS_post_ff(W_down(silu(a W_gate) * a W_up))
  head     final RMSNorm, untied lm_head.

A linear-attention layer here has as many key heads as value heads; the
kernels take fewer (value head h on key head h // r), which is
models/qwen3_next.py's layer.  Where each norm stands is no key of the
published config: the benchmark's configuration file lists every such
choice under ``assumed`` and the plain reference
(benchmark/reference/olmo_hybrid.py) is written from the same entries.

What a layer leaves behind (serving/lane.py): a full-attention layer a K
and a V row a TOKEN (the pool's page kind ``full``; the lane numbers its
cache layers 0 .. n_full - 1); a linear-attention layer two tensors a
SEQUENCE owns (the kind ``state``, one block a sequence): the rule's
state ``s`` [d_k, H d_v] float32 and the convolution's last K - 1
pre-activation inputs ``conv`` [(K - 1) (2 H d_k + H d_v)] float32.

``OlmoHybridConfig.decode_lane()`` hands ``_decoder`` and the head to
serving/lane.py, which builds the decode lane's two executables around
them, and ``build_olmo_hybrid_lm`` a whole sequence on the same
parameter names, caches and state program-local.  Matrices are stored in
``cfg.dtype`` (bfloat16 in the serving lane) and multiplied in it with
float32 accumulation; norm gains, the convolution's taps, ``A_log``,
``dt_bias``, the state and activations between ops are float32; K/V
rows are ``cfg.dtype``.
"""

from __future__ import annotations

import functools

from paddle_tpu.fluid import layers
from paddle_tpu.fluid.initializer import Constant, Normal
from paddle_tpu.fluid.param_attr import ParamAttr

from .decode_blocks import _attr, _linear, _next_token, _rms, _swiglu_ffn

LINEAR, FULL = "linear_attention", "full_attention"


class OlmoHybridConfig:
    def __init__(self, vocab_size=100352, hidden_size=3840,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=30, num_key_value_heads=30,
                 head_dim=None, layer_types=None, linear_num_key_heads=30,
                 linear_num_value_heads=30, linear_key_head_dim=96,
                 linear_value_head_dim=192, linear_conv_kernel_dim=4,
                 linear_allow_neg_eigval=True, rms_norm_eps=1e-6,
                 rope_theta=500000.0, max_position_embeddings=65536,
                 l2norm_eps=1e-6, dtype="bfloat16", prefill_chunk=None,
                 initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.head_dim = head_dim or hidden_size // num_attention_heads
        if layer_types is None:  # every fourth layer full
            layer_types = [FULL if (i + 1) % 4 == 0 else LINEAR
                           for i in range(num_hidden_layers)]
        self.layer_types = list(layer_types)
        self.linear_heads = linear_num_value_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.linear_allow_neg_eigval = linear_allow_neg_eigval
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.max_position_embeddings = max_position_embeddings
        self.l2norm_eps = l2norm_eps
        self.dtype = dtype
        self.prefill_chunk = prefill_chunk
        self.initializer_range = initializer_range
        if num_key_value_heads != num_attention_heads:
            raise ValueError("OlmoHybridConfig: plain multi-head attention "
                             "(num_key_value_heads = num_attention_heads)")
        if linear_num_key_heads != linear_num_value_heads:
            raise ValueError(
                "OlmoHybridConfig: as many key heads as value heads in a "
                "linear-attention layer (the delta-rule layer with value "
                "heads in groups a key head is models/qwen3_next.py's)")
        if (len(self.layer_types) != num_hidden_layers
                or set(self.layer_types) - {LINEAR, FULL}):
            raise ValueError(
                f"OlmoHybridConfig: layer_types names "
                f"{len(self.layer_types)} layers of {num_hidden_layers}, "
                f"each {LINEAR!r} or {FULL!r}")

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=96, hidden_size=48, intermediate_size=64,
                 num_hidden_layers=4, num_attention_heads=3,
                 num_key_value_heads=3, head_dim=16,
                 layer_types=[LINEAR, LINEAR, FULL, LINEAR],
                 linear_num_key_heads=3, linear_num_value_heads=3,
                 linear_key_head_dim=8, linear_value_head_dim=16,
                 max_position_embeddings=128, dtype="float32")
        d.update(kw)
        return cls(**d)

    @property
    def full_layers(self):
        return [i for i, t in enumerate(self.layer_types) if t == FULL]

    @property
    def linear_layers(self):
        return [i for i, t in enumerate(self.layer_types) if t == LINEAR]

    @property
    def conv_channels(self):
        return self.linear_heads * (2 * self.linear_key_head_dim
                                    + self.linear_value_head_dim)

    def cache_rows(self, pool_dtype=None):
        """What a token leaves in each full-attention layer: a K and a V
        row, the heads side by side."""
        from paddle_tpu.serving import lane

        dtype = pool_dtype or self.dtype
        if dtype == "int8":
            raise ValueError(
                "models/olmo_hybrid.py: no int8 form of this K/V pool (the "
                "dual-int8 pool is models/gpt.py's)")
        return lane.kv_rows(self.num_attention_heads, self.head_dim, dtype)

    def seq_state(self):
        """What a sequence owns in each linear-attention layer
        (serving/lane.py ``SeqState``): the rule's state, d_k rows of the
        heads' d_v columns side by side (kernels/primitives/gdn.py), and
        the convolution's last K - 1 inputs, both float32."""
        from paddle_tpu.serving import lane

        return [
            lane.SeqState("s", (self.linear_key_head_dim,
                                self.linear_heads
                                * self.linear_value_head_dim), "float32"),
            lane.SeqState("conv", ((self.linear_conv_kernel_dim - 1)
                                   * self.conv_channels,), "float32")]

    def decode_lane(self):
        """This model's decode-lane declaration (serving/lane.py)."""
        from paddle_tpu.serving import lane

        return lane.scaffold(
            functools.partial(_decoder, cfg=self),
            functools.partial(_next_token, cfg=self, prefix="olmo"),
            num_layers=len(self.full_layers),
            max_position=self.max_position_embeddings,
            cache_rows=self.cache_rows,
            pool_dtype=self.dtype, prefill_chunk=self.prefill_chunk,
            seq_state=self.seq_state(), state_layers=self.linear_layers)


# ---------------------------------------------------------------------------
# layer pieces
# ---------------------------------------------------------------------------


def _linear_attention(x, state, block, q_start, last_idx, row_valid, cfg,
                      name, attn_force):
    """One linear-attention layer's mixer over x [B, T, D]; ``state`` =
    (s, conv) the layer's two state vars, ``block`` the state-block feed.
    ``q_start`` / ``last_idx`` / ``row_valid`` are the chunk's (None in a
    decode step)."""
    L = layers
    h, dk, dv = (cfg.linear_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    s_var, conv_var = state
    u = _rms(x, name + "_input_norm", cfg)
    qkv = L.concat([_linear(u, h * dk, name + "_q", cfg),
                    _linear(u, h * dk, name + "_k", cfg),
                    _linear(u, h * dv, name + "_v", cfg)], axis=-1)
    qkv = L.short_conv(
        qkv, cfg.linear_conv_kernel_dim, conv_var, block, q_start, last_idx,
        param_attr=ParamAttr(name=name + "_conv.w_0",
                             initializer=Normal(0.0, 0.3)))
    q, k, v, g, beta = L.gdn_inputs(
        qkv, _linear(u, h, name + "_a", cfg), _linear(u, h, name + "_b", cfg),
        h, dk, dv, beta_scale=2.0 if cfg.linear_allow_neg_eigval else 1.0,
        epsilon=cfg.l2norm_eps, row_valid=row_valid,
        a_log_attr=ParamAttr(name=name + "_A_log",
                             initializer=Constant(0.0)),
        dt_bias_attr=ParamAttr(name=name + "_dt_bias",
                               initializer=Constant(0.0)))
    o = L.gated_delta_rule(q, k, v, g, beta, s_var, block, q_start,
                           force=attn_force)
    o = L.gated_rms_norm(
        o, _linear(u, h * dv, name + "_g", cfg), epsilon=cfg.rms_norm_eps,
        activation="silu",
        param_attr=ParamAttr(name=name + "_o_norm.scale",
                             initializer=Constant(1.0)))
    return _linear(o, cfg.hidden_size, name + "_o", cfg)


def _full_attention(x, pos, page_table, q_start, pools, write, shape, cfg,
                    name, attn_force):
    """Causal multi-head attention of one layer through the page table;
    writes the token's K and V rows first (a query sees its own
    position)."""
    L = layers
    b, t = shape
    h, d = cfg.num_attention_heads, cfg.head_dim
    k_pool, v_pool = pools
    q = _rms(_linear(x, h * d, name + "_q", cfg), name + "_q_norm", cfg)
    k = _rms(_linear(x, h * d, name + "_k", cfg), name + "_k_norm", cfg)
    v = _linear(x, h * d, name + "_v", cfg)
    q = L.rope_half(L.reshape(q, shape=[b, t, h, d]), pos,
                    theta=cfg.rope_theta)
    k = L.rope_half(L.reshape(k, shape=[b, t, h, d]), pos,
                    theta=cfg.rope_theta)
    write(k_pool, L.cast(L.reshape(k, shape=[b, t, h * d]), k_pool.dtype))
    write(v_pool, L.cast(v, v_pool.dtype))
    o = L.paged_attention(
        L.transpose(q, perm=[0, 2, 1, 3]), k_pool, v_pool, page_table,
        q_start, sm_scale=float(d) ** -0.5, force=attn_force)
    o = L.reshape(L.transpose(o, perm=[0, 2, 1, 3]), shape=[b, t, h * d])
    return _rms(_linear(o, cfg.hidden_size, name + "_o", cfg),
                name + "_post_attn_norm", cfg)


def _decoder(frame, cfg):
    """Embedding and every block over the frame's tokens (serving/lane.py
    ``Frame``) -> hidden [B, T, D] (before the final norm).  The frame's
    ``pools`` hold the full-attention layers' (K, V) in order, its
    ``states`` {layer: (s, conv)} of the linear-attention layers, which
    read the chunk's ``q_start`` / ``last_idx`` / ``row_valid`` and in a
    decode step none of them."""
    from paddle_tpu.serving import lane

    L = layers
    b, t = frame.shape
    emb = L.embedding(frame.tok, size=[cfg.vocab_size, cfg.hidden_size],
                      param_attr=_attr("olmo_embed.w_0", cfg),
                      dtype=cfg.dtype)
    x = L.cast(L.reshape(emb, shape=[b, t, cfg.hidden_size]), "float32")
    chunk = ((frame.q_start, frame.last_idx, frame.row_valid)
             if frame.last_idx is not None else (None, None, None))
    full = iter(frame.pools)
    for layer, kind in enumerate(cfg.layer_types):
        name = f"olmo_layer_{layer}"
        if kind == LINEAR:
            mixed = _linear_attention(x, frame.states[layer],
                                      frame.state_block, *chunk, cfg, name,
                                      frame.attn_force)
        else:
            mixed = _full_attention(
                x, frame.pos, frame.tables[lane.FULL], frame.q_start, next(full),
                frame.writes[lane.FULL], frame.shape, cfg, name, frame.attn_force)
        x = L.elementwise_add(x, mixed)
        ffn = _swiglu_ffn(x, cfg.intermediate_size, name + "_ffn", cfg)
        x = L.elementwise_add(x, _rms(ffn, name + "_post_ff_norm", cfg))
    return x


def build_olmo_hybrid_lm(cfg: OlmoHybridConfig = None, is_test=True,
                         seq_len=None, page_size=None, attn_force=None):
    """A whole sequence in one pass: logprobs [S, V] of every position of
    ``pf_tok`` [1, S] (serving/lane.py ``build_whole_sequence``: the
    decode lane's blocks over caches and state that live and die inside
    the program).  Inference only (``is_test`` is accepted for the zoo's
    calling convention)."""
    del is_test
    cfg = cfg or OlmoHybridConfig()
    return cfg.decode_lane().build_whole_sequence(
        seq_len or cfg.prefill_chunk or 128, page_size, attn_force)
