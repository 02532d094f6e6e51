"""Qwen3-Next-style decoder LM (``model_type: qwen3_next``), Fluid
graph-building style: gated-delta-rule linear-attention layers with MORE
VALUE HEADS THAN KEY HEADS beside gated grouped-query full-attention
layers (3 : 1 as published), softmax-routed experts and a gated shared
expert behind every layer, zero-centred norm gains.

  x0       E[tok]
  block    a = x + Mix(N_in(x)); y = a + F(N_post(a));
           N(x) = x rsqrt(mean x^2 + eps) (1 + w), w stored
  linear_attention  (H_k key heads, H_v = r H_k value heads, d_k, d_v;
           ops/gdn_ops.py)  u = N_in(x)
           [q~ | k~ | v~] = u W_qkv            (H_k d_k, H_k d_k, H_v d_v)
           z = u W_z (H_v d_v), [b | a] = u W_ba (H_v each)
           each channel of [q~ | k~ | v~] through a depthwise causal
           convolution over time of ``linear_conv_kernel_dim`` taps, then
           SiLU: q', k', v'
           q = l2norm(q') / sqrt(d_k), k = l2norm(k') a KEY head, v = v'
           beta = sigmoid(b), g = -exp(A_log) softplus(a + dt_bias),
           alpha = exp(g), all a VALUE head; value head h reads key head
           h // r
           S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T,
           o_t = S_t^T q_t, S_0 = 0                  (a value head)
           Mix = [RMS_head(o; plain gain d_v) * silu(z)] W_o
           (kernels/primitives/gdn.py, the grouped-head bodies)
  full_attention    (H heads on H_kv K/V heads of ``head_dim``, no bias)
           u = N_in(x); [q | gate] = u W_q, a head's 2 d columns its d of
           q then its d of gate; k = u W_k, v = u W_v;
           q, k <- N over each head's d entries; RoPE (``rotate_half``
           form, theta) on the FIRST ``partial_rotary_factor`` d entries
           of each head of q and k; query head j reads K/V head
           j // (H / H_kv); causal softmax(q k^T / sqrt(d)) v;
           Mix = (o * sigmoid(gate)) W_o
  F        s = softmax(u' W_r) over ``num_experts`` in float32, u' =
           N_post(a); picks = the ``num_experts_per_tok`` largest; gates
           s_pick / sum of the picked (``norm_topk_prob``); the picks that
           land on the ``held_experts`` experts from ``first_expert`` this
           process holds (ops/mla_ops.py ``moe_ffn_held``), plus
           sigmoid(u' w_sg) x one shared SwiGLU of width
           ``shared_expert_intermediate_size``
           (decode_blocks.py ``expert_ffn``)
  head     final norm (1 + w), untied lm_head.

What the published config has no key for (the gate on attention and its
place in ``W_q``, the norm a head before RoPE, ``1 + w``, the shared
expert's gate, the l2norm, the convolution) is listed under ``assumed``
in the benchmark's configuration file, and the plain reference
(benchmark/reference/qwen3_next.py) is written from the same entries.
The source stores ``[W_q | W_k | W_v | W_z]`` as one matrix with its
columns interleaved by key-head group and ``[W_b | W_a]`` as one: a
checkpoint's layout, not an equation; here ``W_qkv``, ``W_z`` and
``W_ba`` are three products.

What a layer leaves behind (serving/lane.py): a full-attention layer a K
and a V row a TOKEN, the K/V heads side by side (the pool's page kind
``full``; the lane numbers its cache layers 0 .. n_full - 1); a
linear-attention layer two tensors a SEQUENCE owns (the kind ``state``,
one block a sequence): the rule's state ``s`` [d_k, H_v d_v] float32 and
the convolution's last K - 1 pre-activation inputs ``conv``
[(K - 1) (2 H_k d_k + H_v d_v)] float32.

``Qwen3NextConfig.decode_lane()`` hands ``_decoder`` and the head to
serving/lane.py, which builds the decode lane's two executables around
them, and ``build_qwen3_next_lm`` a whole sequence on the same parameter
names.  Matrices are stored in ``cfg.dtype`` (bfloat16 in the serving
lane) and multiplied in it with float32 accumulation; norm gains, the
convolution's taps, ``A_log``, ``dt_bias``, the router's product, the
state and activations between ops are float32; K/V rows are
``cfg.dtype``.  The family's multi-token-prediction layer is not here
(nothing in serving/decode.py verifies a drafted token).
"""

from __future__ import annotations

import functools

from paddle_tpu.fluid import layers
from paddle_tpu.fluid.initializer import Constant, Normal
from paddle_tpu.fluid.param_attr import ParamAttr

from . import moe_stats
from .decode_blocks import _attr, _linear, _next_token, _rms, expert_ffn

LINEAR, FULL = "linear_attention", "full_attention"


class Qwen3NextConfig:
    """The source's keys under their own names; ``held_experts`` /
    ``first_expert`` say which of the ``num_experts`` this process holds
    (all of them by default)."""

    # every RMSNorm but the delta-rule layer's gated output norm:
    # RMS(x) (1 + w), w stored (decode_blocks.py ``_rms``)
    norm_gain_offset = 1.0
    # the names decode_blocks.py ``expert_ffn`` reads (kimi_vl.py's keys)
    first_k_dense_replace = 0
    routed_scaling_factor = 1.0
    n_routed_experts = property(lambda self: self.num_experts)

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 num_hidden_layers=48, full_attention_interval=4,
                 layer_types=None, num_attention_heads=16,
                 num_key_value_heads=2, head_dim=256,
                 partial_rotary_factor=0.25, rope_theta=10000000.0,
                 linear_num_key_heads=16, linear_num_value_heads=32,
                 linear_key_head_dim=128, linear_value_head_dim=128,
                 linear_conv_kernel_dim=4, num_experts=512,
                 num_experts_per_tok=10, moe_intermediate_size=512,
                 shared_expert_intermediate_size=512, norm_topk_prob=True,
                 decoder_sparse_step=1, mlp_only_layers=(),
                 intermediate_size=5120, rms_norm_eps=1e-6,
                 max_position_embeddings=262144, l2norm_eps=1e-6,
                 held_experts=None, first_expert=0, dtype="bfloat16",
                 prefill_chunk=None, initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.full_attention_interval = full_attention_interval
        if layer_types is None:  # every n-th layer full, the others linear
            n = full_attention_interval
            layer_types = [FULL if (i + 1) % n == 0 else LINEAR
                           for i in range(num_hidden_layers)]
        self.layer_types = list(layer_types)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.partial_rotary_factor = partial_rotary_factor
        self.rotary_dim = int(head_dim * partial_rotary_factor)
        self.rope_theta = rope_theta
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = shared_expert_intermediate_size
        self.norm_topk_prob = norm_topk_prob
        # enters no equation here: every layer is an expert layer
        self.intermediate_size = intermediate_size
        self.rms_norm_eps = rms_norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.l2norm_eps = l2norm_eps
        self.held_experts = (num_experts if held_experts is None
                             else held_experts)
        self.first_expert = first_expert
        self.dtype = dtype
        self.prefill_chunk = prefill_chunk
        self.initializer_range = initializer_range
        if decoder_sparse_step != 1 or list(mlp_only_layers):
            raise ValueError(
                "Qwen3NextConfig: experts behind every layer "
                "(decoder_sparse_step 1, mlp_only_layers empty)")
        if (num_attention_heads % num_key_value_heads
                or linear_num_value_heads % linear_num_key_heads):
            raise ValueError(
                "Qwen3NextConfig: query heads in whole groups a K/V head, "
                "value heads in whole groups a key head")
        if (len(self.layer_types) != num_hidden_layers
                or set(self.layer_types) - {LINEAR, FULL}):
            raise ValueError(
                f"Qwen3NextConfig: layer_types names "
                f"{len(self.layer_types)} layers of {num_hidden_layers}, "
                f"each {LINEAR!r} or {FULL!r}")

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=96, hidden_size=64, num_hidden_layers=4,
                 layer_types=[LINEAR, LINEAR, FULL, LINEAR],
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 linear_num_key_heads=2, linear_num_value_heads=4,
                 linear_key_head_dim=8, linear_value_head_dim=8,
                 num_experts=16, num_experts_per_tok=3,
                 moe_intermediate_size=24,
                 shared_expert_intermediate_size=24, intermediate_size=96,
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
    def moe_layers(self):
        return list(range(self.num_hidden_layers))

    @property
    def conv_channels(self):
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    def cache_rows(self, pool_dtype=None):
        """What a token leaves in each full-attention layer: a K and a V
        row, the K/V heads side by side."""
        from paddle_tpu.serving import lane

        dtype = pool_dtype or self.dtype
        if dtype == "int8":
            raise ValueError(
                "models/qwen3_next.py: no int8 form of the grouped-query "
                "K/V pool (the dual-int8 pool is models/gpt.py's)")
        return lane.kv_rows(self.num_key_value_heads, self.head_dim, dtype)

    def seq_state(self):
        """What a sequence owns in each linear-attention layer
        (serving/lane.py ``SeqState``): the rule's state, d_k rows of the
        VALUE heads' d_v columns side by side
        (kernels/primitives/gdn.py), and the convolution's last K - 1
        inputs, both float32."""
        from paddle_tpu.serving import lane

        return [
            lane.SeqState("s", (self.linear_key_head_dim,
                                self.linear_num_value_heads
                                * self.linear_value_head_dim), "float32"),
            lane.SeqState("conv", ((self.linear_conv_kernel_dim - 1)
                                   * self.conv_channels,), "float32")]

    def decode_lane(self):
        """This model's decode-lane declaration (serving/lane.py)."""
        from paddle_tpu.serving import lane

        return lane.scaffold(
            functools.partial(_decoder, cfg=self),
            functools.partial(_next_token, cfg=self, prefix="qwen3n"),
            num_layers=len(self.full_layers),
            max_position=self.max_position_embeddings,
            cache_rows=self.cache_rows,
            pool_dtype=self.dtype, prefill_chunk=self.prefill_chunk,
            device_counters=moe_stats.expert_stats_counters(self),
            book_counters=functools.partial(moe_stats.book_expert_stats,
                                            self),
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
    hk, hv, dk, dv = (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
                      cfg.linear_key_head_dim, cfg.linear_value_head_dim)
    s_var, conv_var = state
    u = _rms(x, name + "_input_norm", cfg)
    qkv = L.short_conv(
        _linear(u, cfg.conv_channels, name + "_qkv", cfg),
        cfg.linear_conv_kernel_dim, conv_var, block, q_start, last_idx,
        param_attr=ParamAttr(name=name + "_conv.w_0",
                             initializer=Normal(0.0, 0.3)))
    b, a = L.split(_linear(u, 2 * hv, name + "_ba", cfg), [hv, hv], dim=-1)
    q, k, v, g, beta = L.gdn_inputs(
        qkv, a, b, hv, dk, dv, beta_scale=1.0, epsilon=cfg.l2norm_eps,
        row_valid=row_valid, key_heads=hk,
        a_log_attr=ParamAttr(name=name + "_A_log",
                             initializer=Constant(0.0)),
        dt_bias_attr=ParamAttr(name=name + "_dt_bias",
                               initializer=Constant(0.0)))
    o = L.gated_delta_rule(q, k, v, g, beta, s_var, block, q_start,
                           force=attn_force)
    o = L.gated_rms_norm(
        o, _linear(u, hv * dv, name + "_z", cfg), epsilon=cfg.rms_norm_eps,
        activation="silu",
        param_attr=ParamAttr(name=name + "_o_norm.scale",
                             initializer=Constant(1.0)))
    return _linear(o, cfg.hidden_size, name + "_o", cfg)


def _full_attention(x, pos, page_table, q_start, pools, write, shape, cfg,
                    name, attn_force):
    """Gated grouped-query attention of one layer through the page table;
    writes the token's K and V rows first (a query sees its own
    position)."""
    L = layers
    b, t = shape
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    k_pool, v_pool = pools
    u = _rms(x, name + "_input_norm", cfg)
    # a head's 2 d columns: its d of q, then its d of gate
    q, gate = L.split(L.reshape(_linear(u, hq * 2 * d, name + "_q", cfg),
                                shape=[b, t, hq, 2 * d]), [d, d], dim=-1)
    q = _rms(q, name + "_q_norm", cfg)
    k = _rms(L.reshape(_linear(u, hkv * d, name + "_k", cfg),
                       shape=[b, t, hkv, d]), name + "_k_norm", cfg)
    v = _linear(u, hkv * d, name + "_v", cfg)
    q = L.rope_half(q, pos, theta=cfg.rope_theta, rotary_dim=cfg.rotary_dim)
    k = L.rope_half(k, pos, theta=cfg.rope_theta, rotary_dim=cfg.rotary_dim)
    write(k_pool, L.cast(L.reshape(k, shape=[b, t, hkv * d]), k_pool.dtype))
    write(v_pool, L.cast(v, v_pool.dtype))
    o = L.paged_attention(
        L.transpose(q, perm=[0, 2, 1, 3]), k_pool, v_pool, page_table,
        q_start, sm_scale=float(d) ** -0.5, force=attn_force)
    o = L.reshape(L.transpose(o, perm=[0, 2, 1, 3]), shape=[b, t, hq * d])
    o = L.sigmoid_gate(o, L.reshape(gate, shape=[b, t, hq * d]))
    return _linear(o, cfg.hidden_size, name + "_o", cfg)


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
                      param_attr=_attr("qwen3n_embed.w_0", cfg),
                      dtype=cfg.dtype)
    x = L.cast(L.reshape(emb, shape=[b, t, cfg.hidden_size]), "float32")
    chunk = ((frame.q_start, frame.last_idx, frame.row_valid)
             if frame.last_idx is not None else (None, None, None))
    full = iter(frame.pools)
    for layer, kind in enumerate(cfg.layer_types):
        name = f"qwen3n_layer_{layer}"
        if kind == LINEAR:
            mixed = _linear_attention(x, frame.states[layer],
                                      frame.state_block, *chunk, cfg, name,
                                      frame.attn_force)
        else:
            mixed = _full_attention(
                x, frame.pos, frame.tables[lane.FULL], frame.q_start,
                next(full), frame.writes[lane.FULL], frame.shape, cfg, name,
                frame.attn_force)
        x = L.elementwise_add(x, mixed)
        x = L.elementwise_add(x, expert_ffn(
            x, layer, frame.row_valid, frame.counted_as, cfg, name,
            frame.attn_force, score_func="softmax",
            shared_width=cfg.shared_expert_intermediate_size,
            shared_gate=True))
    return x


def build_qwen3_next_lm(cfg: Qwen3NextConfig = None, is_test=True,
                        seq_len=None, page_size=None, attn_force=None):
    """A whole sequence in one pass: logprobs [S, V] of every position of
    ``pf_tok`` [1, S] (serving/lane.py ``build_whole_sequence``: the
    decode lane's blocks over caches and state that live and die inside
    the program).  Inference only (``is_test`` is accepted for the zoo's
    calling convention)."""
    del is_test
    cfg = cfg or Qwen3NextConfig()
    return cfg.decode_lane().build_whole_sequence(
        seq_len or cfg.prefill_chunk or 128, page_size, attn_force)
