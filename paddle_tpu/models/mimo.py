"""MiMo-V2-style decoder LM (``model_type: mimo_v2``), Fluid
graph-building style: grouped-query attention in layers of two kinds —
``window`` (``hybrid_layer_pattern`` 1: the last ``sliding_window``
tokens, ``swa_num_key_value_heads`` K/V heads, a learned sink logit a
query head in the softmax, ``swa_rope_theta``) and ``full`` (pattern 0:
the whole context, ``num_key_value_heads`` K/V heads, the plain softmax,
``rope_theta``) —, query and key heads of ``head_dim`` beside value
heads of ``v_head_dim``, rotary positions on a head's first
``rotary_dim`` entries, two RMSNorms a block, and sigmoid-routed experts
of which this process holds a share, with no shared expert.

  x0 = E[tok]                                              (unscaled)
  block    a = x + Attn(RMS_1(x));  y = a + F(RMS_2(a))
  Attn(u)  q = u W_q [H x d], k = u W_k [Hkv x d],
           v = attention_value_scale * (u W_v) [Hkv x dv], no biases, no
           q/k norm; RoPE (``rotate_half`` form, the kind's theta) on
           the FIRST rotary_dim = int(d * partial_rotary_factor) entries
           of each q and k head, the others pass; query head j reads K/V
           head j // (H/Hkv); scores q.k / sqrt(d); key t visible to
           query s iff t <= s and, in a window layer, s - t <
           sliding_window; softmax — in a window layer with the head's
           sink logit b_h as one more column that carries no value
           (``add_swa_attention_sink_bias``); o = softmax . v [H x dv];
           output o W_o, W_o: H*dv -> D.  A token's cache rows are k
           (Hkv*d) and v (Hkv*dv), the K/V heads side by side, at the
           widths of its layer's KIND: the lane declares its rows by
           kind (serving/lane.py), and a window layer's live in a
           window kind of the pool, which gives a page back once it lies
           below the window.
  F        layers with ``moe_layer_freq`` 0: SwiGLU of width
           ``intermediate_size``.  The others: s = sigmoid(u W_r) over
           ``n_routed_experts`` in float32; picks = top-k of s + b;
           gates s / (sum of the picked s + 1e-20) (norm_topk_prob),
           times routed_scaling_factor (1 where the source has none);
           the picks that land on the ``held_experts`` experts from
           ``first_expert`` that this process holds (the others add
           nothing: the partial sum an expert-parallel deployment adds
           up across chips, ops/mla_ops.py ``moe_ffn_held``); experts
           ``moe_intermediate_size`` wide.
  head     final RMSNorm, untied lm_head.

``MiMoConfig.decode_lane()`` hands ``_decoder`` and the head to
serving/lane.py, which builds the decode lane's two executables around
them, and ``build_mimo_lm`` a whole sequence on the same parameter
names, its caches program-local.
Matrices are stored in ``cfg.dtype`` (bfloat16 in the serving lane) and
multiplied in it with float32 accumulation; norm scales, the router's
bias and product, the sinks and activations between ops are float32;
cache rows are ``cfg.dtype``.
"""

from __future__ import annotations

import functools

from paddle_tpu.fluid import layers
from paddle_tpu.fluid.initializer import Normal
from paddle_tpu.fluid.param_attr import ParamAttr

from . import moe_stats
from .decode_blocks import _attr, _linear, _next_token, _rms, _swiglu_ffn

FULL, WINDOW = 0, 1     # hybrid_layer_pattern's two values


class MiMoConfig:
    def __init__(self, vocab_size=152576, hidden_size=4096,
                 num_hidden_layers=48, hybrid_layer_pattern=None,
                 moe_layer_freq=None, intermediate_size=16384,
                 moe_intermediate_size=2048, num_attention_heads=64,
                 num_key_value_heads=4, swa_num_key_value_heads=8,
                 head_dim=192, v_head_dim=128, sliding_window=128,
                 partial_rotary_factor=0.334, rope_theta=10000000.0,
                 swa_rope_theta=10000.0, attention_value_scale=0.707,
                 add_swa_attention_sink_bias=True,
                 add_full_attention_sink_bias=False, n_routed_experts=256,
                 num_experts_per_tok=8, norm_topk_prob=True,
                 routed_scaling_factor=None, layernorm_epsilon=1e-5,
                 max_position_embeddings=1048576, held_experts=None,
                 first_expert=0, dtype="bfloat16", prefill_chunk=None,
                 initializer_range=0.02, sink_init_std=1.0):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        if hybrid_layer_pattern is None:    # every sixth layer full
            hybrid_layer_pattern = [FULL if i % 6 == 5 or i == 0 else WINDOW
                                    for i in range(num_hidden_layers)]
        self.hybrid_layer_pattern = [int(k) for k in hybrid_layer_pattern]
        if moe_layer_freq is None:          # one leading dense layer
            moe_layer_freq = [int(i > 0) for i in range(num_hidden_layers)]
        self.moe_layer_freq = [int(k) for k in moe_layer_freq]
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.swa_num_key_value_heads = swa_num_key_value_heads
        self.head_dim = head_dim
        self.v_head_dim = v_head_dim
        self.sliding_window = sliding_window
        self.partial_rotary_factor = partial_rotary_factor
        self.rotary_dim = int(head_dim * partial_rotary_factor)
        self.rope_theta = rope_theta
        self.swa_rope_theta = swa_rope_theta
        self.attention_value_scale = attention_value_scale
        self.add_swa_attention_sink_bias = add_swa_attention_sink_bias
        self.add_full_attention_sink_bias = add_full_attention_sink_bias
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = (1.0 if routed_scaling_factor is None
                                      else float(routed_scaling_factor))
        self.layernorm_epsilon = layernorm_epsilon
        self.rms_norm_eps = layernorm_epsilon   # decode_blocks._rms's name
        self.max_position_embeddings = max_position_embeddings
        # the experts this process holds of n_routed_experts (all of them
        # by default): ids first_expert .. first_expert + held_experts
        self.held_experts = (n_routed_experts if held_experts is None
                             else held_experts)
        self.first_expert = first_expert
        self.dtype = dtype
        self.prefill_chunk = prefill_chunk
        self.initializer_range = initializer_range
        self.sink_init_std = sink_init_std
        for heads in (num_key_value_heads, swa_num_key_value_heads):
            if num_attention_heads % heads:
                raise ValueError("MiMoConfig: query heads in whole groups")
        if self.rotary_dim % 2:
            raise ValueError(f"MiMoConfig: rotary_dim {self.rotary_dim} "
                             f"turns its entries in pairs")
        for key in ("hybrid_layer_pattern", "moe_layer_freq"):
            got = getattr(self, key)
            if len(got) != num_hidden_layers or set(got) - {0, 1}:
                raise ValueError(
                    f"MiMoConfig: {key} names {len(got)} layers of "
                    f"{num_hidden_layers}, each 0 or 1")

    @classmethod
    def tiny(cls, **kw):
        """Every mechanism at a size the CPU holds: K heads wider than V
        heads, two K/V head counts, a window shorter than the chunk."""
        d = dict(vocab_size=96, hidden_size=64, num_hidden_layers=7,
                 hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1],
                 moe_layer_freq=[0, 1, 1, 1, 1, 1, 1], intermediate_size=96,
                 moe_intermediate_size=32, num_attention_heads=8,
                 num_key_value_heads=2, swa_num_key_value_heads=4,
                 head_dim=24, v_head_dim=16, sliding_window=6,
                 n_routed_experts=16, num_experts_per_tok=2,
                 max_position_embeddings=256, dtype="float32")
        d.update(kw)
        return cls(**d)

    @property
    def moe_layers(self):
        return [i for i, k in enumerate(self.moe_layer_freq) if k]

    @property
    def layer_windows(self):
        """Per layer, the W of a window layer or None (serving/lane.py
        ``layer_windows``)."""
        return [self.sliding_window if kind == WINDOW else None
                for kind in self.hybrid_layer_pattern]

    def kv_heads(self, window):
        """The K/V heads of a layer of the kind ``window`` names."""
        return (self.num_key_value_heads if window is None
                else self.swa_num_key_value_heads)

    def has_sink(self, window):
        return (self.add_full_attention_sink_bias if window is None
                else self.add_swa_attention_sink_bias)

    def cache_rows(self, pool_dtype=None):
        """What a token leaves in a layer, BY KIND: a K row of its kind's
        K/V heads x head_dim and a V row of as many x v_head_dim."""
        from paddle_tpu.serving import lane

        dtype = pool_dtype or self.dtype
        if dtype == "int8":
            raise ValueError(
                "models/mimo.py: no int8 form of the grouped-query K/V "
                "pool (the dual-int8 pool is models/gpt.py's)")
        return {
            lane.kind_name(w): [
                lane.CacheRow("k", self.kv_heads(w) * self.head_dim, dtype),
                lane.CacheRow("v", self.kv_heads(w) * self.v_head_dim,
                              dtype)]
            for w in lane.kinds_of(self.layer_windows)}

    def decode_lane(self):
        """This model's decode-lane declaration (serving/lane.py)."""
        from paddle_tpu.serving import lane

        return lane.scaffold(
            functools.partial(_decoder, cfg=self),
            functools.partial(_next_token, cfg=self, prefix="mimo"),
            num_layers=self.num_hidden_layers,
            max_position=self.max_position_embeddings,
            cache_rows=self.cache_rows,
            pool_dtype=self.dtype, prefill_chunk=self.prefill_chunk,
            device_counters=moe_stats.expert_stats_counters(self),
            book_counters=functools.partial(moe_stats.book_expert_stats,
                                            self),
            layer_windows=self.layer_windows)


# ---------------------------------------------------------------------------
# layer pieces
# ---------------------------------------------------------------------------


def _attention(u, pos, page_table, q_start, pools, write, shape, window, cfg,
               name, attn_force):
    """Grouped-query attention of one layer over its normed input ``u``
    through its kind's page table; writes the token's K and V rows first
    (a query sees its own position)."""
    L = layers
    b, t = shape
    hq, hkv = cfg.num_attention_heads, cfg.kv_heads(window)
    d, dv = cfg.head_dim, cfg.v_head_dim
    theta = cfg.rope_theta if window is None else cfg.swa_rope_theta
    k_pool, v_pool = pools
    q = L.rope_half(L.reshape(_linear(u, hq * d, name + "_q", cfg, d),
                              shape=[b, t, hq, d]), pos, theta=theta,
                    rotary_dim=cfg.rotary_dim)
    k = L.rope_half(L.reshape(_linear(u, hkv * d, name + "_k", cfg, d),
                              shape=[b, t, hkv, d]), pos, theta=theta,
                    rotary_dim=cfg.rotary_dim)
    v = L.scale(_linear(u, hkv * dv, name + "_v", cfg),
                scale=float(cfg.attention_value_scale))
    write(k_pool, L.cast(L.reshape(k, shape=[b, t, hkv * d]), k_pool.dtype))
    write(v_pool, L.cast(v, v_pool.dtype))
    sinks = None
    if cfg.has_sink(window):
        sinks = L.create_parameter(
            [hq], "float32", attr=ParamAttr(
                name=name + "_sink.b_0",
                initializer=Normal(0.0, cfg.sink_init_std)))
    o = L.paged_attention(
        L.transpose(q, perm=[0, 2, 1, 3]), k_pool, v_pool, page_table,
        q_start, sm_scale=float(d) ** -0.5, force=attn_force, window=window,
        sinks=sinks)
    o = L.reshape(L.transpose(o, perm=[0, 2, 1, 3]), shape=[b, t, hq * dv])
    return _linear(o, cfg.hidden_size, name + "_o", cfg)


def _ffn(x, layer, row_valid, counted_as, cfg, name, attn_force):
    if not cfg.moe_layer_freq[layer]:
        return _swiglu_ffn(x, cfg.intermediate_size, name + "_ffn", cfg)
    stats = (moe_stats.expert_stats_var(cfg, layer, counted_as)
             if counted_as else None)
    return layers.moe_ffn_held(
        x, cfg.n_routed_experts, cfg.held_experts,
        cfg.moe_intermediate_size, cfg.num_experts_per_tok,
        first_expert=cfg.first_expert,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob, row_valid=row_valid, stats=stats,
        dtype=cfg.dtype, force=attn_force, name=name + "_moe")


def _decoder(frame, cfg):
    """Embedding and every block over the frame's tokens (serving/lane.py
    ``Frame``) -> hidden [B, T, D] (before the final norm); a layer
    reads the page table and the writer of its cache kind."""
    from paddle_tpu.serving import lane

    L = layers
    b, t = frame.shape
    emb = L.embedding(frame.tok, size=[cfg.vocab_size, cfg.hidden_size],
                      param_attr=_attr("mimo_embed.w_0", cfg),
                      dtype=cfg.dtype)
    x = L.cast(L.reshape(emb, shape=[b, t, cfg.hidden_size]), "float32")
    for layer, window in enumerate(cfg.layer_windows):
        name = f"mimo_layer_{layer}"
        kind = lane.kind_name(window)
        x = L.elementwise_add(x, _attention(
            _rms(x, name + "_input_norm", cfg), frame.pos, frame.tables[kind],
            frame.q_start, frame.pools[layer], frame.writes[kind],
            frame.shape, window, cfg, name, frame.attn_force))
        x = L.elementwise_add(x, _ffn(
            _rms(x, name + "_post_attn_norm", cfg), layer, frame.row_valid,
            frame.counted_as, cfg, name, frame.attn_force))
    return x


def build_mimo_lm(cfg: MiMoConfig = None, is_test=True, seq_len=None,
                  page_size=None, attn_force=None):
    """A whole sequence in one pass: logprobs [S, V] of every position of
    ``pf_tok`` [1, S] (serving/lane.py ``build_whole_sequence``: the
    decode lane's blocks over caches that live and die inside the
    program).  Inference only (``is_test`` is accepted for the zoo's
    calling convention)."""
    del is_test
    cfg = cfg or MiMoConfig()
    return cfg.decode_lane().build_whole_sequence(
        seq_len or cfg.prefill_chunk or 128, page_size, attn_force)
