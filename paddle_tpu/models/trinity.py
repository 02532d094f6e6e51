"""Trinity-style decoder LM (``model_type: afmoe``), Fluid graph-building
style: grouped-query attention in layers of two kinds — ``sliding``
(the last ``sliding_window`` tokens, rotary positions) and ``full`` (the
whole context, no positional encoding) —, a sigmoid gate on attention's
output, four RMSNorms a block, and sigmoid-routed experts of which this
process holds a share.

  x0 = E[tok] * sqrt(hidden)                               (mup_enabled)
  block    a = x + RMS_post_attn(Attn(RMS_in(x)))
           y = a + RMS_post_mlp(F(RMS_pre_mlp(a)))
  Attn(u)  q = u W_q [H x d], k = u W_k, v = u W_v [Hkv x d], no biases;
           q, k <- RMSNorm over each head's d entries (q_norm, k_norm);
           RoPE (``rotate_half`` form, whole head, theta) on q, k in
           sliding layers ONLY; query head j reads K/V head j // (H/Hkv);
           scores q.k / sqrt(d); key t visible to query s iff t <= s
           and, in a sliding layer, s - t < sliding_window; softmax;
           o = (softmax . v) * sigmoid(u W_g) elementwise, W_g: D -> H*d;
           output o W_o.  A token's cache rows are k and v, the K/V heads
           side by side; a sliding layer's live in a WINDOW kind of the
           pool, which gives a page back once it lies below the window
           (serving/lane.py ``layer_windows``, serving/kv_pool.py).
  F        the first ``num_dense_layers`` layers: SwiGLU of width
           ``intermediate_size``.  The others: s = sigmoid(u W_r) over
           ``num_experts`` in float32; picks = top-k of s + b; gates
           route_scale * s / (sum of the picked s + 1e-20) (route_norm);
           the picks that land on the ``held_experts`` experts from
           ``first_expert`` that this process holds (the others add
           nothing: the partial sum an expert-parallel deployment adds up
           across chips, ops/mla_ops.py ``moe_ffn_held``), plus one
           shared SwiGLU expert; experts ``moe_intermediate_size`` wide.
  head     final RMSNorm, untied lm_head.

``TrinityConfig.decode_lane()`` hands ``_decoder`` and the head to
serving/lane.py, which builds the decode lane's two executables around
them, and ``build_trinity_lm`` a whole sequence on the same parameter
names, its caches program-local.
Matrices are stored in ``cfg.dtype`` (bfloat16 in the serving lane) and
multiplied in it with float32 accumulation; norm scales, the router's
bias, its product and activations between ops are float32; cache rows
are ``cfg.dtype``.
"""

from __future__ import annotations

import functools

from paddle_tpu.fluid import layers

from . import moe_stats
from .decode_blocks import _attr, _linear, _next_token, _rms, _swiglu_ffn

SLIDING, FULL = "sliding_attention", "full_attention"


class TrinityConfig:
    def __init__(self, vocab_size=200192, hidden_size=3072,
                 num_hidden_layers=60, num_dense_layers=6,
                 intermediate_size=12288, moe_intermediate_size=3072,
                 num_attention_heads=48, num_key_value_heads=8,
                 head_dim=128, sliding_window=4096, layer_types=None,
                 global_attn_every_n_layers=4, num_experts=256,
                 num_experts_per_tok=4, num_shared_experts=1,
                 route_norm=True, route_scale=2.448, rms_norm_eps=1e-5,
                 rope_theta=10000.0, max_position_embeddings=262144,
                 mup_enabled=True, held_experts=None, first_expert=0,
                 dtype="bfloat16", prefill_chunk=None,
                 initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_dense_layers = num_dense_layers
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.sliding_window = sliding_window
        if layer_types is None:  # every n-th layer full, the others sliding
            n = global_attn_every_n_layers
            layer_types = [FULL if (i + 1) % n == 0 else SLIDING
                           for i in range(num_hidden_layers)]
        self.layer_types = list(layer_types)
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.route_norm = route_norm
        self.route_scale = route_scale
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.max_position_embeddings = max_position_embeddings
        self.mup_enabled = mup_enabled
        # the experts this process holds of num_experts (all of them by
        # default): ids first_expert .. first_expert + held_experts
        self.held_experts = (num_experts if held_experts is None
                             else held_experts)
        self.first_expert = first_expert
        self.dtype = dtype
        self.prefill_chunk = prefill_chunk
        self.initializer_range = initializer_range
        if num_shared_experts != 1:
            raise ValueError("TrinityConfig: one shared expert a layer")
        if num_attention_heads % num_key_value_heads:
            raise ValueError("TrinityConfig: query heads in whole groups")
        if (len(self.layer_types) != num_hidden_layers
                or set(self.layer_types) - {SLIDING, FULL}):
            raise ValueError(
                f"TrinityConfig: layer_types names {len(self.layer_types)} "
                f"layers of {num_hidden_layers}, each {SLIDING!r} or "
                f"{FULL!r}")

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=96, hidden_size=64, num_hidden_layers=5,
                 num_dense_layers=1, intermediate_size=96,
                 moe_intermediate_size=32, num_attention_heads=6,
                 num_key_value_heads=2, head_dim=16, sliding_window=8,
                 layer_types=[SLIDING, SLIDING, SLIDING, FULL, SLIDING],
                 num_experts=16, num_experts_per_tok=2,
                 max_position_embeddings=128, dtype="float32")
        d.update(kw)
        return cls(**d)

    @property
    def moe_layers(self):
        return list(range(self.num_dense_layers, self.num_hidden_layers))

    @property
    def layer_windows(self):
        """Per layer, the W of a sliding layer or None (serving/lane.py
        ``layer_windows``)."""
        return [self.sliding_window if kind == SLIDING else None
                for kind in self.layer_types]

    def cache_rows(self, pool_dtype=None):
        """What a token leaves in each layer: a K and a V row of the K/V
        heads side by side."""
        from paddle_tpu.serving import lane

        dtype = pool_dtype or self.dtype
        if dtype == "int8":
            raise ValueError(
                "models/trinity.py: no int8 form of the grouped-query "
                "K/V pool (the dual-int8 pool is models/gpt.py's)")
        return lane.kv_rows(self.num_key_value_heads, self.head_dim, dtype)

    def decode_lane(self):
        """This model's decode-lane declaration (serving/lane.py)."""
        from paddle_tpu.serving import lane

        return lane.scaffold(
            functools.partial(_decoder, cfg=self),
            functools.partial(_next_token, cfg=self, prefix="trinity"),
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


def _attention(x, pos, page_table, q_start, pools, write, shape, window, cfg,
               name, attn_force):
    """Gated grouped-query attention of one layer through its kind's page
    table; writes the token's K and V rows first (a query sees its own
    position)."""
    L = layers
    b, t = shape
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    k_pool, v_pool = pools
    u = _rms(x, name + "_input_norm", cfg)
    q = _rms(L.reshape(_linear(u, hq * d, name + "_q", cfg),
                       shape=[b, t, hq, d]), name + "_q_norm", cfg)
    k = _rms(L.reshape(_linear(u, hkv * d, name + "_k", cfg),
                       shape=[b, t, hkv, d]), name + "_k_norm", cfg)
    v = _linear(u, hkv * d, name + "_v", cfg)
    if window is not None:  # full layers carry no positional encoding
        q = L.rope_half(q, pos, theta=cfg.rope_theta)
        k = L.rope_half(k, pos, theta=cfg.rope_theta)
    write(k_pool, L.cast(L.reshape(k, shape=[b, t, hkv * d]), k_pool.dtype))
    write(v_pool, L.cast(v, v_pool.dtype))
    o = L.paged_attention(
        L.transpose(q, perm=[0, 2, 1, 3]), k_pool, v_pool, page_table,
        q_start, sm_scale=float(d) ** -0.5, force=attn_force, window=window)
    o = L.reshape(L.transpose(o, perm=[0, 2, 1, 3]), shape=[b, t, hq * d])
    o = L.sigmoid_gate(o, _linear(u, hq * d, name + "_gate", cfg))
    return _linear(o, cfg.hidden_size, name + "_o", cfg)


def _ffn(x, layer, row_valid, counted_as, cfg, name, attn_force):
    if layer < cfg.num_dense_layers:
        return _swiglu_ffn(x, cfg.intermediate_size, name + "_ffn", cfg)
    stats = (moe_stats.expert_stats_var(cfg, layer, counted_as)
             if counted_as else None)
    routed = layers.moe_ffn_held(
        x, cfg.num_experts, cfg.held_experts, cfg.moe_intermediate_size,
        cfg.num_experts_per_tok, first_expert=cfg.first_expert,
        routed_scaling_factor=cfg.route_scale,
        norm_topk_prob=cfg.route_norm, row_valid=row_valid, stats=stats,
        dtype=cfg.dtype, force=attn_force, name=name + "_moe")
    shared = _swiglu_ffn(x, cfg.moe_intermediate_size, name + "_shared", cfg)
    return layers.elementwise_add(routed, shared)


def _decoder(frame, cfg):
    """Embedding and every block over the frame's tokens (serving/lane.py
    ``Frame``) -> hidden [B, T, D] (before the final norm); a layer
    reads the page table and the writer of its cache kind."""
    from paddle_tpu.serving import lane

    L = layers
    b, t = frame.shape
    emb = L.embedding(frame.tok, size=[cfg.vocab_size, cfg.hidden_size],
                      param_attr=_attr("trinity_embed.w_0", cfg),
                      dtype=cfg.dtype)
    x = L.cast(L.reshape(emb, shape=[b, t, cfg.hidden_size]), "float32")
    if cfg.mup_enabled:
        x = L.scale(x, scale=float(cfg.hidden_size) ** 0.5)
    for layer, window in enumerate(cfg.layer_windows):
        name = f"trinity_layer_{layer}"
        kind = lane.kind_name(window)
        attn = _attention(x, frame.pos, frame.tables[kind], frame.q_start,
                          frame.pools[layer], frame.writes[kind], frame.shape,
                          window, cfg, name, frame.attn_force)
        x = L.elementwise_add(x, _rms(attn, name + "_post_attn_norm", cfg))
        ffn = _ffn(_rms(x, name + "_pre_mlp_norm", cfg), layer,
                   frame.row_valid, frame.counted_as, cfg, name,
                   frame.attn_force)
        x = L.elementwise_add(x, _rms(ffn, name + "_post_mlp_norm", cfg))
    return x


def build_trinity_lm(cfg: TrinityConfig = None, is_test=True, seq_len=None,
                     page_size=None, attn_force=None):
    """A whole sequence in one pass: logprobs [S, V] of every position of
    ``pf_tok`` [1, S] (serving/lane.py ``build_whole_sequence``: the
    decode lane's blocks over caches that live and die inside the
    program).  Inference only (``is_test`` is accepted for the zoo's
    calling convention)."""
    del is_test
    cfg = cfg or TrinityConfig()
    return cfg.decode_lane().build_whole_sequence(
        seq_len or cfg.prefill_chunk or 128, page_size, attn_force)
