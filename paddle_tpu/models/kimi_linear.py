"""Kimi-Linear-style decoder LM (``model_type: kimi_linear``), Fluid
graph-building style: Kimi-Delta-Attention layers (a delta rule whose
decay is one number a KEY CHANNEL) beside latent-attention layers with
no rotary positions (3 : 1 as published), sigmoid-routed experts and a
shared one behind every layer but the first.

  x0       E[tok]
  block    a = x + Mix(RMS(x)); y = a + F(RMS(a));  u = RMS(x) below
  KDA      (H heads, d_k = d_v = ``linear_attn_config.head_dim``;
           ops/gdn_ops.py)
           [q~ | k~ | v~] = u [W_q | W_k | W_v]            (H d_k each)
           each channel through a depthwise causal convolution over
           time of ``short_conv_kernel_size`` taps, then SiLU: q', k', v'
           q = l2norm(q') / sqrt(d_k), k = l2norm(k'), v = v'
           g = -exp(A_log_h) softplus((u W_f_a) W_f_b + dt_bias) in
           R^(H x d_k), alpha = exp(g); beta = sigmoid(u W_b) a head
           S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1}
                 + beta_t k_t v_t^T,  o_t = S_t^T q_t,  S_0 = 0
           Mix = [RMS_head(o; gain d_v) * sigmoid((u W_g_a) W_g_b)] W_o
           (kernels/primitives/kda.py)
  latent   q = u W_q -> H x [nope | rope]; [c | k_r] = u W_kva;
           c <- RMS(c); NO rotation (``mla_use_nope``): the entries named
           rope are plain entries of the key every head shares.  A
           token's cache row is [c | k_r]; the two forms of
           kernels/primitives/mla.py, as models/kimi_vl.py runs them
           (decode_blocks.py ``latent_attention``).  Order comes from the
           KDA layers alone.
  F        the first ``first_k_dense_replace`` layers: SwiGLU.  The
           others: s = sigmoid(u W_r) in float32; picks = top-k of s + b
           (one group: no group limit); gates scaling x s / (sum of the
           picked s + 1e-20); the picks on the ``held_experts`` experts
           this process holds, plus ONE shared SwiGLU
           (decode_blocks.py ``expert_ffn``).
  head     final RMSNorm, untied lm_head.

What the published config has no key for (the low-rank width of the two
gate projections, where each norm stands, the selection bias) is listed
under ``assumed`` in the benchmark's configuration file, and the plain
reference (benchmark/reference/kimi_linear.py) is written from the same
entries.

What a layer leaves behind (serving/lane.py): a latent layer ONE row a
token, [c | k_r] stored at whole lane tiles (the pool's page kind
``full``; the lane numbers its cache layers 0 .. n_latent - 1); a KDA
layer two tensors a SEQUENCE owns (the kind ``state``, one block a
sequence): the rule's state ``s`` [d_k, H d_v] float32 and the
convolution's last K - 1 pre-activation inputs ``conv`` [(K - 1) 3 H d_k]
float32.

``KimiLinearConfig.decode_lane()`` hands ``_decoder`` and the head to
serving/lane.py, which builds the decode lane's two executables around
them, and ``build_kimi_linear_lm`` a whole sequence on the same
parameter names.  Matrices are stored in ``cfg.dtype`` (bfloat16 in the
serving lane) and multiplied in it with float32 accumulation; norm gains,
the convolution's taps, ``A_log``, ``dt_bias``, the router's product,
the state and activations between ops are float32; latent rows are
``cfg.dtype``.
"""

from __future__ import annotations

import functools

from paddle_tpu.fluid import layers
from paddle_tpu.fluid.initializer import Constant, Normal
from paddle_tpu.fluid.param_attr import ParamAttr

from . import moe_stats
from .decode_blocks import (_attr, _linear, _next_token, _rms, expert_ffn,
                            latent_attention)

KDA, LATENT = "kda", "latent"

# the published layout (27 layers; numbered from 1 as the source does)
PUBLISHED_LINEAR_ATTN = {
    "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
    "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                   22, 23, 25, 26],
    "num_heads": 32, "head_dim": 128, "short_conv_kernel_size": 4}


class KimiLinearConfig:
    """The source's keys under their own names; ``held_experts`` /
    ``first_expert`` say which of the ``num_experts`` this process holds
    (all of them by default), ``gate_low_rank_dim`` the width of the two
    low-rank gate projections (``linear_attn_config.head_dim`` where
    None)."""

    def __init__(self, vocab_size=163840, hidden_size=2304,
                 num_hidden_layers=27, first_k_dense_replace=1,
                 intermediate_size=9216, moe_intermediate_size=1024,
                 num_attention_heads=32, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 linear_attn_config=None, num_experts=256,
                 num_experts_per_token=8, num_shared_experts=1,
                 routed_scaling_factor=2.446, moe_renormalize=True,
                 rms_norm_eps=1e-5, model_max_length=1048576,
                 gate_low_rank_dim=None, l2norm_eps=1e-6, held_experts=None,
                 first_expert=0, dtype="bfloat16", prefill_chunk=None,
                 initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.first_k_dense_replace = first_k_dense_replace
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_attention_heads = num_attention_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        la = dict(linear_attn_config or PUBLISHED_LINEAR_ATTN)
        self.linear_attn_config = la
        self.linear_heads = int(la["num_heads"])
        self.linear_head_dim = int(la["head_dim"])
        self.short_conv_kernel_size = int(la["short_conv_kernel_size"])
        kda = {int(n) for n in la["kda_layers"]}
        full = {int(n) for n in la["full_attn_layers"]}
        if kda & full or kda | full != set(range(1, num_hidden_layers + 1)):
            raise ValueError(
                f"KimiLinearConfig: linear_attn_config's kda_layers and "
                f"full_attn_layers must name each of the layers 1 .. "
                f"{num_hidden_layers} once, got {sorted(kda)} and "
                f"{sorted(full)}")
        self.layer_kinds = [KDA if n + 1 in kda else LATENT
                            for n in range(num_hidden_layers)]
        self.num_experts = num_experts
        self.num_experts_per_token = num_experts_per_token
        self.num_shared_experts = num_shared_experts
        self.routed_scaling_factor = routed_scaling_factor
        self.moe_renormalize = moe_renormalize
        self.rms_norm_eps = rms_norm_eps
        self.model_max_length = model_max_length
        self.gate_low_rank_dim = int(gate_low_rank_dim
                                     or self.linear_head_dim)
        self.l2norm_eps = l2norm_eps
        self.held_experts = (num_experts if held_experts is None
                             else held_experts)
        self.first_expert = first_expert
        self.dtype = dtype
        self.prefill_chunk = prefill_chunk
        self.initializer_range = initializer_range

    # the names decode_blocks.py's shared blocks read (kimi_vl.py's keys)
    n_routed_experts = property(lambda self: self.num_experts)
    num_experts_per_tok = property(lambda self: self.num_experts_per_token)
    n_shared_experts = property(lambda self: self.num_shared_experts)
    norm_topk_prob = property(lambda self: self.moe_renormalize)

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=96, hidden_size=64, num_hidden_layers=4,
                 first_k_dense_replace=1, intermediate_size=96,
                 moe_intermediate_size=24, num_attention_heads=4,
                 kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16,
                 linear_attn_config={
                     "kda_layers": [1, 2, 4], "full_attn_layers": [3],
                     "num_heads": 3, "head_dim": 8,
                     "short_conv_kernel_size": 4},
                 num_experts=8, num_experts_per_token=2,
                 num_shared_experts=1, model_max_length=128,
                 dtype="float32")
        d.update(kw)
        return cls(**d)

    @property
    def kda_layers(self):
        return [i for i, k in enumerate(self.layer_kinds) if k == KDA]

    @property
    def latent_layers(self):
        return [i for i, k in enumerate(self.layer_kinds) if k == LATENT]

    @property
    def moe_layers(self):
        return list(range(self.first_k_dense_replace,
                          self.num_hidden_layers))

    @property
    def conv_channels(self):
        return 3 * self.linear_heads * self.linear_head_dim

    def cache_rows(self, pool_dtype=None):
        """What a token leaves in each latent layer: the row
        [c_kv | k_r], stored at whole lane tiles (576 -> 640)."""
        from paddle_tpu.serving.lane import CacheRow, lane_padded

        dtype = pool_dtype or self.dtype
        if dtype == "int8":
            raise ValueError(
                "models/kimi_linear.py: no int8 form of the latent cache "
                "(the dual-int8 pool is dense K/V's, models/gpt.py)")
        return [CacheRow("latent", lane_padded(
            self.kv_lora_rank + self.qk_rope_head_dim), dtype)]

    def seq_state(self):
        """What a sequence owns in each KDA layer (serving/lane.py
        ``SeqState``): the rule's state, d_k rows of the heads' d_v
        columns side by side (kernels/primitives/gdn.py's layout), and
        the convolution's last K - 1 inputs, both float32."""
        from paddle_tpu.serving import lane

        h, d = self.linear_heads, self.linear_head_dim
        return [
            lane.SeqState("s", (d, h * d), "float32"),
            lane.SeqState("conv", ((self.short_conv_kernel_size - 1)
                                   * self.conv_channels,), "float32")]

    def decode_lane(self):
        """This model's decode-lane declaration (serving/lane.py)."""
        from paddle_tpu.serving import lane

        return lane.scaffold(
            functools.partial(_decoder, cfg=self),
            functools.partial(_next_token, cfg=self, prefix="klin"),
            num_layers=len(self.latent_layers),
            max_position=self.model_max_length,
            cache_rows=self.cache_rows,
            pool_dtype=self.dtype, prefill_chunk=self.prefill_chunk,
            device_counters=moe_stats.expert_stats_counters(self),
            book_counters=functools.partial(moe_stats.book_expert_stats,
                                            self),
            seq_state=self.seq_state(), state_layers=self.kda_layers)


# ---------------------------------------------------------------------------
# layer pieces
# ---------------------------------------------------------------------------


def _low_rank(u, width, name, cfg):
    """(u W_a) W_b through ``gate_low_rank_dim``."""
    return _linear(_linear(u, cfg.gate_low_rank_dim, name + "_a", cfg),
                   width, name + "_b", cfg)


def _kda(x, state, block, q_start, last_idx, row_valid, cfg, name,
         attn_force):
    """One KDA layer's mixer over x [B, T, D]; ``state`` = (s, conv) the
    layer's two state vars, ``block`` the state-block feed.  ``q_start``
    / ``last_idx`` / ``row_valid`` are the chunk's (None in a decode
    step)."""
    L = layers
    h, d = cfg.linear_heads, cfg.linear_head_dim
    s_var, conv_var = state
    u = _rms(x, name + "_attn_norm", cfg)
    qkv = L.concat([_linear(u, h * d, name + "_q", cfg),
                    _linear(u, h * d, name + "_k", cfg),
                    _linear(u, h * d, name + "_v", cfg)], axis=-1)
    qkv = L.short_conv(
        qkv, cfg.short_conv_kernel_size, conv_var, block, q_start, last_idx,
        param_attr=ParamAttr(name=name + "_conv.w_0",
                             initializer=Normal(0.0, 0.3)))
    q, k, v, g, beta = L.gdn_inputs(
        qkv, _low_rank(u, h * d, name + "_f", cfg),
        _linear(u, h, name + "_b", cfg), h, d, d, beta_scale=1.0,
        epsilon=cfg.l2norm_eps, row_valid=row_valid,
        a_log_attr=ParamAttr(name=name + "_A_log",
                             initializer=Constant(0.0)),
        dt_bias_attr=ParamAttr(name=name + "_dt_bias",
                               initializer=Constant(0.0)))
    o = L.gated_delta_rule(q, k, v, g, beta, s_var, block, q_start,
                           force=attn_force)
    o = L.gated_rms_norm(
        o, _low_rank(u, h * d, name + "_g", cfg), epsilon=cfg.rms_norm_eps,
        activation="sigmoid",
        param_attr=ParamAttr(name=name + "_o_norm.scale",
                             initializer=Constant(1.0)))
    return _linear(o, cfg.hidden_size, name + "_o", cfg)


def _decoder(frame, cfg):
    """Embedding and every block over the frame's tokens (serving/lane.py
    ``Frame``) -> hidden [B, T, D] (before the final norm).  The frame's
    ``pools`` hold the latent layers' rows in order, its ``states``
    {layer: (s, conv)} of the KDA layers, which read the chunk's
    ``q_start`` / ``last_idx`` / ``row_valid`` and in a decode step none
    of them.  No layer reads the positions."""
    from paddle_tpu.serving.lane import FULL

    L = layers
    b, t = frame.shape
    emb = L.embedding(frame.tok, size=[cfg.vocab_size, cfg.hidden_size],
                      param_attr=_attr("klin_embed.w_0", cfg),
                      dtype=cfg.dtype)
    x = L.cast(L.reshape(emb, shape=[b, t, cfg.hidden_size]), "float32")
    chunk = ((frame.q_start, frame.last_idx, frame.row_valid)
             if frame.last_idx is not None else (None, None, None))
    latent = iter(frame.pools)
    for layer, kind in enumerate(cfg.layer_kinds):
        name = f"klin_layer_{layer}"
        if kind == KDA:
            mixed = _kda(x, frame.states[layer], frame.state_block, *chunk,
                         cfg, name, frame.attn_force)
        else:
            mixed = latent_attention(
                x, frame.pos, frame.tables[FULL], frame.q_start,
                next(latent), frame.writes[FULL], frame.shape, cfg, name,
                frame.attn_force, rotate=False)
        x = L.elementwise_add(x, mixed)
        x = L.elementwise_add(x, expert_ffn(x, layer, frame.row_valid,
                                            frame.counted_as, cfg, name,
                                            frame.attn_force))
    return x


def build_kimi_linear_lm(cfg: KimiLinearConfig = None, is_test=True,
                         seq_len=None, page_size=None, attn_force=None):
    """A whole sequence in one pass: logprobs [S, V] of every position of
    ``pf_tok`` [1, S] (serving/lane.py ``build_whole_sequence``: the
    decode lane's blocks over caches and state that live and die inside
    the program).  Inference only (``is_test`` is accepted for the zoo's
    calling convention)."""
    del is_test
    cfg = cfg or KimiLinearConfig()
    return cfg.decode_lane().build_whole_sequence(
        seq_len or cfg.prefill_chunk or 128, page_size, attn_force)
