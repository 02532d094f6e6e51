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

Where each norm stands is no key of the published config: the
benchmark's configuration file lists every such choice under ``assumed``
and the plain reference (benchmark/reference/olmo_hybrid.py) is written
from the same entries.

What a layer leaves behind (serving/lane.py): a full-attention layer a K
and a V row a TOKEN (the pool's page kind ``full``; the lane numbers its
cache layers 0 .. n_full - 1); a linear-attention layer two tensors a
SEQUENCE owns (the kind ``state``, one block a sequence): the rule's
state ``s`` [d_k, H d_v] float32 and the convolution's last K - 1
pre-activation inputs ``conv`` [(K - 1) (2 H d_k + H d_v)] float32.

Three builders on the same parameter names: ``build_olmo_hybrid_lm`` (a
whole sequence, caches and state program-local),
``build_olmo_hybrid_decode_step`` and ``build_olmo_hybrid_prefill_chunk``
(the decode lane's two executables; ``OlmoHybridConfig.decode_lane()``
hands them to ``serving.DecodeEngine``).  Matrices are stored in
``cfg.dtype`` (bfloat16 in the serving lane) and multiplied in it with
float32 accumulation; norm gains, the convolution's taps, ``A_log``,
``dt_bias``, the state and activations between ops are float32; K/V
rows are ``cfg.dtype``.
"""

from __future__ import annotations

import functools

from paddle_tpu import fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.initializer import Constant, Normal
from paddle_tpu.fluid.param_attr import ParamAttr

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
            raise ValueError("OlmoHybridConfig: as many key heads as value "
                             "heads in a linear-attention layer")
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

        return lane.DecodeLane(
            num_layers=len(self.full_layers),
            max_position=self.max_position_embeddings,
            cache_rows=self.cache_rows,
            build_decode_step=functools.partial(
                build_olmo_hybrid_decode_step, self),
            build_prefill_chunk=functools.partial(
                build_olmo_hybrid_prefill_chunk, self),
            pool_dtype=self.dtype, prefill_chunk=self.prefill_chunk,
            seq_state=self.seq_state(), state_layers=self.linear_layers)


# ---------------------------------------------------------------------------
# layer pieces
# ---------------------------------------------------------------------------


def _attr(name, cfg):
    return ParamAttr(name=name,
                     initializer=Normal(0.0, cfg.initializer_range))


def _linear(x, size, name, cfg):
    return layers.weight_matmul(x, size, param_attr=_attr(name + ".w_0", cfg),
                                dtype=cfg.dtype)


def _rms(x, name, cfg):
    return layers.rms_norm(
        x, epsilon=cfg.rms_norm_eps,
        param_attr=ParamAttr(name=name + ".scale",
                             initializer=Constant(1.0)))


def _swiglu_ffn(x, name, cfg):
    hidden = layers.swiglu(
        _linear(x, cfg.intermediate_size, name + "_gate", cfg),
        _linear(x, cfg.intermediate_size, name + "_up", cfg))
    return _linear(hidden, cfg.hidden_size, name + "_down", cfg)


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


def _decoder(tok, pos, page_table, q_start, pools, write, states, block,
             shape, cfg, attn_force=None, chunk=None):
    """Embedding and every block over tok/pos [B, T] -> hidden [B, T, D]
    (before the final norm).  ``pools``: per full-attention layer, in
    order, its (K, V) pool vars; ``states``: {layer: (s, conv)} of the
    linear-attention layers; ``chunk``: (last_idx, row_valid) of a
    prefill chunk, None in a decode step."""
    L = layers
    b, t = shape
    emb = L.embedding(tok, size=[cfg.vocab_size, cfg.hidden_size],
                      param_attr=_attr("olmo_embed.w_0", cfg),
                      dtype=cfg.dtype)
    x = L.cast(L.reshape(emb, shape=[b, t, cfg.hidden_size]), "float32")
    last_idx, row_valid = chunk if chunk is not None else (None, None)
    full = iter(pools)
    for layer, kind in enumerate(cfg.layer_types):
        name = f"olmo_layer_{layer}"
        if kind == LINEAR:
            mixed = _linear_attention(
                x, states[layer], block,
                q_start if chunk is not None else None, last_idx, row_valid,
                cfg, name, attn_force)
        else:
            mixed = _full_attention(x, pos, page_table, q_start, next(full),
                                    write, shape, cfg, name, attn_force)
        x = L.elementwise_add(x, mixed)
        ffn = _swiglu_ffn(x, name + "_ffn", cfg)
        x = L.elementwise_add(x, _rms(ffn, name + "_post_ff_norm", cfg))
    return x


def _next_token(h, cfg):
    """h [N, 1, D] -> (greedy next token [N] int64, logprobs [N, V])."""
    L = layers
    logits = L.reshape(_linear(_rms(h, "olmo_final_norm", cfg),
                               cfg.vocab_size, "olmo_head", cfg),
                       shape=[-1, cfg.vocab_size])
    logp = L.log_softmax(logits)
    return L.argmax(logp, axis=-1), logp


def _declare(cfg, num_pages, page_size, pool_dtype, state_blocks):
    from paddle_tpu.serving import lane

    pools = lane.declare_pool_vars(
        cfg.cache_rows(pool_dtype), len(cfg.full_layers), num_pages,
        page_size)
    states = lane.declare_state_vars(cfg.seq_state(), cfg.linear_layers,
                                     state_blocks)
    return pools, states


# ---------------------------------------------------------------------------
# the three builders
# ---------------------------------------------------------------------------


def build_olmo_hybrid_decode_step(cfg: OlmoHybridConfig, pool_slots,
                                  num_pages, page_size, max_pages,
                                  pool_dtype=None, attn_force=None,
                                  state_blocks=None):
    """ONE token-level decode step over the paged K/V caches and the
    per-sequence state: the feeds, the output and the slot semantics of
    models/gpt.py build_gpt_decode_step, and ``dec_state_block`` [slots]
    each slot's state block (the trash block 0 for an inactive slot)."""
    from paddle_tpu.serving import lane

    L = layers
    ps = int(pool_slots)
    tok = fluid.data("dec_tok", [ps, 1], False, dtype="int64")
    pos = fluid.data("dec_pos", [ps, 1], False, dtype="int64")
    table = fluid.data("dec_page_table", [ps, int(max_pages)], False,
                       dtype="int32")
    write_page = fluid.data("dec_write_page", [ps], False, dtype="int32")
    write_off = fluid.data("dec_write_off", [ps], False, dtype="int32")
    block = fluid.data(lane.STATE_FEEDS["decode"], [ps], False,
                       dtype="int32")
    feeds = ["dec_tok", "dec_pos", "dec_page_table", "dec_write_page",
             "dec_write_off", lane.STATE_FEEDS["decode"]]
    pools, states = _declare(cfg, num_pages, page_size, pool_dtype,
                             state_blocks or ps + 2)
    q_start = L.cast(L.reshape(pos, shape=[-1]), "int32")

    def write(pool, rows):                                 # rows [PS, 1, w]
        L.kv_cache_write(pool, rows, write_page, write_off)

    x = _decoder(tok, pos, table, q_start, pools, write, states, block,
                 (ps, 1), cfg, attn_force)
    next_tok, logp = _next_token(x, cfg)
    return feeds, next_tok, logp


def _chunk(cfg, c, table, write_pages, q_start, last_idx, pools, states,
           block, attn_force):
    """One sequence's chunk of ``c`` tokens through the blocks; returns
    the hidden state of every position [1, C, D]."""
    L = layers
    tok = fluid.data("pf_tok", [1, c], False, dtype="int64")
    pos = fluid.data("pf_pos", [1, c], False, dtype="int64")

    def write(pool, rows):                                 # rows [1, C, w]
        L.kv_cache_write_pages(pool, L.reshape(rows, shape=[c, 1, -1]),
                               write_pages)

    row_valid = L.cast(L.less_equal(L.range(0, c, 1, "int64"), last_idx),
                       "int32")
    return _decoder(tok, pos, table, q_start, pools, write, states, block,
                    (1, c), cfg, attn_force, chunk=(last_idx, row_valid))


def build_olmo_hybrid_prefill_chunk(cfg: OlmoHybridConfig, chunk_len,
                                    num_pages, page_size, max_pages,
                                    pool_dtype=None, attn_force=None,
                                    state_blocks=None):
    """One prefill CHUNK of a single sequence: the feeds, the output and
    the page-write semantics of models/gpt.py build_gpt_prefill_chunk,
    and ``pf_state_block`` [1] the sequence's state block, read as zeros
    where ``pf_qstart`` is 0 and carried to the next chunk (rows past
    ``pf_last_idx`` leave it alone)."""
    from paddle_tpu.serving import lane

    L = layers
    c = int(chunk_len)
    if c % int(page_size):
        raise ValueError(
            f"prefill chunk_len {c} must be a multiple of page_size "
            f"{page_size} (chunks write whole pages)")
    table = fluid.data("pf_page_table", [1, int(max_pages)], False,
                       dtype="int32")
    write_pages = fluid.data("pf_write_pages", [c // int(page_size)], False,
                             dtype="int32")
    q_start = fluid.data("pf_qstart", [1], False, dtype="int32")
    last_idx = fluid.data("pf_last_idx", [1], False, dtype="int64")
    block = fluid.data(lane.STATE_FEEDS["prefill"], [1], False,
                       dtype="int32")
    feeds = ["pf_tok", "pf_pos", "pf_page_table", "pf_write_pages",
             "pf_qstart", "pf_last_idx", lane.STATE_FEEDS["prefill"]]
    pools, states = _declare(cfg, num_pages, page_size, pool_dtype,
                             state_blocks or 2)
    x = _chunk(cfg, c, table, write_pages, q_start, last_idx, pools, states,
               block, attn_force)
    flat = L.reshape(x, shape=[-1, cfg.hidden_size])
    h_last = L.reshape(L.gather(flat, last_idx),
                       shape=[-1, 1, cfg.hidden_size])
    next_tok, logp = _next_token(h_last, cfg)
    return feeds, next_tok, logp


def build_olmo_hybrid_lm(cfg: OlmoHybridConfig = None, is_test=True,
                         seq_len=None, page_size=None, attn_force=None):
    """A whole sequence in one pass: logprobs [S, V] of every position of
    ``pf_tok`` [1, S].  The same blocks as the decode lane's chunk over
    caches and state that live and die inside the program (the identity
    page table; state block 1 of 2, read as zeros).  Inference only
    (``is_test`` is accepted for the zoo's calling convention)."""
    del is_test
    L = layers
    cfg = cfg or OlmoHybridConfig()
    c = int(seq_len or cfg.prefill_chunk or 128)
    page = int(page_size or min(c, 128))
    if c % page:
        raise ValueError(f"seq_len {c} must be a multiple of page {page}")
    n = c // page
    page_table = L.reshape(L.cast(L.range(1, n + 1, 1, "int64"), "int32"),
                           shape=[1, n])
    q_start = L.fill_constant(shape=[1], value=0, dtype="int32")
    last_idx = L.fill_constant(shape=[1], value=c - 1, dtype="int64")
    block = L.fill_constant(shape=[1], value=1, dtype="int32")
    pools = [tuple(L.fill_constant(shape=[n + 1, page, row.width], value=0.0,
                                   dtype=row.dtype)
                   for row in cfg.cache_rows())
             for _ in cfg.full_layers]
    states = {layer: tuple(L.fill_constant(shape=[2, *st.shape], value=0.0,
                                           dtype=st.dtype)
                           for st in cfg.seq_state())
              for layer in cfg.linear_layers}
    x = _chunk(cfg, c, page_table, L.reshape(page_table, shape=[n]), q_start,
               last_idx, pools, states, block, attn_force)
    _, logp = _next_token(L.reshape(x, shape=[c, 1, cfg.hidden_size]), cfg)
    return logp
