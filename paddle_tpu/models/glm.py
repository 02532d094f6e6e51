"""GLM-5-style decoder LM (``model_type: glm_moe_dsa``), Fluid
graph-building style: multi-head latent attention (MLA), a learned
sparse-attention indexer (DSA), and sigmoid-routed experts of which
this process holds a share.

Pre-norm residual blocks (RMSNorm), untied head.  Per layer:

  MLA      c_q = RMS(x W_qa); q = c_q W_qb -> per head [q_nope | q_rope];
           [c_kv | k_rope] = x W_kva; c_kv <- RMS(c_kv); RoPE (interleaved
           pairs) on q_rope and k_rope, k_rope shared by all heads.  A
           token's LATENT cache row is [c_kv | k_rope].  Attention runs in
           the absorbed form: q_lat = q_nope W_kvb_k (per head, into the
           compressed-KV space), scores = (q_lat . c_kv + q_rope . k_rope)
           / sqrt(nope + rope), o = (softmax . c_kv) W_kvb_v, then W_o.
           W_kvb is stored as its two halves, one [nope, c] and one
           [c, v] matrix a head.
  indexer  qI = c_q W_Iq (RoPE on the first rope dims of each head);
           kI = LayerNorm(x W_Ik) (same RoPE) — the token's INDEXER cache
           row; w = x W_Iw / sqrt(heads * head_dim);
           I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]); a query attends
           the ``index_topk`` positions s <= t with the largest I.
  FFN      the first ``first_k_dense_replace`` layers: SwiGLU.  The
           others: sigmoid router over ``n_routed_experts`` with a
           selection bias, ``num_experts_per_tok`` picks, gates
           normalised and scaled, over the ``held_experts`` experts from
           ``first_expert`` that this process holds (picks on the others
           add nothing: the partial sum an expert-parallel deployment
           adds up across chips), plus one shared SwiGLU expert.

Three builders on the same parameter names: ``build_glm_lm`` (a whole
sequence, its caches program-local), ``build_glm_decode_step`` and
``build_glm_prefill_chunk`` (the decode lane's two executables over the
paged caches; ``GLMConfig.decode_lane()`` hands them to
``serving.DecodeEngine``).  Matrices are stored in ``cfg.dtype``
(bfloat16 in the serving lane) and multiplied in it with float32
accumulation; norm scales, the router's bias and activations between
ops are float32; cache rows are ``cfg.dtype``.
"""

from __future__ import annotations

import functools

from paddle_tpu import fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.initializer import Constant, Normal
from paddle_tpu.fluid.param_attr import ParamAttr

from . import moe_stats


class GLMConfig:
    def __init__(self, vocab_size=154880, hidden_size=6144,
                 num_hidden_layers=78, first_k_dense_replace=3,
                 intermediate_size=12288, moe_intermediate_size=2048,
                 num_attention_heads=64, q_lora_rank=2048, kv_lora_rank=512,
                 qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
                 index_n_heads=32, index_head_dim=128, index_topk=2048,
                 n_routed_experts=256, num_experts_per_tok=8,
                 n_shared_experts=1, routed_scaling_factor=2.5,
                 norm_topk_prob=True, rms_norm_eps=1e-5, rope_theta=1e6,
                 max_position_embeddings=202752, held_experts=None,
                 first_expert=0, dtype="bfloat16", prefill_chunk=None,
                 initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.first_k_dense_replace = first_k_dense_replace
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.index_n_heads = index_n_heads
        self.index_head_dim = index_head_dim
        self.index_topk = index_topk
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_shared_experts = n_shared_experts
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.max_position_embeddings = max_position_embeddings
        # the experts this process holds of n_routed_experts (all of them
        # by default): ids first_expert .. first_expert + held_experts
        self.held_experts = (n_routed_experts if held_experts is None
                             else held_experts)
        self.first_expert = first_expert
        self.dtype = dtype
        self.prefill_chunk = prefill_chunk
        self.initializer_range = initializer_range
        if n_shared_experts != 1:
            raise ValueError("GLMConfig: one shared expert a layer")

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=96, hidden_size=32, num_hidden_layers=3,
                 first_k_dense_replace=1, intermediate_size=64,
                 moe_intermediate_size=16, num_attention_heads=4,
                 q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
                 qk_rope_head_dim=4, v_head_dim=8, index_n_heads=2,
                 index_head_dim=8, index_topk=6, n_routed_experts=8,
                 num_experts_per_tok=2, max_position_embeddings=64,
                 dtype="float32")
        d.update(kw)
        return cls(**d)

    @property
    def moe_layers(self):
        return list(range(self.first_k_dense_replace,
                          self.num_hidden_layers))

    def cache_rows(self, pool_dtype=None):
        """What a token leaves in each layer: the latent row and the
        indexer's key, two kinds of state under one page table."""
        from paddle_tpu.serving.lane import CacheRow, lane_padded

        dtype = pool_dtype or self.dtype
        if dtype == "int8":
            raise ValueError(
                "models/glm.py: no int8 form of the latent and indexer "
                "caches (the dual-int8 pool is dense K/V's, models/gpt.py)")
        # [c_kv | k_rope], stored at whole lane tiles (576 -> 640)
        return [CacheRow("latent", lane_padded(
            self.kv_lora_rank + self.qk_rope_head_dim), dtype),
                CacheRow("index", self.index_head_dim, dtype)]

    def decode_lane(self):
        """This model's decode-lane declaration (serving/lane.py)."""
        from paddle_tpu.serving import lane

        return lane.DecodeLane(
            num_layers=self.num_hidden_layers,
            max_position=self.max_position_embeddings,
            cache_rows=self.cache_rows,
            build_decode_step=functools.partial(build_glm_decode_step, self),
            build_prefill_chunk=functools.partial(build_glm_prefill_chunk,
                                                  self),
            pool_dtype=self.dtype, prefill_chunk=self.prefill_chunk,
            device_counters=moe_stats.expert_stats_counters(self),
            book_counters=functools.partial(moe_stats.book_expert_stats,
                                            self))


# ---------------------------------------------------------------------------
# layer pieces
# ---------------------------------------------------------------------------


def _attr(name, cfg, init=None):
    return ParamAttr(name=name, initializer=init or Normal(
        0.0, cfg.initializer_range))


def _linear(x, size, name, cfg, head_dim=None):
    return layers.weight_matmul(x, size, param_attr=_attr(name + ".w_0", cfg),
                                dtype=cfg.dtype, head_dim=head_dim)


def _rms(x, name, cfg):
    return layers.rms_norm(
        x, epsilon=cfg.rms_norm_eps,
        param_attr=ParamAttr(name=name + ".scale",
                             initializer=Constant(1.0)))


def _swiglu_ffn(x, width, name, cfg):
    hidden = layers.swiglu(_linear(x, width, name + "_gate", cfg),
                           _linear(x, width, name + "_up", cfg))
    return _linear(hidden, cfg.hidden_size, name + "_down", cfg)


def _rope(x, pos, cfg):
    return layers.rope_interleaved(x, pos, theta=cfg.rope_theta,
                                   rotary_dim=cfg.qk_rope_head_dim)


def _attention(x, pos, page_table, q_start, pools, write, shape, cfg, name,
               attn_force):
    """MLA over the DSA-selected rows; writes the token's two cache rows
    first (a query sees its own position)."""
    L = layers
    b, t = shape
    heads = cfg.num_attention_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    latent_pool, index_pool = pools
    xa = _rms(x, name + "_attn_norm", cfg)
    c_q = _rms(_linear(xa, cfg.q_lora_rank, name + "_q_a", cfg),
               name + "_q_a_norm", cfg)
    q = L.reshape(_linear(c_q, heads * (nope + rope), name + "_q_b", cfg),
                  shape=[b, t, heads, nope + rope])
    q_nope, q_rope = L.split(q, [nope, rope], dim=-1)
    q_rope = _rope(q_rope, pos, cfg)
    c_kv, k_rope = L.split(
        _linear(xa, cfg.kv_lora_rank + rope, name + "_kv_a", cfg),
        [cfg.kv_lora_rank, rope], dim=-1)
    latent = [_rms(c_kv, name + "_kv_a_norm", cfg), _rope(k_rope, pos, cfg)]
    pad = latent_pool.shape[2] - cfg.kv_lora_rank - rope
    if pad:  # the row is stored at whole lane tiles (lane.lane_padded)
        latent.append(L.fill_constant(shape=[b, t, pad], value=0.0,
                                      dtype="float32"))
    write(latent_pool, L.cast(L.concat(latent, axis=2), latent_pool.dtype))

    hi, di = cfg.index_n_heads, cfg.index_head_dim
    q_idx = _rope(L.reshape(_linear(c_q, hi * di, name + "_idx_q", cfg),
                            shape=[b, t, hi, di]), pos, cfg)
    k_idx = L.layer_norm(
        _linear(xa, di, name + "_idx_k", cfg), begin_norm_axis=2,
        param_attr=ParamAttr(name=name + "_idx_k_norm.scale",
                             initializer=Constant(1.0)),
        bias_attr=ParamAttr(name=name + "_idx_k_norm.bias",
                            initializer=Constant(0.0)))
    write(index_pool, L.cast(_rope(k_idx, pos, cfg), index_pool.dtype))
    w_idx = L.scale(_linear(xa, hi, name + "_idx_w", cfg),
                    scale=float(hi) ** -0.5 * float(di) ** -0.5)
    scores = L.dsa_indexer_scores(q_idx, w_idx, index_pool, page_table,
                                  q_start, force=attn_force)
    selected = L.dsa_topk_select(scores, cfg.index_topk, force=attn_force)

    q_lat = L.headwise_matmul(q_nope, cfg.kv_lora_rank,
                              param_attr=_attr(name + "_kv_b_k.w_0", cfg),
                              dtype=cfg.dtype)
    o_lat = L.sparse_mla_attention(
        q_lat, q_rope, latent_pool, page_table, selected, q_start,
        sm_scale=float(nope + rope) ** -0.5, force=attn_force)
    o = L.headwise_matmul(o_lat, cfg.v_head_dim,
                          param_attr=_attr(name + "_kv_b_v.w_0", cfg),
                          dtype=cfg.dtype)
    return _linear(L.reshape(o, shape=[b, t, heads * cfg.v_head_dim]),
                   cfg.hidden_size, name + "_o", cfg)


def _ffn(x, layer, row_valid, counted_as, cfg, name, attn_force):
    xf = _rms(x, name + "_ffn_norm", cfg)
    if layer < cfg.first_k_dense_replace:
        return _swiglu_ffn(xf, cfg.intermediate_size, name + "_ffn", cfg)
    stats = (moe_stats.expert_stats_var(cfg, layer, counted_as)
             if counted_as else None)
    routed = layers.moe_ffn_held(
        xf, cfg.n_routed_experts, cfg.held_experts,
        cfg.moe_intermediate_size, cfg.num_experts_per_tok,
        first_expert=cfg.first_expert,
        routed_scaling_factor=cfg.routed_scaling_factor,
        norm_topk_prob=cfg.norm_topk_prob, row_valid=row_valid, stats=stats,
        dtype=cfg.dtype, force=attn_force, name=name + "_moe")
    shared = _swiglu_ffn(xf, cfg.moe_intermediate_size, name + "_shared",
                         cfg)
    return layers.elementwise_add(routed, shared)


def _decoder(tok, pos, page_table, q_start, pools, write, row_valid, shape,
             cfg, attn_force=None, counted_as=None):
    """Embedding and every block over tok/pos [B, T] -> hidden [B, T, D]
    (before the final norm)."""
    L = layers
    b, t = shape
    emb = L.embedding(tok, size=[cfg.vocab_size, cfg.hidden_size],
                      param_attr=_attr("glm_embed.w_0", cfg),
                      dtype=cfg.dtype)
    x = L.cast(L.reshape(emb, shape=[b, t, cfg.hidden_size]), "float32")
    for layer in range(cfg.num_hidden_layers):
        name = f"glm_layer_{layer}"
        x = L.elementwise_add(x, _attention(
            x, pos, page_table, q_start, pools[layer], write, shape, cfg,
            name, attn_force))
        x = L.elementwise_add(x, _ffn(x, layer, row_valid, counted_as, cfg,
                                      name, attn_force))
    return x


def _next_token(h, cfg):
    """h [N, 1, D] -> (greedy next token [N] int64, logprobs [N, V])."""
    L = layers
    logits = L.reshape(_linear(_rms(h, "glm_final_norm", cfg),
                               cfg.vocab_size, "glm_head", cfg),
                       shape=[-1, cfg.vocab_size])
    logp = L.log_softmax(logits)
    return L.argmax(logp, axis=-1), logp


def _declare_pools(cfg, num_pages, page_size, pool_dtype):
    from paddle_tpu.serving import lane

    return lane.declare_pool_vars(
        cfg.cache_rows(pool_dtype), cfg.num_hidden_layers, num_pages,
        page_size)


# ---------------------------------------------------------------------------
# the three builders
# ---------------------------------------------------------------------------


def build_glm_decode_step(cfg: GLMConfig, pool_slots, num_pages, page_size,
                          max_pages, pool_dtype=None, attn_force=None):
    """ONE token-level decode step over the paged latent and indexer
    caches: the feeds, the output and the slot semantics of
    models/gpt.py build_gpt_decode_step (inactive slots write the trash
    page; their picks count for nothing)."""
    L = layers
    ps = int(pool_slots)
    tok = fluid.data("dec_tok", [ps, 1], False, dtype="int64")
    pos = fluid.data("dec_pos", [ps, 1], False, dtype="int64")
    page_table = fluid.data("dec_page_table", [ps, int(max_pages)], False,
                            dtype="int32")
    write_page = fluid.data("dec_write_page", [ps], False, dtype="int32")
    write_off = fluid.data("dec_write_off", [ps], False, dtype="int32")
    pools = _declare_pools(cfg, num_pages, page_size, pool_dtype)
    q_start = L.cast(L.reshape(pos, shape=[-1]), "int32")

    def write(pool, rows):                                 # rows [PS, 1, w]
        L.kv_cache_write(pool, rows, write_page, write_off)

    x = _decoder(tok, pos, page_table, q_start, pools, write, write_page,
                 (ps, 1), cfg, attn_force, counted_as="decode")
    next_tok, logp = _next_token(x, cfg)
    feeds = ["dec_tok", "dec_pos", "dec_page_table", "dec_write_page",
             "dec_write_off"]
    return feeds, next_tok, logp


def _chunk(cfg, c, page_table, write_pages, q_start, last_idx, pools,
           attn_force, counted_as="prefill"):
    """One sequence's chunk of ``c`` tokens through the blocks; returns
    the hidden state of every position [1, C, D]."""
    L = layers
    tok = fluid.data("pf_tok", [1, c], False, dtype="int64")
    pos = fluid.data("pf_pos", [1, c], False, dtype="int64")

    def write(pool, rows):                                 # rows [1, C, w]
        L.kv_cache_write_pages(
            pool, L.reshape(rows, shape=[c, 1, -1]), write_pages)

    row_valid = L.cast(L.less_equal(L.range(0, c, 1, "int64"), last_idx),
                       "int32")
    return _decoder(tok, pos, page_table, q_start, pools, write, row_valid,
                    (1, c), cfg, attn_force, counted_as)


def build_glm_prefill_chunk(cfg: GLMConfig, chunk_len, num_pages, page_size,
                            max_pages, pool_dtype=None, attn_force=None):
    """One prefill CHUNK of a single sequence through the paged caches:
    the feeds, the output and the page-write semantics of models/gpt.py
    build_gpt_prefill_chunk."""
    L = layers
    c = int(chunk_len)
    if c % int(page_size):
        raise ValueError(
            f"prefill chunk_len {c} must be a multiple of page_size "
            f"{page_size} (chunks write whole pages)")
    page_table = fluid.data("pf_page_table", [1, int(max_pages)], False,
                            dtype="int32")
    write_pages = fluid.data("pf_write_pages", [c // int(page_size)], False,
                             dtype="int32")
    q_start = fluid.data("pf_qstart", [1], False, dtype="int32")
    last_idx = fluid.data("pf_last_idx", [1], False, dtype="int64")
    pools = _declare_pools(cfg, num_pages, page_size, pool_dtype)
    x = _chunk(cfg, c, page_table, write_pages, q_start, last_idx, pools,
               attn_force)
    flat = L.reshape(x, shape=[-1, cfg.hidden_size])
    h_last = L.reshape(L.gather(flat, last_idx),
                       shape=[-1, 1, cfg.hidden_size])
    next_tok, logp = _next_token(h_last, cfg)
    feeds = ["pf_tok", "pf_pos", "pf_page_table", "pf_write_pages",
             "pf_qstart", "pf_last_idx"]
    return feeds, next_tok, logp


def build_glm_lm(cfg: GLMConfig = None, is_test=True, seq_len=None,
                 page_size=None, attn_force=None):
    """A whole sequence in one pass: logprobs [S, V] of every position of
    ``pf_tok`` [1, S].  The same blocks as the decode lane's chunk over
    caches that live and die inside the program (identity page table).
    Inference only (``is_test`` is accepted for the zoo's calling
    convention)."""
    del is_test
    L = layers
    cfg = cfg or GLMConfig()
    c = int(seq_len or cfg.prefill_chunk or 128)
    page = int(page_size or min(c, 128))
    if c % page:
        raise ValueError(f"seq_len {c} must be a multiple of page {page}")
    n = c // page
    page_table = L.reshape(L.cast(L.range(1, n + 1, 1, "int64"), "int32"),
                           shape=[1, n])
    write_pages = L.reshape(page_table, shape=[n])
    q_start = L.fill_constant(shape=[1], value=0, dtype="int32")
    last_idx = L.fill_constant(shape=[1], value=c - 1, dtype="int64")
    pools = [tuple(L.fill_constant(shape=[n + 1, page, row.width], value=0.0,
                                   dtype=row.dtype)
                   for row in cfg.cache_rows())
             for _ in range(cfg.num_hidden_layers)]
    x = _chunk(cfg, c, page_table, write_pages, q_start, last_idx, pools,
               attn_force, counted_as=None)
    _, logp = _next_token(L.reshape(x, shape=[c, 1, cfg.hidden_size]), cfg)
    return logp
