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

``GLMConfig.decode_lane()`` hands ``_decoder`` and the head to
serving/lane.py, which builds the decode lane's two executables over
the paged caches around them, and ``build_glm_lm`` a whole sequence on
the same parameter names, its caches program-local.  Matrices are stored
in ``cfg.dtype``
(bfloat16 in the serving lane) and multiplied in it with float32
accumulation; norm scales, the router's bias and activations between
ops are float32; cache rows are ``cfg.dtype``.
"""

from __future__ import annotations

import functools

from paddle_tpu.fluid import layers
from paddle_tpu.fluid.initializer import Constant
from paddle_tpu.fluid.param_attr import ParamAttr

from . import moe_stats
from .decode_blocks import (_attr, _linear, _next_token, _rms, _rope,
                            _swiglu_ffn)


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

        return lane.scaffold(
            functools.partial(_decoder, cfg=self),
            functools.partial(_next_token, cfg=self, prefix="glm"),
            num_layers=self.num_hidden_layers,
            max_position=self.max_position_embeddings,
            cache_rows=self.cache_rows,
            pool_dtype=self.dtype, prefill_chunk=self.prefill_chunk,
            device_counters=moe_stats.expert_stats_counters(self),
            book_counters=functools.partial(moe_stats.book_expert_stats,
                                            self))


# ---------------------------------------------------------------------------
# layer pieces
# ---------------------------------------------------------------------------


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


def _decoder(frame, cfg):
    """Embedding and every block over the frame's tokens (serving/lane.py
    ``Frame``) -> hidden [B, T, D] (before the final norm)."""
    from paddle_tpu.serving.lane import FULL

    L = layers
    b, t = frame.shape
    emb = L.embedding(frame.tok, size=[cfg.vocab_size, cfg.hidden_size],
                      param_attr=_attr("glm_embed.w_0", cfg),
                      dtype=cfg.dtype)
    x = L.cast(L.reshape(emb, shape=[b, t, cfg.hidden_size]), "float32")
    for layer in range(cfg.num_hidden_layers):
        name = f"glm_layer_{layer}"
        x = L.elementwise_add(x, _attention(
            x, frame.pos, frame.tables[FULL], frame.q_start,
            frame.pools[layer], frame.writes[FULL], frame.shape, cfg, name,
            frame.attn_force))
        x = L.elementwise_add(x, _ffn(x, layer, frame.row_valid,
                                      frame.counted_as, cfg, name,
                                      frame.attn_force))
    return x


def build_glm_lm(cfg: GLMConfig = None, is_test=True, seq_len=None,
                 page_size=None, attn_force=None):
    """A whole sequence in one pass: logprobs [S, V] of every position of
    ``pf_tok`` [1, S] (serving/lane.py ``build_whole_sequence``: the
    decode lane's blocks over caches that live and die inside the
    program).  Inference only (``is_test`` is accepted for the zoo's
    calling convention)."""
    del is_test
    cfg = cfg or GLMConfig()
    return cfg.decode_lane().build_whole_sequence(
        seq_len or cfg.prefill_chunk or 128, page_size, attn_force)
