"""The parts the decode-lane decoders share (models/glm.py, trinity.py,
kimi_vl.py, olmo_hybrid.py, mimo.py, kimi_linear.py, qwen3_next.py): a
matrix stored in ``cfg.dtype``, an RMSNorm, a SwiGLU, the
latent-attention RoPE, the head and, under public names, the two blocks
more than one model runs: dense latent attention in its two forms
(``latent_attention``; kimi_vl.py, kimi_linear.py) and the routed expert
layer beside a shared expert (``expert_ffn``; those two and
qwen3_next.py).  ``cfg`` is the model's config: ``dtype``,
``initializer_range``, ``rms_norm_eps``, ``hidden_size``, optionally
``norm_gain_offset`` (1 where every RMSNorm's gain is ``1 + w`` with
``w`` stored; absent or 0: a plain gain) and what each part names below.
The programs around a decoder (feeds, pools, page writers) are
serving/lane.py's."""

from __future__ import annotations

from paddle_tpu.fluid import layers
from paddle_tpu.fluid.initializer import Constant, Normal
from paddle_tpu.fluid.param_attr import ParamAttr

from . import moe_stats


def _attr(name, cfg, init=None):
    return ParamAttr(name=name, initializer=init or Normal(
        0.0, cfg.initializer_range))


def _linear(x, size, name, cfg, head_dim=None):
    return layers.weight_matmul(x, size, param_attr=_attr(name + ".w_0", cfg),
                                dtype=cfg.dtype, head_dim=head_dim)


def _rms(x, name, cfg):
    offset = float(getattr(cfg, "norm_gain_offset", 0.0))
    return layers.rms_norm(
        x, epsilon=cfg.rms_norm_eps, gain_offset=offset,
        param_attr=ParamAttr(name=name + ".scale",
                             initializer=Constant(1.0 - offset)))


def _swiglu_ffn(x, width, name, cfg):
    hidden = layers.swiglu(_linear(x, width, name + "_gate", cfg),
                           _linear(x, width, name + "_up", cfg))
    return _linear(hidden, cfg.hidden_size, name + "_down", cfg)


def _rope(x, pos, cfg):
    """Interleaved-pair RoPE on the first ``qk_rope_head_dim`` entries."""
    return layers.rope_interleaved(x, pos, theta=cfg.rope_theta,
                                   rotary_dim=cfg.qk_rope_head_dim)


def _next_token(h, cfg, prefix):
    """h [N, 1, D] -> (greedy next token [N] int64, logprobs [N, V]):
    the final RMSNorm and the untied head, ``<prefix>_final_norm`` /
    ``<prefix>_head``."""
    L = layers
    logits = L.reshape(_linear(_rms(h, prefix + "_final_norm", cfg),
                               cfg.vocab_size, prefix + "_head", cfg),
                       shape=[-1, cfg.vocab_size])
    logp = L.log_softmax(logits)
    return L.argmax(logp, axis=-1), logp


def latent_attention(x, pos, page_table, q_start, pool, write, shape, cfg,
                     name, attn_force, rotate=True):
    """Latent attention over every visible row; writes the token's cache
    row first (a query sees its own position).  A decode step (T = 1)
    takes the latent-space form, a chunk the head-space form: the same
    parameters either way.  ``cfg``: ``num_attention_heads``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``kv_lora_rank``,
    ``v_head_dim`` and, with ``rotate``, ``rope_theta``.  ``rotate``
    False (a model with no positions in its latent layers): the entries
    named rope are plain entries of the query and of the key every head
    shares, and ``pos`` is not read."""
    L = layers
    b, t = shape
    heads = cfg.num_attention_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    (latent_pool,) = pool
    xa = _rms(x, name + "_attn_norm", cfg)
    q = L.reshape(_linear(xa, heads * (nope + rope), name + "_q", cfg,
                          nope + rope),
                  shape=[b, t, heads, nope + rope])
    q_nope, q_rope = L.split(q, [nope, rope], dim=-1)
    if rotate:
        q_rope = _rope(q_rope, pos, cfg)
    c_kv, k_rope = L.split(
        _linear(xa, cfg.kv_lora_rank + rope, name + "_kv_a", cfg),
        [cfg.kv_lora_rank, rope], dim=-1)
    latent = [_rms(c_kv, name + "_kv_a_norm", cfg),
              _rope(k_rope, pos, cfg) if rotate else k_rope]
    pad = latent_pool.shape[2] - cfg.kv_lora_rank - rope
    if pad:  # the row is stored at whole lane tiles (lane.lane_padded)
        latent.append(L.fill_constant(shape=[b, t, pad], value=0.0,
                                      dtype="float32"))
    write(latent_pool, L.cast(L.concat(latent, axis=2), latent_pool.dtype))

    scale = float(nope + rope) ** -0.5
    k_attr = _attr(name + "_kv_b_k.w_0", cfg)
    v_attr = _attr(name + "_kv_b_v.w_0", cfg)
    if t == 1:
        q_lat = L.headwise_matmul(q_nope, cfg.kv_lora_rank,
                                  param_attr=k_attr, dtype=cfg.dtype)
        o_lat = L.paged_mla_attention(q_lat, q_rope, latent_pool,
                                      page_table, q_start, sm_scale=scale,
                                      force=attn_force)
        o = L.headwise_matmul(o_lat, cfg.v_head_dim, param_attr=v_attr,
                              dtype=cfg.dtype)
    else:
        o = L.mla_chunk_attention(
            q_nope, q_rope, latent_pool, page_table, q_start,
            cfg.kv_lora_rank, cfg.v_head_dim, sm_scale=scale, k_attr=k_attr,
            v_attr=v_attr, dtype=cfg.dtype, force=attn_force)
    return _linear(L.reshape(o, shape=[b, t, heads * cfg.v_head_dim]),
                   cfg.hidden_size, name + "_o", cfg)


def expert_ffn(x, layer, row_valid, counted_as, cfg, name, attn_force,
               score_func="sigmoid", shared_width=None, shared_gate=False):
    """The block's feed-forward half over x [B, T, D], after its norm: a
    SwiGLU in the first ``cfg.first_k_dense_replace`` layers; in the
    others the picks of the router (``score_func``: sigmoid scores and a
    selection bias, or a softmax) that land on the
    ``held_experts`` experts this process holds (ops/mla_ops.py
    ``moe_ffn_held``; counted on the device where ``counted_as`` names
    the executable) plus ONE shared SwiGLU of width ``shared_width``
    (``n_shared_experts`` x ``moe_intermediate_size`` where None), times
    ``sigmoid(u w_sg)``, one number a token (``<name>_shared_expert_gate``
    [D, 1]), where ``shared_gate``.  ``cfg``: those, ``intermediate_size``,
    ``n_routed_experts``, ``num_experts_per_tok``, ``first_expert``,
    ``routed_scaling_factor``, ``norm_topk_prob``."""
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
        dtype=cfg.dtype, force=attn_force, name=name + "_moe",
        score_func=score_func)
    shared = _swiglu_ffn(
        xf, shared_width or cfg.n_shared_experts * cfg.moe_intermediate_size,
        name + "_shared", cfg)
    if shared_gate:
        shared = layers.sigmoid_gate(
            shared, _linear(xf, 1, name + "_shared_expert_gate", cfg))
    return layers.elementwise_add(routed, shared)
