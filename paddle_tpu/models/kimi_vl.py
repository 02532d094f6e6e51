"""Kimi-VL-style vision-language decoder (``model_type: kimi_vl``), Fluid
graph-building style: a DeepSeek-V3-shaped decoder with multi-head
latent attention over EVERY visible row (no query low-rank path, no
indexer), sigmoid-routed experts all held here, behind a MoonViT
native-resolution tower and an MLP projector whose rows stand in for the
media placeholder's embedding.

  x0[p]    E[tok[p]], except at a position that holds an image row (the
           media placeholder id): there the next row of the projector's
           output for the request's images, in order.  Positions are
           plain 1-D for every token.
  block    a = x + Attn(RMS(x)); y = a + F(RMS(a))
  Attn(u)  q = u W_q -> H x [nope | rope]; [c | k_r] = u W_kva;
           c <- RMS(c); RoPE (interleaved pairs) on k_r (one row shared
           by all heads) and on each head's rope dims of q.  A token's
           cache row is [c | k_r].  Two forms of the same numbers
           (kernels/primitives/mla.py): the decode step in LATENT space,
           q~_h = q_nope,h W_uk,h, scores (q~_h . c + q_rope,h . k_r) /
           sqrt(nope + rope), o_h = (P_h c) W_uv,h; the prefill chunk in
           HEAD space, k_h = [c W_uk,h | k_r], v_h = c W_uv,h, built
           from the cached rows inside the kernel.  Then W_o.
  F        the first ``first_k_dense_replace`` layers: SwiGLU.  The
           others: s = sigmoid(u W_r) in float32; picks = top-k of s + b
           (no group limit); gates scaling x s / (sum of the picked s +
           1e-20); the picks on the ``held_experts`` experts this process
           holds (all of them by default: ops/mla_ops.py
           ``moe_ffn_held``), plus ONE shared SwiGLU of width
           n_shared_experts x moe_intermediate_size.
  head     final RMSNorm, untied lm_head.
  tower    an image of (grid_h, grid_w) patches of 14 x 14 x 3 values:
           z = patch W_p + b_p + table resized (bicubic) to the grid;
           pre-LayerNorm blocks z += W_o Attn2d(LN z); z += fc1
           gelu_tanh(fc0 LN z), every linear with bias, attention
           bidirectional inside the image with 2-D RoPE on q and k
           (ops/vision_ops.py); a final LayerNorm; 2 x 2 neighbouring
           patches side by side, one image row a 4 patches (row-major
           over the merged grid); projector LN a patch, Linear + exact
           GeLU + Linear to the decoder's width.

``KimiVLConfig.decode_lane()`` hands ``_decoder`` and the head to
serving/lane.py, which builds the decode lane's two executables around
them, and ``build_kimi_vl_vision_encoder`` as the lane's
``ImageEncoder``; ``build_kimi_vl_lm`` builds a whole text sequence on
the same parameter names, its caches program-local.
Matrices are stored in ``cfg.dtype`` (bfloat16 in the serving lane) and
multiplied in it with float32 accumulation; norms, biases, the router's
product, the position table and activations between ops are float32;
cache rows are ``cfg.dtype``, staged image rows float32.
"""

from __future__ import annotations

import functools

import numpy as np

from paddle_tpu import fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.initializer import Constant
from paddle_tpu.fluid.param_attr import ParamAttr

from . import moe_stats
from .decode_blocks import (_attr, _linear, _next_token, expert_ffn,
                            latent_attention)


class KimiVLConfig:
    def __init__(self, vocab_size=163840, hidden_size=2048,
                 num_hidden_layers=27, first_k_dense_replace=1,
                 intermediate_size=11264, moe_intermediate_size=1408,
                 num_attention_heads=16, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 n_routed_experts=64, num_experts_per_tok=6,
                 n_shared_experts=2, routed_scaling_factor=2.446,
                 norm_topk_prob=True, rms_norm_eps=1e-5, rope_theta=8e5,
                 max_position_embeddings=131072,
                 media_placeholder_token_id=163605, vt_hidden_size=1152,
                 vt_num_hidden_layers=27, vt_num_attention_heads=16,
                 vt_intermediate_size=4304, patch_size=14,
                 init_pos_emb_height=64, init_pos_emb_width=64,
                 merge_kernel_size=(2, 2), vt_rope_theta=1e4,
                 vt_layer_norm_eps=1e-5, num_channels=3, image_grids=(),
                 held_experts=None, first_expert=0, dtype="bfloat16",
                 prefill_chunk=None, initializer_range=0.02):
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
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_shared_experts = n_shared_experts
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.max_position_embeddings = max_position_embeddings
        self.media_placeholder_token_id = media_placeholder_token_id
        self.vt_hidden_size = vt_hidden_size
        self.vt_num_hidden_layers = vt_num_hidden_layers
        self.vt_num_attention_heads = vt_num_attention_heads
        self.vt_intermediate_size = vt_intermediate_size
        self.patch_size = patch_size
        self.init_pos_emb_height = init_pos_emb_height
        self.init_pos_emb_width = init_pos_emb_width
        self.merge_kernel_size = tuple(merge_kernel_size)
        self.vt_rope_theta = vt_rope_theta
        self.vt_layer_norm_eps = vt_layer_norm_eps
        self.num_channels = num_channels
        # the patch grids (grid_h, grid_w) whose encoders warm-up compiles
        self.image_grids = [tuple(int(n) for n in g) for g in image_grids]
        self.held_experts = (n_routed_experts if held_experts is None
                             else held_experts)
        self.first_expert = first_expert
        self.dtype = dtype
        self.prefill_chunk = prefill_chunk
        self.initializer_range = initializer_range
        if self.merge_kernel_size != (2, 2):
            raise ValueError("KimiVLConfig: 2 x 2 patches an image row")
        if vt_hidden_size % vt_num_attention_heads or (
                vt_hidden_size // vt_num_attention_heads) % 4:
            raise ValueError("KimiVLConfig: a tower head holds whole "
                             "(column, row) pairs of rotary pairs")

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=96, hidden_size=64, num_hidden_layers=3,
                 first_k_dense_replace=1, intermediate_size=96,
                 moe_intermediate_size=24, num_attention_heads=4,
                 kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
                 n_shared_experts=1, max_position_embeddings=128,
                 media_placeholder_token_id=95, vt_hidden_size=48,
                 vt_num_hidden_layers=2, vt_num_attention_heads=4,
                 vt_intermediate_size=80, patch_size=2,
                 init_pos_emb_height=4, init_pos_emb_width=4,
                 image_grids=((4, 4), (2, 6)), dtype="float32")
        d.update(kw)
        return cls(**d)

    @property
    def moe_layers(self):
        return list(range(self.first_k_dense_replace,
                          self.num_hidden_layers))

    @property
    def patch_values(self):
        return self.num_channels * self.patch_size ** 2

    def image_rows(self, grid):
        """Image rows (prompt positions) of an image of ``grid`` patches."""
        gh, gw = grid
        if gh % 2 or gw % 2:
            raise ValueError(f"an image's patch grid {tuple(grid)} must be "
                             f"even both ways (2 x 2 patches a row)")
        return (gh // 2) * (gw // 2)

    def patchify(self, pixels):
        """pixels [H, W, channels] -> (patches [gh * gw, patch_values]
        float32 in row-major grid order, each (channel, y, x)-major as a
        convolution's filter sees it; (gh, gw))."""
        pixels = np.asarray(pixels, np.float32)
        p = self.patch_size
        h, w, ch = pixels.shape
        if h % (2 * p) or w % (2 * p) or ch != self.num_channels:
            raise ValueError(
                f"an image must be [H, W, {self.num_channels}] with H and W "
                f"multiples of {2 * p}, got {pixels.shape}")
        gh, gw = h // p, w // p
        patches = pixels.reshape(gh, p, gw, p, ch).transpose(0, 2, 4, 1, 3)
        return (np.ascontiguousarray(patches.reshape(gh * gw, -1)),
                (gh, gw))

    def cache_rows(self, pool_dtype=None):
        """What a token leaves in each layer: the latent row
        [c_kv | k_rope], stored at whole lane tiles (576 -> 640)."""
        from paddle_tpu.serving.lane import CacheRow, lane_padded

        dtype = pool_dtype or self.dtype
        if dtype == "int8":
            raise ValueError(
                "models/kimi_vl.py: no int8 form of the latent cache (the "
                "dual-int8 pool is dense K/V's, models/gpt.py)")
        return [CacheRow("latent", lane_padded(
            self.kv_lora_rank + self.qk_rope_head_dim), dtype)]

    def decode_lane(self):
        """This model's decode-lane declaration (serving/lane.py)."""
        from paddle_tpu.serving import lane

        def prepare(pixels):
            patches, grid = self.patchify(pixels)
            return lane.PreparedImage(grid, {"enc_patches": patches},
                                      self.image_rows(grid))

        return lane.scaffold(
            functools.partial(_decoder, cfg=self),
            functools.partial(_next_token, cfg=self, prefix="kimi"),
            num_layers=self.num_hidden_layers,
            max_position=self.max_position_embeddings,
            cache_rows=self.cache_rows,
            pool_dtype=self.dtype, prefill_chunk=self.prefill_chunk,
            device_counters=moe_stats.expert_stats_counters(self),
            book_counters=functools.partial(moe_stats.book_expert_stats,
                                            self),
            encoder=lane.ImageEncoder(
                build=functools.partial(build_kimi_vl_vision_encoder, self),
                prepare=prepare, shapes=self.image_grids,
                rows_of=self.image_rows, row_width=self.hidden_size,
                placeholder_id=self.media_placeholder_token_id))


# ---------------------------------------------------------------------------
# decoder pieces
# ---------------------------------------------------------------------------


def _linear_b(x, size, name, cfg, head_dim=None):
    """x W + b: W in ``cfg.dtype``, the bias float32."""
    bias = layers.create_parameter(
        [size], "float32", attr=_attr(name + ".b_0", cfg, Constant(0.0)))
    return layers.elementwise_add(_linear(x, size, name, cfg, head_dim),
                                  bias)


def _decoder(frame, cfg):
    """Embedding (image rows where the frame's ``image_rows`` = (staged
    rows, index a position) says so) and every block over the frame's
    tokens (serving/lane.py ``Frame``) -> hidden [B, T, D] (before the
    final norm).  A decoded token is never an image row."""
    from paddle_tpu.serving.lane import FULL

    L = layers
    b, t = frame.shape
    emb = L.embedding(frame.tok, size=[cfg.vocab_size, cfg.hidden_size],
                      param_attr=_attr("kimi_embed.w_0", cfg),
                      dtype=cfg.dtype)
    x = L.cast(L.reshape(emb, shape=[b, t, cfg.hidden_size]), "float32")
    if frame.image_rows is not None:
        x = L.select_embedding_rows(x, *frame.image_rows)
    for layer in range(cfg.num_hidden_layers):
        name = f"kimi_layer_{layer}"
        x = L.elementwise_add(x, latent_attention(
            x, frame.pos, frame.tables[FULL], frame.q_start,
            frame.pools[layer], frame.writes[FULL], frame.shape, cfg, name,
            frame.attn_force))
        x = L.elementwise_add(x, expert_ffn(x, layer, frame.row_valid,
                                            frame.counted_as, cfg, name,
                                            frame.attn_force))
    return x


def build_kimi_vl_lm(cfg: KimiVLConfig = None, is_test=True, seq_len=None,
                     page_size=None, attn_force=None):
    """A whole TEXT sequence in one pass: logprobs [S, V] of every
    position of ``pf_tok`` [1, S] (serving/lane.py
    ``build_whole_sequence``: the decode lane's blocks over a cache that
    lives and dies inside the program).  Inference only."""
    del is_test
    cfg = cfg or KimiVLConfig()
    return cfg.decode_lane().build_whole_sequence(
        seq_len or cfg.prefill_chunk or 128, page_size, attn_force)


# ---------------------------------------------------------------------------
# the tower
# ---------------------------------------------------------------------------


def _ln(x, name, cfg):
    return layers.layer_norm(
        x, begin_norm_axis=len(x.shape) - 1, epsilon=cfg.vt_layer_norm_eps,
        param_attr=ParamAttr(name=name + ".scale",
                             initializer=Constant(1.0)),
        bias_attr=ParamAttr(name=name + ".bias", initializer=Constant(0.0)))


def pos_table_var_name(grid):
    """The position table resized to ``grid``: a persistable var the
    encoder's prepare program writes once and its run program reads."""
    return f"kimi_vit_pos@{grid[0]}x{grid[1]}"


def _tower(patches, pos, grid, cfg, attn_force):
    """patches [N, patch_values], pos [N, width] -> image rows
    [N / 4, hidden_size]."""
    L = layers
    gh, gw = grid
    n, width = gh * gw, cfg.vt_hidden_size
    heads = cfg.vt_num_attention_heads
    d = width // heads
    z = L.elementwise_add(_linear_b(patches, width, "kimi_vit_patch", cfg),
                          pos)
    for layer in range(cfg.vt_num_hidden_layers):
        name = f"kimi_vit_layer_{layer}"
        qkv = L.reshape(_linear_b(_ln(z, name + "_ln0", cfg), 3 * width,
                                  name + "_qkv", cfg, d),
                        shape=[n, 3 * heads, d])
        q, k, v = L.split(qkv, 3, dim=1)
        o = L.vit_attention(
            L.rope_2d_interleaved(q, gh, gw, cfg.vt_rope_theta),
            L.rope_2d_interleaved(k, gh, gw, cfg.vt_rope_theta), v,
            sm_scale=float(d) ** -0.5, dtype=cfg.dtype, force=attn_force)
        z = L.elementwise_add(z, _linear_b(
            L.reshape(o, shape=[n, width]), width, name + "_o", cfg))
        hidden = L.gelu(_linear_b(_ln(z, name + "_ln1", cfg),
                                  cfg.vt_intermediate_size, name + "_fc0",
                                  cfg), approximate=True)
        z = L.elementwise_add(z, _linear_b(hidden, width, name + "_fc1",
                                           cfg))
    z = _ln(_ln(z, "kimi_vit_final_ln", cfg), "kimi_proj_ln", cfg)
    # 2 x 2 neighbouring patches side by side, row-major over the merged
    # grid and inside a row
    merged = L.reshape(
        L.transpose(L.reshape(z, shape=[gh // 2, 2, gw // 2, 2, width]),
                    perm=[0, 2, 1, 3, 4]), shape=[n // 4, 4 * width])
    hidden = L.gelu(_linear_b(merged, 4 * width, "kimi_proj_fc0", cfg))
    return _linear_b(hidden, cfg.hidden_size, "kimi_proj_fc1", cfg)


def _pos_table_param(cfg):
    return layers.create_parameter(
        [cfg.init_pos_emb_height, cfg.init_pos_emb_width,
         cfg.vt_hidden_size], "float32",
        attr=_attr("kimi_vit_pos.w_0", cfg))


def build_kimi_vl_vision_encoder(cfg: KimiVLConfig, grid_h, grid_w,
                                 staging_rows, attn_force=None):
    """The tower and the projector over ONE image of (grid_h, grid_w)
    patches, its rows written into the engine's staged image rows
    (``staging_rows`` of them; serving/lane.py ``ImageEncoder``) at the
    fed places: feeds ``enc_patches`` [N, patch_values] float32 and
    ``enc_row_idx`` [N / 4] int32.

    Returns ``(feed names, the prepare program)``.  The prepare
    program resizes the learned position table to this grid
    into a persistable var of its own; the engine runs it ONCE, when it
    builds this shape's encoder, and no encoder run resizes anything."""
    from paddle_tpu.serving import lane

    L = layers
    grid = (int(grid_h), int(grid_w))
    n, n_rows = grid[0] * grid[1], cfg.image_rows(grid)
    prepare = fluid.Program()
    with fluid.program_guard(prepare, fluid.Program()):
        block = prepare.global_block()
        resized = block.create_var(
            name=pos_table_var_name(grid), shape=[n, cfg.vt_hidden_size],
            dtype="float32", persistable=True)
        L.assign(L.bicubic_resize_table(_pos_table_param(cfg), *grid),
                 output=resized)
    prepare.name = "vision_encoder_prepare"

    patches = fluid.data("enc_patches", [n, cfg.patch_values], False,
                         dtype="float32")
    row_idx = fluid.data("enc_row_idx", [n_rows], False, dtype="int32")
    pos = fluid.default_main_program().global_block().create_var(
        name=pos_table_var_name(grid), shape=[n, cfg.vt_hidden_size],
        dtype="float32", persistable=True)
    rows = _tower(patches, pos, grid, cfg, attn_force)
    staging = lane.declare_row_staging(staging_rows, cfg.hidden_size)
    L.kv_cache_write(staging, L.reshape(rows, shape=[n_rows, 1, -1]),
                     row_idx, L.fill_constant(shape=[n_rows], value=0,
                                              dtype="int32"))
    return ["enc_patches", "enc_row_idx"], prepare
