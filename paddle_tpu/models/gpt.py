"""GPT-style causal decoder LM, Fluid graph-building style.

The reference era predates decoder-only LMs as a first-class family (its
Transformer lives in dist_transformer.py, encoder-decoder); this model
extends the zoo with the TPU-first pattern: pre-LN blocks, causal
flash-attention Pallas kernel (or the fused upper-triangle softmax op on the
composed path), weight-tied LM head, and a statically-unrolled beam/greedy
generation program built from the dense beam_search ops.

Parameter names follow the BERT zoo convention ("decoder_layer_N_...") so
the Megatron tensor-parallel sharder maps them by the same patterns.
"""

from __future__ import annotations

import numpy as np

from paddle_tpu import fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.framework import Variable
from paddle_tpu.fluid.initializer import Normal
from paddle_tpu.fluid.param_attr import ParamAttr


class GPTConfig:
    def __init__(self, vocab_size=32000, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=3072, max_position=1024,
                 hidden_dropout=0.1, initializer_range=0.02,
                 use_flash_attention=True):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position = max_position
        self.hidden_dropout = hidden_dropout
        self.initializer_range = initializer_range
        self.use_flash_attention = use_flash_attention

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                 intermediate_size=128, max_position=128)
        d.update(kw)
        return cls(**d)

    def decode_lane(self):
        """This model's decode-lane declaration (serving/lane.py): a K
        and a V row a token a layer, and the paged decoder below."""
        import functools

        from paddle_tpu.serving import lane

        return lane.scaffold(
            functools.partial(_paged_decoder, cfg=self),
            functools.partial(_paged_head, cfg=self),
            num_layers=self.num_layers, max_position=self.max_position,
            cache_rows=functools.partial(
                lane.kv_rows, self.num_heads,
                self.hidden_size // self.num_heads))


def _fc(x, size, name, act=None, init_std=0.02, nfd=2):
    return layers.fc(
        x, size=size, num_flatten_dims=nfd, act=act,
        param_attr=ParamAttr(name=name + ".w_0",
                             initializer=Normal(0.0, init_std)),
        bias_attr=ParamAttr(name=name + ".b_0"))


def _ln(x, name, axis=2):
    return layers.layer_norm(x, begin_norm_axis=axis,
                             param_attr=ParamAttr(name=name + "_scale"),
                             bias_attr=ParamAttr(name=name + "_bias"))


def _attention_incremental(x_new, k_cache, v_cache, cfg: GPTConfig, name):
    """One-token attention against cached K/V (KV-cache decode step).
    x_new: [B', 1, H]; k_cache/v_cache: [B', n, L, d] or None (first step).
    Returns (ctx [B', 1, H], k_cat, v_cat)."""
    h, n = cfg.hidden_size, cfg.num_heads
    d = h // n
    q = _fc(x_new, h, name + "_query_fc", init_std=cfg.initializer_range)
    k = _fc(x_new, h, name + "_key_fc", init_std=cfg.initializer_range)
    v = _fc(x_new, h, name + "_value_fc", init_std=cfg.initializer_range)

    def to_heads(t):
        r = layers.reshape(t, shape=[0, 0, n, d])
        return layers.transpose(r, perm=[0, 2, 1, 3])  # [B', n, 1, d]

    q, k, v = to_heads(q), to_heads(k), to_heads(v)
    k_cat = k if k_cache is None else layers.concat([k_cache, k], axis=2)
    v_cat = v if v_cache is None else layers.concat([v_cache, v], axis=2)
    scores = layers.matmul(q, k_cat, transpose_y=True,
                           alpha=float(d) ** -0.5)   # [B', n, 1, L]
    probs = layers.softmax(scores)  # attends only to past+self: no mask
    ctx = layers.matmul(probs, v_cat)                # [B', n, 1, d]
    ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
    ctx = layers.reshape(ctx, shape=[0, 0, h])
    out = _fc(ctx, h, name + "_output_fc", init_std=cfg.initializer_range)
    return out, k_cat, v_cat


def decoder_layer_incremental(x, caches, cfg: GPTConfig, name):
    """Pre-LN block on ONE new token position with KV caches.
    caches: (k_cache, v_cache) or (None, None).  Returns (x', new caches)."""
    attn, k_cat, v_cat = _attention_incremental(
        _ln(x, name + "_ln_attn"), caches[0], caches[1], cfg, name + "_att")
    x = layers.elementwise_add(x, attn)
    return _ffn_block(x, cfg, name), (k_cat, v_cat)


class KVSink(list):
    """Prefill K/V sink with a STAMPED cache dtype (and recorded
    shapes): `gpt_decoder(kv_sink=KVSink(dtype="float32"))` inserts an
    explicit cast op on every captured K/V, so the program carries the
    cache dtype instead of inheriting whatever the dtype policy lowers
    the attention chain to.  Without the stamp, a bf16-AMP prefill
    silently hands bf16 arrays to an fp32 KV pool (the policy rides the
    LOWERING, not the program, so the vars all claim fp32) — the pool
    write then either implicit-upcasts garbage-precision values or
    trips the kv_cache_write dtype guard at trace time depending on the
    consumer.  A plain list keeps the historic behavior (cache dtype
    follows the compute dtype — what the in-graph generate variants
    want, where cache and compute must agree)."""

    def __init__(self, dtype=None):
        super().__init__()
        self.dtype = dtype
        self.shapes = []

    def append(self, kv):
        k, v = kv
        if self.dtype:
            # always stamp (an identity convert is free — XLA folds it):
            # skipping the cast when the VAR dtype already matches would
            # lose the stamp exactly when the dtype policy makes var and
            # runtime dtype disagree
            k = layers.cast(k, self.dtype)
            v = layers.cast(v, self.dtype)
        self.shapes.append(tuple(k.shape or ()))
        super().append((k, v))


def causal_self_attention(x, cfg: GPTConfig, name, is_test=False,
                          kv_sink=None):
    h, n = cfg.hidden_size, cfg.num_heads
    d = h // n
    q = _fc(x, h, name + "_query_fc", init_std=cfg.initializer_range)
    k = _fc(x, h, name + "_key_fc", init_std=cfg.initializer_range)
    v = _fc(x, h, name + "_value_fc", init_std=cfg.initializer_range)

    def to_heads(t):
        r = layers.reshape(t, shape=[0, 0, n, d])
        return layers.transpose(r, perm=[0, 2, 1, 3])  # [B, n, S, d]

    q, k, v = to_heads(q), to_heads(k), to_heads(v)
    if kv_sink is not None:  # prefill: expose per-layer K/V for the cache
        kv_sink.append((k, v))
    if cfg.use_flash_attention:
        ctx = layers.flash_attention(q, k, v, causal=True,
                                     sm_scale=float(d) ** -0.5)
    else:
        scores = layers.matmul(q, k, transpose_y=True, alpha=float(d) ** -0.5)
        # fused causal softmax (upper triangle masked to -inf)
        probs = layers.softmax_mask_fuse_upper_triangle(scores)
        ctx = layers.matmul(probs, v)
    ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
    ctx = layers.reshape(ctx, shape=[0, 0, h])
    return _fc(ctx, h, name + "_output_fc", init_std=cfg.initializer_range)


def decoder_layer(x, cfg: GPTConfig, name, is_test=False, kv_sink=None):
    # pre-LN (GPT-2 style): x + attn(ln(x)); x + ffn(ln(x))
    attn = causal_self_attention(_ln(x, name + "_ln_attn"), cfg,
                                 name + "_att", is_test=is_test,
                                 kv_sink=kv_sink)
    if cfg.hidden_dropout and not is_test:
        attn = layers.dropout(attn, dropout_prob=cfg.hidden_dropout,
                              is_test=is_test,
                              dropout_implementation="upscale_in_train")
    x = layers.elementwise_add(x, attn)
    ffn = _fc(_ln(x, name + "_ln_ffn"), cfg.intermediate_size,
              name + "_ffn_fc_0", act="gelu",
              init_std=cfg.initializer_range)
    ffn = _fc(ffn, cfg.hidden_size, name + "_ffn_fc_1",
              init_std=cfg.initializer_range)
    if cfg.hidden_dropout and not is_test:
        ffn = layers.dropout(ffn, dropout_prob=cfg.hidden_dropout,
                             is_test=is_test,
                             dropout_implementation="upscale_in_train")
    return layers.elementwise_add(x, ffn)


def gpt_decoder(ids, pos_ids, cfg: GPTConfig, is_test=False, kv_sink=None,
                final_ln=True):
    """Embeddings + N pre-LN causal blocks (+ final LN).  Returns [B,S,H].
    kv_sink: optional list collecting each layer's (K, V) [B,n,S,d] — the
    batched prefill for KV-cache generation."""
    emb = layers.embedding(
        ids, size=[cfg.vocab_size, cfg.hidden_size],
        param_attr=ParamAttr(name="gpt_word_embedding",
                             initializer=Normal(0.0, cfg.initializer_range)))
    pos = layers.embedding(
        pos_ids, size=[cfg.max_position, cfg.hidden_size],
        param_attr=ParamAttr(name="gpt_pos_embedding",
                             initializer=Normal(0.0, cfg.initializer_range)))
    x = layers.elementwise_add(emb, pos)
    if cfg.hidden_dropout and not is_test:
        x = layers.dropout(x, dropout_prob=cfg.hidden_dropout,
                           is_test=is_test,
                           dropout_implementation="upscale_in_train")
    for i in range(cfg.num_layers):
        x = decoder_layer(x, cfg, f"decoder_layer_{i}", is_test=is_test,
                          kv_sink=kv_sink)
    return _ln(x, "gpt_final_ln") if final_ln else x


def _lm_logits(h, cfg: GPTConfig):
    """Weight-tied LM head: logits = h @ word_embedding^T."""
    word_emb = fluid.default_main_program().global_block().var(
        "gpt_word_embedding")
    flat = layers.reshape(h, shape=[-1, cfg.hidden_size])
    logits = layers.matmul(flat, word_emb, transpose_y=True)
    return logits  # [B*S, V]


def build_gpt_lm(cfg: GPTConfig = None, is_test=False):
    """Causal-LM training graph.  Feeds: ids [B,S] int64, labels [B,S]
    int64 (next tokens).  Returns (feed_names, loss)."""
    cfg = cfg or GPTConfig()
    ids = fluid.data("gpt_ids", [-1, -1], False, dtype="int64")
    pos_ids = fluid.data("gpt_pos_ids", [-1, -1], False, dtype="int64")
    labels = fluid.data("gpt_labels", [-1, -1], False, dtype="int64")

    h = gpt_decoder(ids, pos_ids, cfg, is_test=is_test)
    logits = _lm_logits(h, cfg)
    lbl = layers.reshape(labels, shape=[-1, 1])
    loss = layers.mean(layers.softmax_with_cross_entropy(logits, lbl))
    return ["gpt_ids", "gpt_pos_ids", "gpt_labels"], loss


def _init_beam_state(prompt, prompt_len, k):
    """Shared beam bookkeeping: last prompt token tiled to K beams and
    scores with only beam 0 alive (so step 0 picks distinct top-K)."""
    L = layers
    last = L.slice(prompt, axes=[1], starts=[prompt_len - 1],
                   ends=[prompt_len])
    pre_ids = L.reshape(L.stack([last] * k, axis=1), shape=[-1, k])
    bias = np.zeros((1, k), "float32")
    bias[0, 1:] = -1e9
    pre_scores = L.fill_constant_batch_size_like(
        prompt, shape=[-1, k], dtype="float32", value=0.0)
    return pre_ids, pre_scores + L.assign(bias)


def _tile_beams(tsr, k):
    """[B, ...] -> [B*K, ...] beam replication (shared by both KV-cache
    generation variants)."""
    if k == 1:
        return tsr
    L = layers
    shp = tsr.shape
    r = L.stack([tsr] * k, axis=1)
    return L.reshape(r, shape=[-1] + [int(sd) for sd in shp[1:]])


def _reorder_beam_dim(tsr, parent, k, tail_shape):
    """Gather the beam dim of [B*K, *tail_shape] by parent [B, K] with a
    one-hot matmul (static shapes; shared by both generation variants)."""
    if k == 1:
        return tsr
    L = layers
    numel = int(np.prod(tail_shape))
    flat = L.reshape(tsr, shape=[-1, k, numel])
    sel = L.matmul(L.one_hot(parent, k), flat)           # [B, K, numel]
    return L.reshape(sel, shape=[-1] + [int(sd) for sd in tail_shape])


def _decode_tail(step_ids, step_parents, end_id):
    L = layers
    return L.beam_search_decode(L.concat(step_ids, axis=0),
                                L.concat(step_parents, axis=0),
                                end_id=end_id)


def build_gpt_generate(cfg: GPTConfig, prompt_len, gen_len, beam_size=1,
                       end_id=0):
    """Statically-unrolled generation program (greedy when beam_size=1).

    Recomputes the full prefix each step — O(S²) per sequence but every
    step is one compiled XLA program; a KV-cache variant trades memory for
    compute.  Returns (prompt_var, sentence_ids [B, K, gen_len],
    final_beam_scores [B, K])."""
    L = layers
    prompt = fluid.data("gpt_prompt", [-1, prompt_len], False, dtype="int64")

    k = beam_size
    # beams: maintain the full token history [B, K, cur_len]
    hist = L.stack([prompt] * k, axis=1)  # [B, K, P]
    pre_ids, pre_scores = _init_beam_state(prompt, prompt_len, k)

    step_ids, step_parents = [], []
    for t in range(gen_len):
        cur = prompt_len + t
        flat = L.reshape(hist, shape=[-1, cur])          # [B*K, cur]
        pos = L.fill_constant_batch_size_like(
            flat, shape=[-1, cur], dtype="int64", value=0)
        pos = L.elementwise_add(pos, L.assign(
            np.arange(cur, dtype="int64")[None, :]))
        h = gpt_decoder(flat, pos, cfg, is_test=True)
        last = L.slice(h, axes=[1], starts=[cur - 1], ends=[cur])
        logits = _lm_logits(last, cfg)                   # [B*K, V]
        logp = L.log_softmax(logits)
        logp3 = L.reshape(logp, shape=[-1, k, cfg.vocab_size])
        ids, scores, parent = L.beam_search(pre_ids, pre_scores, logp3,
                                            beam_size=k, end_id=end_id)
        # reorder histories by parent and append the chosen tokens.
        # k == 1 skips the reorder (parent is identically 0) — and MUST:
        # one_hot on a [B, 1] input follows the reference's trailing-1
        # squeeze semantics and would collapse the beam rank (the same
        # guard _reorder_beam_dim has always had; greedy build was
        # broken before it)
        if k > 1:
            onehot = L.one_hot(parent, k)                # [B,K,K]
            hist_f = L.cast(hist, "float32")
            hist = L.cast(L.matmul(onehot, hist_f), "int64")
        hist = L.concat([hist, L.unsqueeze(ids, axes=[2])], axis=2)
        pre_ids, pre_scores = ids, scores
        step_ids.append(L.unsqueeze(ids, axes=[0]))
        step_parents.append(L.unsqueeze(L.cast(parent, "int32"), axes=[0]))

    sent = _decode_tail(step_ids, step_parents, end_id)
    return prompt, sent, pre_scores


def _embed_token(tok, pos_value, cfg: GPTConfig):
    """tok: [B', 1] int64 → [B', 1, H] word+position embedding.
    pos_value: python int OR an int64 [1] Variable (while-loop decode)."""
    L = layers
    emb = L.embedding(tok, size=[cfg.vocab_size, cfg.hidden_size],
                      param_attr=ParamAttr(name="gpt_word_embedding"))
    pos = L.fill_constant_batch_size_like(
        tok, shape=[-1, 1], dtype="int64",
        value=0 if isinstance(pos_value, Variable) else pos_value)
    if isinstance(pos_value, Variable):
        pos = L.elementwise_add(pos, pos_value)
    pemb = L.embedding(pos, size=[cfg.max_position, cfg.hidden_size],
                       param_attr=ParamAttr(name="gpt_pos_embedding"))
    # lookup_table squeezes trailing [*, 1] ids to [B, H]: restore the
    # singleton time axis the incremental decoder layers expect
    return L.reshape(L.elementwise_add(emb, pemb),
                     shape=[-1, 1, cfg.hidden_size])


def _ffn_block(x, cfg: GPTConfig, name):
    """Shared pre-LN FFN + residual (decoder_layer / incremental / scan)."""
    ffn = _fc(_ln(x, name + "_ln_ffn"), cfg.intermediate_size,
              name + "_ffn_fc_0", act="gelu", init_std=cfg.initializer_range)
    ffn = _fc(ffn, cfg.hidden_size, name + "_ffn_fc_1",
              init_std=cfg.initializer_range)
    return layers.elementwise_add(x, ffn)


def build_gpt_generate_cached(cfg: GPTConfig, prompt_len, gen_len,
                              beam_size=1, end_id=0):
    """KV-cache generation program: each step computes q/k/v for ONE new
    token and attends against cached K/V — O(L) per step instead of the
    O(L²) full-prefix recompute of build_gpt_generate.  Same beam/greedy
    semantics; caches are reordered by beam parent each step.

    Returns (prompt_var, sentence_ids [B, K, gen_len], final_scores)."""
    L = layers
    n, d = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    k = beam_size
    prompt = fluid.data("gpt_prompt", [-1, prompt_len], False, dtype="int64")

    # ---- prefill: ONE batched causal pass over the whole prompt that
    # also captures every layer's K/V (no per-token unroll)
    pos0 = L.fill_constant_batch_size_like(prompt, shape=[-1, prompt_len],
                                           dtype="int64", value=0)
    pos0 = L.elementwise_add(pos0, L.assign(
        np.arange(prompt_len, dtype="int64")[None, :]))
    kv_sink = []
    x_full = gpt_decoder(prompt, pos0, cfg, is_test=True, kv_sink=kv_sink,
                         final_ln=False)                    # [B, P, H]
    caches = list(kv_sink)                                  # [(K, V)] per layer
    last_x = L.slice(x_full, axes=[1], starts=[prompt_len - 1],
                     ends=[prompt_len])                     # [B, 1, H]

    caches = [(_tile_beams(c[0], k), _tile_beams(c[1], k)) for c in caches]
    h_last = _tile_beams(last_x, k)

    pre_ids, pre_scores = _init_beam_state(prompt, prompt_len, k)


    # logits for the token AFTER the prompt come from the prefill's last h
    x = h_last
    step_ids, step_parents = [], []
    for t in range(gen_len):
        cur = prompt_len + t
        logits = _lm_logits(_ln(x, "gpt_final_ln"), cfg)  # [B*K, V]
        logp = L.log_softmax(logits)
        logp3 = L.reshape(logp, shape=[-1, k, cfg.vocab_size])
        ids, scores, parent = L.beam_search(pre_ids, pre_scores, logp3,
                                            beam_size=k, end_id=end_id)
        caches = [(_reorder_beam_dim(kc, parent, k, (n, cur, d)),
                   _reorder_beam_dim(vc, parent, k, (n, cur, d)))
                  for kc, vc in caches]
        tok = L.reshape(ids, shape=[-1, 1])
        x = _embed_token(tok, cur, cfg)
        new_caches = []
        for li in range(cfg.num_layers):
            x, c = decoder_layer_incremental(x, caches[li], cfg,
                                             f"decoder_layer_{li}")
            new_caches.append(c)
        caches = new_caches
        pre_ids, pre_scores = ids, scores
        step_ids.append(L.unsqueeze(ids, axes=[0]))
        step_parents.append(L.unsqueeze(L.cast(parent, "int32"), axes=[0]))

    sent = _decode_tail(step_ids, step_parents, end_id)
    return prompt, sent, pre_scores


def build_gpt_generate_scan(cfg: GPTConfig, prompt_len, gen_len,
                            beam_size=1, end_id=0):
    """Beam/greedy KV-cache generation as ONE while-loop (lax.while_loop
    under jit) over FIXED-SIZE caches — the TPU-right decode shape: the
    step body compiles once, vs build_gpt_generate_cached's gen_len-times
    unrolled program whose XLA compile time grows linearly (26x slower to
    compile at gen_len 64 in a CPU A/B; ~1.5x slower per step too).

    Caches are preallocated [B*K, n, P+G, d]; each step
      1. runs the SAME beam_search op as the unrolled variant (greedy is
         beam_size=1) — scores and end_id freezing are op-identical,
      2. reorders caches by beam parent with a one-hot matmul (static
         shapes; no gather needed),
      3. writes the new K/V at position `cur` with a one-hot masked
         update and attends over the full cache, positions > cur masked.

    Returns (prompt_var, sentence_ids [B, K, gen_len], scores [B, K]).
    """
    L = layers
    n, d = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    P, G, k = prompt_len, gen_len, beam_size
    Ltot = P + G
    neg = -1e9

    prompt = fluid.data("gpt_prompt", [-1, P], False, dtype="int64")

    # ---- prefill (batched causal pass, captures per-layer K/V) ----
    pos0 = L.fill_constant_batch_size_like(prompt, shape=[-1, P],
                                           dtype="int64", value=0)
    pos0 = L.elementwise_add(pos0, L.assign(np.arange(P, dtype="int64")[None, :]))
    kv_sink = []
    x_full = gpt_decoder(prompt, pos0, cfg, is_test=True, kv_sink=kv_sink,
                         final_ln=False)
    last_x = L.slice(x_full, axes=[1], starts=[P - 1], ends=[P])  # [B,1,H]

    # ---- loop-carried state (assigned before the loop, re-assigned in
    # the body -> while carries) ----
    caches = []
    for kc, vc in kv_sink:
        kc, vc = _tile_beams(kc, k), _tile_beams(vc, k)   # [B*K, n, P, d]
        pad = L.fill_constant_batch_size_like(
            kc, shape=[-1, n, G, d], dtype="float32", value=0.0)
        caches.append((L.assign(L.concat([kc, pad], axis=2)),
                       L.assign(L.concat([vc, pad], axis=2))))
    x = L.assign(_tile_beams(last_x, k))                  # [B*K, 1, H]
    pre_ids, pre_scores = _init_beam_state(prompt, P, k)  # [B, K] each
    pre_ids, pre_scores = L.assign(pre_ids), L.assign(pre_scores)
    ids_buf = L.assign(L.fill_constant_batch_size_like(
        prompt, shape=[G, -1, k], dtype="float32", value=0.0,
        output_dim_idx=1))
    par_buf = L.assign(L.fill_constant_batch_size_like(
        prompt, shape=[G, -1, k], dtype="float32", value=0.0,
        output_dim_idx=1))
    t = L.fill_constant(shape=[1], value=0, dtype="int64")
    g_const = L.fill_constant(shape=[1], value=G, dtype="int64")
    p_const = L.fill_constant(shape=[1], value=P, dtype="int64")
    arange_l = L.assign(np.arange(Ltot, dtype="int64"))   # read-only
    cond = L.less_than(t, g_const)

    w = L.While(cond)
    with w.block():
        # 1. beam step on the carried hidden state (same op as unrolled)
        logits = _lm_logits(_ln(x, "gpt_final_ln"), cfg)  # [B*K, V]
        logp3 = L.reshape(L.log_softmax(logits),
                          shape=[-1, k, cfg.vocab_size])
        ids, scores, parent = L.beam_search(pre_ids, pre_scores, logp3,
                                            beam_size=k, end_id=end_id)
        # record this step's choices at buf[t]
        oh_g = L.reshape(L.one_hot(L.reshape(t, shape=[1, 1]), G),
                         shape=[G, 1, 1])
        keep_g = L.elementwise_sub(
            L.fill_constant(shape=[G, 1, 1], value=1.0, dtype="float32"),
            oh_g)
        L.assign(L.elementwise_add(
            L.elementwise_mul(ids_buf, keep_g),
            L.elementwise_mul(L.unsqueeze(L.cast(ids, "float32"), axes=[0]),
                              oh_g)), ids_buf)
        L.assign(L.elementwise_add(
            L.elementwise_mul(par_buf, keep_g),
            L.elementwise_mul(L.unsqueeze(L.cast(parent, "float32"),
                                          axes=[0]), oh_g)), par_buf)

        cur = L.elementwise_add(p_const, t)               # [1] int64
        tok = L.reshape(ids, shape=[-1, 1])
        x_new = _embed_token(tok, cur, cfg)

        oh_l4 = L.reshape(L.one_hot(L.reshape(cur, shape=[1, 1]), Ltot),
                          shape=[1, 1, Ltot, 1])
        keep_l4 = L.elementwise_sub(
            L.fill_constant(shape=[1, 1, Ltot, 1], value=1.0,
                            dtype="float32"), oh_l4)
        future = L.cast(L.greater_than(arange_l, cur), "float32")
        amask = L.scale(future, scale=neg)                # [Ltot]

        # 3. one decoder pass on the new token against the fixed caches
        xi = x_new
        for li in range(cfg.num_layers):
            name = f"decoder_layer_{li}"
            xa = _ln(xi, name + "_ln_attn")
            q = _fc(xa, cfg.hidden_size, name + "_att_query_fc",
                    init_std=cfg.initializer_range)
            kk = _fc(xa, cfg.hidden_size, name + "_att_key_fc",
                     init_std=cfg.initializer_range)
            vv = _fc(xa, cfg.hidden_size, name + "_att_value_fc",
                     init_std=cfg.initializer_range)

            def to_heads(tn):
                r = L.reshape(tn, shape=[0, 0, n, d])
                return L.transpose(r, perm=[0, 2, 1, 3])  # [B*K,n,1,d]

            q, kk, vv = to_heads(q), to_heads(kk), to_heads(vv)
            kc, vc = caches[li]
            kc_r = _reorder_beam_dim(kc, parent, k, (n, Ltot, d))
            vc_r = _reorder_beam_dim(vc, parent, k, (n, Ltot, d))
            # the genuinely-new piece vs decoder_layer_incremental: masked
            # one-hot write into the FIXED-size cache (no concat — while
            # carries must keep their shape)
            kc_new = L.elementwise_add(L.elementwise_mul(kc_r, keep_l4),
                                       L.elementwise_mul(kk, oh_l4))
            vc_new = L.elementwise_add(L.elementwise_mul(vc_r, keep_l4),
                                       L.elementwise_mul(vv, oh_l4))
            L.assign(kc_new, kc)
            L.assign(vc_new, vc)
            scores_att = L.matmul(q, kc_new, transpose_y=True,
                                  alpha=float(d) ** -0.5)  # [B*K,n,1,Ltot]
            scores_att = L.elementwise_add(scores_att, amask)
            probs = L.softmax(scores_att)
            ctx = L.matmul(probs, vc_new)                  # [B*K,n,1,d]
            ctx = L.transpose(ctx, perm=[0, 2, 1, 3])
            ctx = L.reshape(ctx, shape=[0, 0, cfg.hidden_size])
            attn = _fc(ctx, cfg.hidden_size, name + "_att_output_fc",
                       init_std=cfg.initializer_range)
            xi = _ffn_block(L.elementwise_add(xi, attn), cfg, name)

        L.assign(xi, x)
        L.assign(ids, pre_ids)
        L.assign(scores, pre_scores)
        L.increment(t, in_place=True)
        L.less_than(t, g_const, cond=cond)

    sent = _decode_tail([L.cast(ids_buf, "int64")],
                        [L.cast(par_buf, "int32")], end_id)
    return prompt, sent, pre_scores


def make_fake_lm_batch(cfg: GPTConfig, batch, seq_len, seed=0):
    """Deterministic next-token task: token t+1 = (token t * 3 + 7) % V —
    fully learnable, so tiny models converge fast."""
    rng = np.random.RandomState(seed)
    first = rng.randint(0, cfg.vocab_size, (batch, 1))
    seq = [first]
    for _ in range(seq_len):
        seq.append((seq[-1] * 3 + 7) % cfg.vocab_size)
    toks = np.concatenate(seq, axis=1).astype("int64")
    return {
        "gpt_ids": toks[:, :seq_len],
        "gpt_pos_ids": np.tile(np.arange(seq_len, dtype="int64"),
                               (batch, 1)),
        "gpt_labels": toks[:, 1:seq_len + 1],
    }


# ---------------------------------------------------------------------------
# Paged decode lane (serving/decode.py): the decoder and the head that
# serving/lane.py builds the fixed-shape prefill-chunk and decode-step
# programs around, over a paged KV pool (serving/kv_pool.py).  The pool
# vars are PERSISTABLE program vars — the executor donates their
# buffers, so the pool updates in place across steps, never copied.
# ---------------------------------------------------------------------------

def _paged_decoder(frame, cfg: GPTConfig):
    """Embeddings and every pre-LN block over the frame's tokens
    (serving/lane.py ``Frame``) -> hidden [B, T, H] before the final LN:
    each layer writes the tokens' K and V rows, the heads side by side
    (neither executable copies the pool), and attends the prefix through
    the page table.  The dual-int8 pool's six tensors a layer are K's and
    V's (hi, lo, scale); its write quantises ONCE, at append."""
    from paddle_tpu.serving.lane import FULL

    L = layers
    h, n = cfg.hidden_size, cfg.num_heads
    d = h // n
    table, write = frame.tables[FULL], frame.writes[FULL]
    chunk = frame.last_idx is not None

    emb = L.embedding(frame.tok, size=[cfg.vocab_size, cfg.hidden_size],
                      param_attr=ParamAttr(name="gpt_word_embedding"))
    pemb = L.embedding(frame.pos, size=[cfg.max_position, cfg.hidden_size],
                       param_attr=ParamAttr(name="gpt_pos_embedding"))
    x = L.elementwise_add(emb, pemb)
    if frame.shape[1] == 1:  # lookup_table squeezes trailing [*, 1] ids
        x = L.reshape(x, shape=[-1, 1, h])

    for li in range(cfg.num_layers):
        name = f"decoder_layer_{li}"
        xa = _ln(x, name + "_ln_attn")
        q = _fc(xa, h, name + "_att_query_fc",
                init_std=cfg.initializer_range)
        k = _fc(xa, h, name + "_att_key_fc",
                init_std=cfg.initializer_range)
        v = _fc(xa, h, name + "_att_value_fc",
                init_std=cfg.initializer_range)
        q_h = L.transpose(L.reshape(q, shape=[0, 0, n, d]),
                          perm=[0, 2, 1, 3])               # [B, n, T, d]
        pools = frame.pools[li]
        int8 = len(pools) == 6
        if int8:
            pools = (pools[:3], pools[3:])
        for pool, rows in zip(pools, (k, v)):
            rows = L.reshape(rows, shape=[-1, n, d])
            if chunk and not int8:
                # the KVSink dtype-stamping contract: a bf16-AMP prefill
                # cannot silently hand bf16 arrays to an fp32 pool
                rows = L.cast(rows, pool.dtype)
            write(pool, rows)
        if int8:
            ctx = L.paged_attention_quant(
                q_h, *pools[0], *pools[1], table, frame.q_start,
                sm_scale=float(d) ** -0.5, force=frame.attn_force)
        else:
            ctx = L.paged_attention(q_h, *pools, table, frame.q_start,
                                    sm_scale=float(d) ** -0.5,
                                    force=frame.attn_force)
        ctx = L.reshape(L.transpose(ctx, perm=[0, 2, 1, 3]),
                        shape=[0, 0, h])
        attn = _fc(ctx, h, name + "_att_output_fc",
                   init_std=cfg.initializer_range)
        x = _ffn_block(L.elementwise_add(x, attn), cfg, name)
    return x


def _paged_head(h, cfg: GPTConfig):
    """h [N, 1, H] -> (greedy next token [N] int64, logprobs [N, V]):
    log_softmax -> argmax, the op chain the whole-sequence lane scores
    beams with, so greedy decode is comparable token for token."""
    logp = layers.log_softmax(_lm_logits(_ln(h, "gpt_final_ln"), cfg))
    return layers.argmax(logp, axis=-1), logp
