"""BERT (Transformer encoder) pretraining model, Fluid graph-building style.

Reference analog: the reference has no attention op — its Transformer dist
test composes matmul/softmax layers in Python
(python/paddle/fluid/tests/unittests/dist_transformer.py); this follows the
same composition style with the fluid-era BERT script conventions (feeds:
src_ids/pos_ids/sent_ids/input_mask, masked-LM gather by flat positions).

Parameter names are structured ("encoder_layer_N_multi_head_att_query_fc.w_0")
so the tensor-parallel sharder (paddle_tpu.parallel.hybrid) can map them to
mesh axes by pattern: QKV + FFN-in weights split column-wise over 'mp',
attention-output + FFN-out weights split row-wise — the Megatron layout, which
XLA GSPMD turns into one all-reduce per block over ICI.
"""

from __future__ import annotations

import numpy as np

from paddle_tpu import fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.initializer import Normal
from paddle_tpu.fluid.param_attr import ParamAttr


class BertConfig:
    def __init__(self, vocab_size=30522, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=3072, max_position=512,
                 type_vocab_size=2, hidden_dropout=0.1, attn_dropout=0.1,
                 initializer_range=0.02, use_flash_attention=True,
                 sequence_parallel=False, moe_experts=0, moe_top_k=2):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position = max_position
        self.type_vocab_size = type_vocab_size
        self.hidden_dropout = hidden_dropout
        self.attn_dropout = attn_dropout
        self.initializer_range = initializer_range
        self.use_flash_attention = use_flash_attention
        self.sequence_parallel = sequence_parallel
        self.moe_experts = moe_experts      # >0 → MoE FFN (expert parallel)
        self.moe_top_k = moe_top_k

    @classmethod
    def base(cls, **kw):
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        d = dict(vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
                 intermediate_size=128, max_position=64)
        d.update(kw)
        return cls(**d)


def _fc(x, size, name, act=None, init_std=0.02, num_flatten_dims=2):
    return layers.fc(
        x, size=size, num_flatten_dims=num_flatten_dims, act=act,
        param_attr=ParamAttr(name=name + ".w_0", initializer=Normal(0.0, init_std)),
        bias_attr=ParamAttr(name=name + ".b_0"))


def multi_head_attention(x, attn_bias, cfg: BertConfig, name, is_test=False):
    """Self-attention over [B, S, H]; attn_bias is [B, 1, 1, S] additive."""
    h, n = cfg.hidden_size, cfg.num_heads
    d = h // n
    q = _fc(x, h, name + "_query_fc", init_std=cfg.initializer_range)
    k = _fc(x, h, name + "_key_fc", init_std=cfg.initializer_range)
    v = _fc(x, h, name + "_value_fc", init_std=cfg.initializer_range)

    def to_heads(t):
        r = layers.reshape(t, shape=[0, 0, n, d])
        return layers.transpose(r, perm=[0, 2, 1, 3])  # [B, n, S, d]

    q, k, v = to_heads(q), to_heads(k), to_heads(v)
    use_flash = cfg.use_flash_attention and (is_test or not cfg.attn_dropout)
    if use_flash:
        # Pallas blockwise attention: no [B,n,S,S] score tensor in HBM
        # (attention-probs dropout is not expressible in the kernel — the
        # composed path below keeps exact parity when attn_dropout is on)
        ctx = layers.flash_attention(q, k, v, attn_bias=attn_bias,
                                     sm_scale=float(d) ** -0.5,
                                     sequence_parallel=cfg.sequence_parallel)
    else:
        scores = layers.matmul(q, k, transpose_y=True, alpha=float(d) ** -0.5)
        if attn_bias is not None:
            scores = layers.elementwise_add(scores, attn_bias)
        weights = layers.softmax(scores)
        if cfg.attn_dropout and not is_test:
            weights = layers.dropout(weights, dropout_prob=cfg.attn_dropout,
                                     is_test=is_test,
                                     dropout_implementation="upscale_in_train")
        ctx = layers.matmul(weights, v)  # [B, n, S, d]
    ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
    ctx = layers.reshape(ctx, shape=[0, 0, h])
    return _fc(ctx, h, name + "_output_fc", init_std=cfg.initializer_range)


def encoder_layer(x, attn_bias, cfg: BertConfig, name, is_test=False):
    attn = multi_head_attention(x, attn_bias, cfg, name + "_multi_head_att",
                                is_test=is_test)
    if cfg.hidden_dropout and not is_test:
        attn = layers.dropout(attn, dropout_prob=cfg.hidden_dropout,
                              is_test=is_test,
                              dropout_implementation="upscale_in_train")
    x = layers.layer_norm(layers.elementwise_add(x, attn), begin_norm_axis=2,
                          param_attr=ParamAttr(name=name + "_post_att_ln_scale"),
                          bias_attr=ParamAttr(name=name + "_post_att_ln_bias"))
    if cfg.moe_experts:
        # expert-parallel FFN: expert dim of the weights shards over 'ep'
        ffn = layers.moe_ffn(x, cfg.moe_experts, cfg.intermediate_size,
                             top_k=cfg.moe_top_k, act="gelu",
                             param_attr=ParamAttr(
                                 initializer=Normal(0.0, cfg.initializer_range)),
                             name=name + "_ffn")
    else:
        ffn = _fc(x, cfg.intermediate_size, name + "_ffn_fc_0", act="gelu",
                  init_std=cfg.initializer_range)
        ffn = _fc(ffn, cfg.hidden_size, name + "_ffn_fc_1",
                  init_std=cfg.initializer_range)
    if cfg.hidden_dropout and not is_test:
        ffn = layers.dropout(ffn, dropout_prob=cfg.hidden_dropout,
                             is_test=is_test,
                             dropout_implementation="upscale_in_train")
    return layers.layer_norm(layers.elementwise_add(x, ffn), begin_norm_axis=2,
                             param_attr=ParamAttr(name=name + "_post_ffn_ln_scale"),
                             bias_attr=ParamAttr(name=name + "_post_ffn_ln_bias"))


def bert_encoder(src_ids, pos_ids, sent_ids, input_mask, cfg: BertConfig,
                 is_test=False):
    """Embeddings + N encoder layers.  src/pos/sent ids: [B, S] int64;
    input_mask: [B, S] float (1 = real token).  Returns [B, S, H]."""
    emb = layers.embedding(
        src_ids, size=[cfg.vocab_size, cfg.hidden_size],
        param_attr=ParamAttr(name="word_embedding",
                             initializer=Normal(0.0, cfg.initializer_range)))
    pos = layers.embedding(
        pos_ids, size=[cfg.max_position, cfg.hidden_size],
        param_attr=ParamAttr(name="pos_embedding",
                             initializer=Normal(0.0, cfg.initializer_range)))
    sent = layers.embedding(
        sent_ids, size=[cfg.type_vocab_size, cfg.hidden_size],
        param_attr=ParamAttr(name="sent_embedding",
                             initializer=Normal(0.0, cfg.initializer_range)))
    emb = layers.elementwise_add(layers.elementwise_add(emb, pos), sent)
    emb = layers.layer_norm(emb, begin_norm_axis=2,
                            param_attr=ParamAttr(name="pre_encoder_ln_scale"),
                            bias_attr=ParamAttr(name="pre_encoder_ln_bias"))
    if cfg.hidden_dropout and not is_test:
        emb = layers.dropout(emb, dropout_prob=cfg.hidden_dropout,
                             is_test=is_test,
                             dropout_implementation="upscale_in_train")

    # additive attention bias [B, 1, 1, S]: (mask - 1) * 1e4 → 0 for real
    # tokens, -1e4 for padding
    neg = layers.scale(input_mask, scale=10000.0, bias=-1.0, bias_after_scale=False)
    attn_bias = layers.reshape(neg, shape=[0, 1, 1, input_mask.shape[-1]])
    attn_bias.stop_gradient = True

    x = emb
    for i in range(cfg.num_layers):
        x = encoder_layer(x, attn_bias, cfg, f"encoder_layer_{i}", is_test=is_test)
    return x


def build_bert_pretrain(cfg: BertConfig = None, is_test=False):
    """Full pretraining graph: masked-LM + next-sentence losses.

    Feeds: src_ids/pos_ids/sent_ids [B,S] int64, input_mask [B,S] float32,
    mask_label [M,1] int64, mask_pos [M,1] int64 (flat positions into B*S),
    labels [B,1] int64 (NSP).  Returns (feed_names, total_loss, mlm_loss,
    nsp_acc).
    """
    cfg = cfg or BertConfig.base()
    src_ids = fluid.data("src_ids", [-1, -1], False, dtype="int64")
    pos_ids = fluid.data("pos_ids", [-1, -1], False, dtype="int64")
    sent_ids = fluid.data("sent_ids", [-1, -1], False, dtype="int64")
    input_mask = fluid.data("input_mask", [-1, -1], False, dtype="float32")
    mask_label = fluid.data("mask_label", [-1, 1], False, dtype="int64")
    mask_pos = fluid.data("mask_pos", [-1, 1], False, dtype="int64")
    labels = fluid.data("labels", [-1, 1], False, dtype="int64")

    enc = bert_encoder(src_ids, pos_ids, sent_ids, input_mask, cfg, is_test=is_test)

    # ---- masked LM head ----
    flat = layers.reshape(enc, shape=[-1, cfg.hidden_size])
    masked = layers.gather(flat, mask_pos)  # [M, 1? no: M, H]
    masked = layers.reshape(masked, shape=[-1, cfg.hidden_size])
    trans = layers.fc(
        masked, size=cfg.hidden_size, act="gelu",
        param_attr=ParamAttr(name="mask_lm_trans_fc.w_0",
                             initializer=Normal(0.0, cfg.initializer_range)),
        bias_attr=ParamAttr(name="mask_lm_trans_fc.b_0"))
    trans = layers.layer_norm(trans, begin_norm_axis=1,
                              param_attr=ParamAttr(name="mask_lm_trans_ln_scale"),
                              bias_attr=ParamAttr(name="mask_lm_trans_ln_bias"))
    # decode with tied word embedding: logits = trans @ word_embedding^T + b
    word_emb = fluid.default_main_program().global_block().var("word_embedding")
    mlm_logits = layers.matmul(trans, word_emb, transpose_y=True)
    mlm_bias = layers.create_parameter(
        shape=[cfg.vocab_size], dtype="float32", name="mask_lm_out_fc.b_0",
        default_initializer=fluid.initializer.Constant(0.0))
    mlm_logits = layers.elementwise_add(mlm_logits, mlm_bias)
    mlm_loss = layers.softmax_with_cross_entropy(mlm_logits, mask_label)
    mean_mlm_loss = layers.mean(mlm_loss)

    # ---- next-sentence head on [CLS] ----
    first_tok = layers.slice(enc, axes=[1], starts=[0], ends=[1])
    pooled = layers.fc(
        layers.reshape(first_tok, shape=[-1, cfg.hidden_size]),
        size=cfg.hidden_size, act="tanh",
        param_attr=ParamAttr(name="pooled_fc.w_0",
                             initializer=Normal(0.0, cfg.initializer_range)),
        bias_attr=ParamAttr(name="pooled_fc.b_0"))
    nsp_logits = layers.fc(
        pooled, size=2,
        param_attr=ParamAttr(name="next_sent_fc.w_0",
                             initializer=Normal(0.0, cfg.initializer_range)),
        bias_attr=ParamAttr(name="next_sent_fc.b_0"))
    nsp_loss = layers.softmax_with_cross_entropy(nsp_logits, labels)
    nsp_softmax = layers.softmax(nsp_logits)
    nsp_acc = layers.accuracy(input=nsp_softmax, label=labels)
    mean_nsp_loss = layers.mean(nsp_loss)

    total_loss = layers.elementwise_add(mean_mlm_loss, mean_nsp_loss)
    feeds = ["src_ids", "pos_ids", "sent_ids", "input_mask", "mask_label",
             "mask_pos", "labels"]
    return feeds, total_loss, mean_mlm_loss, nsp_acc


def make_fake_batch(cfg: BertConfig, batch, seq_len, n_masked=None, seed=0,
                    shards=1):
    """Synthetic pretraining batch with the right shapes/dtypes.

    ``mask_pos`` indexes the FLAT [rows * seq_len] activations of the
    device that gathers it, so a data-parallel feed passes ``shards`` =
    the device count: each of the ``shards`` equal batch slices then
    carries positions local to its own rows.  Out-of-range positions or
    masked indices read no error on the device — they read garbage and
    the loss is NaN — so the sizes are checked here."""
    if seq_len > cfg.max_position:
        raise ValueError(
            f"seq_len {seq_len} exceeds the model's max_position "
            f"{cfg.max_position}")
    if batch % shards:
        raise ValueError(f"batch {batch} does not split over {shards} shards")
    rng = np.random.RandomState(seed)
    n_masked = n_masked or max(1, seq_len // 8)
    return {
        "src_ids": rng.randint(0, cfg.vocab_size, (batch, seq_len)).astype("int64"),
        "pos_ids": np.tile(np.arange(seq_len, dtype="int64"), (batch, 1)),
        "sent_ids": rng.randint(0, cfg.type_vocab_size, (batch, seq_len)).astype("int64"),
        "input_mask": np.ones((batch, seq_len), dtype="float32"),
        "mask_label": rng.randint(0, cfg.vocab_size, (batch * n_masked, 1)).astype("int64"),
        "mask_pos": rng.randint(0, batch // shards * seq_len,
                                (batch * n_masked, 1)).astype("int64"),
        "labels": rng.randint(0, 2, (batch, 1)).astype("int64"),
    }
