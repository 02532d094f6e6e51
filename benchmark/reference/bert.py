"""Plain reference: BERT pretraining (Devlin et al. 2018), forward, loss,
gradients and Adam, in straightforward ``jax.numpy`` float32.

It imports nothing of the program and takes nothing the program made: the
weights come from ``init_weights(cfg, seed)`` here (the benchmark puts the
same arrays into the program's scope), the batch from the traffic
generator.  Every matmul goes through the ``matmul`` argument so that the
control (``reference/lowprec.py``) can put a lower precision in its place;
callers wrap the default in ``jax.default_matmul_precision("highest")``.

Architecture as published: token + position + segment embeddings, layer
norm, dropout; N post-LN encoder layers (self-attention with dropout on
the probabilities, output projection, dropout, residual, layer norm; FFN
with exact GeLU, dropout, residual, layer norm); masked-LM head (dense +
GeLU + layer norm, decoder tied to the token embedding, plus a bias) over
the gathered masked positions; next-sentence head (tanh pooler over
[CLS], 2-way dense).  Loss = mean masked-LM cross entropy + mean
next-sentence cross entropy.  Departures from the source config, each in
the configuration file: layer-norm epsilon 1e-5 (source 1e-12), vocabulary
padded to 30528.  Parameter names are the program's, so one seeded dict
serves both.

Dropout is written out as published, with masks of the reference's own.
The program draws its masks from a generator of its own, which the
reference does not import, so with dropout on the two are two draws from
one distribution and no number compared separates a lower precision
(PERF.md, Findings).  The benchmark's configuration therefore has both
rates at 0, and then no mask is drawn here either.

Adam as in Kingma & Ba §2's efficient form, which is what the trainer
states: lr_t = lr * sqrt(1 - b2^t) / (1 - b1^t); p -= lr_t * m / (sqrt(v)
+ eps).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5


def param_shapes(cfg):
    """{name: (shape, init)} with init one of normal / zeros / ones."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    v = cfg["vocab_size"]
    out = {
        "word_embedding": ((v, h), "normal"),
        "pos_embedding": ((cfg["max_position_embeddings"], h), "normal"),
        "sent_embedding": ((cfg["type_vocab_size"], h), "normal"),
        "pre_encoder_ln_scale": ((h,), "ones"),
        "pre_encoder_ln_bias": ((h,), "zeros"),
        "mask_lm_trans_fc.w_0": ((h, h), "normal"),
        "mask_lm_trans_fc.b_0": ((h,), "zeros"),
        "mask_lm_trans_ln_scale": ((h,), "ones"),
        "mask_lm_trans_ln_bias": ((h,), "zeros"),
        "mask_lm_out_fc.b_0": ((v,), "zeros"),
        "pooled_fc.w_0": ((h, h), "normal"),
        "pooled_fc.b_0": ((h,), "zeros"),
        "next_sent_fc.w_0": ((h, 2), "normal"),
        "next_sent_fc.b_0": ((2,), "zeros"),
    }
    for n in range(cfg["num_hidden_layers"]):
        p = f"encoder_layer_{n}"
        for fc in ("query", "key", "value", "output"):
            out[f"{p}_multi_head_att_{fc}_fc.w_0"] = ((h, h), "normal")
            out[f"{p}_multi_head_att_{fc}_fc.b_0"] = ((h,), "zeros")
        out[f"{p}_ffn_fc_0.w_0"] = ((h, i), "normal")
        out[f"{p}_ffn_fc_0.b_0"] = ((i,), "zeros")
        out[f"{p}_ffn_fc_1.w_0"] = ((i, h), "normal")
        out[f"{p}_ffn_fc_1.b_0"] = ((h,), "zeros")
        for ln in ("post_att_ln", "post_ffn_ln"):
            out[f"{p}_{ln}_scale"] = ((h,), "ones")
            out[f"{p}_{ln}_bias"] = ((h,), "zeros")
    return out


def seed_key(seed):
    """A key from any whole number up to 2**63 (seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def init_weights(cfg, seed, dtype=jnp.float32):
    """Every parameter from the seed, on the device, in one jitted call."""
    shapes = param_shapes(cfg)
    std = cfg["initializer_range"]

    groups = {}
    for name, (shape, init) in sorted(shapes.items()):
        groups.setdefault((shape, init), []).append(name)

    @jax.jit
    def make(key):
        # one draw per group of equally shaped tensors, not one per tensor
        out = {}
        for n, ((shape, init), names) in enumerate(sorted(groups.items())):
            if init == "normal":
                block = std * jax.random.normal(
                    jax.random.fold_in(key, n), (len(names),) + shape,
                    jnp.float32)
            else:
                block = jnp.full((len(names),) + shape,
                                 1.0 if init == "ones" else 0.0, jnp.float32)
            for i, name in enumerate(names):
                out[name] = block[i].astype(dtype)
        return out

    return make(seed_key(seed))


def layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0).astype(np.float32)))


def dropout(x, rate, key):
    """upscale-in-train dropout; ``key`` None switches it off."""
    if key is None or not rate:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def _keys(key, n):
    return [None] * n if key is None else list(jax.random.split(key, n))


def encoder_layer(p, prefix, x, attn_bias, cfg, key, matmul):
    heads = cfg["num_attention_heads"]
    b, s, h = x.shape
    d = h // heads
    k_prob, k_att, k_ffn = _keys(key, 3)

    def fc(t, name):
        return matmul(t, p[f"{prefix}_{name}.w_0"]) + p[f"{prefix}_{name}.b_0"]

    def split(t):
        return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

    q = split(fc(x, "multi_head_att_query_fc"))
    k = split(fc(x, "multi_head_att_key_fc"))
    v = split(fc(x, "multi_head_att_value_fc"))
    scores = matmul(q, k.transpose(0, 1, 3, 2)) * (float(d) ** -0.5)
    probs = jax.nn.softmax(scores + attn_bias, axis=-1)
    probs = dropout(probs, cfg["attention_probs_dropout_prob"], k_prob)
    ctx = matmul(probs, v).transpose(0, 2, 1, 3).reshape(b, s, h)
    att = dropout(fc(ctx, "multi_head_att_output_fc"),
                  cfg["hidden_dropout_prob"], k_att)
    x = layer_norm(x + att, p[f"{prefix}_post_att_ln_scale"],
                   p[f"{prefix}_post_att_ln_bias"])
    ffn = fc(gelu(fc(x, "ffn_fc_0")), "ffn_fc_1")
    ffn = dropout(ffn, cfg["hidden_dropout_prob"], k_ffn)
    return layer_norm(x + ffn, p[f"{prefix}_post_ffn_ln_scale"],
                      p[f"{prefix}_post_ffn_ln_bias"])


def _layer(p, x, attn_bias, key, *, prefix, cfg, matmul):
    return encoder_layer(p, prefix, x, attn_bias, cfg, key, matmul)


def loss_sums(p, rows, cfg, key, matmul):
    """(sum of masked-LM cross entropies, sum of next-sentence cross
    entropies) over a block of rows.  ``rows["mask_pos"]`` is flat into
    this block's own rows."""
    k_emb, k_layers = _keys(key, 2)
    x = (p["word_embedding"][rows["src_ids"]]
         + p["pos_embedding"][rows["pos_ids"]]
         + p["sent_embedding"][rows["sent_ids"]])
    x = layer_norm(x, p["pre_encoder_ln_scale"], p["pre_encoder_ln_bias"])
    x = dropout(x, cfg["hidden_dropout_prob"], k_emb)
    attn_bias = ((rows["input_mask"] - 1.0) * 10000.0)[:, None, None, :]
    layer_keys = _keys(k_layers, cfg["num_hidden_layers"])
    for n in range(cfg["num_hidden_layers"]):
        # rematerialised: only each layer's input stays live for the
        # backward pass
        layer = jax.checkpoint(functools.partial(
            _layer, prefix=f"encoder_layer_{n}", cfg=cfg, matmul=matmul))
        x = layer(p, x, attn_bias, layer_keys[n])
    h = x.shape[-1]
    masked = x.reshape(-1, h)[rows["mask_pos"][:, 0]]
    t = gelu(matmul(masked, p["mask_lm_trans_fc.w_0"])
             + p["mask_lm_trans_fc.b_0"])
    t = layer_norm(t, p["mask_lm_trans_ln_scale"], p["mask_lm_trans_ln_bias"])
    logits = matmul(t, p["word_embedding"].T) + p["mask_lm_out_fc.b_0"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    mlm = -jnp.take_along_axis(logp, rows["mask_label"], axis=1).sum()
    pooled = jnp.tanh(matmul(x[:, 0, :], p["pooled_fc.w_0"])
                      + p["pooled_fc.b_0"])
    nsp_logits = matmul(pooled, p["next_sent_fc.w_0"]) + p["next_sent_fc.b_0"]
    nsp = -jnp.take_along_axis(jax.nn.log_softmax(nsp_logits, axis=-1),
                               rows["labels"], axis=1).sum()
    return mlm, nsp


def _block_batch(batch, shards, blocks_per_shard):
    """Cut a batch whose ``mask_pos`` is local to each of ``shards`` equal
    slices into row blocks, each with ``mask_pos`` local to itself."""
    n = batch["src_ids"].shape[0]
    seq = batch["src_ids"].shape[1]
    per_shard = n // shards
    rows_per_block = max(1, per_shard // blocks_per_shard)
    m_per_row = batch["mask_pos"].shape[0] // n
    out = []
    for b0 in range(0, n, rows_per_block):
        r = slice(b0, b0 + rows_per_block)
        m = slice(b0 * m_per_row, (b0 + rows_per_block) * m_per_row)
        in_shard = b0 % per_shard
        out.append({
            "src_ids": batch["src_ids"][r], "pos_ids": batch["pos_ids"][r],
            "sent_ids": batch["sent_ids"][r],
            "input_mask": batch["input_mask"][r], "labels": batch["labels"][r],
            "mask_label": batch["mask_label"][m],
            "mask_pos": batch["mask_pos"][m] - in_shard * seq,
        })
    return out


def block_grad_fn(cfg, matmul, n_rows, n_masked):
    """Jitted (params, rows, key, loss so far, gradients so far) -> the two
    sums with this block's share added."""
    def block(p, rows, k, loss, grads):
        def f(p):
            mlm, nsp = loss_sums(p, rows, cfg, k, matmul)
            return mlm / n_masked + nsp / n_rows
        part, g = jax.value_and_grad(f)(p)
        return loss + part, jax.tree.map(jnp.add, grads, g)
    return jax.jit(block, donate_argnums=(4,))


def loss_and_grads(block, params, blocks, key):
    """Loss and gradients of the whole batch, accumulated block by block
    so that float32 activations of one block, not of the batch, are live."""
    loss = jnp.zeros((), jnp.float32)
    grads = jax.tree.map(jnp.zeros_like, params)
    for n, rows in enumerate(blocks):
        k = None if key is None else jax.random.fold_in(key, n)
        loss, grads = block(params, rows, k, loss, grads)
    return loss, grads


@jax.jit
def adam_step(params, grads, m, v, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    lr_t = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * jnp.square(g), v, grads)
    params = jax.tree.map(
        lambda p, a, c: p - lr_t * a / (jnp.sqrt(c) + eps), params, m, v)
    return params, m, v


@jax.jit
def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for k, x in tree.items()}


def train_readings(cfg, batch, seed, steps, lr, matmul=jnp.matmul, shards=1,
                   blocks_per_shard=4):
    """What `correct` compares, from the reference: each step's loss, the
    per-leaf norm of the first gradient, and the per-leaf norm of the
    parameters' change after ``steps`` steps."""
    params0 = init_weights(cfg, seed)
    params = params0
    zeros = jax.tree.map(jnp.zeros_like, params)
    m, v = zeros, zeros
    key = jax.random.fold_in(seed_key(seed), 0xD0)
    block = block_grad_fn(cfg, matmul, batch["src_ids"].shape[0],
                          batch["mask_pos"].shape[0])
    blocks = [{a: jnp.asarray(b) for a, b in rows.items()}
              for rows in _block_batch(batch, shards, blocks_per_shard)]
    losses, grad_norms = [], None
    for t in range(1, steps + 1):
        loss, grads = loss_and_grads(block, params, blocks,
                                     jax.random.fold_in(key, t))
        losses.append(float(loss))
        if t == 1:
            first_grads = grads
            grad_norms = {a: float(b) for a, b in leaf_norms(grads).items()}
        params, m, v = adam_step(params, grads, m, v, float(t), lr)
    delta = leaf_norms(jax.tree.map(jnp.subtract, params, params0))
    return {"losses": losses, "grad_norms": grad_norms,
            "first_grads": first_grads,
            "delta_norms": {a: float(b) for a, b in delta.items()}}
