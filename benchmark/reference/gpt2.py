"""Plain reference: GPT-2 (Radford et al. 2019) forward pass in
straightforward ``jax.numpy`` float32: no cache, no paging, no batching.

It imports nothing of the program and takes nothing the program made: the
weights come from ``init_weights(cfg, seed)`` here (the benchmark puts the
same arrays into the program's scope).  Every matmul goes through the
``matmul`` argument so that the control (``reference/lowprec.py``) can put
a lower precision in its place; callers wrap the default in
``jax.default_matmul_precision("highest")``.

Architecture as published: token + position embeddings; N pre-LN blocks
(layer norm, causal multi-head self-attention, output projection,
residual; layer norm, FFN, residual); a final layer norm; logits against
the tied token embedding.  Departures from the source config, each in the
configuration file: exact (erf) GeLU where the source has the tanh form,
vocabulary padded to 50304.  Parameter names are the program's.

One jitted layer serves every layer (they share their shapes), so the
reference compiles in seconds and holds one layer's activations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def param_shapes(cfg):
    h, v = cfg["n_embd"], cfg["vocab_size"]
    i = cfg["n_inner"] or 4 * h
    out = {
        "gpt_word_embedding": ((v, h), "normal"),
        "gpt_pos_embedding": ((cfg["n_positions"], h), "normal"),
        "gpt_final_ln_scale": ((h,), "ones"),
        "gpt_final_ln_bias": ((h,), "zeros"),
    }
    for n in range(cfg["n_layer"]):
        p = f"decoder_layer_{n}"
        for fc in ("query", "key", "value", "output"):
            out[f"{p}_att_{fc}_fc.w_0"] = ((h, h), "normal")
            out[f"{p}_att_{fc}_fc.b_0"] = ((h,), "zeros")
        out[f"{p}_ffn_fc_0.w_0"] = ((h, i), "normal")
        out[f"{p}_ffn_fc_0.b_0"] = ((i,), "zeros")
        out[f"{p}_ffn_fc_1.w_0"] = ((i, h), "normal")
        out[f"{p}_ffn_fc_1.b_0"] = ((h,), "zeros")
        for ln in ("ln_attn", "ln_ffn"):
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


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0).astype(np.float32)))


@functools.partial(jax.jit, static_argnames=("heads", "eps", "matmul"))
def block(x, p, *, heads, eps, matmul):
    """One pre-LN block over [T, H]; ``p`` holds the layer's tensors under
    their names less the layer prefix."""
    t, h = x.shape
    d = h // heads

    def fc(v, name):
        return matmul(v, p[f"{name}.w_0"]) + p[f"{name}.b_0"]

    def split(v):
        return v.reshape(t, heads, d).transpose(1, 0, 2)

    a = layer_norm(x, p["ln_attn_scale"], p["ln_attn_bias"], eps)
    q, k, v = (split(fc(a, f"att_{n}_fc")) for n in ("query", "key", "value"))
    scores = matmul(q, k.transpose(0, 2, 1)) * (float(d) ** -0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = matmul(probs, v).transpose(1, 0, 2).reshape(t, h)
    x = x + fc(ctx, "att_output_fc")
    f = layer_norm(x, p["ln_ffn_scale"], p["ln_ffn_bias"], eps)
    return x + fc(gelu(fc(f, "ffn_fc_0")), "ffn_fc_1")


@functools.partial(jax.jit, static_argnames=("eps", "matmul"))
def head(x, rows, scale, bias, emb, *, eps, matmul):
    hidden = layer_norm(x[rows], scale, bias, eps)
    return matmul(hidden, emb.T)


def logits_at(params, cfg, tokens, rows, matmul=jnp.matmul):
    """Logits [len(rows), vocab] of the positions ``rows`` of one sequence
    ``tokens`` (padded by the caller to a fixed length: the mask is causal,
    so padding behind a position does not touch it)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = (params["gpt_word_embedding"][tokens]
         + params["gpt_pos_embedding"][jnp.arange(tokens.shape[0])])
    eps = cfg["layer_norm_epsilon"]
    for n in range(cfg["n_layer"]):
        prefix = f"decoder_layer_{n}_"
        layer = {k[len(prefix):]: v for k, v in params.items()
                 if k.startswith(prefix)}
        x = block(x, layer, heads=cfg["n_head"], eps=eps, matmul=matmul)
    return head(x, jnp.asarray(rows, jnp.int32), params["gpt_final_ln_scale"],
                params["gpt_final_ln_bias"], params["gpt_word_embedding"],
                eps=eps, matmul=matmul)


def served_logits(params, cfg, prompt, served, matmul=jnp.matmul):
    """Logits [len(served), vocab] that predicted each served token: one
    forward over the prompt and the served tokens, padded to the model's
    positions."""
    seq = list(prompt) + list(served[:-1])
    length = cfg["n_positions"]
    tokens = np.zeros(length, np.int32)
    tokens[:len(seq)] = seq
    rows = np.full(cfg["max_served"], len(prompt) - 1, np.int32)
    rows[:len(served)] = len(prompt) - 1 + np.arange(len(served))
    return logits_at(params, cfg, tokens, rows, matmul)[:len(served)]
