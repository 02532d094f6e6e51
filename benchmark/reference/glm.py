"""Plain reference: GLM-5 (``glm_moe_dsa``: multi-head latent attention,
the learned sparse-attention indexer, sigmoid-routed experts) forward
pass in straightforward ``jax.numpy`` float32: no cache, no paging, no
kernels, no batching.

It imports nothing of the program and takes nothing the program made: the
weights come from ``init_weights(cfg, seed)`` here (the benchmark puts the
same arrays into the program's scope).  Matrices are returned in the
configuration's storage dtype (bfloat16) holding values bfloat16
represents exactly, so both sides hold the same weights; every layer
function lifts what it uses to float32.  Every matmul against a weight or
between activations goes through the ``matmul`` argument, so that the
control (``reference/lowprec.py``) can put a lower precision in its place;
callers wrap the default in ``jax.default_matmul_precision("highest")``.

The equations (``cfg`` holds the source's keys; eps = rms_norm_eps):

  block    h <- h + Attn(RMS(h)); h <- h + FFN(RMS(h)); logits = RMS(h) W_head
  MLA      c_q = RMS(x W_qa); per head [q_nope | q_rope] = c_q W_qb;
           [c_kv | k_rope] = x W_kva; c_kv <- RMS(c_kv); RoPE (interleaved
           pairs, theta) on q_rope and on k_rope, which all heads share;
           per head k_nope = c_kv W_kvb_k^T, v = c_kv W_kvb_v (W_kvb held
           as its halves [H, nope, c] and [H, c, v]);
           score[t, s] = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope +
           rope); softmax over s in S_t; Attn = concat(sum p v) W_o
  indexer  qI = c_q W_Iq (RoPE on the first rope dims of each head);
           kI = LayerNorm(x W_Ik) (same RoPE); w = x W_Iw / sqrt(Hi * Di);
           I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]);
           S_t = the index_topk positions s <= t with the largest I[t, s]
           (all of them while t < index_topk; ties to the lower position)
  FFN      layers below first_k_dense_replace: (silu(x W_g) * x W_u) W_d;
           the others: s = sigmoid(x W_r); picks = top-k of s + b;
           g = routed_scaling_factor * s[picks] / sum s[picks]; the sum
           over the picks of g_e SwiGLU_e(x) — only the experts HELD here
           (ids first_expert .. first_expert + n_routed_experts of the
           router's n_routed_experts_total; the others add nothing, as on
           one chip of the expert-parallel deployment) — plus the shared
           expert's SwiGLU(x).  Each held expert runs over every token and
           its gate (0 where not picked) weighs it in.

Long sequences: a layer runs over all positions at once except where a
[queries, positions] tensor appears (indexer scores, attention scores),
which go by blocks of queries against the keys they may see; the last
layer and the logits are computed for the served rows only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 64   # queries scored against their keys at once
KEY_BLOCK = 4096   # sequences are padded, and keys handed over, in these
ROW_BLOCK = 4096   # rows through the FFN at once
KEY_PARAMS = ("attn_norm.scale", "q_a.w_0", "q_a_norm.scale", "kv_a.w_0",
              "kv_a_norm.scale", "idx_k.w_0", "idx_k_norm.scale",
              "idx_k_norm.bias")
QUERY_PARAMS = ("q_b.w_0", "idx_q.w_0", "idx_w.w_0", "kv_b_k.w_0",
                "kv_b_v.w_0", "o.w_0")


def sizes(cfg):
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v=cfg["v_head_dim"], cq=cfg["q_lora_rank"], ckv=cfg["kv_lora_rank"],
        hi=cfg["index_n_heads"], di=cfg["index_head_dim"],
        topk=cfg["index_topk"], experts=cfg["n_routed_experts_total"],
        held=cfg["n_routed_experts"], first=cfg["deployment"]["first_expert"],
        picks=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
        i=cfg["intermediate_size"], vocab=cfg["vocab_size"])


def param_shapes(cfg):
    """{name: (shape, init, kind)}; kind "matrix" is stored in the
    configuration's dtype, "vector" in float32."""
    z = sizes(cfg)
    d, h = z["d"], z["heads"]
    out = {
        "glm_embed.w_0": ((z["vocab"], d), "normal", "matrix"),
        "glm_head.w_0": ((d, z["vocab"]), "normal", "matrix"),
        "glm_final_norm.scale": ((d,), "ones", "vector"),
    }
    for n in range(cfg["num_hidden_layers"]):
        p = f"glm_layer_{n}_"
        out.update({
            p + "attn_norm.scale": ((d,), "ones", "vector"),
            p + "q_a.w_0": ((d, z["cq"]), "normal", "matrix"),
            p + "q_a_norm.scale": ((z["cq"],), "ones", "vector"),
            p + "q_b.w_0": ((z["cq"], h * (z["nope"] + z["rope"])),
                            "normal", "matrix"),
            p + "kv_a.w_0": ((d, z["ckv"] + z["rope"]), "normal", "matrix"),
            p + "kv_a_norm.scale": ((z["ckv"],), "ones", "vector"),
            p + "kv_b_k.w_0": ((h, z["nope"], z["ckv"]), "normal", "matrix"),
            p + "kv_b_v.w_0": ((h, z["ckv"], z["v"]), "normal", "matrix"),
            p + "o.w_0": ((h * z["v"], d), "normal", "matrix"),
            p + "idx_q.w_0": ((z["cq"], z["hi"] * z["di"]), "normal",
                              "matrix"),
            p + "idx_k.w_0": ((d, z["di"]), "normal", "matrix"),
            p + "idx_k_norm.scale": ((z["di"],), "ones", "vector"),
            p + "idx_k_norm.bias": ((z["di"],), "zeros", "vector"),
            p + "idx_w.w_0": ((d, z["hi"]), "normal", "matrix"),
            p + "ffn_norm.scale": ((d,), "ones", "vector"),
        })
        if n < cfg["first_k_dense_replace"]:
            ffn = {"ffn_gate.w_0": (d, z["i"]), "ffn_up.w_0": (d, z["i"]),
                   "ffn_down.w_0": (z["i"], d)}
        else:
            ffn = {"moe_router.w_0": (d, z["experts"]),
                   "moe_experts_gate.w_0": (z["held"], d, z["f"]),
                   "moe_experts_up.w_0": (z["held"], d, z["f"]),
                   "moe_experts_down.w_0": (z["held"], z["f"], d),
                   "shared_gate.w_0": (d, z["f"]),
                   "shared_up.w_0": (d, z["f"]),
                   "shared_down.w_0": (z["f"], d)}
            out[p + "moe_router.b_0"] = ((z["experts"],), "normal", "vector")
        out.update({p + k: (s, "normal", "matrix") for k, s in ffn.items()})
    return out


def seed_key(seed):
    """A key from any whole number up to 2**63 (seeds pass 2**31), of the
    ``rbg`` generator: 3.9 G normal draws take seconds with the chip's
    random-bit generator and most of a minute with threefry."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF), impl="rbg")
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def init_weights(cfg, seed):
    """Every parameter from the seed, on the device, in one jitted call
    (one compile): normal(0, initializer_range) rounded to bfloat16 (so a
    bfloat16 and a float32 holder agree), ones and zeros; matrices in
    ``precision.weights``, vectors in float32.  Each tensor is its own
    draw and its own output, so no second copy of the weights exists."""
    std = float(cfg["assumed"]["initializer_range"])
    storage = jnp.dtype(cfg["precision"]["weights"])
    shapes = sorted(param_shapes(cfg).items())

    @jax.jit
    def make(key):
        out = {}
        for n, (name, (shape, init, kind)) in enumerate(shapes):
            dtype = storage if kind == "matrix" else jnp.float32
            if init == "normal":
                x = std * jax.random.normal(jax.random.fold_in(key, n),
                                            shape, jnp.float32)
                out[name] = x.astype(jnp.bfloat16).astype(dtype)
            else:
                out[name] = jnp.full(shape, 1.0 if init == "ones" else 0.0,
                                     dtype)
        return out

    return make(seed_key(seed))


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def rope(x, pos, theta, rd):
    """Interleaved pairs on the first ``rd`` entries of the last dimension;
    x [T, d] or [T, H, d], pos [T]."""
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    a, b = x[..., 0:rd:2], x[..., 1:rd:2]
    turned = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return jnp.concatenate(
        [turned.reshape(x.shape[:-1] + (rd,)), x[..., rd:]], axis=-1)


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, w_gate, w_up, w_down, matmul):
    return matmul(silu(matmul(x, w_gate)) * matmul(x, w_up), w_down)


def topk_mask(scores, k):
    """[Q, S] bool: the k largest of each row, ties to the lower position
    (``lax.top_k``'s order); every finite score where fewer than k are."""
    k = min(k, scores.shape[1])
    picks = jax.lax.top_k(scores, k)[1]
    hit = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], picks].set(True)
    return hit & jnp.isfinite(scores)


def _f32(p):
    return {k: v.astype(jnp.float32) for k, v in p.items()}


@functools.partial(jax.jit, static_argnames=("z", "eps", "theta", "matmul"))
def layer_keys(x, p, *, z, eps, theta, matmul):
    """What every position gives a layer's attention: the normed input,
    the compressed query, and the two rows a cache would hold, [T, ...]."""
    z = dict(z)
    p = _f32(p)
    pos = jnp.arange(x.shape[0])
    xa = rms_norm(x, p["attn_norm.scale"], eps)
    c_q = rms_norm(matmul(xa, p["q_a.w_0"]), p["q_a_norm.scale"], eps)
    kv = matmul(xa, p["kv_a.w_0"])
    c_kv = rms_norm(kv[:, :z["ckv"]], p["kv_a_norm.scale"], eps)
    k_rope = rope(kv[:, z["ckv"]:], pos, theta, z["rope"])
    k_idx = rope(layer_norm(matmul(xa, p["idx_k.w_0"]),
                            p["idx_k_norm.scale"], p["idx_k_norm.bias"]),
                 pos, theta, z["rope"])
    return xa, c_q, c_kv, k_rope, k_idx


@functools.partial(jax.jit, static_argnames=("z", "theta", "matmul"))
def attend_block(first, xa, c_q, c_kv, k_rope, k_idx, p, *, z, theta,
                 matmul):
    """Queries first .. first + Q (their rows ``xa``, ``c_q``) against
    every position: indexer scores, the selected set, attention over it.
    Returns (Attn [Q, D], after the output projection; the selected-set
    mask [Q, T]).

    Scores and values go through the compressed row: q_nope . (c_kv
    W_kvb_k^T) = (q_nope W_kvb_k) . c_kv, and sum p (c_kv W_kvb_v) =
    (sum p c_kv) W_kvb_v — the same numbers as expanding every position's
    k_nope and v, which at 33k positions would hold 3.8 GB."""
    z = dict(z)
    p = _f32(p)
    nq, t, heads = xa.shape[0], c_kv.shape[0], z["heads"]
    qpos = first + jnp.arange(nq)
    q = matmul(c_q, p["q_b.w_0"]).reshape(nq, heads, z["nope"] + z["rope"])
    q_nope = q[..., :z["nope"]].transpose(1, 0, 2)             # [H, Q, nope]
    q_rope = rope(q[..., z["nope"]:], qpos, theta,
                  z["rope"]).transpose(1, 0, 2)                # [H, Q, rope]
    q_idx = rope(matmul(c_q, p["idx_q.w_0"]).reshape(nq, z["hi"], z["di"]),
                 qpos, theta, z["rope"])
    w_idx = matmul(xa, p["idx_w.w_0"]) * (z["hi"] ** -0.5 * z["di"] ** -0.5)
    s_idx = matmul(q_idx.reshape(nq * z["hi"], z["di"]),
                   k_idx.T).reshape(nq, z["hi"], t)
    index = jnp.sum(jnp.maximum(s_idx, 0.0) * w_idx[:, :, None], axis=1)
    causal = jnp.arange(t)[None, :] <= qpos[:, None]           # [Q, T]
    chosen = topk_mask(jnp.where(causal, index, -jnp.inf), z["topk"])
    scale = float(z["nope"] + z["rope"]) ** -0.5
    q_lat = matmul(q_nope, p["kv_b_k.w_0"])                    # [H, Q, ckv]
    scores = (matmul(q_lat, c_kv.T) + matmul(q_rope, k_rope.T)) * scale
    probs = jax.nn.softmax(jnp.where(chosen[None], scores, -jnp.inf),
                           axis=-1)                            # [H, Q, T]
    out = matmul(matmul(probs, c_kv), p["kv_b_v.w_0"])         # [H, Q, v]
    return matmul(out.transpose(1, 0, 2).reshape(nq, -1),
                  p["o.w_0"]), chosen


@functools.partial(jax.jit, static_argnames=("z", "eps", "dense", "matmul"))
def finish_rows(x, attn, p, *, z, eps, dense, matmul):
    """Attention's residual, FFN, residual over rows [R, D]."""
    z = dict(z)
    x = x + attn
    f = rms_norm(x, p["ffn_norm.scale"], eps)
    if dense:
        return x + swiglu(f, *(p[f"ffn_{k}.w_0"].astype(jnp.float32)
                               for k in ("gate", "up", "down")), matmul)
    s = jax.nn.sigmoid(matmul(f, p["moe_router.w_0"].astype(jnp.float32)))
    picks = jax.lax.top_k(s + p["moe_router.b_0"], z["picks"])[1]
    gates = jnp.take_along_axis(s, picks, axis=1)
    gates = z["scaling"] * gates / jnp.sum(gates, axis=1, keepdims=True)
    # [R, experts] gate of every expert, 0 where it was not picked
    gate_of = jnp.zeros(s.shape, jnp.float32).at[
        jnp.arange(s.shape[0])[:, None], picks].set(gates)
    out = swiglu(f, *(p[f"shared_{k}.w_0"].astype(jnp.float32)
                      for k in ("gate", "up", "down")), matmul)
    for e in range(z["held"]):
        out = out + gate_of[:, z["first"] + e, None] * swiglu(
            f, *(p[f"moe_experts_{k}.w_0"][e].astype(jnp.float32)
                 for k in ("gate", "up", "down")), matmul)
    return x + out


def forward(params, cfg, tokens, rows, matmul=jnp.matmul, selections=None):
    """Logits [len(rows), vocab] of the positions ``rows`` of one sequence
    of ``tokens``.  ``selections``, a list, receives per layer the [T, T]
    selected-set mask (tests; short sequences only).

    Only shapes change what is computed here, never values: the sequence
    is padded to whole KEY_BLOCKs (causal: what lies behind a position
    does not touch it), a block of queries is given the keys up to the
    end of its own KEY_BLOCK (it may see no later one), and the last
    layer runs for the query blocks that hold a wanted row.  So every
    jitted function sees one of a handful of shapes, whatever the
    request's length."""
    z = sizes(cfg)
    z["scaling"] = float(cfg["routed_scaling_factor"])
    zt = tuple(sorted(z.items()))
    eps, theta = cfg["rms_norm_eps"], float(
        cfg["rope_parameters"]["rope_theta"])
    t = len(tokens)
    t_pad = -(-t // KEY_BLOCK) * KEY_BLOCK
    padded = np.zeros(t_pad, np.int32)
    padded[:t] = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int64)
    x = params["glm_embed.w_0"][jnp.asarray(padded)].astype(jnp.float32)
    last = cfg["num_hidden_layers"] - 1
    for n in range(last + 1):
        prefix = f"glm_layer_{n}_"
        p = {k[len(prefix):]: v for k, v in params.items()
             if k.startswith(prefix)}
        xa, c_q, c_kv, k_rope, k_idx = layer_keys(
            x, {k: p[k] for k in KEY_PARAMS}, z=zt, eps=eps, theta=theta,
            matmul=matmul)
        # the last layer's queries: from the block of the first wanted row
        start = (int(rows.min()) // ROW_BLOCK * ROW_BLOCK
                 if n == last and selections is None else 0)
        outs, masks = [], []
        for first in range(start, t_pad, QUERY_BLOCK):
            if first >= t:  # padding rows: nothing reads them
                outs.append(jnp.zeros((QUERY_BLOCK, x.shape[1]),
                                      jnp.float32))
                continue
            q = slice(first, first + QUERY_BLOCK)
            keys = min(t_pad, -(-(first + QUERY_BLOCK) // KEY_BLOCK)
                       * KEY_BLOCK)
            o, chosen = attend_block(
                first, xa[q], c_q[q], c_kv[:keys], k_rope[:keys],
                k_idx[:keys], {k: p[k] for k in QUERY_PARAMS}, z=zt,
                theta=theta, matmul=matmul)
            outs.append(o)
            if selections is not None:
                masks.append(jnp.pad(chosen, ((0, 0), (0, t_pad - keys))))
        if selections is not None:
            selections.append(jnp.concatenate(masks)[:t, :t])
        del xa, c_q, c_kv, k_rope, k_idx
        attn = jnp.concatenate(outs)
        del outs
        rest = {k: v for k, v in p.items()
                if k not in KEY_PARAMS + QUERY_PARAMS}
        x = jnp.concatenate([
            finish_rows(x[start + r:start + r + ROW_BLOCK],
                        attn[r:r + ROW_BLOCK], rest, z=zt, eps=eps,
                        dense=n < cfg["first_k_dense_replace"],
                        matmul=matmul)
            for r in range(0, t_pad - start, ROW_BLOCK)])
        del attn
    hidden = rms_norm(x[jnp.asarray(rows - start, jnp.int32)],
                      params["glm_final_norm.scale"], eps)
    return matmul(hidden, params["glm_head.w_0"].astype(jnp.float32))


def served_logits(params, cfg, prompt, served, matmul=jnp.matmul):
    """Logits [len(served), vocab] that predicted each served token: one
    forward over the prompt and the served tokens."""
    seq = list(prompt) + list(served[:-1])
    rows = len(prompt) - 1 + np.arange(len(served))
    return forward(params, cfg, np.asarray(seq, np.int32), rows, matmul)
