"""Plain reference: MiMo-V2 (``mimo_v2``: grouped-query attention in
sliding-window layers with a learned sink and in full layers, K heads
wider than V heads, rotary positions on part of a head, sigmoid-routed
experts and no shared one) forward pass in straightforward ``jax.numpy``
float32: no cache, no paging, no kernels, no batching.

It imports nothing of the program and takes nothing the program made: the
weights come from ``init_weights(cfg, seed)`` here (the benchmark puts the
same arrays into the program's scope).  Matrices are returned in the
configuration's storage dtype (bfloat16) holding values bfloat16
represents exactly, so both sides hold the same weights; every layer
function lifts what it uses to float32.  Every matmul against a weight or
between activations goes through the ``matmul`` argument, so that the
control (``reference/lowprec.py``) can put a lower precision in its place;
callers wrap the default in ``jax.default_matmul_precision("highest")``.

The equations (``cfg`` holds the source's keys; eps = layernorm_epsilon,
H = num_attention_heads, d = head_dim, dv = v_head_dim, W =
sliding_window, r = int(d * partial_rotary_factor); a layer is a WINDOW
layer where ``hybrid_layer_pattern`` is 1 — Hkv = swa_num_key_value_heads,
theta = swa_rope_theta — and a FULL layer where it is 0 — Hkv =
num_key_value_heads, theta = rope_theta):

  x0       E[tok]                                          (unscaled)
  block    a = x + Attn(RMS_1(x));  y = a + F(RMS_2(a));
           logits = RMS(y) W_head
  Attn(u)  q = u W_q [H, d]; k = u W_k [Hkv, d];
           v = attention_value_scale * (u W_v) [Hkv, dv]
           (assumed.value_scale); RoPE on the FIRST r entries of each q
           and k head (``rotate_half`` form inside them: entry i with
           entry i + r/2, angle pos * theta^(-2i/r); assumed.rope), the
           other d - r pass; query head j reads K/V head j // (H / Hkv);
           score[s, t] = q_s . k_t / sqrt(d) for t <= s and, in a window
           layer, s - t < W (assumed.window); softmax over t — in a
           window layer (add_swa_attention_sink_bias) over t AND one more
           column, the head's sink logit b_h, which carries no value:
           p_t = exp(s_t - m) / (sum_u exp(s_u - m) + exp(b_h - m)), m the
           largest of the scores and b_h; o = sum p v [H, dv];
           Attn = o W_o
  F        layers where moe_layer_freq is 0: (silu(u W_g) * u W_u) W_d;
           the others: s = sigmoid(u W_r); picks = top-k of s + b
           (noaux_tc; n_group = topk_group = 1: no group limit);
           g = s[picks] / (sum s[picks] + 1e-20) (norm_topk_prob), times 1
           (routed_scaling_factor null); the sum over the picks of
           g_e SwiGLU_e(u) — only the experts HELD here (ids first_expert
           .. first_expert + n_routed_experts of the router's
           n_routed_experts_total; the others add nothing, as on one chip
           of the expert-parallel deployment); NO shared expert.  Each
           held expert runs over every token and its gate (0 where not
           picked) weighs it in.

Long sequences: a layer runs over all positions at once except where a
[queries, keys] tensor appears, which goes by blocks of queries against
the keys they may see (a window layer's block: the W + block keys up to
its last query); the last layer and the logits are computed for the
served rows only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128  # queries scored against their keys at once
KEY_BLOCK = 4096   # sequences are padded, and keys handed over, in these
ROW_BLOCK = 4096   # rows through the FFN at once
ATTN_PARAMS = ("input_norm.scale", "q.w_0", "k.w_0", "v.w_0", "o.w_0",
               "sink.b_0")


def sizes(cfg):
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        hd=cfg["head_dim"], vd=cfg["v_head_dim"],
        rot=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        window=cfg["sliding_window"],
        experts=cfg["n_routed_experts_total"], held=cfg["n_routed_experts"],
        first=cfg["deployment"]["first_expert"],
        picks=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
        i=cfg["intermediate_size"], vocab=cfg["vocab_size"])


def kv_heads(cfg, layer):
    """The K/V heads of layer ``layer``: the window kind's or the full
    kind's."""
    return (cfg["swa_num_key_value_heads"]
            if cfg["hybrid_layer_pattern"][layer]
            else cfg["num_key_value_heads"])


def has_sink(cfg, layer):
    return bool(cfg["add_swa_attention_sink_bias"]
                if cfg["hybrid_layer_pattern"][layer]
                else cfg["add_full_attention_sink_bias"])


def param_shapes(cfg):
    """{name: (shape, init, kind)}; kind "matrix" is stored in the
    configuration's dtype, "vector" in float32; init "sink" is
    normal(0, assumed.sink_init_std)."""
    z = sizes(cfg)
    d, hq, hd, vd = z["d"], z["heads"], z["hd"], z["vd"]
    out = {
        "mimo_embed.w_0": ((z["vocab"], d), "normal", "matrix"),
        "mimo_head.w_0": ((d, z["vocab"]), "normal", "matrix"),
        "mimo_final_norm.scale": ((d,), "ones", "vector"),
    }
    for n in range(cfg["num_hidden_layers"]):
        p = f"mimo_layer_{n}_"
        hkv = kv_heads(cfg, n)
        for norm in ("input_norm", "post_attn_norm"):
            out[p + norm + ".scale"] = ((d,), "ones", "vector")
        if has_sink(cfg, n):
            out[p + "sink.b_0"] = ((hq,), "sink", "vector")
        mats = {"q.w_0": (d, hq * hd), "k.w_0": (d, hkv * hd),
                "v.w_0": (d, hkv * vd), "o.w_0": (hq * vd, d)}
        if not cfg["moe_layer_freq"][n]:
            mats.update({"ffn_gate.w_0": (d, z["i"]),
                         "ffn_up.w_0": (d, z["i"]),
                         "ffn_down.w_0": (z["i"], d)})
        else:
            mats.update({"moe_router.w_0": (d, z["experts"]),
                         "moe_experts_gate.w_0": (z["held"], d, z["f"]),
                         "moe_experts_up.w_0": (z["held"], d, z["f"]),
                         "moe_experts_down.w_0": (z["held"], z["f"], d)})
            out[p + "moe_router.b_0"] = ((z["experts"],), "normal", "vector")
        out.update({p + k: (s, "normal", "matrix") for k, s in mats.items()})
    return out


def seed_key(seed):
    """A key from any whole number up to 2**63 (seeds pass 2**31), of the
    ``rbg`` generator: billions of normal draws take seconds with the
    chip's random-bit generator and most of a minute with threefry."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF), impl="rbg")
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def init_weights(cfg, seed):
    """Every parameter from the seed, on the device, in one jitted call
    (one compile): normal(0, initializer_range) rounded to bfloat16 (so a
    bfloat16 and a float32 holder agree), the sinks normal(0,
    sink_init_std), ones; matrices in ``precision.weights``, vectors in
    float32.  Each tensor is its own draw and its own output, so no
    second copy of the weights exists."""
    std = {"normal": float(cfg["assumed"]["initializer_range"]),
           "sink": float(cfg["assumed"]["sink_init_std"])}
    storage = jnp.dtype(cfg["precision"]["weights"])
    shapes = sorted(param_shapes(cfg).items())

    @jax.jit
    def make(key):
        out = {}
        for n, (name, (shape, init, kind)) in enumerate(shapes):
            dtype = storage if kind == "matrix" else jnp.float32
            if init == "ones":
                out[name] = jnp.ones(shape, dtype)
            else:
                x = std[init] * jax.random.normal(
                    jax.random.fold_in(key, n), shape, jnp.float32)
                out[name] = x.astype(jnp.bfloat16).astype(dtype)
        return out

    return make(seed_key(seed))


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, pos, theta, rot):
    """``x cos + rotate_half(x) sin`` over the FIRST ``rot`` entries of
    the last dimension, the others unchanged; x [T, H, d], pos [T]."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos.astype(jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]     # [T, 1, rot]
    head = x[..., :rot]
    turned = jnp.concatenate([-head[..., rot // 2:], head[..., :rot // 2]],
                             axis=-1)
    return jnp.concatenate(
        [head * jnp.cos(ang) + turned * jnp.sin(ang), x[..., rot:]], axis=-1)


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, w_gate, w_up, w_down, matmul):
    return matmul(silu(matmul(x, w_gate)) * matmul(x, w_up), w_down)


def _f32(p):
    return {k: v.astype(jnp.float32) for k, v in p.items()}


@functools.partial(jax.jit, static_argnames=(
    "z", "eps", "theta", "hkv", "value_scale", "matmul"))
def layer_keys(x, p, *, z, eps, theta, hkv, value_scale, matmul):
    """What every position gives a layer's attention: the normed input
    and the K [T, Hkv, d] and V [T, Hkv, dv] rows a cache would hold."""
    z = dict(z)
    p = _f32(p)
    t = x.shape[0]
    u = rms_norm(x, p["input_norm.scale"], eps)
    k = rope(matmul(u, p["k.w_0"]).reshape(t, hkv, z["hd"]), jnp.arange(t),
             theta, z["rot"])
    v = value_scale * matmul(u, p["v.w_0"]).reshape(t, hkv, z["vd"])
    return u, k, v


@functools.partial(jax.jit, static_argnames=(
    "z", "theta", "hkv", "windowed", "matmul"))
def attend_block(first, key0, u, k, v, p, *, z, theta, hkv, windowed,
                 matmul):
    """Queries first .. first + Q (their normed rows ``u``) against the
    keys ``k`` [K, Hkv, d], ``v`` [K, Hkv, dv], which are positions key0
    .. key0 + K.  Returns Attn [Q, D], after the output projection.  A
    layer with a sink has ``sink.b_0`` in ``p``."""
    z = dict(z)
    p = _f32(p)
    nq, nk = u.shape[0], k.shape[0]
    hq, hd, vd = z["heads"], z["hd"], z["vd"]
    g = hq // hkv
    qpos = first + jnp.arange(nq)
    kpos = key0 + jnp.arange(nk)
    q = rope(matmul(u, p["q.w_0"]).reshape(nq, hq, hd), qpos, theta,
             z["rot"])
    # [Hkv, g * Q, d]: the g query heads of a K/V head against its keys
    q = q.reshape(nq, hkv, g, hd).transpose(1, 2, 0, 3).reshape(
        hkv, g * nq, hd)
    scores = matmul(q, k.transpose(1, 2, 0)) * (float(hd) ** -0.5)
    seen = kpos[None, :] <= qpos[:, None]                      # [Q, K]
    if windowed:
        seen &= qpos[:, None] - kpos[None, :] < z["window"]
    seen = jnp.tile(seen, (g, 1))[None]                        # [1, g*Q, K]
    scores = jnp.where(seen, scores, -jnp.inf)
    if "sink.b_0" in p:
        # row r * Q + i of K/V head j is query head j * g + r: its sink
        # logit is one more column of the softmax, with no value
        sink = jnp.repeat(p["sink.b_0"].reshape(hkv, g), nq, axis=1)
        m = jnp.maximum(jnp.max(scores, axis=-1), sink)[..., None]
        e = jnp.exp(scores - m)
        probs = e / (jnp.sum(e, axis=-1, keepdims=True)
                     + jnp.exp(sink[..., None] - m))
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    out = matmul(probs, v.transpose(1, 0, 2))                  # [Hkv, g*Q, dv]
    out = out.reshape(hkv, g, nq, vd).transpose(2, 0, 1, 3).reshape(
        nq, hq * vd)
    return matmul(out, p["o.w_0"])


def routed_experts(f, p, z, matmul):
    """The held experts' part of the expert layer over rows ``f`` [R, D]:
    the router over all ``experts``, the picks that land on experts
    ``first .. first + held`` weighed in, the others adding nothing."""
    s = jax.nn.sigmoid(matmul(f, p["moe_router.w_0"].astype(jnp.float32)))
    picks = jax.lax.top_k(s + p["moe_router.b_0"], z["picks"])[1]
    gates = jnp.take_along_axis(s, picks, axis=1)
    if z["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, axis=1, keepdims=True) + 1e-20)
    gates = z["scaling"] * gates
    # [R, experts] gate of every expert, 0 where it was not picked
    gate_of = jnp.zeros(s.shape, jnp.float32).at[
        jnp.arange(s.shape[0])[:, None], picks].set(gates)
    out = jnp.zeros(f.shape, jnp.float32)
    for e in range(z["held"]):
        out = out + gate_of[:, z["first"] + e, None] * swiglu(
            f, *(p[f"moe_experts_{k}.w_0"][e].astype(jnp.float32)
                 for k in ("gate", "up", "down")), matmul)
    return out


@functools.partial(jax.jit, static_argnames=("z", "eps", "dense", "matmul"))
def finish_rows(x, attn, p, *, z, eps, dense, matmul):
    """Attention's residual, then the FFN and its residual, over rows
    [R, D]."""
    z = dict(z)
    x = x + attn
    f = rms_norm(x, p["post_attn_norm.scale"], eps)
    if dense:
        return x + swiglu(f, *(p[f"ffn_{k}.w_0"].astype(jnp.float32)
                               for k in ("gate", "up", "down")), matmul)
    return x + routed_experts(f, p, z, matmul)


def routing(cfg):
    """The router's two settings as ``routed_experts`` takes them."""
    scaling = cfg.get("routed_scaling_factor")
    return {"scaling": 1.0 if scaling is None else float(scaling),
            "norm_topk_prob": bool(cfg["norm_topk_prob"])}


def forward(params, cfg, tokens, rows, matmul=jnp.matmul):
    """Logits [len(rows), vocab] of the positions ``rows`` of one sequence
    of ``tokens``.

    Only shapes change what is computed here, never values: the sequence
    is padded to whole KEY_BLOCKs (causal: what lies behind a position
    does not touch it), a block of queries of a full layer is given the
    keys up to the end of its own KEY_BLOCK, one of a window layer the
    last ``window`` + block keys up to its own end, and the last layer
    runs for the query blocks that hold a wanted row.  So every jitted
    function sees one of a handful of shapes, whatever the request's
    length."""
    z = dict(sizes(cfg), **routing(cfg))
    zt = tuple(sorted(z.items()))
    eps = cfg["layernorm_epsilon"]
    value_scale = float(cfg["attention_value_scale"])
    t = len(tokens)
    t_pad = -(-t // KEY_BLOCK) * KEY_BLOCK
    padded = np.zeros(t_pad, np.int32)
    padded[:t] = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int64)
    x = params["mimo_embed.w_0"][jnp.asarray(padded)].astype(jnp.float32)
    slab = min(t_pad, -(-(z["window"] + QUERY_BLOCK) // QUERY_BLOCK)
               * QUERY_BLOCK)
    last = cfg["num_hidden_layers"] - 1
    start = 0
    for n in range(last + 1):
        prefix = f"mimo_layer_{n}_"
        p = {k[len(prefix):]: v for k, v in params.items()
             if k.startswith(prefix)}
        windowed = bool(cfg["hybrid_layer_pattern"][n])
        theta = float(cfg["swa_rope_theta"] if windowed
                      else cfg["rope_theta"])
        hkv = kv_heads(cfg, n)
        attn_p = {k: p[k] for k in ATTN_PARAMS if k in p}
        u, k, v = layer_keys(x, attn_p, z=zt, eps=eps, theta=theta, hkv=hkv,
                             value_scale=value_scale, matmul=matmul)
        # the last layer's queries: from the block of the first wanted row
        start = int(rows.min()) // ROW_BLOCK * ROW_BLOCK if n == last else 0
        outs = []
        for first in range(start, t_pad, QUERY_BLOCK):
            if first >= t:  # padding rows: nothing reads them
                outs.append(jnp.zeros((QUERY_BLOCK, x.shape[1]),
                                      jnp.float32))
                continue
            end = first + QUERY_BLOCK
            if windowed:
                key0 = max(0, end - slab)
                keys = slice(key0, key0 + slab)
            else:
                key0 = 0
                keys = slice(0, min(t_pad, -(-end // KEY_BLOCK) * KEY_BLOCK))
            outs.append(attend_block(
                first, key0, u[first:end], k[keys], v[keys], attn_p, z=zt,
                theta=theta, hkv=hkv, windowed=windowed, matmul=matmul))
        del u, k, v
        attn = jnp.concatenate(outs)
        del outs
        rest = {k: v for k, v in p.items() if k not in ATTN_PARAMS}
        x = jnp.concatenate([
            finish_rows(x[start + r:start + r + ROW_BLOCK],
                        attn[r:r + ROW_BLOCK], rest, z=zt, eps=eps,
                        dense=not cfg["moe_layer_freq"][n], matmul=matmul)
            for r in range(0, t_pad - start, ROW_BLOCK)])
        del attn
    hidden = rms_norm(x[jnp.asarray(rows - start, jnp.int32)],
                      params["mimo_final_norm.scale"], eps)
    return matmul(hidden, params["mimo_head.w_0"].astype(jnp.float32))


def served_logits(params, cfg, prompt, served, matmul=jnp.matmul):
    """Logits [len(served), vocab] that predicted each served token: one
    forward over the prompt and the served tokens."""
    seq = list(prompt) + list(served[:-1])
    rows = len(prompt) - 1 + np.arange(len(served))
    return forward(params, cfg, np.asarray(seq, np.int32), rows, matmul)
