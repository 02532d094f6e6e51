"""Plain reference: Trinity (``afmoe``: grouped-query attention in
sliding-window and full layers, a sigmoid gate on attention's output,
sandwich norms, sigmoid-routed experts) forward pass in straightforward
``jax.numpy`` float32: no cache, no paging, no kernels, no batching.

It imports nothing of the program and takes nothing the program made: the
weights come from ``init_weights(cfg, seed)`` here (the benchmark puts the
same arrays into the program's scope).  Matrices are returned in the
configuration's storage dtype (bfloat16) holding values bfloat16
represents exactly, so both sides hold the same weights; every layer
function lifts what it uses to float32.  Every matmul against a weight or
between activations goes through the ``matmul`` argument, so that the
control (``reference/lowprec.py``) can put a lower precision in its place;
callers wrap the default in ``jax.default_matmul_precision("highest")``.

The equations (``cfg`` holds the source's keys; eps = rms_norm_eps,
H = num_attention_heads, Hkv = num_key_value_heads, d = head_dim,
W = sliding_window):

  x0       E[tok] * sqrt(hidden_size)                     (mup_enabled)
  block    a = x + RMS_post_attn(Attn(RMS_in(x)));
           y = a + RMS_post_mlp(F(RMS_pre_mlp(a)));  logits = RMS(y) W_head
  Attn(u)  q = u W_q [H, d]; k = u W_k, v = u W_v [Hkv, d]; q, k <-
           RMSNorm over each head's d entries; in a ``sliding_attention``
           layer RoPE on q and k (``rotate_half`` form over the whole
           head: entry i with entry i + d/2, angle pos * theta^(-2i/d)),
           in a ``full_attention`` layer no positional encoding; query
           head j reads K/V head j // (H / Hkv); score[s, t] = q_s . k_t /
           sqrt(d) for t <= s and, in a sliding layer, s - t < W; softmax
           over t; o = (sum p v) * sigmoid(u W_g) elementwise; Attn = o W_o
  F        layers below num_dense_layers: (silu(u W_g) * u W_u) W_d; the
           others: s = sigmoid(u W_r); picks = top-k of s + b;
           g = route_scale * s[picks] / (sum s[picks] + 1e-20); the sum
           over the picks of g_e SwiGLU_e(u) — only the experts HELD here
           (ids first_expert .. first_expert + num_experts of the router's
           num_experts_total; the others add nothing, as on one chip of
           the expert-parallel deployment) — plus the shared expert's
           SwiGLU(u).  Each held expert runs over every token and its
           gate (0 where not picked) weighs it in.

Long sequences: a layer runs over all positions at once except where a
[queries, keys] tensor appears, which goes by blocks of queries against
the keys they may see (a sliding layer's block: the W + block keys up to
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
SLIDING = "sliding_attention"
ATTN_PARAMS = ("input_norm.scale", "q.w_0", "k.w_0", "v.w_0", "q_norm.scale",
               "k_norm.scale", "gate.w_0", "o.w_0")


def sizes(cfg):
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        window=cfg["sliding_window"], experts=cfg["num_experts_total"],
        held=cfg["num_experts"], first=cfg["deployment"]["first_expert"],
        picks=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
        i=cfg["intermediate_size"], vocab=cfg["vocab_size"])


def param_shapes(cfg):
    """{name: (shape, init, kind)}; kind "matrix" is stored in the
    configuration's dtype, "vector" in float32."""
    z = sizes(cfg)
    d, hq, hkv, hd = z["d"], z["heads"], z["kv_heads"], z["hd"]
    out = {
        "trinity_embed.w_0": ((z["vocab"], d), "normal", "matrix"),
        "trinity_head.w_0": ((d, z["vocab"]), "normal", "matrix"),
        "trinity_final_norm.scale": ((d,), "ones", "vector"),
    }
    for n in range(cfg["num_hidden_layers"]):
        p = f"trinity_layer_{n}_"
        for norm, width in (("input_norm", d), ("post_attn_norm", d),
                            ("pre_mlp_norm", d), ("post_mlp_norm", d),
                            ("q_norm", hd), ("k_norm", hd)):
            out[p + norm + ".scale"] = ((width,), "ones", "vector")
        mats = {"q.w_0": (d, hq * hd), "k.w_0": (d, hkv * hd),
                "v.w_0": (d, hkv * hd), "gate.w_0": (d, hq * hd),
                "o.w_0": (hq * hd, d)}
        if n < cfg["num_dense_layers"]:
            mats.update({"ffn_gate.w_0": (d, z["i"]),
                         "ffn_up.w_0": (d, z["i"]),
                         "ffn_down.w_0": (z["i"], d)})
        else:
            mats.update({"moe_router.w_0": (d, z["experts"]),
                         "moe_experts_gate.w_0": (z["held"], d, z["f"]),
                         "moe_experts_up.w_0": (z["held"], d, z["f"]),
                         "moe_experts_down.w_0": (z["held"], z["f"], d),
                         "shared_gate.w_0": (d, z["f"]),
                         "shared_up.w_0": (d, z["f"]),
                         "shared_down.w_0": (z["f"], d)})
            out[p + "moe_router.b_0"] = ((z["experts"],), "normal", "vector")
        out.update({p + k: (s, "normal", "matrix") for k, s in mats.items()})
    return out


def seed_key(seed):
    """A key from any whole number up to 2**63 (seeds pass 2**31), of the
    ``rbg`` generator: 4.3 G normal draws take seconds with the chip's
    random-bit generator and most of a minute with threefry."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF), impl="rbg")
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def init_weights(cfg, seed):
    """Every parameter from the seed, on the device, in one jitted call
    (one compile): normal(0, initializer_range) rounded to bfloat16 (so a
    bfloat16 and a float32 holder agree), ones; matrices in
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
                out[name] = jnp.ones(shape, dtype)
        return out

    return make(seed_key(seed))


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """``x cos + rotate_half(x) sin`` over the whole last dimension;
    x [T, H, d], pos [T]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]     # [T, 1, d]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, w_gate, w_up, w_down, matmul):
    return matmul(silu(matmul(x, w_gate)) * matmul(x, w_up), w_down)


def _f32(p):
    return {k: v.astype(jnp.float32) for k, v in p.items()}


@functools.partial(jax.jit,
                   static_argnames=("z", "eps", "theta", "sliding", "matmul"))
def layer_keys(x, p, *, z, eps, theta, sliding, matmul):
    """What every position gives a layer's attention: the normed input
    and the K and V rows a cache would hold, [T, Hkv, d]."""
    z = dict(z)
    p = _f32(p)
    t = x.shape[0]
    u = rms_norm(x, p["input_norm.scale"], eps)
    k = rms_norm(matmul(u, p["k.w_0"]).reshape(t, z["kv_heads"], z["hd"]),
                 p["k_norm.scale"], eps)
    if sliding:
        k = rope(k, jnp.arange(t), theta)
    return u, k, matmul(u, p["v.w_0"]).reshape(t, z["kv_heads"], z["hd"])


@functools.partial(jax.jit,
                   static_argnames=("z", "eps", "theta", "sliding", "matmul"))
def attend_block(first, key0, u, k, v, p, *, z, eps, theta, sliding, matmul):
    """Queries first .. first + Q (their normed rows ``u``) against the
    keys ``k``, ``v`` [K, Hkv, d], which are positions key0 .. key0 + K.
    Returns Attn [Q, D], after the gate and the output projection."""
    z = dict(z)
    p = _f32(p)
    nq, nk = u.shape[0], k.shape[0]
    hq, hkv, hd = z["heads"], z["kv_heads"], z["hd"]
    g = hq // hkv
    qpos = first + jnp.arange(nq)
    kpos = key0 + jnp.arange(nk)
    q = rms_norm(matmul(u, p["q.w_0"]).reshape(nq, hq, hd),
                 p["q_norm.scale"], eps)
    if sliding:
        q = rope(q, qpos, theta)
    # [Hkv, g * Q, d]: the g query heads of a K/V head against its keys
    q = q.reshape(nq, hkv, g, hd).transpose(1, 2, 0, 3).reshape(
        hkv, g * nq, hd)
    scores = matmul(q, k.transpose(1, 2, 0)) * (float(hd) ** -0.5)
    seen = kpos[None, :] <= qpos[:, None]                      # [Q, K]
    if sliding:
        seen &= qpos[:, None] - kpos[None, :] < z["window"]
    seen = jnp.tile(seen, (g, 1))[None]                        # [1, g*Q, K]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = matmul(probs, v.transpose(1, 0, 2))                  # [Hkv, g*Q, d]
    out = out.reshape(hkv, g, nq, hd).transpose(2, 0, 1, 3).reshape(
        nq, hq * hd)
    out = out * jax.nn.sigmoid(matmul(u, p["gate.w_0"]))
    return matmul(out, p["o.w_0"])


def shared_expert(f, p, matmul):
    return swiglu(f, *(p[f"shared_{k}.w_0"].astype(jnp.float32)
                       for k in ("gate", "up", "down")), matmul)


def routed_experts(f, p, z, matmul):
    """The held experts' part of the expert layer over rows ``f`` [R, D]:
    the router over all ``experts``, the picks that land on experts
    ``first .. first + held`` weighed in, the others adding nothing."""
    s = jax.nn.sigmoid(matmul(f, p["moe_router.w_0"].astype(jnp.float32)))
    picks = jax.lax.top_k(s + p["moe_router.b_0"], z["picks"])[1]
    gates = jnp.take_along_axis(s, picks, axis=1)
    if z["route_norm"]:
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
    """Attention's normed residual, FFN, its normed residual over rows
    [R, D]."""
    z = dict(z)
    x = x + rms_norm(attn, p["post_attn_norm.scale"], eps)
    f = rms_norm(x, p["pre_mlp_norm.scale"], eps)
    if dense:
        out = swiglu(f, *(p[f"ffn_{k}.w_0"].astype(jnp.float32)
                          for k in ("gate", "up", "down")), matmul)
    else:
        out = shared_expert(f, p, matmul) + routed_experts(f, p, z, matmul)
    return x + rms_norm(out, p["post_mlp_norm.scale"], eps)


def forward(params, cfg, tokens, rows, matmul=jnp.matmul):
    """Logits [len(rows), vocab] of the positions ``rows`` of one sequence
    of ``tokens``.

    Only shapes change what is computed here, never values: the sequence
    is padded to whole KEY_BLOCKs (causal: what lies behind a position
    does not touch it), a block of queries of a full layer is given the
    keys up to the end of its own KEY_BLOCK, one of a sliding layer the
    last ``window`` + block keys up to its own end, and the last layer
    runs for the query blocks that hold a wanted row.  So every jitted
    function sees one of a handful of shapes, whatever the request's
    length."""
    z = sizes(cfg)
    z["scaling"] = float(cfg["route_scale"])
    z["route_norm"] = bool(cfg["route_norm"])
    zt = tuple(sorted(z.items()))
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    t = len(tokens)
    t_pad = -(-t // KEY_BLOCK) * KEY_BLOCK
    padded = np.zeros(t_pad, np.int32)
    padded[:t] = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int64)
    x = params["trinity_embed.w_0"][jnp.asarray(padded)].astype(jnp.float32)
    if cfg["mup_enabled"]:
        x = x * float(cfg["hidden_size"]) ** 0.5
    slab = min(t_pad, -(-(z["window"] + QUERY_BLOCK) // QUERY_BLOCK)
               * QUERY_BLOCK)
    last = cfg["num_hidden_layers"] - 1
    start = 0
    for n in range(last + 1):
        prefix = f"trinity_layer_{n}_"
        p = {k[len(prefix):]: v for k, v in params.items()
             if k.startswith(prefix)}
        sliding = cfg["layer_types"][n] == SLIDING
        attn_p = {k: p[k] for k in ATTN_PARAMS}
        kw = dict(z=zt, eps=eps, theta=theta, sliding=sliding, matmul=matmul)
        u, k, v = layer_keys(x, attn_p, **kw)
        # the last layer's queries: from the block of the first wanted row
        start = int(rows.min()) // ROW_BLOCK * ROW_BLOCK if n == last else 0
        outs = []
        for first in range(start, t_pad, QUERY_BLOCK):
            if first >= t:  # padding rows: nothing reads them
                outs.append(jnp.zeros((QUERY_BLOCK, x.shape[1]),
                                      jnp.float32))
                continue
            end = first + QUERY_BLOCK
            if sliding:
                key0 = max(0, end - slab)
                keys = slice(key0, key0 + slab)
            else:
                key0 = 0
                keys = slice(0, min(t_pad, -(-end // KEY_BLOCK) * KEY_BLOCK))
            outs.append(attend_block(first, key0, u[first:end], k[keys],
                                     v[keys], attn_p, **kw))
        del u, k, v
        attn = jnp.concatenate(outs)
        del outs
        rest = {k: v for k, v in p.items() if k not in ATTN_PARAMS}
        x = jnp.concatenate([
            finish_rows(x[start + r:start + r + ROW_BLOCK],
                        attn[r:r + ROW_BLOCK], rest, z=zt, eps=eps,
                        dense=n < cfg["num_dense_layers"], matmul=matmul)
            for r in range(0, t_pad - start, ROW_BLOCK)])
        del attn
    hidden = rms_norm(x[jnp.asarray(rows - start, jnp.int32)],
                      params["trinity_final_norm.scale"], eps)
    return matmul(hidden, params["trinity_head.w_0"].astype(jnp.float32))


def served_logits(params, cfg, prompt, served, matmul=jnp.matmul):
    """Logits [len(served), vocab] that predicted each served token: one
    forward over the prompt and the served tokens."""
    seq = list(prompt) + list(served[:-1])
    rows = len(prompt) - 1 + np.arange(len(served))
    return forward(params, cfg, np.asarray(seq, np.int32), rows, matmul)
