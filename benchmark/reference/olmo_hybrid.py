"""Plain reference: Olmo-Hybrid (``olmo_hybrid``: gated-delta-rule
linear-attention layers beside full-attention layers, a SwiGLU in every
block) forward pass in straightforward ``jax.numpy`` float32: the
recurrence token by token, no chunked form, no cache, no paging, no
kernels, no batching.

It imports nothing of the program and takes nothing the program made: the
weights come from ``init_weights(cfg, seed)`` here (the benchmark puts the
same arrays into the program's scope).  Matrices are returned in the
configuration's storage dtype (bfloat16) holding values bfloat16
represents exactly, so both sides hold the same weights; every layer
function lifts what it uses to float32.  Every matmul against a weight,
and attention's two products, go through the ``matmul`` argument, so
that the control (``reference/lowprec.py``) can put a lower precision in
its place; callers wrap the default in
``jax.default_matmul_precision("highest")``.  The recurrence's own inner
products are float32 always (the configuration states the state in
float32); ``state_dtype`` rounds the state after every token, which is
how the control reads what a bfloat16 state would give.

The equations (``cfg`` holds the source's keys and, under ``assumed``,
what the source has no key for; eps = rms_norm_eps, D = hidden_size, H
heads; in a linear layer d_k = linear_key_head_dim, d_v =
linear_value_head_dim, K = linear_conv_kernel_dim; in a full layer d =
head_dim):

  x0       E[tok];  logits = RMS_final(x_last) W_head
  linear   u = RMS_in(x); [q~ | k~ | v~] = u [W_q | W_k | W_v];
           c_t = sum_{j<K} w[j] z_{t-K+1+j} per channel (z = 0 before the
           sequence), [q' | k' | v'] = silu(c);
           q = l2norm(q') / sqrt(d_k), k = l2norm(k'), l2norm(x) =
           x rsqrt(sum x^2 + l2norm_eps); v = v';
           beta = 2 sigmoid(u W_b) (linear_allow_neg_eigval, else 1 x);
           g = -exp(A_log) softplus(u W_a + dt_bias); alpha = exp(g);
           S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T,
           S_0 = 0; o_t = S_t^T q_t;
           a = x + [RMS_head(o; gain d_v) * silu(u W_g)] W_o
  full     q = RMS_q(x W_q), k = RMS_k(x W_k) over the whole projection;
           v = x W_v; RoPE (``rotate_half`` form over the whole head:
           entry i with entry i + d/2, angle pos * theta^(-2i/d)) on q
           and k; score[s, t] = q_s . k_t / sqrt(d) for t <= s; softmax;
           a = x + RMS_post_attn((sum p v) W_o)
  both     y = a + RMS_post_ff((silu(a W_gate) * a W_up) W_down)

Long sequences: a layer runs over all positions at once except where a
[queries, keys] tensor appears, which goes by blocks of queries against
the keys up to the end of their own KEY_BLOCK, and the FFN, which goes
by ROW_BLOCKs; the recurrence is one ``lax.scan`` over the positions;
the logits are computed for the served rows only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128  # queries scored against their keys at once
KEY_BLOCK = 4096   # sequences are padded, and keys handed over, in these
ROW_BLOCK = 4096   # rows through the FFN at once
LINEAR = "linear_attention"


def sizes(cfg):
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        hd=cfg["assumed"]["head_dim"], i=cfg["intermediate_size"],
        lh=cfg["linear_num_value_heads"], dk=cfg["linear_key_head_dim"],
        dv=cfg["linear_value_head_dim"], taps=cfg["linear_conv_kernel_dim"],
        vocab=cfg["vocab_size"])


def param_shapes(cfg):
    """{name: (shape, init, kind)}; kind "matrix" is stored in the
    configuration's dtype, "vector" in float32."""
    z = sizes(cfg)
    d, h, hd, lh, dk, dv = z["d"], z["heads"], z["hd"], z["lh"], z["dk"], \
        z["dv"]
    out = {
        "olmo_embed.w_0": ((z["vocab"], d), "normal", "matrix"),
        "olmo_head.w_0": ((d, z["vocab"]), "normal", "matrix"),
        "olmo_final_norm.scale": ((d,), "ones", "vector"),
    }
    for n, kind in enumerate(cfg["layer_types"]):
        p = f"olmo_layer_{n}_"
        mats = {"ffn_gate.w_0": (d, z["i"]), "ffn_up.w_0": (d, z["i"]),
                "ffn_down.w_0": (z["i"], d)}
        norms = {"post_ff_norm": d}
        if kind == LINEAR:
            mats.update({"q.w_0": (d, lh * dk), "k.w_0": (d, lh * dk),
                         "v.w_0": (d, lh * dv), "a.w_0": (d, lh),
                         "b.w_0": (d, lh), "g.w_0": (d, lh * dv),
                         "o.w_0": (lh * dv, d)})
            norms.update({"input_norm": d, "o_norm": dv})
            out[p + "conv.w_0"] = ((z["taps"], lh * (2 * dk + dv)), "conv",
                                   "vector")
            out[p + "A_log"] = ((lh,), "a_log", "vector")
            out[p + "dt_bias"] = ((lh,), "dt_bias", "vector")
        else:
            mats.update({"q.w_0": (d, h * hd), "k.w_0": (d, h * hd),
                         "v.w_0": (d, h * hd), "o.w_0": (h * hd, d)})
            norms.update({"q_norm": h * hd, "k_norm": h * hd,
                          "post_attn_norm": d})
        out.update({p + k: (s, "normal", "matrix") for k, s in mats.items()})
        out.update({p + k + ".scale": ((w,), "ones", "vector")
                    for k, w in norms.items()})
    return out


def seed_key(seed):
    """A key from any whole number up to 2**63 (seeds pass 2**31), of the
    ``rbg`` generator (the chip's random-bit generator: seconds for the
    2.4 G normal draws)."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF), impl="rbg")
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def init_weights(cfg, seed):
    """Every parameter from the seed, on the device, in one jitted call:
    matrices normal(0, initializer_range) rounded to bfloat16 (so a
    bfloat16 and a float32 holder agree); norm gains ones; the
    convolution's taps uniform(-1/2, 1/2) (``assumed.conv_init``);
    ``A_log`` = log(A), A uniform in (0, 16); ``dt_bias`` the inverse
    softplus of dt, dt log-uniform in (0.001, 0.1) (``assumed.gate_init``).
    Matrices in ``precision.weights``, vectors in float32; each tensor
    its own draw and its own output."""
    std = float(cfg["assumed"]["initializer_range"])
    storage = jnp.dtype(cfg["precision"]["weights"])
    shapes = sorted(param_shapes(cfg).items())

    @jax.jit
    def make(key):
        out = {}
        for n, (name, (shape, init, kind)) in enumerate(shapes):
            k = jax.random.fold_in(key, n)
            if init == "normal":
                x = std * jax.random.normal(k, shape, jnp.float32)
                out[name] = x.astype(jnp.bfloat16).astype(
                    storage if kind == "matrix" else jnp.float32)
            elif init == "conv":
                out[name] = jax.random.uniform(k, shape, jnp.float32,
                                               -0.5, 0.5)
            elif init == "a_log":
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1e-3, 16.0))
            elif init == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, np.log(0.001), np.log(0.1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            else:
                out[name] = jnp.ones(shape, jnp.float32)
        return out

    return make(seed_key(seed))


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def l2norm(x, eps):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


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


def _f32(p):
    return {k: v.astype(jnp.float32) for k, v in p.items()}


def rounded_to(x, dtype):
    """float32 ``x`` holding only values ``dtype`` has.  An explicit
    ``reduce_precision``: the compiler may drop a convert there and back
    (it allows itself excess precision), and the control would then read
    the float32 state under another name."""
    info = jnp.finfo(dtype)
    if info.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def delta_rule(q, k, v, g, beta, state_dtype):
    """The recurrence, one token after another: q, k [T, H, d_k], v
    [T, H, d_v], g, beta [T, H] -> o [T, H, d_v]."""

    def token(s, x):
        q, k, v, g, beta = x
        s = jnp.exp(g)[:, None, None] * s
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", s, k))
        s = rounded_to(s + k[:, :, None] * u[:, None, :], state_dtype)
        return s, jnp.einsum("hkv,hk->hv", s, q)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(token, s0, (q, k, v, g, beta))[1]


@functools.partial(jax.jit, static_argnames=("z", "eps", "l2_eps",
                                             "beta_scale", "matmul",
                                             "state_dtype"))
def linear_layer(x, p, *, z, eps, l2_eps, beta_scale, matmul, state_dtype):
    """A linear-attention layer's mixer over every position x [T, D]:
    what it adds to the residual stream."""
    z = dict(z)
    p = _f32(p)
    t = x.shape[0]
    h, dk, dv, taps = z["lh"], z["dk"], z["dv"], z["taps"]
    u = rms_norm(x, p["input_norm.scale"], eps)
    pre = jnp.concatenate([matmul(u, p["q.w_0"]), matmul(u, p["k.w_0"]),
                           matmul(u, p["v.w_0"])], axis=-1)     # [T, ch]
    ext = jnp.concatenate([jnp.zeros((taps - 1, pre.shape[1]), jnp.float32),
                           pre])
    conv = silu(sum(p["conv.w_0"][j] * ext[j:j + t] for j in range(taps)))
    q = l2norm(conv[:, :h * dk].reshape(t, h, dk), l2_eps) * dk ** -0.5
    k = l2norm(conv[:, h * dk:2 * h * dk].reshape(t, h, dk), l2_eps)
    v = conv[:, 2 * h * dk:].reshape(t, h, dv)
    beta = beta_scale * jax.nn.sigmoid(matmul(u, p["b.w_0"]))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(matmul(u, p["a.w_0"])
                                               + p["dt_bias"])
    o = delta_rule(q, k, v, g, beta, state_dtype)
    o = rms_norm(o, p["o_norm.scale"], eps).reshape(t, h * dv)
    return matmul(o * silu(matmul(u, p["g.w_0"])), p["o.w_0"])


@functools.partial(jax.jit, static_argnames=("z", "eps", "theta", "matmul"))
def full_keys(x, p, *, z, eps, theta, matmul):
    """The K and V rows a cache would hold, [T, H, d]."""
    z = dict(z)
    p = _f32(p)
    t = x.shape[0]
    k = rms_norm(matmul(x, p["k.w_0"]), p["k_norm.scale"], eps)
    k = rope(k.reshape(t, z["heads"], z["hd"]), jnp.arange(t), theta)
    return k, matmul(x, p["v.w_0"]).reshape(t, z["heads"], z["hd"])


@functools.partial(jax.jit, static_argnames=("z", "eps", "theta", "matmul"))
def attend_block(first, x, k, v, p, *, z, eps, theta, matmul):
    """Queries first .. first + Q (their rows ``x``) against the keys
    ``k``, ``v`` [K, H, d], positions 0 .. K.  Returns what the layer
    adds to the residual stream, [Q, D]."""
    z = dict(z)
    p = _f32(p)
    nq, nk = x.shape[0], k.shape[0]
    h, hd = z["heads"], z["hd"]
    qpos = first + jnp.arange(nq)
    q = rms_norm(matmul(x, p["q.w_0"]), p["q_norm.scale"], eps)
    q = rope(q.reshape(nq, h, hd), qpos, theta)
    scores = matmul(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) * hd ** -0.5
    seen = jnp.arange(nk)[None, :] <= qpos[:, None]            # [Q, K]
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    out = matmul(probs, v.transpose(1, 0, 2))                  # [H, Q, d]
    out = out.transpose(1, 0, 2).reshape(nq, h * hd)
    return rms_norm(matmul(out, p["o.w_0"]), p["post_attn_norm.scale"], eps)


@functools.partial(jax.jit, static_argnames=("eps", "matmul"))
def ffn_rows(x, p, *, eps, matmul):
    """x + RMS_post_ff(SwiGLU(x)) over rows [R, D]."""
    p = _f32(p)
    out = matmul(silu(matmul(x, p["ffn_gate.w_0"]))
                 * matmul(x, p["ffn_up.w_0"]), p["ffn_down.w_0"])
    return x + rms_norm(out, p["post_ff_norm.scale"], eps)


FFN_PARAMS = ("ffn_gate.w_0", "ffn_up.w_0", "ffn_down.w_0",
              "post_ff_norm.scale")


def forward(params, cfg, tokens, rows, matmul=jnp.matmul,
            state_dtype=jnp.float32):
    """Logits [len(rows), vocab] of the positions ``rows`` of one sequence
    of ``tokens``.

    Only shapes change what is computed here, never values: the sequence
    is padded to whole KEY_BLOCKs (causal: what lies behind a position
    does not touch it), and a block of queries is given the keys up to
    the end of its own KEY_BLOCK.  So every jitted function sees one of
    a handful of shapes, whatever the request's length."""
    z = sizes(cfg)
    zt = tuple(sorted(z.items()))
    eps = float(cfg["rms_norm_eps"])
    assumed = cfg["assumed"]
    theta = float(assumed["rope_theta"])
    t = len(tokens)
    t_pad = -(-t // KEY_BLOCK) * KEY_BLOCK
    padded = np.zeros(t_pad, np.int32)
    padded[:t] = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int64)
    x = params["olmo_embed.w_0"][jnp.asarray(padded)].astype(jnp.float32)
    for n, kind in enumerate(cfg["layer_types"]):
        prefix = f"olmo_layer_{n}_"
        p = {k[len(prefix):]: v for k, v in params.items()
             if k.startswith(prefix)}
        mixer = {k: v for k, v in p.items() if k not in FFN_PARAMS}
        if kind == LINEAR:
            x = x + linear_layer(
                x, mixer, z=zt, eps=eps, l2_eps=float(assumed["l2norm_eps"]),
                beta_scale=2.0 if cfg["linear_allow_neg_eigval"] else 1.0,
                matmul=matmul, state_dtype=state_dtype)
        else:
            kw = dict(z=zt, eps=eps, theta=theta, matmul=matmul)
            k, v = full_keys(x, mixer, **kw)
            outs = []
            for first in range(0, t_pad, QUERY_BLOCK):
                if first >= t:  # padding rows: nothing reads them
                    outs.append(jnp.zeros((QUERY_BLOCK, x.shape[1]),
                                          jnp.float32))
                    continue
                end = first + QUERY_BLOCK
                keys = slice(0, min(t_pad, -(-end // KEY_BLOCK) * KEY_BLOCK))
                outs.append(attend_block(first, x[first:end], k[keys],
                                         v[keys], mixer, **kw))
            del k, v
            x = x + jnp.concatenate(outs)
            del outs
        x = jnp.concatenate([
            ffn_rows(x[r:r + ROW_BLOCK], {k: p[k] for k in FFN_PARAMS},
                     eps=eps, matmul=matmul)
            for r in range(0, t_pad, ROW_BLOCK)])
    hidden = rms_norm(x[jnp.asarray(rows, jnp.int32)],
                      params["olmo_final_norm.scale"], eps)
    return matmul(hidden, params["olmo_head.w_0"].astype(jnp.float32))


def served_logits(params, cfg, prompt, served, matmul=jnp.matmul,
                  state_dtype=jnp.float32):
    """Logits [len(served), vocab] that predicted each served token: one
    forward over the prompt and the served tokens."""
    seq = list(prompt) + list(served[:-1])
    rows = len(prompt) - 1 + np.arange(len(served))
    return forward(params, cfg, np.asarray(seq, np.int32), rows, matmul,
                   state_dtype)
