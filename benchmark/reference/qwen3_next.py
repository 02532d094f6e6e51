"""Plain reference: Qwen3-Next (``qwen3_next``: gated-delta-rule layers
with more value heads than key heads beside gated grouped-query attention,
softmax-routed experts and a gated shared expert, zero-centred norm gains)
forward pass in straightforward ``jax.numpy`` float32: the recurrence token
by token, no chunked form, no cache, no paging, no kernels, no batching.

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
what the source has no key for; eps = rms_norm_eps, D = hidden_size;
layer i, from 0, is ``full`` where (i + 1) % full_attention_interval ==
0, else ``linear``):

  x0       E[tok];  a = x + Mix(N_in(x)); y = a + F(N_post(a));
           logits = N_final(x_last) W_head;
           N(x) = x rsqrt(mean x^2 + eps) (1 + w), w stored
  linear   H_k = linear_num_key_heads, H_v = linear_num_value_heads = r
           H_k, d_k, d_v the two head dims, K = linear_conv_kernel_dim;
           u = N_in(x); [q~ | k~ | v~] = u W_qkv (H_k d_k, H_k d_k, H_v
           d_v wide); z = u W_z; [b | a] = u W_ba (H_v each);
           c_t = sum_{j<K} w[j] p_{t-K+1+j} per channel of [q~ | k~ | v~]
           (p = 0 before the sequence), [q' | k' | v'] = silu(c);
           q = l2norm(q') / sqrt(d_k), k = l2norm(k') a KEY head,
           l2norm(x) = x rsqrt(sum x^2 + l2norm_eps); v = v';
           beta = sigmoid(b), g = -exp(A_log) softplus(a + dt_bias),
           alpha = exp(g), a VALUE head; value head h reads the q and k
           of key head h // r;
           S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T,
           S_0 = 0; o_t = S_t^T q_t  (S' = alpha_t S_{t-1}; S_t = S' +
           k_t (beta_t (v_t - S'^T k_t))^T);
           Mix = [RMS_head(o; PLAIN gain d_v) * silu(z)] W_o
  full     H = num_attention_heads on H_kv = num_key_value_heads K/V
           heads of d = head_dim; u = N_in(x); [q | gate] = u W_q, a
           head's 2 d columns its d of q then its d of gate; k = u W_k,
           v = u W_v; q, k <- N over each head's d entries; RoPE in the
           rotate_half form on the FIRST partial_rotary_factor x d
           entries of each head of q and k: entry i < r/2 turns with
           entry i + r/2 by pos theta^(-2i/r); query head j reads K/V
           head j // (H / H_kv); score[t, s] = q_t . k_s / sqrt(d),
           softmax over s <= t; Mix = (o * sigmoid(gate)) W_o
  F        f = N_post(a); s = softmax(f W_r) over num_experts_total;
           picks = the num_experts_per_tok largest; gate = s[picks] / sum
           s[picks] (norm_topk_prob); sum over the picks of gate_e
           SwiGLU_e(f) -- only the experts HELD here (ids first_expert ..
           first_expert + num_experts of the router's
           num_experts_total): every held expert runs over every token,
           its gate 0 where not picked, and a pick on an absent expert
           adds nothing -- plus sigmoid(f w_sg) x ONE shared SwiGLU of
           width shared_expert_intermediate_size

Departures, all of shape and none of value.  Long sequences go by
blocks: the sequence is padded to whole KEY_BLOCKs (causal: what lies
behind a position does not touch it); a linear layer runs one KEY_BLOCK
of positions after another, ONE ``lax.scan`` over a block's tokens, the
state and the convolution's last K - 1 inputs handed from block to
block; a [queries, keys] tensor goes by blocks of queries against the
keys up to the end of their own KEY_BLOCK; the FFN goes by ROW_BLOCKs;
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
LINEAR, FULL = "linear", "full"
KEY_PARAMS = ("input_norm.scale", "k.w_0", "v.w_0", "k_norm.scale")
QUERY_PARAMS = ("q.w_0", "q_norm.scale", "o.w_0")


def sizes(cfg):
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        rot=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        hk=cfg["linear_num_key_heads"], hv=cfg["linear_num_value_heads"],
        dk=cfg["linear_key_head_dim"], dv=cfg["linear_value_head_dim"],
        taps=cfg["linear_conv_kernel_dim"],
        experts=cfg["num_experts_total"], held=cfg["num_experts"],
        first=cfg["deployment"]["first_expert"],
        picks=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
        fs=cfg["shared_expert_intermediate_size"], vocab=cfg["vocab_size"])


def layer_kinds(cfg):
    n = cfg["full_attention_interval"]
    return [FULL if (i + 1) % n == 0 else LINEAR
            for i in range(cfg["num_hidden_layers"])]


def param_shapes(cfg):
    """{name: (shape, init, kind)}; kind "matrix" is stored in the
    configuration's dtype, "vector" in float32."""
    z = sizes(cfg)
    d = z["d"]
    conv = 2 * z["hk"] * z["dk"] + z["hv"] * z["dv"]
    out = {
        "qwen3n_embed.w_0": ((z["vocab"], d), "normal", "matrix"),
        "qwen3n_head.w_0": ((d, z["vocab"]), "normal", "matrix"),
        "qwen3n_final_norm.scale": ((d,), "normal", "vector"),
    }
    for n, kind in enumerate(layer_kinds(cfg)):
        p = f"qwen3n_layer_{n}_"
        norms = {"input_norm": d, "ffn_norm": d}
        if kind == LINEAR:
            mats = {"qkv.w_0": (d, conv), "z.w_0": (d, z["hv"] * z["dv"]),
                    "ba.w_0": (d, 2 * z["hv"]),
                    "o.w_0": (z["hv"] * z["dv"], d)}
            out[p + "o_norm.scale"] = ((z["dv"],), "ones", "vector")
            out[p + "conv.w_0"] = ((z["taps"], conv), "conv", "vector")
            out[p + "A_log"] = ((z["hv"],), "a_log", "vector")
            out[p + "dt_bias"] = ((z["hv"],), "dt_bias", "vector")
        else:
            mats = {"q.w_0": (d, z["heads"] * 2 * z["hd"]),
                    "k.w_0": (d, z["kv_heads"] * z["hd"]),
                    "v.w_0": (d, z["kv_heads"] * z["hd"]),
                    "o.w_0": (z["heads"] * z["hd"], d)}
            norms.update({"q_norm": z["hd"], "k_norm": z["hd"]})
        mats.update({
            "moe_router.w_0": (d, z["experts"]),
            "moe_experts_gate.w_0": (z["held"], d, z["f"]),
            "moe_experts_up.w_0": (z["held"], d, z["f"]),
            "moe_experts_down.w_0": (z["held"], z["f"], d),
            "shared_gate.w_0": (d, z["fs"]), "shared_up.w_0": (d, z["fs"]),
            "shared_down.w_0": (z["fs"], d),
            "shared_expert_gate.w_0": (d, 1)})
        out.update({p + k: (s, "normal", "matrix") for k, s in mats.items()})
        # zero-centred gains: the stored w, drawn (the source draws it 0),
        # so that 1 + w is held by every comparison
        out.update({p + k + ".scale": ((w,), "normal", "vector")
                    for k, w in norms.items()})
    return out


def seed_key(seed):
    """A key from any whole number up to 2**63 (seeds pass 2**31), of the
    ``rbg`` generator (the chip's random-bit generator: seconds for the
    3.7 G normal draws)."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF), impl="rbg")
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def init_weights(cfg, seed):
    """Every parameter from the seed, on the device, in one jitted call:
    matrices and the norms' stored ``w`` normal(0, initializer_range)
    rounded to bfloat16 (so a bfloat16 and a float32 holder agree); the
    delta-rule layer's gated output norm's plain gain ones; the
    convolution's taps uniform(-1/2, 1/2) (``assumed.conv_init``);
    ``A_log`` = log(A), A uniform in (0, 16); ``dt_bias`` the inverse
    softplus of dt, dt log-uniform in (0.001, 0.1)
    (``assumed.gate_init``).  Matrices in ``precision.weights``, vectors
    in float32; each tensor its own draw and its own output."""
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


def rms(x, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def norm(x, w, eps):
    """The model's RMSNorm: a zero-centred gain, 1 + w."""
    return rms(x, eps) * (1.0 + w)


def l2norm(x, eps):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, w_gate, w_up, w_down, matmul):
    return matmul(silu(matmul(x, w_gate)) * matmul(x, w_up), w_down)


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


def rope(x, pos, theta, rot):
    """x [T, H, d]: the first ``rot`` entries of each head turn in the
    rotate_half form (entry i with entry i + rot/2, by pos
    theta^(-2i/rot)); the others pass."""
    half = rot // 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos.astype(jnp.float32)[:, None] * inv               # [T, rot/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


# ---------------------------------------------------------------------------
# a linear-attention layer: one KEY_BLOCK of positions after another
# ---------------------------------------------------------------------------


def delta_rule(s0, q, k, v, g, beta, state_dtype):
    """The recurrence, one token after another, from the state ``s0``
    [H, d_k, d_v]: q, k [T, H, d_k], v [T, H, d_v], g, beta [T, H] (every
    operand a VALUE head's) -> (the state after them, o [T, H, d_v])."""

    def token(s, x):
        q, k, v, g, beta = x
        s = jnp.exp(g)[:, None, None] * s
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", s, k))
        s = rounded_to(s + k[:, :, None] * u[:, None, :], state_dtype)
        return s, jnp.einsum("hkv,hk->hv", s, q)

    return jax.lax.scan(token, s0, (q, k, v, g, beta))


@functools.partial(jax.jit, static_argnames=("z", "eps", "l2_eps", "matmul",
                                             "state_dtype"))
def linear_block(x, s0, tail, p, *, z, eps, l2_eps, matmul, state_dtype):
    """A linear layer's mixer over the next block of positions x [T, D],
    from the state ``s0`` [H_v, d_k, d_v] and the K - 1 pre-activation
    inputs ``tail`` [K - 1, channels] before them -> (what the layer adds
    to the residual stream [T, D], the state after, the tail after)."""
    z = dict(z)
    p = _f32(p)
    t = x.shape[0]
    hk, hv, dk, dv, taps = z["hk"], z["hv"], z["dk"], z["dv"], z["taps"]
    u = norm(x, p["input_norm.scale"], eps)
    ext = jnp.concatenate([tail, matmul(u, p["qkv.w_0"])])
    conv = silu(sum(p["conv.w_0"][j] * ext[j:j + t] for j in range(taps)))
    q = l2norm(conv[:, :hk * dk].reshape(t, hk, dk), l2_eps) * dk ** -0.5
    k = l2norm(conv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk), l2_eps)
    v = conv[:, 2 * hk * dk:].reshape(t, hv, dv)
    # value head h reads key head h // (H_v / H_k)
    q, k = (jnp.repeat(y, hv // hk, axis=1) for y in (q, k))
    ba = matmul(u, p["ba.w_0"])
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
    s, o = delta_rule(s0, q, k, v, g, beta, state_dtype)
    o = (rms(o, eps) * p["o_norm.scale"]).reshape(t, hv * dv)   # plain gain
    return matmul(o * silu(matmul(u, p["z.w_0"])), p["o.w_0"]), s, ext[t:]


def linear_layer(x, p, **kw):
    """The mixer over every position x [T, D] (T whole KEY_BLOCKs)."""
    z = dict(kw["z"])
    s = jnp.zeros((z["hv"], z["dk"], z["dv"]), jnp.float32)
    tail = jnp.zeros((z["taps"] - 1,
                      2 * z["hk"] * z["dk"] + z["hv"] * z["dv"]), jnp.float32)
    outs = []
    for first in range(0, x.shape[0], KEY_BLOCK):
        out, s, tail = linear_block(x[first:first + KEY_BLOCK], s, tail, p,
                                    **kw)
        outs.append(out)
    return jnp.concatenate(outs)


# ---------------------------------------------------------------------------
# a full-attention layer: blocks of queries against the keys they may see
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("z", "eps", "theta", "matmul"))
def layer_keys(x, p, *, z, eps, theta, matmul):
    """What every position gives a full layer's attention: the normed
    input, the normed and rotated keys and the values, [T, ...]."""
    z = dict(z)
    p = _f32(p)
    t = x.shape[0]
    u = norm(x, p["input_norm.scale"], eps)
    k = norm(matmul(u, p["k.w_0"]).reshape(t, z["kv_heads"], z["hd"]),
             p["k_norm.scale"], eps)
    k = rope(k, jnp.arange(t), theta, z["rot"])
    return u, k, matmul(u, p["v.w_0"]).reshape(t, z["kv_heads"], z["hd"])


@functools.partial(jax.jit, static_argnames=("z", "eps", "theta", "matmul"))
def attend_block(first, u, k, v, p, *, z, eps, theta, matmul):
    """Queries first .. first + Q (their normed rows ``u``) against the
    keys ``k``, ``v`` [K, Hkv, d] of positions 0 .. K -> what the layer
    adds to the residual stream [Q, D], after the gate and the output
    projection."""
    z = dict(z)
    p = _f32(p)
    nq, nk = u.shape[0], k.shape[0]
    hq, hkv, hd = z["heads"], z["kv_heads"], z["hd"]
    grp = hq // hkv
    qpos = first + jnp.arange(nq)
    qg = matmul(u, p["q.w_0"]).reshape(nq, hq, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:].reshape(nq, hq * hd)
    q = rope(norm(q, p["q_norm.scale"], eps), qpos, theta, z["rot"])
    # [Hkv, grp * Q, d]: the grp query heads of a K/V head against its keys
    q = q.reshape(nq, hkv, grp, hd).transpose(1, 2, 0, 3).reshape(
        hkv, grp * nq, hd)
    scores = matmul(q, k.transpose(1, 2, 0)) * (float(hd) ** -0.5)
    seen = jnp.arange(nk)[None, :] <= qpos[:, None]            # [Q, K]
    seen = jnp.tile(seen, (grp, 1))[None]                      # [1, grp*Q, K]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = matmul(probs, v.transpose(1, 0, 2))                # [Hkv, grp*Q, d]
    out = out.reshape(hkv, grp, nq, hd).transpose(2, 0, 1, 3).reshape(
        nq, hq * hd)
    return matmul(out * jax.nn.sigmoid(gate), p["o.w_0"])


def full_layer(x, p, t, **kw):
    """The mixer over every position x [T_pad, D], ``t`` of them real."""
    t_pad = x.shape[0]
    u, k, v = layer_keys(x, {n: p[n] for n in KEY_PARAMS}, **kw)
    outs = []
    for first in range(0, t_pad, QUERY_BLOCK):
        if first >= t:  # padding rows: nothing reads them
            outs.append(jnp.zeros((QUERY_BLOCK, x.shape[1]), jnp.float32))
            continue
        end = first + QUERY_BLOCK
        keys = min(t_pad, -(-end // KEY_BLOCK) * KEY_BLOCK)
        outs.append(attend_block(first, u[first:end], k[:keys], v[:keys],
                                 {n: p[n] for n in QUERY_PARAMS}, **kw))
    return jnp.concatenate(outs)


# ---------------------------------------------------------------------------
# the feed-forward half
# ---------------------------------------------------------------------------


def route(f, w_r, picks, normalize, matmul):
    """(picks [R, k], gates [R, k]) of the softmax router over every
    expert."""
    s = jax.nn.softmax(matmul(f, w_r), axis=-1)
    gates, picked = jax.lax.top_k(s, picks)
    if normalize:
        gates = gates / jnp.sum(gates, axis=1, keepdims=True)
    return picked, gates


@functools.partial(jax.jit, static_argnames=("z", "eps", "matmul"))
def finish_rows(x, mixed, p, *, z, eps, matmul):
    """The mixer's residual, then the expert layer and its residual, over
    rows [R, D]."""
    z = dict(z)
    x = x + mixed
    f = norm(x, p["ffn_norm.scale"], eps)
    picks, gates = route(f, p["moe_router.w_0"].astype(jnp.float32),
                         z["picks"], z["normalize"], matmul)
    # [R, experts] gate of every expert, 0 where it was not picked
    gate_of = jnp.zeros((f.shape[0], z["experts"]), jnp.float32).at[
        jnp.arange(f.shape[0])[:, None], picks].set(gates)
    shared = swiglu(f, *(p[f"shared_{k}.w_0"].astype(jnp.float32)
                         for k in ("gate", "up", "down")), matmul)
    out = shared * jax.nn.sigmoid(matmul(
        f, p["shared_expert_gate.w_0"].astype(jnp.float32)))

    def add_expert(e, acc):  # a held expert: id first + e of the router's
        gate = jax.lax.dynamic_slice_in_dim(gate_of, z["first"] + e, 1, 1)
        return acc + gate * swiglu(
            f, *(p[f"moe_experts_{k}.w_0"][e].astype(jnp.float32)
                 for k in ("gate", "up", "down")), matmul)

    return x + jax.lax.fori_loop(0, z["held"], add_expert, out)


def forward(params, cfg, tokens, rows, matmul=jnp.matmul,
            state_dtype=jnp.float32):
    """Logits [len(rows), vocab] of the positions ``rows`` of one sequence
    of ``tokens``.

    Only shapes change what is computed here, never values (the module's
    "Departures"): every jitted function sees one of a handful of
    shapes, whatever the request's length."""
    z = sizes(cfg)
    z["normalize"] = bool(cfg["norm_topk_prob"])
    zt = tuple(sorted(z.items()))
    eps = float(cfg["rms_norm_eps"])
    t = len(tokens)
    t_pad = -(-t // KEY_BLOCK) * KEY_BLOCK
    padded = np.zeros(t_pad, np.int32)
    padded[:t] = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int64)
    x = params["qwen3n_embed.w_0"][jnp.asarray(padded)].astype(jnp.float32)
    for n, kind in enumerate(layer_kinds(cfg)):
        prefix = f"qwen3n_layer_{n}_"
        p = {k[len(prefix):]: v for k, v in params.items()
             if k.startswith(prefix)}
        ffn = {k: v for k, v in p.items()
               if k.startswith(("ffn_", "moe_", "shared_"))}
        mixer = {k: v for k, v in p.items() if k not in ffn}
        if kind == LINEAR:
            mixed = linear_layer(
                x, mixer, z=zt, eps=eps,
                l2_eps=float(cfg["assumed"]["l2norm_eps"]), matmul=matmul,
                state_dtype=state_dtype)
        else:
            mixed = full_layer(x, mixer, t, z=zt, eps=eps,
                               theta=float(cfg["rope_theta"]), matmul=matmul)
        x = jnp.concatenate([
            finish_rows(x[r:r + ROW_BLOCK], mixed[r:r + ROW_BLOCK], ffn,
                        z=zt, eps=eps, matmul=matmul)
            for r in range(0, t_pad, ROW_BLOCK)])
        del mixed
    hidden = norm(x[jnp.asarray(rows, jnp.int32)],
                  params["qwen3n_final_norm.scale"], eps)
    return matmul(hidden, params["qwen3n_head.w_0"].astype(jnp.float32))


def served_logits(params, cfg, prompt, served, matmul=jnp.matmul,
                  state_dtype=jnp.float32):
    """Logits [len(served), vocab] that predicted each served token: one
    forward over the prompt and the served tokens."""
    seq = list(prompt) + list(served[:-1])
    rows = len(prompt) - 1 + np.arange(len(served))
    return forward(params, cfg, np.asarray(seq, np.int32), rows, matmul,
                   state_dtype)
