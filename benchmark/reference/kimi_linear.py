"""Plain reference: Kimi-Linear (``kimi_linear``: Kimi-Delta-Attention
layers, a delta rule whose decay is one number a key channel, beside
latent-attention layers with no rotary positions; sigmoid-routed experts
and a shared one) forward pass in straightforward ``jax.numpy`` float32:
the recurrence token by token, no chunked form, no cache, no paging, no
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
what the source has no key for; eps = rms_norm_eps, D = hidden_size;
layers are numbered from 1 in ``linear_attn_config``'s two lists):

  x0       E[tok];  a = x + Mix(RMS_1(x)); y = a + F(RMS_2(a));
           logits = RMS_final(x_last) W_head;  u = RMS_1(x) below
  KDA      H = num_heads, d = head_dim (keys and values), K =
           short_conv_kernel_size, r = assumed.gate_low_rank_dim:
           [q~ | k~ | v~] = u [W_q | W_k | W_v];
           c_t = sum_{j<K} w[j] z_{t-K+1+j} per channel (z = 0 before the
           sequence), [q' | k' | v'] = silu(c);
           q = l2norm(q') / sqrt(d), k = l2norm(k'), l2norm(x) =
           x rsqrt(sum x^2 + l2norm_eps); v = v';
           g = -exp(A_log_h) softplus((u W_f_a) W_f_b + dt_bias) in
           R^(H x d), alpha = exp(g); beta = sigmoid(u W_b) a head;
           S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1}
                 + beta_t k_t v_t^T, S_0 = 0; o_t = S_t^T q_t
           (S' = Diag(alpha_t) S_{t-1}; S_t = S' + k_t (beta_t (v_t -
           S'^T k_t))^T);
           Mix = [RMS_head(o; gain d) * sigmoid((u W_g_a) W_g_b)] W_o
  latent   per head [q_nope | q_r] = u W_q; [c | k_r] = u W_kva;
           c <- RMS(c); NO rotation anywhere (mla_use_nope): the entries
           named rope are plain entries of the key every head shares;
           HEAD space: k_nope = c W_uk^T, v = c W_uv (W_uk [H, nope, c],
           W_uv [H, c, v]); score[t, s] = (q_nope . k_nope + q_r . k_r) /
           sqrt(nope + rope); softmax over s <= t; Mix = concat(sum p v)
           W_o
  F        layers below first_k_dense_replace: (silu(f W_g) * f W_u) W_d;
           the others: s = sigmoid(f W_r); picks = top-k of s + b;
           gate = routed_scaling_factor * s[picks] / (sum s[picks] +
           1e-20) (moe_renormalize); sum over the picks of gate_e
           SwiGLU_e(f) -- only the experts HELD here (ids first_expert ..
           first_expert + num_experts of the router's num_experts_total):
           every held expert runs over every token, its gate 0 where not
           picked, and a pick on an absent expert adds nothing -- plus
           ONE shared SwiGLU of width num_shared_experts x
           moe_intermediate_size

Departures, all of shape and none of value.  Long sequences go by
blocks: the sequence is padded to whole KEY_BLOCKs (causal: what lies
behind a position does not touch it); a KDA layer runs one KEY_BLOCK of
positions after another, ONE ``lax.scan`` over a block's tokens, the
state and the convolution's last K - 1 inputs handed from block to
block (its [positions, 3 H d] pre-activations would not fit beside the
weights otherwise); a [queries, keys] tensor goes by blocks of queries
against the keys up to the end of their own KEY_BLOCK; the FFN goes by
ROW_BLOCKs; the logits are computed for the served rows only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128  # queries scored against their keys at once
KEY_BLOCK = 4096   # sequences are padded, and keys handed over, in these
ROW_BLOCK = 4096   # rows through the FFN at once
KDA, LATENT = "kda", "latent"
KEY_PARAMS = ("attn_norm.scale", "kv_a.w_0", "kv_a_norm.scale",
              "kv_b_k.w_0", "kv_b_v.w_0")
QUERY_PARAMS = ("q.w_0", "o.w_0")


def sizes(cfg):
    la = cfg["linear_attn_config"]
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v=cfg["v_head_dim"], ckv=cfg["kv_lora_rank"],
        lh=la["num_heads"], hd=la["head_dim"],
        taps=la["short_conv_kernel_size"],
        rank=cfg["assumed"]["gate_low_rank_dim"],
        experts=cfg["num_experts_total"], held=cfg["num_experts"],
        first=cfg["deployment"]["first_expert"],
        picks=cfg["num_experts_per_token"], f=cfg["moe_intermediate_size"],
        shared=cfg["num_shared_experts"], i=cfg["intermediate_size"],
        vocab=cfg["vocab_size"])


def layer_kinds(cfg):
    """The kind of each layer 0 .. num_hidden_layers - 1, from
    ``linear_attn_config``'s two lists (which number from 1)."""
    la = cfg["linear_attn_config"]
    kda, full = set(la["kda_layers"]), set(la["full_attn_layers"])
    n = cfg["num_hidden_layers"]
    if kda & full or kda | full != set(range(1, n + 1)):
        raise ValueError(f"kda_layers {sorted(kda)} and full_attn_layers "
                         f"{sorted(full)} do not name layers 1 .. {n} once")
    return [KDA if i + 1 in kda else LATENT for i in range(n)]


def param_shapes(cfg):
    """{name: (shape, init, kind)}; kind "matrix" is stored in the
    configuration's dtype, "vector" in float32."""
    z = sizes(cfg)
    d, h, lh, hd = z["d"], z["heads"], z["lh"], z["hd"]
    out = {
        "klin_embed.w_0": ((z["vocab"], d), "normal", "matrix"),
        "klin_head.w_0": ((d, z["vocab"]), "normal", "matrix"),
        "klin_final_norm.scale": ((d,), "ones", "vector"),
    }
    for n, kind in enumerate(layer_kinds(cfg)):
        p = f"klin_layer_{n}_"
        norms = {"attn_norm": d, "ffn_norm": d}
        if kind == KDA:
            mats = {"q.w_0": (d, lh * hd), "k.w_0": (d, lh * hd),
                    "v.w_0": (d, lh * hd), "f_a.w_0": (d, z["rank"]),
                    "f_b.w_0": (z["rank"], lh * hd), "b.w_0": (d, lh),
                    "g_a.w_0": (d, z["rank"]),
                    "g_b.w_0": (z["rank"], lh * hd), "o.w_0": (lh * hd, d)}
            norms["o_norm"] = hd
            out[p + "conv.w_0"] = ((z["taps"], 3 * lh * hd), "conv", "vector")
            out[p + "A_log"] = ((lh,), "a_log", "vector")
            out[p + "dt_bias"] = ((lh * hd,), "dt_bias", "vector")
        else:
            mats = {"q.w_0": (d, h * (z["nope"] + z["rope"])),
                    "kv_a.w_0": (d, z["ckv"] + z["rope"]),
                    "kv_b_k.w_0": (h, z["nope"], z["ckv"]),
                    "kv_b_v.w_0": (h, z["ckv"], z["v"]),
                    "o.w_0": (h * z["v"], d)}
            norms["kv_a_norm"] = z["ckv"]
        if n < cfg["first_k_dense_replace"]:
            mats.update({"ffn_gate.w_0": (d, z["i"]),
                         "ffn_up.w_0": (d, z["i"]),
                         "ffn_down.w_0": (z["i"], d)})
        else:
            fs = z["shared"] * z["f"]
            mats.update({
                "moe_router.w_0": (d, z["experts"]),
                "moe_experts_gate.w_0": (z["held"], d, z["f"]),
                "moe_experts_up.w_0": (z["held"], d, z["f"]),
                "moe_experts_down.w_0": (z["held"], z["f"], d),
                "shared_gate.w_0": (d, fs), "shared_up.w_0": (d, fs),
                "shared_down.w_0": (fs, d)})
            out[p + "moe_router.b_0"] = ((z["experts"],), "normal", "vector")
        out.update({p + k: (s, "normal", "matrix") for k, s in mats.items()})
        out.update({p + k + ".scale": ((w,), "ones", "vector")
                    for k, w in norms.items()})
    return out


def seed_key(seed):
    """A key from any whole number up to 2**63 (seeds pass 2**31), of the
    ``rbg`` generator (the chip's random-bit generator: seconds for the
    3.8 G normal draws)."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF), impl="rbg")
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def init_weights(cfg, seed):
    """Every parameter from the seed, on the device, in one jitted call:
    matrices and the selection bias normal(0, initializer_range) rounded
    to bfloat16 (so a bfloat16 and a float32 holder agree); norm gains
    ones; the convolution's taps uniform(-1/2, 1/2)
    (``assumed.conv_init``); ``A_log`` = log(A), A uniform in (0, 16);
    ``dt_bias`` the inverse softplus of dt, dt log-uniform in (0.001,
    0.1) (``assumed.gate_init``).  Matrices in ``precision.weights``,
    vectors in float32; each tensor its own draw and its own output."""
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


# ---------------------------------------------------------------------------
# a KDA layer: one KEY_BLOCK of positions after another
# ---------------------------------------------------------------------------


def delta_rule(s0, q, k, v, g, beta, state_dtype):
    """The recurrence, one token after another, from the state ``s0``
    [H, d, d]: q, k, g [T, H, d], v [T, H, d], beta [T, H] -> (the state
    after them, o [T, H, d])."""

    def token(s, x):
        q, k, v, g, beta = x
        s = jnp.exp(g)[:, :, None] * s
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", s, k))
        s = rounded_to(s + k[:, :, None] * u[:, None, :], state_dtype)
        return s, jnp.einsum("hkv,hk->hv", s, q)

    return jax.lax.scan(token, s0, (q, k, v, g, beta))


@functools.partial(jax.jit, static_argnames=("z", "eps", "l2_eps", "matmul",
                                             "state_dtype"))
def kda_block(x, s0, tail, p, *, z, eps, l2_eps, matmul, state_dtype):
    """A KDA layer's mixer over the next block of positions x [T, D],
    from the state ``s0`` [H, d, d] and the K - 1 pre-activation inputs
    ``tail`` [K - 1, 3 H d] before them -> (what the layer adds to the
    residual stream [T, D], the state after, the tail after)."""
    z = dict(z)
    p = _f32(p)
    t = x.shape[0]
    h, d, taps = z["lh"], z["hd"], z["taps"]
    u = rms_norm(x, p["attn_norm.scale"], eps)
    pre = jnp.concatenate([matmul(u, p["q.w_0"]), matmul(u, p["k.w_0"]),
                           matmul(u, p["v.w_0"])], axis=-1)     # [T, 3 H d]
    ext = jnp.concatenate([tail, pre])
    conv = silu(sum(p["conv.w_0"][j] * ext[j:j + t] for j in range(taps)))
    q = l2norm(conv[:, :h * d].reshape(t, h, d), l2_eps) * d ** -0.5
    k = l2norm(conv[:, h * d:2 * h * d].reshape(t, h, d), l2_eps)
    v = conv[:, 2 * h * d:].reshape(t, h, d)
    beta = jax.nn.sigmoid(matmul(u, p["b.w_0"]))
    decay = matmul(matmul(u, p["f_a.w_0"]), p["f_b.w_0"]) + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        decay.reshape(t, h, d))
    s, o = delta_rule(s0, q, k, v, g, beta, state_dtype)
    o = rms_norm(o, p["o_norm.scale"], eps).reshape(t, h * d)
    gate = jax.nn.sigmoid(matmul(matmul(u, p["g_a.w_0"]), p["g_b.w_0"]))
    return matmul(o * gate, p["o.w_0"]), s, ext[t:]


def kda_layer(x, p, **kw):
    """The mixer over every position x [T, D] (T whole KEY_BLOCKs)."""
    z = dict(kw["z"])
    s = jnp.zeros((z["lh"], z["hd"], z["hd"]), jnp.float32)
    tail = jnp.zeros((z["taps"] - 1, 3 * z["lh"] * z["hd"]), jnp.float32)
    outs = []
    for first in range(0, x.shape[0], KEY_BLOCK):
        out, s, tail = kda_block(x[first:first + KEY_BLOCK], s, tail, p,
                                 **kw)
        outs.append(out)
    return jnp.concatenate(outs)


# ---------------------------------------------------------------------------
# a latent layer: blocks of queries against the keys they may see
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("z", "eps", "matmul"))
def layer_keys(x, p, *, z, eps, matmul):
    """What every position gives a latent layer's attention: the normed
    input and every head's keys and values, [T, ...].  No position
    enters."""
    z = dict(z)
    p = _f32(p)
    xa = rms_norm(x, p["attn_norm.scale"], eps)
    kv = matmul(xa, p["kv_a.w_0"])
    c_kv = rms_norm(kv[:, :z["ckv"]], p["kv_a_norm.scale"], eps)
    k_r = kv[:, z["ckv"]:]
    k_nope = matmul(c_kv, p["kv_b_k.w_0"].transpose(0, 2, 1))  # [H, T, nope]
    v = matmul(c_kv, p["kv_b_v.w_0"])                          # [H, T, v]
    return xa, k_nope, k_r, v


@functools.partial(jax.jit, static_argnames=("z", "matmul"))
def attend_block(first, xa, k_nope, k_r, v, p, *, z, matmul):
    """Queries first .. first + Q (their rows ``xa``) against every
    position handed over -> what the layer adds to the residual stream
    [Q, D], after the output projection."""
    z = dict(z)
    p = _f32(p)
    nq, t, heads = xa.shape[0], k_r.shape[0], z["heads"]
    qpos = first + jnp.arange(nq)
    q = matmul(xa, p["q.w_0"]).reshape(nq, heads, z["nope"] + z["rope"])
    q_nope = q[..., :z["nope"]].transpose(1, 0, 2)             # [H, Q, nope]
    q_r = q[..., z["nope"]:].transpose(1, 0, 2)                # [H, Q, rope]
    scale = float(z["nope"] + z["rope"]) ** -0.5
    scores = (matmul(q_nope, k_nope.transpose(0, 2, 1))
              + matmul(q_r, k_r.T)) * scale                    # [H, Q, T]
    causal = jnp.arange(t)[None, :] <= qpos[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                           axis=-1)
    out = matmul(probs, v)                                     # [H, Q, v]
    return matmul(out.transpose(1, 0, 2).reshape(nq, -1), p["o.w_0"])


def latent_layer(x, p, t, *, z, eps, matmul):
    """The mixer over every position x [T_pad, D], ``t`` of them real."""
    t_pad = x.shape[0]
    xa, k_nope, k_r, v = layer_keys(x, {k: p[k] for k in KEY_PARAMS}, z=z,
                                    eps=eps, matmul=matmul)
    outs = []
    for first in range(0, t_pad, QUERY_BLOCK):
        if first >= t:  # padding rows: nothing reads them
            outs.append(jnp.zeros((QUERY_BLOCK, x.shape[1]), jnp.float32))
            continue
        q = slice(first, first + QUERY_BLOCK)
        keys = min(t_pad, -(-(first + QUERY_BLOCK) // KEY_BLOCK) * KEY_BLOCK)
        outs.append(attend_block(
            first, xa[q], k_nope[:, :keys], k_r[:keys], v[:, :keys],
            {k: p[k] for k in QUERY_PARAMS}, z=z, matmul=matmul))
    return jnp.concatenate(outs)


# ---------------------------------------------------------------------------
# the feed-forward half
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("z", "eps", "dense", "matmul"))
def finish_rows(x, mixed, p, *, z, eps, dense, matmul):
    """The mixer's residual, then the FFN and its residual, over rows
    [R, D]."""
    z = dict(z)
    x = x + mixed
    f = rms_norm(x, p["ffn_norm.scale"], eps)
    if dense:
        return x + swiglu(f, *(p[f"ffn_{k}.w_0"].astype(jnp.float32)
                               for k in ("gate", "up", "down")), matmul)
    s = jax.nn.sigmoid(matmul(f, p["moe_router.w_0"].astype(jnp.float32)))
    picks = jax.lax.top_k(s + p["moe_router.b_0"], z["picks"])[1]
    gates = jnp.take_along_axis(s, picks, axis=1)
    if z["renormalize"]:
        gates = gates / (jnp.sum(gates, axis=1, keepdims=True) + 1e-20)
    gates = z["scaling"] * gates
    # [R, experts] gate of every expert, 0 where it was not picked
    gate_of = jnp.zeros(s.shape, jnp.float32).at[
        jnp.arange(s.shape[0])[:, None], picks].set(gates)
    out = swiglu(f, *(p[f"shared_{k}.w_0"].astype(jnp.float32)
                      for k in ("gate", "up", "down")), matmul)

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
    z["scaling"] = float(cfg["routed_scaling_factor"])
    z["renormalize"] = bool(cfg["moe_renormalize"])
    zt = tuple(sorted(z.items()))
    eps = float(cfg["rms_norm_eps"])
    t = len(tokens)
    t_pad = -(-t // KEY_BLOCK) * KEY_BLOCK
    padded = np.zeros(t_pad, np.int32)
    padded[:t] = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int64)
    x = params["klin_embed.w_0"][jnp.asarray(padded)].astype(jnp.float32)
    for n, kind in enumerate(layer_kinds(cfg)):
        prefix = f"klin_layer_{n}_"
        p = {k[len(prefix):]: v for k, v in params.items()
             if k.startswith(prefix)}
        ffn = {k: v for k, v in p.items()
               if k.startswith(("ffn_", "moe_", "shared_"))}
        mixer = {k: v for k, v in p.items() if k not in ffn}
        if kind == KDA:
            mixed = kda_layer(
                x, mixer, z=zt, eps=eps,
                l2_eps=float(cfg["assumed"]["l2norm_eps"]), matmul=matmul,
                state_dtype=state_dtype)
        else:
            mixed = latent_layer(x, mixer, t, z=zt, eps=eps, matmul=matmul)
        x = jnp.concatenate([
            finish_rows(x[r:r + ROW_BLOCK], mixed[r:r + ROW_BLOCK], ffn,
                        z=zt, eps=eps,
                        dense=n < cfg["first_k_dense_replace"],
                        matmul=matmul)
            for r in range(0, t_pad, ROW_BLOCK)])
        del mixed
    hidden = rms_norm(x[jnp.asarray(rows, jnp.int32)],
                      params["klin_final_norm.scale"], eps)
    return matmul(hidden, params["klin_head.w_0"].astype(jnp.float32))


def served_logits(params, cfg, prompt, served, matmul=jnp.matmul,
                  state_dtype=jnp.float32):
    """Logits [len(served), vocab] that predicted each served token: one
    forward over the prompt and the served tokens."""
    seq = list(prompt) + list(served[:-1])
    rows = len(prompt) - 1 + np.arange(len(served))
    return forward(params, cfg, np.asarray(seq, np.int32), rows, matmul,
                   state_dtype)
