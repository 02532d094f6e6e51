"""Plain reference: Kimi-VL (``kimi_vl``: a DeepSeek-V3-shaped decoder
with multi-head latent attention over every visible position and
sigmoid-routed experts, behind a MoonViT tower and an MLP projector)
forward pass in straightforward ``jax.numpy`` float32: no cache, no
paging, no kernels, no batching.

It imports nothing of the program and takes nothing the program made: the
weights come from ``init_weights(cfg, seed)`` here (the benchmark puts the
same arrays into the program's scope).  Matrices are returned in the
configuration's storage dtype (bfloat16) holding values bfloat16
represents exactly, so both sides hold the same weights; every layer
function lifts what it uses to float32.  Every matmul against a weight or
between activations goes through the ``matmul`` argument, so that the
control (``reference/lowprec.py``) can put a lower precision in its place;
callers wrap the default in ``jax.default_matmul_precision("highest")``.

The equations (``cfg`` holds the source's keys; ``cfg["vision_config"]``
the tower's; eps = rms_norm_eps):

  input    x0[p] = E[tok[p]], except where tok[p] is
           media_placeholder_token_id: there the next row of
           Project(Tower(image)) over the request's images, in order
  block    h <- h + Attn(RMS(h)); h <- h + FFN(RMS(h)); logits = RMS(h) W_head
  MLA      per head [q_nope | q_rope] = x W_q; [c | k_rope] = x W_kva;
           c <- RMS(c); RoPE (interleaved pairs, theta) on q_rope and on
           k_rope, which all heads share; HEAD space: k_nope = c W_uk^T,
           v = c W_uv (W_uk [H, nope, c], W_uv [H, c, v]);
           score[t, s] = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope +
           rope); softmax over s <= t; Attn = concat(sum p v) W_o
  FFN      layers below first_k_dense_replace: (silu(x W_g) * x W_u) W_d;
           the others: s = sigmoid(x W_r); picks = top-k of s + b;
           g = routed_scaling_factor * s[picks] / (sum s[picks] + 1e-20);
           sum over the picks of g_e SwiGLU_e(x) (every expert runs over
           every token, its gate 0 where not picked), plus ONE shared
           SwiGLU of width n_shared_experts x moe_intermediate_size
  tower    image [H, W, 3] -> patches of p x p x 3, each flattened
           (channel, y, x)-major, in row-major order of the grid (gh, gw):
           z = patch W_p + b_p + Resize(table)[gh, gw]; blocks
           z += W_o Attn2d(LN z) + b_o; z += fc1(gelu_tanh(fc0(LN z)));
           Attn2d: [q | k | v] = u W_qkv + b, heads of d = width / heads;
           q, k turned by 2-D RoPE (pair 2k of a head by col x
           10000^(-4k/d), pair 2k + 1 by row x the same); softmax(q k^T /
           sqrt(d)) v over all patches of the image; a final LN;
           2 x 2 neighbouring patches side by side (row-major over the
           merged grid and inside a row); projector: LN a patch, Linear +
           exact GeLU + Linear
  Resize   bicubic, Keys' kernel with a = -0.75, half-pixel centres, taps
           past an edge read the edge (torch's F.interpolate(bicubic,
           align_corners=False)), separable: A_h table A_w^T

Long sequences: a layer runs over all positions at once except where a
[queries, positions] tensor appears (attention scores), which go by
blocks of queries against the keys they may see; the last layer and the
logits are computed for the served rows only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128  # queries scored against their keys at once
KEY_BLOCK = 4096   # sequences are padded, and keys handed over, in these
ROW_BLOCK = 4096   # rows through the FFN at once
KEY_PARAMS = ("attn_norm.scale", "kv_a.w_0", "kv_a_norm.scale",
              "kv_b_k.w_0", "kv_b_v.w_0")
QUERY_PARAMS = ("q.w_0", "o.w_0")
CUBIC_A = -0.75


def sizes(cfg):
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v=cfg["v_head_dim"], ckv=cfg["kv_lora_rank"],
        experts=cfg["n_routed_experts"], picks=cfg["num_experts_per_tok"],
        f=cfg["moe_intermediate_size"], shared=cfg["n_shared_experts"],
        i=cfg["intermediate_size"], vocab=cfg["vocab_size"])


def tower_sizes(cfg):
    vc = cfg["vision_config"]
    return dict(
        w=vc["hidden_size"], heads=vc["num_attention_heads"],
        i=vc["intermediate_size"], layers=vc["num_hidden_layers"],
        p=vc["patch_size"], th=vc["init_pos_emb_height"],
        tw=vc["init_pos_emb_width"], ch=vc.get("num_channels", 3))


def param_shapes(cfg):
    """{name: (shape, init, kind)}; kind "matrix" is stored in the
    configuration's dtype, "vector" in float32."""
    z, t = sizes(cfg), tower_sizes(cfg)
    d, h, w = z["d"], z["heads"], t["w"]
    out = {
        "kimi_embed.w_0": ((z["vocab"], d), "normal", "matrix"),
        "kimi_head.w_0": ((d, z["vocab"]), "normal", "matrix"),
        "kimi_final_norm.scale": ((d,), "ones", "vector"),
    }
    for n in range(cfg["num_hidden_layers"]):
        p = f"kimi_layer_{n}_"
        out.update({
            p + "attn_norm.scale": ((d,), "ones", "vector"),
            p + "q.w_0": ((d, h * (z["nope"] + z["rope"])), "normal",
                          "matrix"),
            p + "kv_a.w_0": ((d, z["ckv"] + z["rope"]), "normal", "matrix"),
            p + "kv_a_norm.scale": ((z["ckv"],), "ones", "vector"),
            p + "kv_b_k.w_0": ((h, z["nope"], z["ckv"]), "normal", "matrix"),
            p + "kv_b_v.w_0": ((h, z["ckv"], z["v"]), "normal", "matrix"),
            p + "o.w_0": ((h * z["v"], d), "normal", "matrix"),
            p + "ffn_norm.scale": ((d,), "ones", "vector"),
        })
        if n < cfg["first_k_dense_replace"]:
            ffn = {"ffn_gate.w_0": (d, z["i"]), "ffn_up.w_0": (d, z["i"]),
                   "ffn_down.w_0": (z["i"], d)}
        else:
            fs = z["shared"] * z["f"]
            ffn = {"moe_router.w_0": (d, z["experts"]),
                   "moe_experts_gate.w_0": (z["experts"], d, z["f"]),
                   "moe_experts_up.w_0": (z["experts"], d, z["f"]),
                   "moe_experts_down.w_0": (z["experts"], z["f"], d),
                   "shared_gate.w_0": (d, fs), "shared_up.w_0": (d, fs),
                   "shared_down.w_0": (fs, d)}
            out[p + "moe_router.b_0"] = ((z["experts"],), "normal", "vector")
        out.update({p + k: (s, "normal", "matrix") for k, s in ffn.items()})

    def linear(name, n_in, n_out):
        out[name + ".w_0"] = ((n_in, n_out), "normal", "matrix")
        out[name + ".b_0"] = ((n_out,), "normal", "vector")

    def norm(name, n):
        out[name + ".scale"] = ((n,), "ones", "vector")
        out[name + ".bias"] = ((n,), "zeros", "vector")

    linear("kimi_vit_patch", t["ch"] * t["p"] ** 2, w)
    out["kimi_vit_pos.w_0"] = ((t["th"], t["tw"], w), "normal", "vector")
    for n in range(t["layers"]):
        p = f"kimi_vit_layer_{n}_"
        norm(p + "ln0", w)
        linear(p + "qkv", w, 3 * w)
        linear(p + "o", w, w)
        norm(p + "ln1", w)
        linear(p + "fc0", w, t["i"])
        linear(p + "fc1", t["i"], w)
    norm("kimi_vit_final_ln", w)
    norm("kimi_proj_ln", w)
    linear("kimi_proj_fc0", 4 * w, 4 * w)
    linear("kimi_proj_fc1", 4 * w, d)
    return out


def seed_key(seed):
    """A key from any whole number up to 2**63 (seeds pass 2**31), of the
    ``rbg`` generator: 3.8 G normal draws take seconds with the chip's
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


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def turn_pairs(x, ang):
    """Interleaved pairs (x[2j], x[2j + 1]) of the last dimension turned
    by ang[..., j]."""
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def rope(x, pos, theta, rd):
    """Interleaved pairs on the whole last dimension (``rd`` wide); x
    [T, rd] or [T, H, rd], pos [T]."""
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = pos.astype(jnp.float32)[:, None] * inv
    return turn_pairs(x, ang[:, None, :] if x.ndim == 3 else ang)


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, w_gate, w_up, w_down, matmul):
    return matmul(silu(matmul(x, w_gate)) * matmul(x, w_up), w_down)


def _f32(p):
    return {k: v.astype(jnp.float32) for k, v in p.items()}


# ---------------------------------------------------------------------------
# the tower: one whole image at a time
# ---------------------------------------------------------------------------


def bicubic_weights(n_in, n_out):
    """[n_out, n_in]: each output position's four taps (Keys' kernel,
    a = -0.75, half-pixel centres, edge taps clamped)."""
    a = CUBIC_A

    def near(t):   # |t| <= 1
        return ((a + 2) * t - (a + 3)) * t * t + 1

    def far(t):    # 1 < |t| < 2
        return ((a * t - 5 * a) * t + 8 * a) * t - 4 * a

    out = np.zeros((n_out, n_in))
    for i in range(n_out):
        centre = (i + 0.5) * n_in / n_out - 0.5
        first = int(np.floor(centre)) - 1
        for tap in range(first, first + 4):
            dist = abs(centre - tap)
            out[i, min(max(tap, 0), n_in - 1)] += (
                near(dist) if dist <= 1 else far(dist))
    return jnp.asarray(out, jnp.float32)


def patches_of(pixels, p):
    """pixels [H, W, ch] -> [gh * gw, ch * p * p], a patch's values
    (channel, y, x)-major, patches in row-major order; (gh, gw)."""
    pixels = jnp.asarray(pixels, jnp.float32)
    h, w, ch = pixels.shape
    gh, gw = h // p, w // p
    x = pixels.reshape(gh, p, gw, p, ch).transpose(0, 2, 4, 1, 3)
    return x.reshape(gh * gw, ch * p * p), (gh, gw)


@functools.partial(jax.jit, static_argnames=("grid", "t", "eps", "theta",
                                             "matmul"))
def tower_rows(patches, p, *, grid, t, eps, theta, matmul):
    """patches [N, ch * p * p] of one image -> its rows [N / 4, D]."""
    t = dict(t)
    p = _f32(p)
    gh, gw = grid
    n, w, heads = gh * gw, t["w"], t["heads"]
    d = w // heads

    def linear(x, name):
        return matmul(x, p[name + ".w_0"]) + p[name + ".b_0"]

    def norm(x, name):
        return layer_norm(x, p[name + ".scale"], p[name + ".bias"], eps)

    table = jnp.einsum("ih,hwd,jw->ijd", bicubic_weights(t["th"], gh),
                       p["kimi_vit_pos.w_0"], bicubic_weights(t["tw"], gw),
                       precision=jax.lax.Precision.HIGHEST)
    z = linear(patches, "kimi_vit_patch") + table.reshape(n, w)
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 4, dtype=jnp.float32) / d))
    rows, cols = jnp.divmod(jnp.arange(n), gw)
    ang = jnp.stack([cols[:, None] * freqs, rows[:, None] * freqs],
                    axis=-1).reshape(n, 1, d // 2)     # col, row, col, ...
    for layer in range(t["layers"]):
        name = f"kimi_vit_layer_{layer}_"
        qkv = linear(norm(z, name + "ln0"), name + "qkv").reshape(
            n, 3, heads, d)
        q = turn_pairs(qkv[:, 0], ang).transpose(1, 0, 2)     # [H, N, d]
        k = turn_pairs(qkv[:, 1], ang).transpose(1, 0, 2)
        v = qkv[:, 2].transpose(1, 0, 2)

        def one_head(qkv_h):
            q_h, k_h, v_h = qkv_h
            probs = jax.nn.softmax(matmul(q_h, k_h.T) * d ** -0.5, axis=-1)
            return matmul(probs, v_h)

        o = jax.lax.map(one_head, (q, k, v))                  # [H, N, d]
        z = z + linear(o.transpose(1, 0, 2).reshape(n, w), name + "o")
        z = z + linear(jax.nn.gelu(linear(norm(z, name + "ln1"),
                                          name + "fc0"), approximate=True),
                       name + "fc1")
    z = norm(norm(z, "kimi_vit_final_ln"), "kimi_proj_ln")
    merged = z.reshape(gh // 2, 2, gw // 2, 2, w).transpose(
        0, 2, 1, 3, 4).reshape(n // 4, 4 * w)
    return linear(jax.nn.gelu(linear(merged, "kimi_proj_fc0"),
                              approximate=False), "kimi_proj_fc1")


def encode_image(params, cfg, pixels, matmul=jnp.matmul):
    """One image [H, W, 3] -> the rows that stand at its placeholder
    positions, [H / 28 * W / 28, hidden_size]."""
    t = tower_sizes(cfg)
    patches, grid = patches_of(pixels, t["p"])
    p = {k: v for k, v in params.items()
         if k.startswith(("kimi_vit_", "kimi_proj_"))}
    return tower_rows(
        patches, p, grid=grid, t=tuple(sorted(t.items())),
        eps=float(cfg["assumed"]["vision_layer_norm_eps"]),
        theta=float(cfg["assumed"]["vision_rope_theta"]), matmul=matmul)


# ---------------------------------------------------------------------------
# the decoder
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("z", "eps", "theta", "matmul"))
def layer_keys(x, p, *, z, eps, theta, matmul):
    """What every position gives a layer's attention: the normed input
    and every head's keys and values, [T, ...]."""
    z = dict(z)
    p = _f32(p)
    pos = jnp.arange(x.shape[0])
    xa = rms_norm(x, p["attn_norm.scale"], eps)
    kv = matmul(xa, p["kv_a.w_0"])
    c_kv = rms_norm(kv[:, :z["ckv"]], p["kv_a_norm.scale"], eps)
    k_rope = rope(kv[:, z["ckv"]:], pos, theta, z["rope"])
    k_nope = matmul(c_kv, p["kv_b_k.w_0"].transpose(0, 2, 1))  # [H, T, nope]
    v = matmul(c_kv, p["kv_b_v.w_0"])                          # [H, T, v]
    return xa, k_nope, k_rope, v


@functools.partial(jax.jit, static_argnames=("z", "theta", "matmul"))
def attend_block(first, xa, k_nope, k_rope, v, p, *, z, theta, matmul):
    """Queries first .. first + Q (their rows ``xa``) against every
    position handed over -> Attn [Q, D], after the output projection."""
    z = dict(z)
    p = _f32(p)
    nq, t, heads = xa.shape[0], k_rope.shape[0], z["heads"]
    qpos = first + jnp.arange(nq)
    q = matmul(xa, p["q.w_0"]).reshape(nq, heads, z["nope"] + z["rope"])
    q_nope = q[..., :z["nope"]].transpose(1, 0, 2)             # [H, Q, nope]
    q_rope = rope(q[..., z["nope"]:], qpos, theta,
                  z["rope"]).transpose(1, 0, 2)                # [H, Q, rope]
    scale = float(z["nope"] + z["rope"]) ** -0.5
    scores = (matmul(q_nope, k_nope.transpose(0, 2, 1))
              + matmul(q_rope, k_rope.T)) * scale              # [H, Q, T]
    causal = jnp.arange(t)[None, :] <= qpos[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                           axis=-1)
    out = matmul(probs, v)                                     # [H, Q, v]
    return matmul(out.transpose(1, 0, 2).reshape(nq, -1), p["o.w_0"])


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
    gates = z["scaling"] * gates / (
        jnp.sum(gates, axis=1, keepdims=True) + 1e-20)
    # [R, experts] gate of every expert, 0 where it was not picked
    gate_of = jnp.zeros(s.shape, jnp.float32).at[
        jnp.arange(s.shape[0])[:, None], picks].set(gates)
    out = swiglu(f, *(p[f"shared_{k}.w_0"].astype(jnp.float32)
                      for k in ("gate", "up", "down")), matmul)

    def add_expert(e, acc):
        return acc + gate_of[:, e, None] * swiglu(
            f, *(p[f"moe_experts_{k}.w_0"][e].astype(jnp.float32)
                 for k in ("gate", "up", "down")), matmul)

    return x + jax.lax.fori_loop(0, z["experts"], add_expert, out)


def input_rows(params, cfg, tokens, images, matmul, prompt_len=None):
    """x0 [T, D]: token embeddings, image rows at the PROMPT's placeholder
    positions (the images' rows in order).  A generated token that
    happens to be the placeholder id is a token like any other."""
    tokens = np.asarray(tokens, np.int64)
    x = params["kimi_embed.w_0"][jnp.asarray(tokens, jnp.int32)].astype(
        jnp.float32)
    held = np.flatnonzero(tokens[:prompt_len]
                          == cfg["media_placeholder_token_id"])
    if not len(held) and not images:
        return x
    rows = jnp.concatenate([encode_image(params, cfg, im, matmul)
                            for im in images])
    if rows.shape[0] != len(held):
        raise ValueError(f"{len(held)} placeholder positions, "
                         f"{rows.shape[0]} image rows")
    return x.at[jnp.asarray(held, jnp.int32)].set(rows)


def forward(params, cfg, tokens, rows, matmul=jnp.matmul, images=(),
            prompt_len=None):
    """Logits [len(rows), vocab] of the positions ``rows`` of one sequence
    of ``tokens`` whose placeholder positions (among the first
    ``prompt_len``: all of them by default) hold ``images``' rows.

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
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    t = len(tokens)
    t_pad = -(-t // KEY_BLOCK) * KEY_BLOCK
    rows = np.asarray(rows, np.int64)
    x = jnp.pad(input_rows(params, cfg, tokens, images, matmul, prompt_len),
                ((0, t_pad - t), (0, 0)))
    last = cfg["num_hidden_layers"] - 1
    for n in range(last + 1):
        prefix = f"kimi_layer_{n}_"
        p = {k[len(prefix):]: v for k, v in params.items()
             if k.startswith(prefix)}
        xa, k_nope, k_rope, v = layer_keys(
            x, {k: p[k] for k in KEY_PARAMS}, z=zt, eps=eps, theta=theta,
            matmul=matmul)
        # the last layer's queries: from the block of the first wanted row
        start = int(rows.min()) // ROW_BLOCK * ROW_BLOCK if n == last else 0
        outs = []
        for first in range(start, t_pad, QUERY_BLOCK):
            if first >= t:  # padding rows: nothing reads them
                outs.append(jnp.zeros((QUERY_BLOCK, x.shape[1]),
                                      jnp.float32))
                continue
            q = slice(first, first + QUERY_BLOCK)
            keys = min(t_pad, -(-(first + QUERY_BLOCK) // KEY_BLOCK)
                       * KEY_BLOCK)
            outs.append(attend_block(
                first, xa[q], k_nope[:, :keys], k_rope[:keys], v[:, :keys],
                {k: p[k] for k in QUERY_PARAMS}, z=zt, theta=theta,
                matmul=matmul))
        del xa, k_nope, k_rope, v
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
                      params["kimi_final_norm.scale"], eps)
    return matmul(hidden, params["kimi_head.w_0"].astype(jnp.float32))


def served_logits(params, cfg, prompt, served, matmul=jnp.matmul,
                  images=()):
    """Logits [len(served), vocab] that predicted each served token: one
    forward over the prompt (its images' rows at the placeholder
    positions) and the served tokens."""
    seq = list(prompt) + list(served[:-1])
    rows = len(prompt) - 1 + np.arange(len(served))
    return forward(params, cfg, np.asarray(seq, np.int64), rows, matmul,
                   images, len(prompt))
