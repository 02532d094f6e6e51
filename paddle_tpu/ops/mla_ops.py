"""Ops of a latent-attention, sparse-expert decoder served through the
decode lane (models/glm.py): what the older zoo had no op for.

  weight_matmul      x @ W computed in W's storage dtype (bf16 weights in
                     the serving lane) with float32 accumulation and a
                     float32 result — the serving lane's matmul
  headwise_matmul    one matrix a head: [.., H, a] x [H, a, b] (the two
                     halves of MLA's KV up-projection, absorbed)
  rms_norm, swiglu, rope_interleaved
  dsa_indexer_scores, dsa_topk_select, sparse_mla_attention
                     learned sparse attention over the paged indexer and
                     latent caches (kernels/primitives/dsa.py)
  paged_mla_attention, mla_chunk_attention
                     dense latent attention over the paged latent cache,
                     in latent space (decode step) and in head space
                     (prefill chunk) (kernels/primitives/mla.py)
  moe_ffn_held       the expert layer of ONE chip of an expert-parallel
                     deployment: routes over every expert (sigmoid scores
                     and a selection bias, or a softmax), computes the
                     picks that land on the experts it holds

All inference-only (grad=None), like every decode-lane op.  Activations
between ops are float32; an op rounds its operands to the weights' (or
the cache's) dtype where it multiplies them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from paddle_tpu.fluid.registry import simple_op


def _f32(x):
    return x.astype(jnp.float32)


def _pin_product(out, w, head_dim):
    """Constrain ``out = x @ w`` to the row-major layout where the caller
    splits it into heads of ``head_dim`` that are no whole 128-lane tiles
    and the weight is at least as large as the product, and book the
    choice.  There XLA:TPU's layout assignment gives the dot the layout
    the reshape wants and pays with a copy of the WEIGHT, an entry
    parameter, through HBM in every run (MiMo-V2.5's 100.7-MB ``W_q``,
    64 heads of 192, in every layer of a prefill chunk); told the
    product's layout it relays the product after the dot.  The constraint
    sits on the product because one on the weight changes nothing (a
    parameter arrives row-major already: the copy is what XLA inserts
    behind it).  Every other product is XLA's: a pin there buys nothing
    (the weight copies left are XLA's own fetches into fast memory) and
    can cost a relay of the product (docs/KERNELS.md "The layout of
    weight_matmul's product")."""
    from paddle_tpu.observability import metrics as obs

    pinned = bool(head_dim and head_dim % 128
                  and w.size * w.dtype.itemsize
                  >= out.size * out.dtype.itemsize)
    obs.counter(
        "pt_weight_matmul_layout_total",
        "Trace-time choices of weight_matmul: products of this many rows "
        "whose layout was pinned row-major (heads that are no whole lane "
        "tiles under a weight as large) or left to XLA",
        labels=("rows", "pinned"),
    ).labels(rows=str(math.prod(out.shape[:-1])),
             pinned=str(pinned).lower()).inc()
    if not pinned:
        return out
    return with_layout_constraint(
        out, Layout(major_to_minor=tuple(range(out.ndim))))


@simple_op("weight_matmul", ["X", "W"], ["Out"], grad=None)
def _weight_matmul(ctx, x, w, attrs):
    return _pin_product(
        jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32), w,
        attrs.get("head_dim"))


@simple_op("headwise_matmul", ["X", "W"], ["Out"], grad=None)
def _headwise_matmul(ctx, x, w, attrs):
    """x [B, T, H, a], w [H, a, b] -> [B, T, H, b]."""
    return jnp.einsum("bthx,hxy->bthy", x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


@simple_op("rms_norm", ["X", "Scale"], ["Out"], grad=None)
def _rms_norm(ctx, x, scale, attrs):
    """x rsqrt(mean x^2 + eps) (``gain_offset`` + Scale): a plain gain,
    or, with ``gain_offset`` 1, a zero-centred one (``1 + w`` with ``w``
    stored)."""
    x = _f32(x)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    gain = _f32(scale)
    if attrs.get("gain_offset"):
        gain = float(attrs["gain_offset"]) + gain
    return x * jax.lax.rsqrt(var + attrs.get("epsilon", 1e-5)) * gain


def _silu_times(gate, up):
    gate = _f32(gate)
    return gate * jax.nn.sigmoid(gate) * _f32(up)


@simple_op("swiglu", ["Gate", "Up"], ["Out"], grad=None)
def _swiglu(ctx, gate, up, attrs):
    return _silu_times(gate, up)


@simple_op("rope_interleaved", ["X", "Pos"], ["Out"], grad=None)
def _rope_interleaved(ctx, x, pos, attrs):
    """Rotary embedding on the first ``rotary_dim`` entries of the last
    dimension, pairs interleaved: (x[2i], x[2i+1]) turn by pos *
    theta^(-2i/rotary_dim).  x [B, T, d] or [B, T, H, d]; pos [B, T]."""
    rd = int(attrs["rotary_dim"])
    x = _f32(x)
    inv = 1.0 / (float(attrs["theta"]) ** (
        jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = _f32(pos)[..., None] * inv                       # [B, T, rd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == 4:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    rot, rest = x[..., :rd], x[..., rd:]
    pairs = rot.reshape(rot.shape[:-1] + (rd // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return jnp.concatenate([turned.reshape(rot.shape), rest], axis=-1)


@simple_op("dsa_indexer_scores",
           ["Q", "W", "IndexPages", "PageTable", "QStart"], ["Out"],
           grad=None)
def _dsa_indexer_scores(ctx, q, w, index_pages, page_table, q_start, attrs):
    from paddle_tpu.kernels import primitives as _prims

    return _prims.dsa_indexer_scores(q, w, index_pages, page_table, q_start,
                                     force=attrs.get("force"))


@simple_op("dsa_topk_select", ["Scores"], ["Out"], grad=None)
def _dsa_topk_select(ctx, scores, attrs):
    from paddle_tpu.kernels import primitives as _prims

    return _prims.dsa_topk_select(scores, int(attrs["k"]),
                                  force=attrs.get("force"))


@simple_op("sparse_mla_attention",
           ["QLatent", "QRope", "LatentPages", "PageTable", "Selected",
            "QStart"], ["Out"], grad=None)
def _sparse_mla_attention(ctx, q_lat, q_rope, latent_pages, page_table,
                          selected, q_start, attrs):
    from paddle_tpu.kernels import primitives as _prims

    return _prims.sparse_mla_attention(
        q_lat, q_rope, latent_pages, page_table, selected, q_start,
        sm_scale=attrs["sm_scale"], force=attrs.get("force"))


@simple_op("paged_mla_attention",
           ["QLatent", "QRope", "LatentPages", "PageTable", "QStart"],
           ["Out"], grad=None)
def _paged_mla_attention(ctx, q_lat, q_rope, latent_pages, page_table,
                         q_start, attrs):
    from paddle_tpu.kernels import primitives as _prims

    return _prims.paged_mla_attention(
        q_lat, q_rope, latent_pages, page_table, q_start,
        sm_scale=attrs["sm_scale"], force=attrs.get("force"))


@simple_op("mla_chunk_attention",
           ["QNope", "QRope", "LatentPages", "PageTable", "QStart", "WUk",
            "WUv"], ["Out"], grad=None)
def _mla_chunk_attention(ctx, q_nope, q_rope, latent_pages, page_table,
                         q_start, w_uk, w_uv, attrs):
    from paddle_tpu.kernels import primitives as _prims

    return _prims.mla_chunk_attention(
        q_nope, q_rope, latent_pages, page_table, q_start, w_uk, w_uv,
        sm_scale=attrs["sm_scale"], force=attrs.get("force"))


def route_sigmoid_topk(x2, router_w, router_b, top_k, scaling, normalize):
    """Sigmoid scores over every expert (the router's product in float32
    whatever the weights' storage), the ``top_k`` largest of score +
    selection bias, gates from the scores alone (``noaux_tc`` with one
    group): (picks [N, k] int32, gates [N, k] float32)."""
    # float32 at full precision, as the source computes its gate: top-k
    # is a discontinuity, and 8 of 256 sigmoid scores crowd near 1 — a
    # bfloat16 product flips picks that an exact one does not
    scores = jax.nn.sigmoid(jnp.dot(
        _f32(x2), _f32(router_w), precision=jax.lax.Precision.HIGHEST))
    picks = jax.lax.top_k(scores + _f32(router_b), top_k)[1]
    gates = jnp.take_along_axis(scores, picks, axis=1)
    if normalize:
        gates = gates / (jnp.sum(gates, axis=1, keepdims=True) + 1e-20)
    return picks.astype(jnp.int32), gates * scaling


def route_softmax_topk(x2, router_w, top_k, scaling, normalize):
    """Softmax scores over every expert (the router's product, the
    softmax and the top-k in float32 at full precision), the ``top_k``
    largest, gates the picked scores over their sum (``normalize``) or as
    they are: (picks [N, k] int32, gates [N, k] float32).  No selection
    bias."""
    with jax.named_scope("moe_route"):
        scores = jax.nn.softmax(jnp.dot(
            _f32(x2), _f32(router_w), precision=jax.lax.Precision.HIGHEST),
            axis=-1)
        gates, picks = jax.lax.top_k(scores, top_k)
        if normalize:
            gates = gates / jnp.sum(gates, axis=1, keepdims=True)
        return picks.astype(jnp.int32), gates * scaling


@simple_op("moe_ffn_held",
           ["X", "RouterW", "RouterBias", "WGate", "WUp", "WDown",
            "RowValid", "Stats"], ["Out", "StatsOut"],
           optional=("RouterBias", "RowValid", "Stats"), grad=None,
           inplace={"StatsOut": "Stats"})
def _moe_ffn_held(ctx, x, router_w, router_b, w_gate, w_up, w_down,
                  row_valid, stats, attrs):
    """One chip's share of an expert-parallel SwiGLU expert layer.

    The router keeps its full width (``RouterW`` [D, E], ``RouterBias``
    [E]; ``score_func`` "sigmoid", the default, or "softmax", which has
    no bias: ``route_softmax_topk``); this chip holds experts
    ``first_expert .. first_expert + Eh``
    (``WGate``/``WUp`` [Eh, D, F], ``WDown`` [Eh, F, D]).  Every token
    picks ``top_k`` of all E; the picks that land on held experts are
    sorted by expert and go through a grouped product
    (kernels/primitives/grouped.py) — no expert runs on a token that did
    not pick it, and no pick is dropped.  Picks on absent experts add
    nothing: the result is this chip's partial sum, which an
    expert-parallel deployment would add up across chips.

    ``RowValid`` [rows] (> 0 = a real token): padding rows pick nothing.
    ``Stats`` [Eh + 2] int32, added to in place: picks each held expert
    got and picks that went to absent experts, over the valid rows, and
    the held experts that got at least one pick in this call (the
    expert weights the call had to read)."""
    from paddle_tpu.kernels import primitives as _prims

    top_k = int(attrs["top_k"])
    first = int(attrs.get("first_expert", 0))
    held_n = w_gate.shape[0]
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    scaling = float(attrs.get("routed_scaling_factor", 1.0))
    normalize = bool(attrs.get("norm_topk_prob", True))
    if attrs.get("score_func", "sigmoid") == "softmax":
        picks, gates = route_softmax_topk(x2, router_w, top_k, scaling,
                                          normalize)
    else:
        picks, gates = route_sigmoid_topk(x2, router_w, router_b, top_k,
                                          scaling, normalize)
    local = picks - first
    held = (local >= 0) & (local < held_n)
    valid = jnp.ones((n, 1), bool) if row_valid is None else (
        row_valid.reshape(-1, 1) > 0)
    group = jnp.where(held & valid, local, held_n).reshape(-1)  # [N*k]
    sizes = jnp.sum(jax.nn.one_hot(group, held_n + 1, dtype=jnp.int32),
                    axis=0)
    order = jnp.argsort(group, stable=True)      # held picks first, by expert
    rows = x2[order // top_k].astype(w_gate.dtype)
    force = attrs.get("force")
    hidden = _silu_times(
        _prims.grouped_matmul(rows, w_gate, sizes[:held_n], force=force),
        _prims.grouped_matmul(rows, w_up, sizes[:held_n], force=force))
    out = _prims.grouped_matmul(hidden.astype(w_down.dtype), w_down,
                                sizes[:held_n], force=force)
    out = out * gates.reshape(-1)[order][:, None]
    back = jnp.argsort(order)
    out = jnp.sum(out[back].reshape(n, top_k, d), axis=1).reshape(x.shape)
    if stats is None:
        return out, None
    absent = jnp.sum(valid) * top_k - jnp.sum(sizes[:held_n])
    touched = jnp.sum(sizes[:held_n] > 0)
    return out, stats + jnp.concatenate(
        [sizes[:held_n], jnp.stack([absent, touched]).astype(jnp.int32)])
