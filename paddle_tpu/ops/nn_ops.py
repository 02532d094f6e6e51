"""NN op lowerings: conv, pooling, normalization, dropout, attention helpers.

Reference analogs: conv_op.cc (+conv_cudnn_op.cu.cc), pool_op.cc,
batch_norm_op.cc, layer_norm_op.cc, group_norm_op.cc, dropout_op.cc,
interpolate_op.cc.  Convs lower to lax.conv_general_dilated — XLA maps them
onto the MXU directly; no im2col (reference operators/math/im2col.cc) is
needed.  NCHW semantics are preserved at the API level; XLA picks device
layouts itself.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.fluid.registry import register_op, simple_op
from .common import conv_nd_raw, mxu_conv_kwargs, op_rng_key

# ---------------------------------------------------------------------------
# convolution
# ---------------------------------------------------------------------------


def _conv_nd(x, w, strides, paddings, dilations, groups, nd):
    return conv_nd_raw(x, w, strides, paddings, dilations, groups, nd=nd,
                       **mxu_conv_kwargs(x, w)).astype(x.dtype)


@simple_op("conv2d", ["Input", "Filter", "Bias"], ["Output"], optional=("Bias",))
def _conv2d(ctx, x, w, bias, attrs):
    out = _conv_nd(x, w, attrs.get("strides", [1, 1]), attrs.get("paddings", [0, 0]),
                   attrs.get("dilations", [1, 1]), attrs.get("groups", 1), 2)
    if bias is not None:
        out = out + jnp.reshape(bias, (1, -1, 1, 1))
    return out


@simple_op("depthwise_conv2d", ["Input", "Filter", "Bias"], ["Output"], optional=("Bias",))
def _depthwise_conv2d(ctx, x, w, bias, attrs):
    a = dict(attrs)
    a["groups"] = jnp.shape(x)[1]
    return _conv2d(ctx, x, w, bias, a)


@simple_op("conv3d", ["Input", "Filter", "Bias"], ["Output"], optional=("Bias",))
def _conv3d(ctx, x, w, bias, attrs):
    out = _conv_nd(x, w, attrs.get("strides", [1, 1, 1]), attrs.get("paddings", [0, 0, 0]),
                   attrs.get("dilations", [1, 1, 1]), attrs.get("groups", 1), 3)
    if bias is not None:
        out = out + jnp.reshape(bias, (1, -1, 1, 1, 1))
    return out


@simple_op("conv2d_transpose", ["Input", "Filter", "Bias"], ["Output"], optional=("Bias",))
def _conv2d_transpose(ctx, x, w, bias, attrs):
    strides = tuple(attrs.get("strides", [1, 1]))
    paddings = attrs.get("paddings", [0, 0])
    dilations = tuple(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    # Filter layout is (in, out/groups, kh, kw) in the reference.
    pads = [(d * (k - 1) - p, d * (k - 1) - p)
            for p, k, d in zip(paddings, jnp.shape(w)[2:], dilations)]
    wt = jnp.flip(w, axis=(-2, -1))
    if groups == 1:
        wt = jnp.swapaxes(wt, 0, 1)  # (out, in, kh, kw)
    else:
        ci, co_g = jnp.shape(w)[0], jnp.shape(w)[1]
        wt = jnp.reshape(wt, (groups, ci // groups, co_g) + tuple(jnp.shape(w)[2:]))
        wt = jnp.swapaxes(wt, 1, 2)
        wt = jnp.reshape(wt, (groups * co_g, ci // groups) + tuple(jnp.shape(w)[2:]))
    dn = jax.lax.conv_dimension_numbers(jnp.shape(x), jnp.shape(wt), ("NCHW", "OIHW", "NCHW"))
    out = jax.lax.conv_general_dilated(
        x, wt, window_strides=(1, 1), padding=pads, lhs_dilation=strides,
        rhs_dilation=dilations, dimension_numbers=dn, feature_group_count=groups,
        **mxu_conv_kwargs(x, wt)).astype(x.dtype)
    if bias is not None:
        out = out + jnp.reshape(bias, (1, -1, 1, 1))
    return out


# ---------------------------------------------------------------------------
# pooling (reference pool_op.cc)
# ---------------------------------------------------------------------------


@simple_op("pool2d", ["X"], ["Out"])
def _pool2d(ctx, x, attrs):
    ptype = attrs.get("pooling_type", "max")
    ksize = list(attrs.get("ksize", [2, 2]))
    strides = list(attrs.get("strides", ksize))
    paddings = list(attrs.get("paddings", [0, 0]))
    if attrs.get("global_pooling", False) or attrs.get("adaptive", False) and ksize == [1, 1]:
        if ptype == "max":
            return jnp.max(x, axis=(2, 3), keepdims=True)
        return jnp.mean(x, axis=(2, 3), keepdims=True)
    if attrs.get("adaptive", False):
        # adaptive pooling to output size ksize: split H/W into ksize bins
        n, c, h, wd = jnp.shape(x)
        oh, ow = ksize
        assert h % oh == 0 and wd % ow == 0, "adaptive pool needs divisible dims"
        r = jnp.reshape(x, (n, c, oh, h // oh, ow, wd // ow))
        return jnp.max(r, axis=(3, 5)) if ptype == "max" else jnp.mean(r, axis=(3, 5))
    window = (1, 1, ksize[0], ksize[1])
    strides_full = (1, 1, strides[0], strides[1])
    pads = ((0, 0), (0, 0), (paddings[0], paddings[0]), (paddings[1], paddings[1]))
    if attrs.get("ceil_mode", False):
        n, c, h, wd = jnp.shape(x)
        extra_h = _ceil_extra(h, ksize[0], strides[0], paddings[0])
        extra_w = _ceil_extra(wd, ksize[1], strides[1], paddings[1])
        pads = ((0, 0), (0, 0), (paddings[0], paddings[0] + extra_h),
                (paddings[1], paddings[1] + extra_w))
    # NB: init values must be python/numpy scalars, not jnp arrays — a traced
    # init forces the generic reduce_window primitive, which has no transpose
    # rule (breaks the whole-block vjp under jit).
    if ptype == "max":
        init = -np.inf if jnp.issubdtype(x.dtype, jnp.floating) else np.iinfo(x.dtype).min
        return jax.lax.reduce_window(x, np.asarray(init, x.dtype), jax.lax.max,
                                     window, strides_full, pads)
    summed = jax.lax.reduce_window(x, np.asarray(0.0, x.dtype), jax.lax.add,
                                   window, strides_full, pads)
    if attrs.get("exclusive", True) and (paddings[0] or paddings[1]):
        ones = jnp.ones_like(x)
        counts = jax.lax.reduce_window(ones, np.asarray(0.0, x.dtype), jax.lax.add,
                                       window, strides_full, pads)
        return summed / counts
    return summed / (ksize[0] * ksize[1])


def _ceil_extra(size, k, s, p):
    import math

    out_floor = (size + 2 * p - k) // s + 1
    out_ceil = math.ceil((size + 2 * p - k) / s) + 1
    return (out_ceil - out_floor) * s


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


@simple_op(
    "batch_norm",
    ["X", "Scale", "Bias", "Mean", "Variance"],
    ["Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"],
    grad="bn_custom",
    inplace={"MeanOut": "Mean", "VarianceOut": "Variance"},
)
def _batch_norm(ctx, x, scale, bias, mean, var, attrs):
    """Reference batch_norm_op.cc.  MeanOut/VarianceOut alias Mean/Variance
    (running stats updated in place → buffer donation in the executor)."""
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    layout = attrs.get("data_layout", "NCHW")
    is_test = attrs.get("is_test", False) or ctx.is_test
    axes = (0, 2, 3) if (layout == "NCHW" and jnp.ndim(x) == 4) else tuple(
        i for i in range(jnp.ndim(x)) if i != (1 if layout == "NCHW" else jnp.ndim(x) - 1))
    ch_axis = 1 if layout == "NCHW" else jnp.ndim(x) - 1

    def rs(v):
        shape = [1] * jnp.ndim(x)
        shape[ch_axis] = -1
        return jnp.reshape(v, shape)

    if is_test and not attrs.get("trainable_statistics", False):
        inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
        y = ((x.astype(jnp.float32) - rs(mean.astype(jnp.float32)))
             * rs(inv * scale.astype(jnp.float32))
             + rs(bias.astype(jnp.float32))).astype(x.dtype)
        return y, mean, var, mean, var
    xf = x.astype(jnp.float32)
    bmean = jnp.mean(xf, axis=axes)
    bvar = jnp.mean(jnp.square(xf), axis=axes) - jnp.square(bmean)
    inv = jax.lax.rsqrt(bvar + eps)
    y = ((xf - rs(bmean)) * rs(inv) * rs(scale.astype(jnp.float32))
         + rs(bias.astype(jnp.float32))).astype(x.dtype)
    new_mean = momentum * mean + (1 - momentum) * bmean.astype(mean.dtype)
    new_var = momentum * var + (1 - momentum) * bvar.astype(var.dtype)
    return y, new_mean, new_var, bmean, inv


def _bn_grad_maker(op, out_grads, wanted, uniq):
    """batch_norm grad: d(Y)→d(X,Scale,Bias); running-stat updates carry no
    grad.  Uses a vjp over the normalization only (not the stat update)."""
    ins = {k: list(v) for k, v in op.inputs.items()}
    ins["Y@GRAD"] = [out_grads[op.outputs["Y"][0]]]
    outs = {}
    pairs = []
    for slot in ("X", "Scale", "Bias"):
        n = op.inputs[slot][0]
        if n in wanted:
            g = uniq(n)
            outs[slot + "@GRAD"] = [g]
            pairs.append((n, g))
    return [("batch_norm_grad", ins, outs, dict(op.attrs))], pairs


@simple_op("batch_norm_grad",
           ["X", "Scale", "Bias", "Mean", "Variance", "Y@GRAD"],
           ["X@GRAD", "Scale@GRAD", "Bias@GRAD"], grad=None,
           optional=("Mean", "Variance"))
def _batch_norm_grad(ctx, x, scale, bias, mean, var, dy, attrs):
    def f(x_, s_, b_):
        y = _batch_norm(ctx, x_, s_, b_, mean, var, attrs)[0]
        return y

    _, vjp = jax.vjp(f, x, scale, bias)
    dx, ds, db = vjp(dy)
    return dx, ds, db


from paddle_tpu.fluid import registry as _registry

_registry.get_op("batch_norm").grad_maker = _bn_grad_maker


@simple_op("layer_norm", ["X", "Scale", "Bias"], ["Y", "Mean", "Variance"],
           optional=("Scale", "Bias"))
def _layer_norm(ctx, x, scale, bias, attrs):
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(begin, jnp.ndim(x)))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    norm_shape = jnp.shape(x)[begin:]
    if scale is not None:
        y = y * jnp.reshape(scale.astype(jnp.float32), norm_shape)
    if bias is not None:
        y = y + jnp.reshape(bias.astype(jnp.float32), norm_shape)
    return (y.astype(x.dtype), jnp.reshape(mean, jnp.shape(x)[:begin]),
            jnp.reshape(var, jnp.shape(x)[:begin]))


@simple_op("group_norm", ["X", "Scale", "Bias"], ["Y", "Mean", "Variance"],
           optional=("Scale", "Bias"))
def _group_norm(ctx, x, scale, bias, attrs):
    eps = attrs.get("epsilon", 1e-5)
    groups = attrs.get("groups", 1)
    n, c = jnp.shape(x)[0], jnp.shape(x)[1]
    r = jnp.reshape(x.astype(jnp.float32), (n, groups, -1))
    mean = jnp.mean(r, axis=-1, keepdims=True)
    var = jnp.var(r, axis=-1, keepdims=True)
    y = jnp.reshape((r - mean) * jax.lax.rsqrt(var + eps), jnp.shape(x))
    if scale is not None:
        y = y * jnp.reshape(scale, (1, c) + (1,) * (jnp.ndim(x) - 2))
    if bias is not None:
        y = y + jnp.reshape(bias, (1, c) + (1,) * (jnp.ndim(x) - 2))
    return y.astype(x.dtype), jnp.squeeze(mean, -1), jnp.squeeze(var, -1)


@simple_op("instance_norm", ["X", "Scale", "Bias"], ["Y", "SavedMean", "SavedVariance"],
           optional=("Scale", "Bias"))
def _instance_norm(ctx, x, scale, bias, attrs):
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(2, jnp.ndim(x)))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    c = jnp.shape(x)[1]
    shp = (1, c) + (1,) * (jnp.ndim(x) - 2)
    if scale is not None:
        y = y * jnp.reshape(scale, shp)
    if bias is not None:
        y = y + jnp.reshape(bias, shp)
    return y, jnp.reshape(mean, (-1,)), jnp.reshape(var, (-1,))


# ---------------------------------------------------------------------------
# dropout — custom grad through the saved Mask so forward/backward agree
# ---------------------------------------------------------------------------


def _dropout_grad_maker(op, out_grads, wanted, uniq):
    x = op.inputs["X"][0]
    if x not in wanted:
        return [], []
    g = uniq(x)
    ins = {"Out@GRAD": [out_grads[op.outputs["Out"][0]]], "Mask": list(op.outputs["Mask"])}
    return [("dropout_grad", ins, {"X@GRAD": [g]}, dict(op.attrs))], [(x, g)]


@simple_op("dropout", ["X"], ["Out", "Mask"], grad="custom",
           grad_maker=_dropout_grad_maker)
def _dropout(ctx, x, attrs):
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    is_test = attrs.get("is_test", False) or ctx.is_test
    # Mask is uint8 0/1 (reference dropout_op.h stores uint8 too): the mask
    # is saved activation-sized for the grad op, and a dozen [B,S,H] /
    # [B,heads,S,S] masks per step at 1 byte instead of 2-4 is real HBM;
    # the grad op reapplies the upscale factor from attrs.
    if is_test:
        if impl == "upscale_in_train":
            return x, jnp.ones(jnp.shape(x), jnp.uint8)
        return x * (1.0 - p), jnp.ones(jnp.shape(x), jnp.uint8)
    k = op_rng_key(ctx, attrs)
    keep = jax.random.bernoulli(k, 1.0 - p, jnp.shape(x))
    mask = keep.astype(x.dtype)
    if impl == "upscale_in_train":
        scale = 1.0 / max(1.0 - p, 1e-8)
        return x * mask * jnp.asarray(scale, x.dtype), keep.astype(jnp.uint8)
    return x * mask, keep.astype(jnp.uint8)


@simple_op("dropout_grad", ["Out@GRAD", "Mask"], ["X@GRAD"], grad=None)
def _dropout_grad(ctx, dy, mask, attrs):
    m = mask.astype(dy.dtype)
    if attrs.get("dropout_implementation",
                 "downgrade_in_infer") == "upscale_in_train":
        p = attrs.get("dropout_prob", 0.5)
        m = m * jnp.asarray(1.0 / max(1.0 - p, 1e-8), dy.dtype)
    return dy * m


_registry.get_op("dropout").grad_maker = _dropout_grad_maker


# ---------------------------------------------------------------------------
# misc nn
# ---------------------------------------------------------------------------


@simple_op("lrn", ["X"], ["Out", "MidOut"])
def _lrn(ctx, x, attrs):
    n = attrs.get("n", 5)
    k, alpha, beta = attrs.get("k", 2.0), attrs.get("alpha", 1e-4), attrs.get("beta", 0.75)
    sq = jnp.square(x)
    pad = n // 2
    sq_p = jnp.pad(sq, ((0, 0), (pad, pad), (0, 0), (0, 0)))
    acc = sum(sq_p[:, i:i + jnp.shape(x)[1]] for i in range(n))
    mid = k + alpha * acc
    return x / jnp.power(mid, beta), mid


@simple_op("softmax_mask_fuse_upper_triangle", ["X"], ["Out"])
def _causal_softmax(ctx, x, attrs):
    L = jnp.shape(x)[-1]
    mask = jnp.tril(jnp.ones((L, L), bool))
    return jax.nn.softmax(jnp.where(mask, x, jnp.asarray(-1e9, x.dtype)), axis=-1)


def _interp_out_hw(attrs, h, w, out_size):
    """out_h/out_w attrs, falling back to the `scale` attr (reference
    interpolate_op.cc InterpolateOpMaker: scale used when out_h <= 0).
    The reference's OutSize tensor input (a RUNTIME size override) cannot
    exist under XLA's static shapes — fail by name instead of silently
    producing the attr-sized output."""
    if out_size is not None:
        raise NotImplementedError(
            "interp ops: the OutSize tensor input is a runtime shape "
            "override the XLA lowering cannot honor — set static "
            "out_h/out_w (or scale) attrs instead")
    oh, ow = attrs.get("out_h"), attrs.get("out_w")
    scale = float(attrs.get("scale", 0.0) or 0.0)
    if (not oh or oh <= 0) and scale > 0:
        oh = int(h * scale)
    if (not ow or ow <= 0) and scale > 0:
        ow = int(w * scale)
    if not oh or not ow or oh <= 0 or ow <= 0:
        raise ValueError(
            "interp ops need a static output size: set out_h/out_w > 0 "
            f"or scale > 0 (got out_h={attrs.get('out_h')!r}, "
            f"out_w={attrs.get('out_w')!r}, scale={scale!r})")
    return int(oh), int(ow)


def _interp_src_coords(out_len, in_len, align_corners, align_mode):
    """Source coordinates per reference interpolate_op.h: align_corners →
    ratio (in-1)/(out-1), src = ratio·dst; else ratio in/out with
    align_mode 0 = half-pixel (max(ratio·(dst+½)−½, 0)), mode 1 =
    src = ratio·dst."""
    d = jnp.arange(out_len, dtype=jnp.float32)
    if align_corners:
        return d * ((in_len - 1) / max(out_len - 1, 1))
    ratio = in_len / out_len
    if int(align_mode) == 0:
        return jnp.maximum(ratio * (d + 0.5) - 0.5, 0.0)
    return ratio * d


@simple_op("bilinear_interp", ["X", "OutSize"], ["Out"], optional=("OutSize",),
           no_grad_inputs=("OutSize",))
def _bilinear_interp(ctx, x, out_size, attrs):
    """Reference interpolate_op.h BilinearInterpolation.  align_corners
    DEFAULTS TO TRUE in the reference op maker — jax.image.resize is
    always half-pixel, so the coordinates are computed explicitly (the
    resize spelling silently shifted every default-attrs upsample;
    caught by the torch-oracle sweep, r5)."""
    n, c, h, w = jnp.shape(x)
    oh, ow = _interp_out_hw(attrs, h, w, out_size)
    ac = bool(attrs.get("align_corners", True))
    am = attrs.get("align_mode", 1)
    sy = _interp_src_coords(oh, h, ac, am)
    sx = _interp_src_coords(ow, w, ac, am)
    y0 = jnp.clip(jnp.floor(sy).astype(jnp.int32), 0, h - 1)
    x0 = jnp.clip(jnp.floor(sx).astype(jnp.int32), 0, w - 1)
    y1 = jnp.minimum(y0 + 1, h - 1)
    x1 = jnp.minimum(x0 + 1, w - 1)
    wy = (sy - y0.astype(jnp.float32)).astype(x.dtype)  # [oh]
    wx = (sx - x0.astype(jnp.float32)).astype(x.dtype)  # [ow]
    rows0 = jnp.take(x, y0, axis=2)
    rows1 = jnp.take(x, y1, axis=2)
    top = rows0 * (1 - wy)[None, None, :, None] \
        + rows1 * wy[None, None, :, None]
    left = jnp.take(top, x0, axis=3)
    right = jnp.take(top, x1, axis=3)
    return left * (1 - wx)[None, None, None, :] + right * wx[None, None, None, :]


@simple_op("nearest_interp", ["X", "OutSize"], ["Out"], optional=("OutSize",),
           no_grad_inputs=("OutSize",))
def _nearest_interp(ctx, x, out_size, attrs):
    """Reference NearestNeighborInterpolate: align_corners (default true)
    rounds ratio·dst with ratio (in-1)/(out-1); else floor with in/out."""
    n, c, h, w = jnp.shape(x)
    oh, ow = _interp_out_hw(attrs, h, w, out_size)
    ac = bool(attrs.get("align_corners", True))
    if ac:
        # reference rounds HALF UP (static_cast<int>(ratio*k + 0.5)), not
        # banker's — jnp.round(0.5) would pick the wrong pixel
        iy = jnp.floor(_interp_src_coords(oh, h, True, 1) + 0.5)
        ix = jnp.floor(_interp_src_coords(ow, w, True, 1) + 0.5)
    else:
        iy = jnp.floor(_interp_src_coords(oh, h, False, 1))
        ix = jnp.floor(_interp_src_coords(ow, w, False, 1))
    iy = jnp.clip(iy.astype(jnp.int32), 0, h - 1)
    ix = jnp.clip(ix.astype(jnp.int32), 0, w - 1)
    return jnp.take(jnp.take(x, iy, axis=2), ix, axis=3)


@simple_op("temporal_shift", ["X"], ["Out"])
def _temporal_shift(ctx, x, attrs):
    seg, ratio = attrs.get("seg_num"), attrs.get("shift_ratio", 0.25)
    nt, c, h, w = jnp.shape(x)
    r = jnp.reshape(x, (-1, seg, c, h, w))
    fold = int(c * ratio)
    left = jnp.pad(r[:, 1:, :fold], ((0, 0), (0, 1), (0, 0), (0, 0), (0, 0)))
    right = jnp.pad(r[:, :-1, fold:2 * fold], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    rest = r[:, :, 2 * fold:]
    return jnp.reshape(jnp.concatenate([left, right, rest], axis=2), (nt, c, h, w))


@simple_op("flash_attention", ["Q", "K", "V", "Bias"], ["Out"],
           optional=("Bias",))
def _flash_attention(ctx, q, k, v, bias, attrs):
    """Blockwise attention without materializing S×S scores — Pallas kernel
    on TPU, XLA reference elsewhere (paddle_tpu/kernels/flash_attention.py).
    The reference framework has no attention op at all (SURVEY.md §5).

    attrs["sequence_parallel"]: when tracing under an active mesh with an
    'sp' axis, lower to ring attention — K/V chunks rotate over the sequence
    axis via ppermute (kernels/ring_attention.py) instead of being gathered.
    """
    from paddle_tpu.kernels import flash_attention as _fa
    from paddle_tpu.parallel import mesh as pmesh

    causal = attrs.get("causal", False)
    sm_scale = attrs.get("sm_scale")
    if attrs.get("sequence_parallel"):
        mesh = pmesh.current_mesh()
        if mesh is not None and pmesh.SEQ_AXIS in mesh.axis_names \
                and mesh.shape[pmesh.SEQ_AXIS] > 1:
            from paddle_tpu.kernels import ring_attention as _ra

            return _ra(q, k, v, bias=bias, causal=causal, sm_scale=sm_scale,
                       mesh=mesh)
    return _fa(q, k, v, bias=bias, causal=causal, sm_scale=sm_scale,
               force=attrs.get("force"))


@simple_op("ragged_attention", ["Q", "K", "V", "Lengths"], ["Out"],
           grad=None)
def _ragged_attention(ctx, q, k, v, lengths, attrs):
    """Variable-length attention driven by a per-sequence length vector
    (kernels/primitives/ragged.py): row b attends keys j < lengths[b],
    no padded position is ever scored.  The serving lane's ragged form
    (docs/SERVING.md "Ragged serving") — inference-only (grad=None),
    like every decode-lane op."""
    from paddle_tpu.kernels import primitives as _prims

    return _prims.ragged_attention(
        q, k, v, lengths, causal=attrs.get("causal", False),
        sm_scale=attrs.get("sm_scale"), force=attrs.get("force"))


@simple_op("moe_ffn", ["X", "GateW", "W1", "B1", "W2", "B2"], ["Out"],
           optional=("B1", "B2"))
def _moe_ffn(ctx, x, gate_w, w1, b1, w2, b2, attrs):
    """Mixture-of-experts FFN with SOFTMAX top-k gating: softmax over all
    experts' logits, the top_k probabilities kept and renormalised to sum
    to 1, biased two-matrix experts with GELU (or ReLU) — the
    GShard/Switch-style gate, trainable (auto grad).  No reference analog
    — the reference has no MoE; this is the expert-parallel building
    block, SURVEY.md §2.8 'Expert parallel'.  The sigmoid-scored,
    bias-selected gate over SwiGLU experts of which a process holds a
    share (inference only, grouped product instead of dense dispatch) is
    ``moe_ffn_held`` (ops/mla_ops.py).

    Dense-dispatch formulation: every expert runs over every token and the
    gate weights combine them.  That trades FLOPs for a perfectly static,
    GSPMD-friendly program — with the expert dim of W1/W2 sharded over the
    'ep' mesh axis each device computes only its experts, and the final
    combine contracts over experts (XLA inserts the psum over ep).  Capacity
    factors / token dropping, which exist to make sparse dispatch
    shape-static, are unnecessary by construction.

    x: [B, S, D]; gate_w: [D, E]; w1: [E, D, H]; b1: [E, H];
    w2: [E, H, D]; b2: [E, D].  attrs: top_k (default 2), act.
    """
    top_k = int(attrs.get("top_k", 2))
    e = w1.shape[0]
    logits = jnp.einsum("bsd,de->bse", x, gate_w,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if top_k < e:
        kth = jax.lax.top_k(probs, top_k)[0][..., -1:]
        probs = jnp.where(probs >= kth, probs, 0.0)
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    h = jnp.einsum("bsd,edh->ebsh", x, w1)
    if b1 is not None:
        h = h + b1[:, None, None, :]
    act = attrs.get("act", "gelu")
    h = jax.nn.gelu(h) if act == "gelu" else jax.nn.relu(h)
    y = jnp.einsum("ebsh,ehd->ebsd", h, w2)
    if b2 is not None:
        y = y + b2[:, None, None, :]
    out = jnp.einsum("ebsd,bse->bsd", y, probs.astype(y.dtype))
    return out.astype(x.dtype)
