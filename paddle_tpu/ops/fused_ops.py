"""Fused ops — compositional lowerings for the reference's CPU-fusion family.

Reference analogs: paddle/fluid/operators/fused/ — fusion_lstm_op.cc,
fusion_gru_op.cc, fused_embedding_seq_pool_op.cc, fusion_seqpool_concat_op.cc,
fused_elemwise_activation_op.{cc,h}, fusion_squared_mat_sub_op.cc,
fusion_repeated_fc_relu_op.cc.  The reference hand-writes jitcode/intrinsic
kernels for these because its executor dispatches one kernel per op; under
XLA the *unfused* graph already fuses (elementwise into matmuls, gather into
reduce), so these lowerings exist for INTEROP — a reference-exported program
containing fused ops must load and run — and simply compose the same
primitive lowerings the fusion was built from.  Numerics therefore match the
unfused composition exactly.

Sequence layout note: the reference's fused sequence ops take LoD tensors
([total_T, ...] + offsets); this framework's dense analog is [B, T, ...]
plus an optional Length vector (see ops/sequence_ops.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from paddle_tpu.fluid.registry import simple_op

from .common import act_attr, bcast_to, mxu_dot
from .rnn_ops import _act, _gru, _lstm
from .sequence_ops import _seq_unfold, _sequence_pool
from .tensor_ops import _lookup_table


def _fc_project(x, w):
    """x: [B, T, M] @ w: [M, KD] on the MXU."""
    return mxu_dot(x, w)


@simple_op("fusion_lstm",
           ["X", "WeightX", "WeightH", "Bias", "H0", "C0", "Length"],
           ["Hidden", "Cell", "XX"],
           optional=("Bias", "H0", "C0", "Length"),
           no_grad_inputs=("Length",))
def _fusion_lstm(ctx, x, wx, wh, bias, h0, c0, length, attrs):
    """fc(X·WeightX + Bias[:4D]) then the lstm recurrence (fusion_lstm_op.cc
    SeqCompute: FCCompute + per-step GEMM_WH_ADDON + jit LSTMCtHt, gate order
    {c~, i, f, o} — jit/refer/refer.h:170).  Peephole weights ride in
    Bias[4D:7D] exactly like the unfused lstm op, so the shared `_lstm`
    lowering handles peepholes + is_reverse + length masking.  The gate bias
    is folded into XX here (FCCompute adds it, so XX is the *biased*
    projection in the reference) and zeroed before `_lstm` to avoid a
    double add."""
    xx = _fc_project(x, wx)
    if bias is not None:
        bias = jnp.reshape(bias, (-1,))
        d4 = jnp.shape(wh)[1]
        xx = xx + bias[None, None, :d4].astype(x.dtype)
        # keep only the peephole tail (if any) for _lstm
        bias = jnp.concatenate(
            [jnp.zeros((d4,), bias.dtype), bias[d4:]])
    hidden, cell = _lstm(ctx, xx, wh, bias, h0, c0, length, attrs)
    return hidden, cell, xx


@simple_op("fused_embedding_fc_lstm",
           ["Ids", "Embeddings", "WeightH", "Bias", "H0", "C0", "Length"],
           ["Hidden", "Cell", "XX"],
           optional=("H0", "C0", "Length"),
           no_grad_inputs=("Ids", "Length"))
def _fused_embedding_fc_lstm(ctx, ids, embeddings, wh, bias, h0, c0,
                             length, attrs):
    """lookup_table + fc + lstm (fused_embedding_fc_lstm_op.cc
    SeqCompute): the fuse pass pre-bakes emb@WeightX + fc bias into the
    Embeddings table ([vocab, 4D]), so XX is a plain row lookup; the
    kernel reads Bias only for the peephole tail (op.cc:260 wc_data =
    bias + D4), which the shared `_lstm` consumes with a zeroed gate
    bias."""
    xx = _lookup_table(ctx, embeddings, ids, {})  # [B, T, 4D]
    d4 = int(jnp.shape(wh)[1])
    bias = jnp.reshape(bias, (-1,))
    lstm_bias = jnp.concatenate(
        [jnp.zeros((d4,), bias.dtype), bias[d4:]])
    hidden, cell = _lstm(ctx, xx, wh, lstm_bias, h0, c0, length, attrs)
    return hidden, cell, xx


@simple_op("fusion_gru",
           ["X", "WeightX", "WeightH", "Bias", "H0", "Length"],
           ["Hidden", "XX"],
           optional=("Bias", "H0", "Length"),
           no_grad_inputs=("Length",))
def _fusion_gru(ctx, x, wx, wh, bias, h0, length, attrs):
    """fc(X·WeightX + Bias) then the gru recurrence (fusion_gru_op.cc
    SeqCompute: FCCompute + jit GRUH1/HtPart1/HtPart2 — gates {u, r, c~},
    h = u·c~ + (1-u)·h_prev, i.e. origin_mode=False in the unfused gru)."""
    xx = _fc_project(x, wx)
    if bias is not None:
        xx = xx + jnp.reshape(bias, (1, 1, -1)).astype(x.dtype)
    # this reference version's fusion_gru always computes the
    # origin_mode=False form (jit GRUHtPart2), but pass a present attr
    # through so newer exports with an explicit origin_mode stay correct
    gru_attrs = dict(attrs)
    gru_attrs.setdefault("origin_mode", False)
    hidden = _gru(ctx, xx, wh, None, h0, length, gru_attrs)
    return hidden, xx


@simple_op("fused_embedding_seq_pool", ["W", "Ids", "Length"], ["Out"],
           optional=("Length",), no_grad_inputs=("Ids", "Length"))
def _fused_embedding_seq_pool(ctx, w, ids, length, attrs):
    """lookup_table + sequence_pool(SUM) (fused_embedding_seq_pool_op.cc —
    combiner is ENFORCEd to "sum" at this version, op.cc:43).  Ids: [B, T]
    or [B, T, 1]; Out: [B, D] summed over valid timesteps."""
    combiner = attrs.get("combiner", "sum")
    if combiner != "sum":
        raise NotImplementedError(
            f"fused_embedding_seq_pool combiner={combiner!r}; the reference "
            "enforces 'sum' (fused_embedding_seq_pool_op.cc:43)")
    emb = _lookup_table(ctx, w, ids, attrs)  # [B, T, D]
    out, _ = _sequence_pool(ctx, emb, length, {"pooltype": "SUM"})
    return out


@simple_op("fusion_seqpool_concat", ["X*", "Length*"], ["Out"],
           optional=("Length",), no_grad_inputs=("Length",))
def _fusion_seqpool_concat(ctx, xs, lengths, attrs):
    """sequence_pool over each input then concat on axis 1
    (fusion_seqpool_concat_op.cc — pooltype ∈ {SUM, AVERAGE, SQRT})."""
    pooled = _pooled_columns(ctx, xs, lengths,
                             attrs.get("pooltype", "SUM"))
    return jnp.concatenate(pooled, axis=int(attrs.get("axis", 1)))


_UNARY_FUNCTORS = {
    "scale": lambda x, attrs: x * jnp.asarray(attrs.get("scale", 1.0), x.dtype),
    "relu": lambda x, attrs: jax.nn.relu(x),
    "tanh": lambda x, attrs: jnp.tanh(x),
    "sigmoid": lambda x, attrs: jax.nn.sigmoid(x),
}

_BINARY_FUNCTORS = {
    "elementwise_add": jnp.add,
    "elementwise_mul": jnp.multiply,
}


@simple_op("fused_elemwise_activation", ["X", "Y"], ["Out", "IntermediateOut"])
def _fused_elemwise_activation(ctx, x, y, attrs):
    """Compose two functors (fused_elemwise_activation_op.cc): with
    functor_list = [f1, f2] —
      f2 binary  → Out = f1(f2(X, Y)), IntermediateOut = f2(X, Y)
      f2 unary   → Out = f1(X, f2(Y)), IntermediateOut = f2(Y)
    (IsUnaryCompound, op.cc:22; Y broadcasts to X via `axis` like the
    standalone elementwise ops)."""
    functors = list(attrs.get("functor_list", ()))
    if len(functors) != 2:
        raise ValueError(f"functor_list must have 2 entries, got {functors}")
    f1, f2 = functors
    axis = attrs.get("axis", -1)
    if f2 in _BINARY_FUNCTORS:       # Unary(Binary(X, Y))
        if f1 not in _UNARY_FUNCTORS:
            raise NotImplementedError(f"functor pair {functors}")
        inter = _BINARY_FUNCTORS[f2](x, bcast_to(y, x, axis))
        return _UNARY_FUNCTORS[f1](inter, attrs), inter
    if f1 in _BINARY_FUNCTORS and f2 in _UNARY_FUNCTORS:  # Binary(X, Unary(Y))
        inter = _UNARY_FUNCTORS[f2](y, attrs)
        return _BINARY_FUNCTORS[f1](x, bcast_to(inter, x, axis)), inter
    raise NotImplementedError(f"functor pair {functors}")


@simple_op("fusion_squared_mat_sub", ["X", "Y"], ["SquaredX", "SquaredY",
                                                  "SquaredXY", "Out"])
def _fusion_squared_mat_sub(ctx, x, y, attrs):
    """Out = scalar * ((X·Y)² - X²·Y²) (fusion_squared_mat_sub_op.cc)."""
    s = jnp.asarray(attrs.get("scalar", 1.0), x.dtype)
    xy = mxu_dot(x, y)
    x2, y2 = x * x, y * y
    x2y2 = mxu_dot(x2, y2)
    return x2, y2, x2y2, s * (xy * xy - x2y2)


@simple_op("fusion_repeated_fc_relu", ["X", "W*", "Bias*"], ["ReluOut*", "Out"])
def _fusion_repeated_fc_relu(ctx, x, ws, biases, attrs):
    """Stack of fc+relu layers, last layer relu too
    (fusion_repeated_fc_relu_op.cc) — XLA fuses the bias+relu into each
    matmul epilogue on its own."""
    if len(ws) != len(biases):
        raise ValueError(
            f"fusion_repeated_fc_relu: {len(ws)} weights vs {len(biases)} "
            "biases (the reference enforces W.size == Bias.size)")
    relus = []
    h = x
    for w, b in zip(ws, biases):
        h = jax.nn.relu(
            mxu_dot(h, w) + jnp.reshape(b, (1, -1)).astype(x.dtype))
        relus.append(h)
    return tuple(relus[:-1]), relus[-1]


def _pooled_columns(ctx, xs, lengths, ptype, transform=None):
    """sequence_pool each input (padding the lengths list), applying an
    optional per-column transform — shared by the seqpool fusions."""
    lengths = list(lengths) if lengths else [None] * len(xs)
    lengths += [None] * (len(xs) - len(lengths))
    cols = []
    for x, ln in zip(xs, lengths):
        pooled = _sequence_pool(ctx, x, ln, {"pooltype": ptype})[0]
        cols.append(transform(pooled) if transform else pooled)
    return cols


@simple_op("fusion_seqpool_cvm_concat", ["X*", "CVM", "Length*"], ["Out"],
           optional=("Length",), no_grad_inputs=("CVM", "Length"))
def _fusion_seqpool_cvm_concat(ctx, xs, cvm, lengths, attrs):
    """sequence_pool each input, CVM-transform each pooled row, concat
    (fusion_seqpool_cvm_concat_op.cc — the CTR ingest fusion)."""
    from .detection_extra_ops import _cvm

    use_cvm = bool(attrs.get("use_cvm", True))
    cols = _pooled_columns(
        ctx, xs, lengths, attrs.get("pooltype", "SUM"),
        transform=lambda p: _cvm(ctx, p, cvm, {"use_cvm": use_cvm}))
    return jnp.concatenate(cols, axis=int(attrs.get("axis", 1)))


@simple_op("fusion_seqconv_eltadd_relu", ["X", "Filter", "Bias", "Length"],
           ["Out", "ColMat"], optional=("Length",),
           no_grad_inputs=("Length",))
def _fusion_seqconv_eltadd_relu(ctx, x, w, bias, length, attrs):
    """sequence_conv + bias + relu (fusion_seqconv_eltadd_relu_op.cc);
    ColMat is the REAL unfolded im2col intermediate (attrs pass straight
    to the shared unfold so the centered-window contextStart default
    cannot diverge from the unfused composition; XLA drops ColMat when
    nothing consumes it)."""
    col = _seq_unfold(x, length, attrs)
    out = jax.nn.relu(mxu_dot(col, w) + jnp.reshape(bias, (1, 1, -1)))
    return out, col


@simple_op("fusion_seqexpand_concat_fc", ["X*", "FCWeight", "FCBias"],
           ["Out", "FCOut"], optional=("FCBias",))
def _fusion_seqexpand_concat_fc(ctx, xs, w, bias, attrs):
    """X[0]: [B, T, D0] sequence; X[1:]: [B, Di] per-batch rows expanded
    over T; concat features, then fc + activation
    (fusion_seqexpand_concat_fc_op.cc)."""
    ref = xs[0]
    b, t = jnp.shape(ref)[0], jnp.shape(ref)[1]
    feats = [ref] + [jnp.broadcast_to(z[:, None, :],
                                      (b, t, jnp.shape(z)[-1]))
                     for z in xs[1:]]
    cat = jnp.concatenate(feats, axis=-1)
    out = mxu_dot(cat, w)
    if bias is not None:
        out = out + jnp.reshape(bias, (1, 1, -1))
    try:
        out = _act(act_attr(attrs.get("fc_activation") or None,
                            "identity"))(out)  # "" == identity
    except KeyError as e:
        raise NotImplementedError(f"fc_activation {e.args[0]!r}") from e
    return out, out


@simple_op("fusion_transpose_flatten_concat", ["X*"], ["Out"])
def _fusion_transpose_flatten_concat(ctx, xs, attrs):
    """transpose(trans_axis) → flatten from flatten_axis (2D) → concat on
    concat_axis (fusion_transpose_flatten_concat_op.cc)."""
    trans = [int(a) for a in attrs.get("trans_axis", [])]
    flat_axis = int(attrs.get("flatten_axis", 1))
    concat_axis = int(attrs.get("concat_axis", 1))
    outs = []
    for x in xs:
        t = jnp.transpose(x, trans) if trans else x
        lead = math.prod(jnp.shape(t)[:flat_axis]) if flat_axis else 1
        outs.append(jnp.reshape(t, (lead, -1)))
    return jnp.concatenate(outs, axis=concat_axis)


@simple_op("attention_lstm",
           ["X", "C0", "H0", "AttentionWeight", "AttentionBias",
            "AttentionScalar", "AttentionScalarBias", "LSTMWeight",
            "LSTMBias", "Length"],
           ["Hidden", "Cell", "AttentionedX", "AttentionFCOut", "LSTMX",
            "LSTMOUT"],
           optional=("H0", "AttentionBias", "AttentionScalar",
                     "AttentionScalarBias", "Length"),
           no_grad_inputs=("Length",), grad=None)
def _attention_lstm(ctx, x, c0, h0, aw, ab, ascalar, ascalar_bias, lw, lb,
                    length, attrs):
    """Attention LSTM (reference attention_lstm_op.cc:339-411): per step,
    score EVERY position of the row against the previous cell
    (relu(x·aw[:M] + c_prev·aw[M:]) → optional scalar stage → softmax over
    the valid positions), sum-pool the scored positions into lstm_x [M],
    then one LSTM step with the combined (D+M)x4D weight, gate order
    {forget, input, output, cand} and hidden rows FIRST in the weight.

    Dense layout: X is [B, T, M] + optional Length (the reference walks
    LoD rows); the scan runs the padded T with finished rows frozen."""
    b, t, m = jnp.shape(x)
    d4 = jnp.shape(lw)[1]
    d = d4 // 4
    act_gate = _act(attrs.get("gate_activation", "sigmoid"))
    act_cell = _act(attrs.get("cell_activation", "tanh"))
    act_cand = _act(attrs.get("candidate_activation", "tanh"))

    atted_x = mxu_dot(jnp.reshape(x, (b * t, m)), aw[:m])  # [B*T, 1]
    if ab is not None:
        atted_x = atted_x + jnp.reshape(ab, ())
    atted_x = jnp.reshape(atted_x, (b, t))

    if length is None:
        valid = jnp.ones((b, t), bool)
        ln = jnp.full((b,), t, jnp.int32)
    else:
        ln = jnp.reshape(length, (-1,)).astype(jnp.int32)
        valid = jnp.arange(t)[None, :] < ln[:, None]

    h_init = (jnp.zeros((b, d), x.dtype) if h0 is None
              else h0.astype(x.dtype))

    def step(carry, i):
        c_prev, h_prev = carry
        cell_bias = mxu_dot(c_prev, aw[m:])            # [B, 1]
        fc = jax.nn.relu(atted_x + cell_bias)          # [B, T]
        if ascalar is not None:
            fc = fc * jnp.reshape(ascalar, ())
            sb = (jnp.reshape(ascalar_bias, ())
                  if ascalar_bias is not None else 0.0)
            fc = jax.nn.relu(fc + sb)
        fc = jnp.where(valid, fc, -jnp.inf)
        probs = jax.nn.softmax(fc.astype(jnp.float32), axis=1).astype(
            x.dtype)
        lstm_x = jnp.einsum("bt,btm->bm", probs, x)    # sum pool
        gates = (mxu_dot(lstm_x, lw[d:]) + mxu_dot(h_prev, lw[:d])
                 + jnp.reshape(lb, (-1,)))
        f_g = act_gate(gates[:, :d])
        i_g = act_gate(gates[:, d:2 * d])
        o_g = act_gate(gates[:, 2 * d:3 * d])
        cand = act_cand(gates[:, 3 * d:])
        c_new = f_g * c_prev + i_g * cand
        h_new = act_cell(c_new) * o_g
        on = (i < ln)[:, None]                         # freeze finished rows
        c_next = jnp.where(on, c_new, c_prev)
        h_next = jnp.where(on, h_new, h_prev)
        out_h = jnp.where(on, h_new, jnp.zeros_like(h_new))
        out_c = jnp.where(on, c_new, jnp.zeros_like(c_new))
        return (c_next, h_next), (out_h, out_c, lstm_x, gates)

    (_, _), (hs, cs, lx, lo) = jax.lax.scan(
        step, (c0.astype(x.dtype), h_init), jnp.arange(t))
    hidden = jnp.moveaxis(hs, 0, 1)                    # [B, T, D]
    cell = jnp.moveaxis(cs, 0, 1)
    return (hidden, cell, atted_x[..., None], jnp.zeros((t, 1), x.dtype),
            lx[-1], lo[-1])


@simple_op("conv2d_fusion", ["Input", "Filter", "Bias", "ResidualData"],
           ["Output", "Outputs*"], optional=("Bias", "ResidualData"))
def _conv2d_fusion(ctx, x, w, bias, residual, attrs):
    """y = act(conv(x) + residual + bias) with optional channel split
    (reference conv_fusion_op.cc; the CUDNN fused path's math, composed —
    XLA fuses the epilogue into the conv anyway)."""
    from .nn_ops import _conv2d

    out = _conv2d(ctx, x, w, bias, attrs)
    if residual is not None:
        out = out + residual
    out = _act(act_attr(attrs.get("activation", "relu"), "relu"))(out)
    split = [int(s) for s in attrs.get("split_channels", [])]
    if split:
        parts, start = [], 0
        for s in split:
            parts.append(out[:, start:start + s])
            start += s
        return out, tuple(parts)
    return out, ()


@simple_op("conv2d_inception_fusion",
           ["Input", "Filter*", "Bias*"], ["Output", "TempOutput*"],
           grad=None)
def _fusion_conv_inception(ctx, x, filters, biases, attrs):
    """GoogLeNet tower fusion (fused/fusion_conv_inception_op.{cc,cu},
    registered as conv2d_inception_fusion): with 4
    filters f0..f3 —
      branch A: 3x3 pool(x) (stride 1, pad 1, attr pooling_type) → 1x1
        conv f0 → oc0 channels;
      conv1: 1x1 f1 on x → first oc1 = f1_out - 2·f2_in channels go to the
        output, the remaining 2·f2_in feed conv2;
      conv2: 3x3 f2, groups=2, pad 1 → first oc2 = f2_out - f3_in channels
        to the output, last f3_in feed conv3;
      conv3: 3x3 f3, pad 1 → oc3 channels.
    Every conv applies bias + activation (the CUDNN fused epilogue);
    Output = channel-concat[A, conv1, conv2, conv3]."""
    from .nn_ops import _conv2d

    act = _act(act_attr(attrs.get("activation", "relu"), "relu"))
    pool_type = attrs.get("pooling_type", "max")
    exclusive = attrs.get("exclusive", True)
    f0, f1, f2, f3 = filters
    b0, b1, b2, b3 = biases
    pads = [(1, 1), (1, 1)]
    if pool_type == "max":
        pooled = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 1, 1),
            [(0, 0), (0, 0)] + pads)
    else:
        s = jax.lax.reduce_window(
            x, 0.0, jax.lax.add, (1, 1, 3, 3), (1, 1, 1, 1),
            [(0, 0), (0, 0)] + pads)
        if exclusive:
            cnt = jax.lax.reduce_window(
                jnp.ones_like(x), 0.0, jax.lax.add, (1, 1, 3, 3),
                (1, 1, 1, 1), [(0, 0), (0, 0)] + pads)
            pooled = s / cnt
        else:
            pooled = s / 9.0

    def conv(inp, w, b, pad, groups=1):
        a = {"strides": [1, 1], "paddings": [pad, pad],
             "dilations": [1, 1], "groups": groups}
        return act(_conv2d(ctx, inp, w, b, a))

    f2_in = jnp.shape(f2)[1]  # per-group input channels (groups=2)
    f3_in = jnp.shape(f3)[1]
    branch_a = conv(pooled, f0, b0, 0)
    c1 = conv(x, f1, b1, 0)
    oc1 = jnp.shape(f1)[0] - 2 * f2_in
    c1_out, c1_tail = c1[:, :oc1], c1[:, oc1:]
    c2 = conv(c1_tail, f2, b2, 1, groups=2)
    oc2 = jnp.shape(f2)[0] - f3_in
    c2_out, c2_tail = c2[:, :oc2], c2[:, oc2:]
    c3 = conv(c2_tail, f3, b3, 1)
    out = jnp.concatenate([branch_a, c1_out, c2_out, c3], axis=1)
    return out, (jnp.zeros_like(pooled),)


# ---------------------------------------------------------------------------
# fused bias + GeLU + dropout (TPU-native, no reference analog): the
# graph-optimization pass layer (paddle_tpu/passes/fuse_bias_act.py)
# rewrites the FFN `elementwise_add -> gelu -> [dropout]` chain to this
# one op, a single XLA fusion (kernels/fused_bias_act.py).  The dropout
# mask is SAVED (Mask output,
# uint8, the standalone dropout op's convention) so forward and backward
# agree exactly; `rng_op_index` pins the mask stream to the absorbed
# dropout op's pre-fusion identity, which is what makes the fused
# program's masks match the unfused program's (the pass's parity gate).
# ---------------------------------------------------------------------------


def _fused_bias_act_grad_maker(op, out_grads, wanted, uniq):
    outs = {}
    pairs = []
    for slot in ("X", "Bias"):
        n = op.inputs.get(slot, [None])[0]
        if n is None or n not in wanted:
            continue
        g = uniq(n)
        outs[slot + "@GRAD"] = [g]
        pairs.append((n, g))
    if not outs:
        return [], []
    ins = {"X": list(op.inputs["X"]), "Bias": list(op.inputs["Bias"]),
           "Out@GRAD": [out_grads[op.outputs["Out"][0]]]}
    if op.outputs.get("Mask"):
        ins["Mask"] = list(op.outputs["Mask"])
    return [("fused_bias_act_dropout_grad", ins, outs, dict(op.attrs))], pairs


@simple_op("fused_bias_act_dropout", ["X", "Bias"], ["Out", "Mask"],
           grad="custom", grad_maker=_fused_bias_act_grad_maker)
def _fused_bias_act_dropout(ctx, x, bias, attrs):
    from paddle_tpu.kernels import fused_bias_act as fba

    from .common import op_rng_key

    act = attrs.get("act", "gelu")
    if act != "gelu":
        raise NotImplementedError(
            f"fused_bias_act_dropout supports act='gelu', got {act!r}")
    p = float(attrs.get("dropout_prob", 0.0) or 0.0)
    impl_ = attrs.get("dropout_implementation", "upscale_in_train")
    if p > 0.0 and impl_ != "upscale_in_train":
        # the pass only ever emits upscale semantics; a hand-built
        # downgrade desc must fail loudly — the mask-replay backward
        # bakes the upscale factor in
        raise NotImplementedError(
            "fused_bias_act_dropout supports "
            f"dropout_implementation='upscale_in_train', got {impl_!r}")
    is_test = bool(attrs.get("is_test", False) or ctx.is_test)
    key = None
    if p > 0.0 and not is_test:
        key = op_rng_key(ctx, attrs)
    out, mask = fba.fused_bias_gelu_dropout(
        x, bias, dropout_prob=p, is_test=is_test,
        approximate=attrs.get("approximate", False), rng_key=key)
    return out, mask


@simple_op("fused_bias_act_dropout_grad",
           ["X", "Bias", "Mask", "Out@GRAD"], ["X@GRAD", "Bias@GRAD"],
           grad=None, optional=("Mask",))
def _fused_bias_act_dropout_grad(ctx, x, bias, mask, dy, attrs):
    from paddle_tpu.kernels import fused_bias_act as fba

    return fba.fused_bias_gelu_dropout_grad(
        x, bias, mask, dy,
        dropout_prob=float(attrs.get("dropout_prob", 0.0) or 0.0),
        is_test=bool(attrs.get("is_test", False) or ctx.is_test),
        approximate=attrs.get("approximate", False))
