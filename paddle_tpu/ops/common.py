"""Shared helpers for op lowerings."""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def np_dtype(name):
    if isinstance(name, (int, np.integer)):
        # programs written by actual Fluid (cast/fill ops loaded via
        # proto_compat) carry dtypes as VarType.Type enum integers
        from paddle_tpu.fluid.proto_compat import _DTYPE_BY_ENUM

        name = _DTYPE_BY_ENUM[int(name)]
    if name == "bfloat16":
        return jnp.bfloat16
    return np.dtype(name)


def _rng_impl():
    """PRNG implementation for random ops: XLA's RngBitGenerator ("rbg")
    on TPU, threefry elsewhere.

    Threefry generates bits with a long fused elementwise chain — cheap on
    CPU, but on TPU it burns VPU cycles that a dropout-heavy train step
    (tens of bernoulli draws over B*S*H activations) actually feels.  The
    rbg impl lowers to one rng_bit_generator HLO (hardware Philox path).
    Determinism still holds per (key, backend); the trade is only that
    rbg streams differ from threefry streams, so PT_RNG_IMPL=threefry
    pins cross-platform reproducibility when someone needs it.
    """
    import os

    forced = os.environ.get("PT_RNG_IMPL", "").strip().lower()
    if forced == "rbg":
        return "rbg"
    if forced in ("threefry", "threefry2x32"):
        return "threefry2x32"
    if forced:
        # someone pinning streams for reproducibility must not silently
        # get the platform default because of a typo
        raise ValueError(f"PT_RNG_IMPL={forced!r}: use 'rbg' or 'threefry'")
    from paddle_tpu.fluid.platform_utils import is_tpu

    return "rbg" if is_tpu() else "threefry2x32"


def op_rng_key(ctx, attrs):
    """Per-op, per-step PRNG key.

    The reference's random ops carry a `seed` attr (0 = nondeterministic,
    drawn from a global engine).  Here randomness is functional: key =
    fold(seed_or_op_identity, op_index, step) so (a) every random op in a
    program draws an independent stream, (b) streams advance each executor
    step, (c) runs are reproducible given program.random_seed.

    `rng_op_index` attr: a fusion pass that absorbs a random op
    (paddle_tpu/passes/fuse_bias_act.py swallowing a dropout) stamps the
    absorbed op's pre-fusion identity here so the fused program draws the
    SAME mask stream the unfused program would — the pass's cross-program
    parity contract.
    """
    seed = int(attrs.get("seed", 0) or 0)
    if not seed:
        prog = getattr(ctx, "program", None)
        seed = int(getattr(prog, "random_seed", 0) or 0) or 0x5EED
    base = jax.random.key(np.uint32(seed), impl=_rng_impl())
    idx = attrs.get("rng_op_index")
    if idx is None:
        idx = getattr(ctx, "op_index", 0)
    k = jax.random.fold_in(base, np.uint32(idx))
    k = jax.random.fold_in(k, ctx.step)
    # under shard_map, decorrelate streams across devices (each shard of a
    # data-parallel batch must get an independent dropout mask)
    for ax in getattr(ctx, "mesh_axes", ()):
        k = jax.random.fold_in(k, jax.lax.axis_index(ax))
    return k


def length_mask(length, t):
    """[B, T] bool mask of valid time positions from lengths [B]; None →
    None.  Single home for the dense-sequence masking convention (used by
    sequence/rnn/structured op families)."""
    if length is None:
        return None
    return jnp.arange(t)[None, :] < jnp.reshape(length, (-1, 1)).astype(jnp.int32)


_ACT_ENUM = {0: "identity", 1: "sigmoid", 2: "tanh", 3: "relu"}


def act_attr(val, default):
    """Normalize an activation attr that may be a string or the reference's
    int enum (gru_unit_op.cc ActType) to a canonical string name."""
    if val is None:
        return default
    if isinstance(val, str):
        return val
    return _ACT_ENUM.get(int(val), default)


def bcast_to(y, x, axis):
    """Reference elementwise broadcast semantics (elementwise_op_function.h):
    Y's dims align with X's starting at `axis`; axis=-1 means right-aligned
    (numpy rules)."""
    xr, yr = jnp.ndim(x), jnp.ndim(y)
    if axis is None or axis == -1 or yr == xr:
        return y
    # pad Y with trailing 1s so its dims sit at positions [axis, axis+yr)
    new_shape = list(jnp.shape(y)) + [1] * (xr - axis - yr)
    return jnp.reshape(y, [1] * axis + new_shape)


def flatten_to_2d(x, num_col_dims):
    """Reference `mul` op semantics: collapse leading num_col_dims dims into
    rows, the rest into cols."""
    shape = jnp.shape(x)
    rows = 1
    for s in shape[:num_col_dims]:
        rows *= s
    cols = 1
    for s in shape[num_col_dims:]:
        cols *= s
    return jnp.reshape(x, (rows, cols))


def _all_bf16(*operands):
    return all(o.dtype == jnp.bfloat16 for o in operands)


def mxu_dot(x, y):
    """MXU matmul with dtype-aware accumulation.

    bf16×bf16: a PLAIN bf16 dot.  The MXU accumulates in fp32 internally
    either way, but spelling it `dot(..., preferred_element_type=f32)
    .astype(bf16)` poisons the BACKWARD pass: the transpose of the final
    convert makes the cotangent fp32, so every grad dot runs as an
    fp32×fp32 contraction — 6 MXU passes instead of 1 (measured 1/6 of
    peak on v5e).  A plain bf16 dot keeps fwd AND bwd single-pass.

    fp32 (and other) inputs keep explicit fp32 accumulation."""
    if _all_bf16(x, y):
        return jnp.dot(x, y)
    return jnp.dot(x, y, preferred_element_type=jnp.float32).astype(x.dtype)


def mxu_matmul(x, y):
    """Batched-matmul variant of `mxu_dot` (same backward rationale)."""
    if _all_bf16(x, y):
        return jnp.matmul(x, y)
    return jnp.matmul(x, y,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def mxu_conv_kwargs(x, w):
    """kwargs for lax.conv_general_dilated under the same policy: bf16
    inputs run the native single-pass conv; everything else accumulates
    fp32 explicitly.  Call sites follow with `.astype(x.dtype)`, which is
    a trace-time no-op on the bf16 path (dtypes already match) so it
    cannot reintroduce the backward-pass convert."""
    if _all_bf16(x, w):
        return {}
    return {"preferred_element_type": jnp.float32}


def conv_nd_raw(x, w, strides, paddings, dilations, groups, nd=2, **kw):
    """Paddle-convention n-D conv, shared by the fp32/bf16 lowering
    (ops/nn_ops.py _conv_nd) and the int8 PTQ kernel (int8_conv2d):
    per-spatial-dim int paddings or flattened (before, after) pairs,
    NCHW/OIHW layouts.  Extra kwargs pass straight to
    lax.conv_general_dilated (preferred_element_type etc.) so precision
    policy stays at the call site while the geometry normalization —
    where padding bugs would silently diverge int8 from fp32 — lives in
    exactly one place."""
    pads = [(p, p) for p in paddings]
    if len(pads) == nd * 2:  # (before, after) per dim flattened
        pads = [(paddings[2 * i], paddings[2 * i + 1]) for i in range(nd)]
    dn = jax.lax.conv_dimension_numbers(
        jnp.shape(x), jnp.shape(w),
        ("NCHW", "OIHW", "NCHW") if nd == 2 else ("NCDHW", "OIDHW", "NCDHW"))
    return jax.lax.conv_general_dilated(
        x, w, window_strides=tuple(strides), padding=pads,
        rhs_dilation=tuple(dilations), dimension_numbers=dn,
        feature_group_count=groups, **kw)
