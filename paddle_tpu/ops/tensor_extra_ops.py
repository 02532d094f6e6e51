"""Long-tail tensor ops (reference operators/: multiplex_op.cc, rank/size,
is_empty_op.cc, unique_op.cc, shard_index_op.cc, space_to_depth_op.cc,
pad_constant_like_op.cc, *_batch_size_like, hash_op.cc, selected_rows utils,
py_func_op.cc, save/load ops).

Static-shape stance: ops whose reference output is data-dependently sized
(`unique`) return padded, input-sized tensors plus an explicit element count —
the XLA-compatible encoding of a ragged result (same trade as LoD → padding).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.fluid.registry import register_op, simple_op
from .common import np_dtype, op_rng_key


@simple_op("multiplex", ["X*", "Ids"], ["Out"], no_grad_inputs=("Ids",))
def _multiplex(ctx, xs, ids, attrs):
    # reference multiplex_op.cc: out[i] = X[ids[i]][i]
    stacked = jnp.stack(xs, axis=0)                       # [K, N, ...]
    idx = jnp.reshape(ids, (-1,)).astype(jnp.int32)       # [N]
    return stacked[idx, jnp.arange(stacked.shape[1])]


@simple_op("rank", ["Input"], ["Out"], grad=None)
def _rank(ctx, x, attrs):
    return jnp.asarray(jnp.ndim(x), dtype=jnp.int32)


@simple_op("size", ["Input"], ["Out"], grad=None)
def _size(ctx, x, attrs):
    return jnp.asarray(jnp.size(x), dtype=jnp.int64)


@simple_op("is_empty", ["X"], ["Out"], grad=None)
def _is_empty(ctx, x, attrs):
    return jnp.asarray(jnp.size(x) == 0)


@simple_op("unique", ["X"], ["Out", "Index"], grad=None)
def _unique(ctx, x, attrs):
    """Static-shape unique: Out is padded to len(X) (first-occurrence order
    is NOT preserved — ascending like jnp.unique); Index maps each x element
    to its position in Out (reference unique_op.cc semantics for Index)."""
    flat = jnp.reshape(x, (-1,))
    uniq, inv = jnp.unique(flat, return_inverse=True, size=flat.size,
                           fill_value=flat[0] if flat.size else 0)
    return uniq, inv.astype(jnp.int32)


@simple_op("unique_with_counts", ["X"], ["Out", "Index", "Count"], grad=None)
def _unique_with_counts(ctx, x, attrs):
    """unique_with_counts_op.h keeps FIRST-OCCURRENCE order (the doc
    example: [2,3,3,1,5,3] → [2,3,1,5]); jnp.unique sorts, so reorder by
    each unique's first index (r5 review).  Fixed capacity: padded with
    x[0] / zero counts (static-shape stance)."""
    flat = jnp.reshape(x, (-1,))
    n = flat.size
    uniq, first, inv, counts = jnp.unique(
        flat, return_index=True, return_inverse=True, return_counts=True,
        size=n, fill_value=flat[0] if n else 0)
    # padded entries carry first-index 0 in some jax versions — push them
    # last by keying on (is_pad, first_index)
    is_pad = counts == 0
    order = jnp.argsort(jnp.where(is_pad, n + 1, first))
    pos = jnp.argsort(order)  # old unique slot → new position
    return (uniq[order], pos[inv].astype(jnp.int32),
            counts[order].astype(jnp.int64))


@simple_op("shard_index", ["X"], ["Out"], grad=None)
def _shard_index(ctx, x, attrs):
    index_num = attrs["index_num"]
    nshards = attrs["nshards"]
    shard_id = attrs["shard_id"]
    ignore_value = attrs.get("ignore_value", -1)
    shard_size = (index_num + nshards - 1) // nshards
    in_shard = (x // shard_size) == shard_id
    return jnp.where(in_shard, x % shard_size,
                     jnp.full_like(x, ignore_value))


@simple_op("space_to_depth", ["X"], ["Out"])
def _space_to_depth(ctx, x, attrs):
    b = attrs.get("blocksize", 2)
    n, c, h, w = x.shape
    x = jnp.reshape(x, (n, c, h // b, b, w // b, b))
    x = jnp.transpose(x, (0, 3, 5, 1, 2, 4))
    return jnp.reshape(x, (n, c * b * b, h // b, w // b))


@simple_op("pad_constant_like", ["X", "Y"], ["Out"], no_grad_inputs=("X",))
def _pad_constant_like(ctx, x, y, attrs):
    pad_value = attrs.get("pad_value", 0.0)
    pads = [(0, xd - yd) for xd, yd in zip(x.shape, y.shape)]
    return jnp.pad(y, pads, constant_values=pad_value)


@simple_op("uniform_random_batch_size_like", ["Input"], ["Out"], grad=None)
def _uniform_random_batch_size_like(ctx, ref, attrs):
    shape = list(attrs["shape"])
    shape[attrs.get("output_dim_idx", 0)] = ref.shape[
        attrs.get("input_dim_idx", 0)]
    key = op_rng_key(ctx, attrs)
    return jax.random.uniform(
        key, tuple(shape), dtype=np_dtype(attrs.get("dtype", "float32")),
        minval=attrs.get("min", -1.0), maxval=attrs.get("max", 1.0))


@simple_op("gaussian_random_batch_size_like", ["Input"], ["Out"], grad=None)
def _gaussian_random_batch_size_like(ctx, ref, attrs):
    shape = list(attrs["shape"])
    shape[attrs.get("output_dim_idx", 0)] = ref.shape[
        attrs.get("input_dim_idx", 0)]
    key = op_rng_key(ctx, attrs)
    return (attrs.get("mean", 0.0) + attrs.get("std", 1.0) *
            jax.random.normal(key, tuple(shape),
                              dtype=np_dtype(attrs.get("dtype", "float32"))))


@simple_op("hash", ["X"], ["Out"], grad=None)
def _hash(ctx, x, attrs):
    """Deterministic integer hashing (reference hash_op.cc uses xxhash; we
    use a splitmix64-style mixer — same contract: stable hash of each input
    row per hash seed, modulo mod_by)."""
    num_hash = attrs.get("num_hash", 1)
    mod_by = attrs.get("mod_by", 1)
    flat = jnp.reshape(x, (x.shape[0], -1)).astype(jnp.uint32)

    def mix(h):  # murmur3 fmix32 (32-bit: x64 mode is off under jit)
        h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
        h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
        return h ^ (h >> 16)

    outs = []
    for i in range(num_hash):
        h = jnp.full((x.shape[0],), np.uint32((0x9E3779B9 * (i + 1)) & 0xFFFFFFFF),
                     dtype=jnp.uint32)
        for j in range(flat.shape[1]):
            h = mix(h ^ flat[:, j])
        outs.append((h % jnp.uint32(mod_by)).astype(jnp.int64))
    return jnp.stack(outs, axis=1)[:, :, None]


# SelectedRows are represented densely on TPU (sparse embedding grads are
# dense row-gathers under XLA); the conversion ops are identities.
@simple_op("get_tensor_from_selected_rows", ["X"], ["Out"])
def _get_tensor_from_selected_rows(ctx, x, attrs):
    return x


@simple_op("merge_selected_rows", ["X"], ["Out"])
def _merge_selected_rows(ctx, x, attrs):
    return x


# ---------------------------------------------------------------------------
# py_func (reference operators/py_func_op.cc): arbitrary python in the graph.
# TPU-native: jax.pure_callback — runs the python on host mid-computation
# with declared (static) output shapes, instead of the reference's direct
# C++->python call.  Forward-only: backward_func emits a py_func grad op.
# ---------------------------------------------------------------------------

_PY_FUNCS: list = []


def register_py_func(fn) -> int:
    _PY_FUNCS.append(fn)
    return len(_PY_FUNCS) - 1


def _py_func_lower(ctx, xs, attrs):
    fn = _PY_FUNCS[attrs["func_id"]]
    out_shapes = [tuple(s) for s in attrs["out_shapes"]]
    out_dtypes = attrs["out_dtypes"]
    result_shape = [
        jax.ShapeDtypeStruct(s, np.dtype(d))
        for s, d in zip(out_shapes, out_dtypes)
    ]

    def host_fn(*arrays):
        out = fn(*arrays)
        if not isinstance(out, (tuple, list)):
            out = (out,)
        return tuple(np.asarray(o, dtype=np.dtype(d)).reshape(s)
                     for o, s, d in zip(out, out_shapes, out_dtypes))

    out = jax.pure_callback(host_fn, result_shape, *xs)
    return list(out)  # "Out*" is variadic: always a list, even for one output


def _py_func_grad_lower(ctx, xs, dys, attrs):
    """Backward host callback: backward_func(*xs, *douts) -> dx per input.
    Grad shapes/dtypes equal the (trace-time concrete) input shapes, so no
    declared shapes are needed."""
    fn = _PY_FUNCS[attrs["func_id"]]
    result_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in xs]

    def host_fn(*arrays):
        n = len(result_shape)
        out = fn(*arrays[:n], *arrays[n:])
        if not isinstance(out, (tuple, list)):
            out = (out,)
        return tuple(
            np.zeros(s.shape, s.dtype) if o is None
            else np.asarray(o, dtype=s.dtype).reshape(s.shape)
            for o, s in zip(out, result_shape))

    return list(jax.pure_callback(host_fn, result_shape, *xs, *dys))


def _py_func_grad_maker(op, out_grads, wanted, uniq):
    """Emit a py_func_grad op when backward_func was supplied; otherwise the
    op is a stop-gradient boundary (reference py_func_op.cc behaves the
    same)."""
    if "backward_func_id" not in op.attrs:
        return [], []
    xs = op.inputs["X"]
    if not any(n in wanted for n in xs):
        return [], []
    pre = []
    gnames = []
    for n in op.outputs["Out"]:
        if n in out_grads:
            gnames.append(out_grads[n])
        else:  # output off the loss path still occupies its positional slot
            z = n + "@GRAD@ZERO"
            pre.append(("fill_zeros_like", {"X": [n]}, {"Out": [z]}, {}))
            gnames.append(z)
    out_names, pairs = [], []
    for n in xs:
        g = uniq(n)
        out_names.append(g)
        if n in wanted:
            pairs.append((n, g))
    attrs = {"func_id": op.attrs["backward_func_id"]}
    return pre + [("py_func_grad", {"X": list(xs), "DOut": gnames},
                   {"DX": out_names}, attrs)], pairs


register_op("py_func", ["X*"], ["Out*"], _py_func_lower, grad=None,
            grad_maker=_py_func_grad_maker)
register_op("py_func_grad", ["X*", "DOut*"], ["DX*"], _py_func_grad_lower,
            grad=None)


def _load_var_run(scope, op, place):
    """Host op (reference load_op): load a saved array into the scope var."""
    path = op.attrs["file_path"]
    name = op.outputs["Out"][0]
    if path.endswith(".npz"):
        with np.load(path, allow_pickle=False) as data:
            arr = data[name] if name in data else data[list(data.files)[0]]
    else:
        arr = np.load(path, allow_pickle=False)
    if op.attrs.get("load_as_fp16"):
        arr = arr.astype(np.float16)
    scope.set(name, arr)


def _no_lower(ctx, attrs):  # host-only op: never traced
    raise RuntimeError("load_var is a host op")


register_op("load_var", [], ["Out"], _no_lower, grad=None,
            host_run=_load_var_run)


@simple_op("random_crop", ["X"], ["Out"], grad=None)
def _random_crop(ctx, x, attrs):
    """Random crop of the trailing dims to attrs['shape'] (reference
    random_crop_op.cc).  Offsets drawn per call via the op rng; the leading
    (batch/channel) dims not covered by `shape` pass through."""
    shape = list(attrs["shape"])
    key = op_rng_key(ctx, attrs)
    nd = len(shape)
    lead = x.ndim - nd
    starts = []
    for i, target in enumerate(shape):
        extent = x.shape[lead + i]
        key, sub = jax.random.split(key)
        max_off = extent - target
        off = jax.random.randint(sub, (), 0, max_off + 1) if max_off > 0 else 0
        starts.append(off)
    start_full = [0] * lead + [jnp.asarray(s) for s in starts]
    sizes = list(x.shape[:lead]) + shape
    return jax.lax.dynamic_slice(x, [jnp.asarray(s, jnp.int32)
                                     for s in start_full], sizes)
