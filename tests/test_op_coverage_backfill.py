"""Numeric backfill for registry ops no other test exercised (r4 verdict
item 4).  Each test pins an op against an INDEPENDENT numpy rendering of
the reference kernel's documented semantics (file cited per test), run
through the real executor/shard_map path — the same per-op discipline as
the reference's ~300 test_*_op.py files (op_test.py:134 check_output).

tests/test_op_coverage.py enumerates the registry and fails if an op is
in neither the test corpus nor the documented waiver list; this file
exists to keep that waiver list short."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import registry
from paddle_tpu.fluid.executor import Scope, scope_guard, trace_block
from paddle_tpu.parallel import mesh as pmesh


def _run_one_op(op_type, inputs, outputs, attrs=None, scope_vars=None):
    """Build a one-op program (feeds → op → fetches) and run it."""
    main = fluid.Program()
    with fluid.program_guard(main):
        block = main.global_block()
        feed = {}
        ins = {}
        for slot, pairs in inputs.items():
            names = []
            for name, arr in pairs:
                arr = np.asarray(arr)
                if not block.has_var(name):
                    block.create_var(name=name, shape=arr.shape,
                                     dtype=str(arr.dtype), is_data=True)
                feed[name] = arr
                names.append(name)
            ins[slot] = names
        outs = {}
        for slot, names in outputs.items():
            for n in names:
                block.create_var(name=n, shape=None, dtype="float32")
            outs[slot] = list(names)
        block.append_op(op_type, inputs=ins, outputs=outs,
                        attrs=dict(attrs or {}))
    fetch = [n for ns in outputs.values() for n in ns]
    scope = Scope()
    with scope_guard(scope):
        for k, v in (scope_vars or {}).items():
            scope.set(k, np.asarray(v))
        exe = fluid.Executor(fluid.CPUPlace())
        vals = exe.run(main, feed=feed, fetch_list=fetch)
    return dict(zip(fetch, [np.asarray(v) for v in vals]))


# ---------------------------------------------------------------------------
# collective tail (collective_ops.py) on the 8-device mesh via shard_map —
# the same numeric pattern test_data_parallel uses for c_allreduce_sum
# ---------------------------------------------------------------------------

def test_collective_tail_numerics():
    """c_allreduce_avg/min, (c_)broadcast, allreduce, c_concat, c_split,
    c_scatter, c_identity, alltoall, partial_allgather: exact numpy
    references (reference collective/*.cc semantics)."""
    main = fluid.Program()
    with fluid.program_guard(main):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        block = main.global_block()
        for t in ("c_allreduce_avg", "c_allreduce_min", "allreduce",
                  "c_broadcast", "broadcast", "c_concat", "c_split",
                  "c_scatter", "c_identity", "alltoall",
                  "partial_allgather"):
            out = block.create_var(name=t + "_out", dtype="float32")
            block.append_op(t, inputs={"X": ["x"]}, outputs={"Out": [out.name]},
                            attrs={"ring_id": 0, "nranks": 8, "root": 2})

    mesh = pmesh.build_mesh({"dp": 8})
    data = np.arange(256, dtype="float32").reshape(64, 4)
    shards = data.reshape(8, 8, 4)  # [dev, rows, 4]

    names = [op.type + "_out" for op in main.global_block().ops
             if op.type != "feed"]

    def body(xs):
        env = {"x": xs}
        ctx = registry.LowerContext(mesh_axes=("dp",),
                                    block=main.global_block())
        trace_block(main.global_block(), env, ctx)
        return tuple(env[n] for n in names)

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                              out_specs=tuple(P("dp") for _ in names),
                              check_vma=False))
    got = dict(zip(names, [np.asarray(v) for v in f(data)]))

    tile = lambda a: np.tile(a, (8, 1))
    np.testing.assert_allclose(got["c_allreduce_avg_out"],
                               tile(shards.mean(0)))
    np.testing.assert_allclose(got["c_allreduce_min_out"],
                               tile(shards.min(0)))
    np.testing.assert_allclose(got["allreduce_out"], tile(shards.sum(0)))
    # broadcast root=2: every device sees device 2's shard
    np.testing.assert_allclose(got["c_broadcast_out"], tile(shards[2]))
    np.testing.assert_allclose(got["broadcast_out"], tile(shards[2]))
    # c_concat: all shards concatenated on the LAST axis
    np.testing.assert_allclose(
        got["c_concat_out"],
        np.tile(np.concatenate(list(shards), axis=-1), (8, 1)))
    # c_split: device i keeps column block i of its shard (4 cols / 8
    # devices is not splittable; width-4 over nranks 8 would be 0 — use
    # the gathered layout check instead: each device's out has width 4//8
    # → covered below by explicit small case)
    np.testing.assert_allclose(got["c_identity_out"], data)
    # partial_allgather == c_allgather layout
    np.testing.assert_allclose(got["partial_allgather_out"],
                               tile(data.reshape(-1, 4)[:64]).reshape(
                                   8 * 64, 4)[:512])
    # c_scatter root-agnostic row split: device i takes row block i
    np.testing.assert_allclose(
        got["c_scatter_out"],
        np.concatenate([shards[i][i * 1:(i + 1) * 1] for i in range(8)]))
    # alltoall: device i's rows are the i-th row-chunks of every device
    xs8 = shards.reshape(8, 8, 1, 4)
    expect = np.concatenate(
        [np.concatenate([xs8[j, i] for j in range(8)]) for i in range(8)])
    np.testing.assert_allclose(got["alltoall_out"], expect)


def test_c_split_column_shard_per_rank():
    """c_split_op.cc: device i keeps column block i of its input."""
    main = fluid.Program()
    with fluid.program_guard(main):
        fluid.layers.data(name="x", shape=[16], dtype="float32")
        block = main.global_block()
        out = block.create_var(name="c_split_out", dtype="float32")
        block.append_op("c_split", inputs={"X": ["x"]},
                        outputs={"Out": [out.name]},
                        attrs={"ring_id": 0, "nranks": 8})
    block = main.global_block()
    mesh = pmesh.build_mesh({"dp": 8})
    xv = np.random.RandomState(0).randn(8, 16).astype("float32")

    def body(xs):
        env = {"x": xs}
        ctx = registry.LowerContext(mesh_axes=("dp",), block=block)
        trace_block(block, env, ctx,
                    ops=[op for op in block.ops if op.type == "c_split"])
        return env["c_split_out"]

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                              out_specs=P("dp"), check_vma=False))
    split = np.asarray(f(xv))
    # device i keeps columns [i*2, i*2+2) of ITS row (16 cols / 8 ranks)
    expect = np.stack([xv[i, i * 2:(i + 1) * 2] for i in range(8)])
    np.testing.assert_allclose(split, expect)


def test_c_embedding_shard_contract():
    """c_embedding_op.cc per-shard contract (single shard, no mesh):
    rows in [start_index, start_index + rows(W)) look up locally, ids
    outside contribute zeros (the cross-shard psum — covered by the
    allreduce tests — then sums the shards)."""
    wv = np.random.RandomState(1).randn(4, 3).astype("float32")
    ids = np.array([[2, 5, 7, 3]], "int64")  # shard covers vocab [4, 8)
    got = _run_one_op(
        "c_embedding", {"W": [("w", wv)], "Ids": [("ids", ids)]},
        {"Out": ["o"]}, {"start_index": 4})
    expect = np.zeros((1, 4, 3), "float32")
    expect[0, 1] = wv[1]  # id 5 → local row 1
    expect[0, 2] = wv[3]  # id 7 → local row 3
    np.testing.assert_allclose(got["o"], expect, rtol=1e-6)


# ---------------------------------------------------------------------------
# stream-sync / comm-bootstrap contract no-ops (collective_ops.py tail)
# ---------------------------------------------------------------------------

def test_stream_sync_ops_are_identity_and_comm_init_noops():
    """XLA dataflow subsumes stream sync (c_sync_calc_stream_op.cc etc.):
    the ops must be exact identities; comm bootstrap ops (c_comm_init*,
    *gen_nccl_id) execute as no-ops without disturbing the program."""
    x = np.arange(6, dtype="float32").reshape(2, 3)
    main = fluid.Program()
    with fluid.program_guard(main):
        block = main.global_block()
        block.create_var(name="x", shape=x.shape, dtype="float32",
                         is_data=True)
        prev = "x"
        chain = ("c_sync_calc_stream", "c_wait_compute", "c_wait_comm",
                 "rnn_memory_helper")
        for i, t in enumerate(chain):
            nxt = f"id_{i}"
            block.create_var(name=nxt, dtype="float32")
            block.append_op(t, inputs={"X": [prev]}, outputs={"Out": [nxt]},
                            attrs={})
            prev = nxt
        block.create_var(name="sync_multi", dtype="float32")
        block.append_op("c_sync_comm_stream", inputs={"X": [prev]},
                        outputs={"Out": ["sync_multi"]}, attrs={})
        for t in ("c_comm_init", "c_comm_init_all", "c_gen_nccl_id",
                  "gen_nccl_id"):
            block.append_op(t, inputs={}, outputs={}, attrs={})
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        (out,) = exe.run(main, feed={"x": x}, fetch_list=["sync_multi"])
    np.testing.assert_array_equal(np.asarray(out), x)


# ---------------------------------------------------------------------------
# optimizer tail (optimizer_ops.py / interop_tail_ops.py)
# ---------------------------------------------------------------------------

def test_adamw_step_matches_numpy():
    """adamw_op semantics: adam update then decoupled weight decay
    p -= lr * coeff * p (reference adamw: Loshchilov-Hutter)."""
    rng = np.random.RandomState(0)
    p = rng.randn(4, 3).astype("float32")
    g = rng.randn(4, 3).astype("float32")
    m1 = rng.rand(4, 3).astype("float32")
    m2 = rng.rand(4, 3).astype("float32")
    b1, b2, eps, lr, coeff = 0.9, 0.999, 1e-8, 0.01, 0.05
    b1p, b2p = np.array([b1], "float32"), np.array([b2], "float32")
    got = _run_one_op(
        "adamw",
        {"Param": [("p", p)], "Grad": [("g", g)], "Moment1": [("m1", m1)],
         "Moment2": [("m2", m2)],
         "LearningRate": [("lr", np.array([lr], "float32"))],
         "Beta1Pow": [("b1p", b1p)], "Beta2Pow": [("b2p", b2p)]},
        {"ParamOut": ["p_out"], "Moment1Out": ["m1_out"],
         "Moment2Out": ["m2_out"], "Beta1PowOut": ["b1p_out"],
         "Beta2PowOut": ["b2p_out"]},
        {"beta1": b1, "beta2": b2, "epsilon": eps, "coeff": coeff})
    m1n = b1 * m1 + (1 - b1) * g
    m2n = b2 * m2 + (1 - b2) * g * g
    # reference adam_op.h: Beta1Pow INPUT is already beta1^t for this step
    lr_t = lr * np.sqrt(1 - b2p[0]) / (1 - b1p[0])
    pn = p - lr_t * m1n / (np.sqrt(m2n) + eps) - lr * coeff * p
    np.testing.assert_allclose(got["m1_out"], m1n, rtol=1e-5)
    np.testing.assert_allclose(got["m2_out"], m2n, rtol=1e-5)
    np.testing.assert_allclose(got["b1p_out"], b1p * b1, rtol=1e-6)
    np.testing.assert_allclose(got["p_out"], pn, rtol=1e-4, atol=1e-5)


def test_proximal_adagrad_matches_numpy():
    """optimizers/proximal_adagrad_op.cc: m += g²;
    prox = p - lr·g/√m; p = sign(prox)·max(0,|prox|-lr·l1)/(1+lr·l2)."""
    rng = np.random.RandomState(1)
    p = rng.randn(5).astype("float32")
    m = rng.rand(5).astype("float32")
    g = rng.randn(5).astype("float32")
    lr, l1, l2 = 0.1, 0.05, 0.02
    got = _run_one_op(
        "proximal_adagrad",
        {"Param": [("p", p)], "Moment": [("m", m)], "Grad": [("g", g)],
         "LearningRate": [("lr", np.array([lr], "float32"))]},
        {"ParamOut": ["p_out"], "MomentOut": ["m_out"]},
        {"l1": l1, "l2": l2})
    mn = m + g * g
    prox = p - lr * g / np.sqrt(mn)
    pn = np.sign(prox) * np.maximum(0.0, np.abs(prox) - lr * l1) / (
        1.0 + lr * l2)
    np.testing.assert_allclose(got["m_out"], mn, rtol=1e-5)
    np.testing.assert_allclose(got["p_out"], pn, rtol=1e-4, atol=1e-5)


def test_dpsgd_zero_sigma_is_clipped_sgd():
    """dpsgd_op.cc with sigma=0: deterministic SGD on the l2-clipped
    gradient (clip C: g *= min(1, C/||g||))."""
    p = np.array([1.0, -2.0, 3.0], "float32")
    g = np.array([3.0, 4.0, 0.0], "float32")  # ||g|| = 5
    got = _run_one_op(
        "dpsgd",
        {"Param": [("p", p)], "Grad": [("g", g)],
         "LearningRate": [("lr", np.array([0.5], "float32"))]},
        {"ParamOut": ["p_out"]},
        {"clip": 2.5, "sigma": 0.0})
    np.testing.assert_allclose(got["p_out"], p - 0.5 * (g * 0.5), rtol=1e-6)


# ---------------------------------------------------------------------------
# misc numeric tail
# ---------------------------------------------------------------------------

def test_dgc_clip_by_norm_rampup_gate():
    """dgc_clip_by_norm_op.cc: clip_by_norm, but a pass-through before
    rampup_begin_step."""
    x = np.array([3.0, 4.0], "float32")  # norm 5
    for step, expect in ((0.0, x), (10.0, x * (2.0 / 5.0))):
        got = _run_one_op(
            "dgc_clip_by_norm",
            {"X": [("x", x)],
             "current_step": [("st", np.array([step], "float32"))]},
            {"Out": ["o"]},
            {"max_norm": 2.0, "rampup_begin_step": 5.0})
        np.testing.assert_allclose(got["o"], expect, rtol=1e-6)


def test_requantize_matches_formula():
    """mkldnn requantize_op.cc: int8 → int8 at a new scale:
    round(x · s_out/s_in), saturated."""
    x = np.array([-100, -3, 0, 7, 100], "int8")
    got = _run_one_op("requantize", {"Input": [("x", x)]},
                      {"Output": ["o"]},
                      {"Scale_in": 1.0, "Scale_out": 2.0})
    np.testing.assert_array_equal(
        got["o"], np.clip(np.round(x.astype("float32") * 2.0),
                          -128, 127).astype("int8"))


def test_where_index_matches_numpy():
    """Valid rows in argwhere order, then -1 sentinel rows (the
    fixed-capacity static-shape encoding; found the original dynamic
    jnp.nonzero lowering could not trace under jit at all)."""
    c = np.array([[True, False], [False, True]])
    got = _run_one_op("where_index", {"Condition": [("c", c)]},
                      {"Out": ["o"]}, {})
    np.testing.assert_array_equal(got["o"][:2], np.argwhere(c))
    np.testing.assert_array_equal(got["o"][2:], -np.ones((2, 2), "int64"))


def test_sequence_pad_dense_contract():
    """sequence_pad in the padded-dense representation: identity payload +
    per-row length output (full T without Length input)."""
    x = np.arange(12, dtype="float32").reshape(2, 3, 2)
    got = _run_one_op(
        "sequence_pad",
        {"X": [("x", x)], "PadValue": [("pv", np.zeros((1,), "float32"))]},
        {"Out": ["o"], "OutLength": ["ol"]}, {})
    np.testing.assert_array_equal(got["o"], x)
    np.testing.assert_array_equal(got["ol"], [3, 3])


def test_positive_negative_pair_hand_counted():
    """positive_negative_pair_op.cc: over same-query pairs with different
    labels, count concordant / discordant / tied score orderings.
    Reference is an independent O(n²) python loop."""
    score = np.array([[0.9], [0.5], [0.7], [0.2]], "float32")
    label = np.array([[1.0], [0.0], [0.0], [1.0]], "float32")
    qid = np.array([[7], [7], [7], [7]], "int64")
    pos = neg = neu = 0
    n = 4
    for i in range(n):
        for j in range(i + 1, n):
            if label[i, 0] == label[j, 0]:
                continue
            ds = score[i, 0] - score[j, 0]
            dl = label[i, 0] - label[j, 0]
            if ds * dl > 0:
                pos += 1
            elif ds * dl < 0:
                neg += 1
            else:
                neu += 1
    got = _run_one_op(
        "positive_negative_pair",
        {"Score": [("s", score)], "Label": [("l", label)],
         "QueryID": [("q", qid)]},
        {"PositivePair": ["pp"], "NegativePair": ["np_"],
         "NeutralPair": ["up"]}, {"column": -1})
    assert float(got["pp"]) == pos
    assert float(got["np_"]) == neg
    assert float(got["up"]) == neu


def test_similarity_focus_tiny_hand_case():
    """similarity_focus_op.cc documented effect: {0,1} mask marking, per
    selected channel, the positions holding that slice's maxima; mask
    broadcast over the axis.  Tiny case derivable by hand."""
    x = np.zeros((1, 2, 2, 2), "float32")
    x[0, 0] = [[5.0, 1.0], [0.0, 2.0]]  # max of channel 0 at (0,0)
    x[0, 1] = [[1.0, 1.0], [1.0, 9.0]]  # ignored (indexes=[0])
    got = _run_one_op("similarity_focus", {"X": [("x", x)]},
                      {"Out": ["o"]}, {"axis": 1, "indexes": [0]})
    expect = np.zeros((1, 2, 2, 2), "float32")
    expect[0, :, 0, 0] = 1.0
    np.testing.assert_array_equal(got["o"], expect)


def test_anchor_generator_square_anchor_centers():
    """anchor_generator_op.cc with one size and aspect ratio 1: anchor at
    cell (y,x) is the stride-centered square of side `size`; variances
    tile the attr."""
    h = w = 2
    inp = np.zeros((1, 3, h, w), "float32")
    got = _run_one_op(
        "anchor_generator", {"Input": [("i", inp)]},
        {"Anchors": ["a"], "Variances": ["v"]},
        {"anchor_sizes": [32.0], "aspect_ratios": [1.0],
         "stride": [16.0, 16.0], "variances": [0.1, 0.1, 0.2, 0.2],
         "offset": 0.5})
    a = got["a"].reshape(h, w, 1, 4)
    for y in range(h):
        for x in range(w):
            cx, cy = (x + 0.5) * 16.0, (y + 0.5) * 16.0
            np.testing.assert_allclose(
                a[y, x, 0], [cx - 16.0, cy - 16.0, cx + 16.0, cy + 16.0],
                rtol=1e-5)
    np.testing.assert_allclose(got["v"].reshape(-1, 4),
                               np.tile([0.1, 0.1, 0.2, 0.2], (h * w, 1)))


def test_box_decoder_and_assign_identity_deltas():
    """box_decoder_and_assign_op.cc: zero deltas with unit variances
    decode back to the prior box; the assigned box is the best-scoring
    class's decode."""
    prior = np.array([[0.0, 0.0, 10.0, 10.0]], "float32")
    pvar = np.array([[1.0, 1.0, 1.0, 1.0]], "float32")
    # 2 classes → target box layout [N, 4*C], score [N, C]
    tbox = np.zeros((1, 8), "float32")
    score = np.array([[0.2, 0.7]], "float32")
    got = _run_one_op(
        "box_decoder_and_assign",
        {"PriorBox": [("pb", prior)], "PriorBoxVar": [("pv", pvar)],
         "TargetBox": [("tb", tbox)], "BoxScore": [("sc", score)]},
        {"DecodeBox": ["db"], "OutputAssignBox": ["ab"]},
        {"box_clip": 1e8})
    np.testing.assert_allclose(got["db"].reshape(1, 2, 4)[0, 0], prior[0],
                               rtol=1e-5)
    np.testing.assert_allclose(got["ab"][0], prior[0], rtol=1e-5)


# ---------------------------------------------------------------------------
# tensor-array / control-flow op types (tensor_array_ops.py) — the layer
# tests use array_write/array_read layer names; pin the OP types here
# ---------------------------------------------------------------------------

def test_tensor_array_op_types_execute_numerically():
    main = fluid.Program()
    with fluid.program_guard(main), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[3], dtype="float32")
        i0 = fluid.layers.fill_constant(shape=[1], dtype="int64", value=0)
        i1 = fluid.layers.fill_constant(shape=[1], dtype="int64", value=1)
        arr = fluid.layers.array_write(x, i0)
        fluid.layers.array_write(x * 2.0, i1, array=arr)
        back = fluid.layers.array_read(arr, i1)
        ln = fluid.layers.array_length(arr)
    types = {op.type for op in main.global_block().ops}
    assert {"write_to_array", "read_from_array", "lod_array_length"} <= types
    xv = np.ones((2, 3), "float32")
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        bv, lv = exe.run(main, feed={"x": xv}, fetch_list=[back, ln])
    np.testing.assert_allclose(np.asarray(bv), xv * 2.0)
    assert int(np.asarray(lv).reshape(-1)[0]) == 2


def test_shrink_rnn_memory_static_shape_contract():
    """shrink_rnn_memory_op.cc drops finished-sequence rows; the
    documented static-shape deviation (tensor_array_ops.py module
    docstring, PARITY.md) keeps ALL rows — finished rows compute on and
    are masked at array_to_lod_tensor reassembly.  Pin that contract:
    full-capacity identity, composing with the rank table untouched."""
    x = np.arange(8, dtype="float32").reshape(2, 4)
    main = fluid.Program()
    with fluid.program_guard(main), fluid.unique_name.guard():
        xv = fluid.layers.data(name="x", shape=[4], dtype="float32")
        lens = fluid.layers.data(name="lens", shape=[1], dtype="int64")
        table = fluid.layers.lod_rank_table(lens)
        i = fluid.layers.fill_constant(shape=[1], dtype="int64", value=2)
        block = main.global_block()
        out = block.create_var(name="shrunk", dtype="float32")
        block.append_op("shrink_rnn_memory",
                        inputs={"X": [xv.name], "I": [i.name],
                                "RankTable": [table.name]},
                        outputs={"Out": [out.name]}, attrs={})
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        (got,) = exe.run(main, feed={
            "x": x, "lens": np.array([[3], [1]], "int64")},
            fetch_list=["shrunk"])
    np.testing.assert_allclose(np.asarray(got), x)


# ---------------------------------------------------------------------------
# host / interop aliases
# ---------------------------------------------------------------------------

def test_registry_aliases_share_lowering():
    """split_byref == split, conditional_block_infer == conditional_block,
    cross_entropy_grad2 == cross_entropy2_grad (reference REGISTER twins)."""
    assert (registry.get_op("split_byref").lower
            is registry.get_op("split").lower)
    assert (registry.get_op("conditional_block_infer").lower
            is registry.get_op("conditional_block").lower)
    assert (registry.get_op("cross_entropy_grad2").lower
            is registry.get_op("cross_entropy2_grad").lower)


def test_split_byref_numerics():
    x = np.arange(12, dtype="float32").reshape(2, 6)
    got = _run_one_op("split_byref", {"X": [("x", x)]},
                      {"Out": ["a", "b", "c"]}, {"num": 3, "axis": 1})
    np.testing.assert_allclose(got["a"], x[:, :2])
    np.testing.assert_allclose(got["c"], x[:, 4:])


def test_fake_init_and_load_delete_var_host_ops(tmp_path):
    """fake_init declares without real contents (fake_init_op.cc);
    load_var reads a saved var (load_op.cc); delete_var frees it
    (delete_var_op.cc); ref_by_trainer_id picks X[trainer_id]."""
    val = np.arange(6, dtype="float32").reshape(2, 3)
    path = str(tmp_path / "v_loaded.npy")
    np.save(path, val)

    main = fluid.Program()
    with fluid.program_guard(main):
        blk = main.global_block()
        blk.create_var(name="fi", dtype="float32", persistable=True)
        blk.append_op("fake_init", inputs={}, outputs={"Out": ["fi"]},
                      attrs={"shape": [2, 2]})
        blk.create_var(name="v_loaded", shape=val.shape, dtype="float32",
                       persistable=True)
        blk.append_op("load_var", inputs={},
                      outputs={"Out": ["v_loaded"]},
                      attrs={"file_path": path})
        blk.create_var(name="tid", shape=[1], dtype="int64",
                       persistable=True)
        blk.create_var(name="picked", dtype="float32", persistable=True)
        blk.append_op("ref_by_trainer_id",
                      inputs={"X": ["fi", "v_loaded"], "TrainerId": ["tid"]},
                      outputs={"Out": ["picked"]}, attrs={})
        blk.append_op("delete_var", inputs={"X": ["fi"]}, outputs={},
                      attrs={})
    scope2 = Scope()
    with scope_guard(scope2):
        scope2.set("tid", np.array([1], "int64"))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(main, feed={}, fetch_list=[])
        np.testing.assert_allclose(np.asarray(scope2.get("v_loaded")), val)
        np.testing.assert_allclose(np.asarray(scope2.get("picked")), val)
        assert scope2.get("fi") is None  # delete_var freed it


def test_static_rnn_cumulative_sum_matches_numpy():
    """static_rnn (recurrent_op.cc / layers StaticRNN → lax.scan):
    h_t = h_{t-1} + x_t over a time-major sequence; stacked outputs are
    the cumulative sums, LastMem the final one."""
    T, B, D = 3, 2, 4
    xv = np.random.RandomState(0).randn(T, B, D).astype("float32")
    main = fluid.Program()
    with fluid.program_guard(main), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[B, D], dtype="float32",
                              append_batch_size=False)
        # time-major feed: use the raw [T,B,D] var
        xr = fluid.layers.reshape(x, shape=[-1, B, D])
        h0 = fluid.layers.fill_constant(shape=[B, D], dtype="float32",
                                        value=0.0)
        rnn = fluid.layers.StaticRNN()
        with rnn.step():
            xt = rnn.step_input(xr)
            h = rnn.memory(init=h0)
            nh = fluid.layers.elementwise_add(h, xt)
            rnn.update_memory(h, nh)
            rnn.step_output(nh)
        out = rnn()
    assert "static_rnn" in {op.type for op in main.global_block().ops}
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        (got,) = exe.run(main, feed={"x": xv.reshape(T * B, D)},
                         fetch_list=[out])
    np.testing.assert_allclose(np.asarray(got), np.cumsum(xv, axis=0),
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# detection heavies: invariant tests (full reference-numeric pinning is
# impractical for these kernels; shape/range/degenerate-case invariants
# catch wiring and indexing regressions — documented as invariant-level
# coverage in test_op_coverage.py)
# ---------------------------------------------------------------------------

def test_tree_conv_invariants():
    """tree_conv_op.cc (TBCNN): [B,N,D]x[D,3,K] → [B,N,K]; zero filter →
    zero output; finite on a real tree."""
    rng = np.random.RandomState(0)
    nodes = rng.randn(1, 3, 4).astype("float32")
    edges = np.array([[[1, 2], [1, 3], [0, 0]]], "int64")  # 1-based, pad 0
    w0 = np.zeros((4, 3, 5), "float32")
    got = _run_one_op("tree_conv",
                      {"NodesVector": [("n", nodes)],
                       "EdgeSet": [("e", edges)], "Filter": [("w", w0)]},
                      {"Out": ["o"]}, {})
    assert got["o"].shape == (1, 3, 5)
    np.testing.assert_allclose(got["o"], 0.0)
    w = rng.randn(4, 3, 5).astype("float32")
    got = _run_one_op("tree_conv",
                      {"NodesVector": [("n", nodes)],
                       "EdgeSet": [("e", edges)], "Filter": [("w", w)]},
                      {"Out": ["o"]}, {})
    assert np.isfinite(got["o"]).all() and np.abs(got["o"]).max() > 0


def test_ssd_loss_invariants():
    """ssd_loss_op.cc: scalar-per-image loss, finite and positive for a
    mismatched prediction, near-zero confidence loss weight respected."""
    rng = np.random.RandomState(1)
    prior = np.array([[0.1, 0.1, 0.4, 0.4], [0.5, 0.5, 0.9, 0.9]],
                     "float32")
    pvar = np.tile(np.array([[0.1, 0.1, 0.2, 0.2]], "float32"), (2, 1))
    loc = rng.randn(1, 2, 4).astype("float32")
    conf = rng.randn(1, 2, 3).astype("float32")
    gt = np.array([[[0.12, 0.12, 0.38, 0.38]]], "float32")
    lbl = np.array([[[1]]], "int64")
    got = _run_one_op(
        "ssd_loss_op",
        {"Location": [("loc", loc)], "Confidence": [("cf", conf)],
         "GtBox": [("gt", gt)], "GtLabel": [("gl", lbl)],
         "PriorBox": [("pb", prior)], "PriorBoxVar": [("pv", pvar)]},
        {"Loss": ["l"]}, {})
    assert got["l"].shape[0] == 1
    assert np.isfinite(got["l"]).all() and (got["l"] > 0).all()


def test_retinanet_target_assign_invariants():
    """retinanet_target_assign_op.cc: anchors vs one gt box — the
    best-overlap anchor must be foreground (label 1), counts consistent."""
    anchor = np.array([[0, 0, 10, 10], [20, 20, 30, 30], [0, 0, 9, 9]],
                      "float32")
    gt = np.array([[[0.0, 0.0, 10.0, 10.0]]], "float32")   # [N=1, G=1, 4]
    glab = np.array([[2]], "int64")                          # [N=1, G=1]
    crowd = np.array([[0]], "int64")
    iminfo = np.array([[64.0, 64.0, 1.0]], "float32")
    got = _run_one_op(
        "retinanet_target_assign",
        {"Anchor": [("a", anchor)], "GtBoxes": [("g", gt)],
         "GtLabels": [("gl", glab)], "IsCrowd": [("ic", crowd)],
         "ImInfo": [("ii", iminfo)]},
        {"LocationIndex": ["li"], "ScoreIndex": ["si"],
         "TargetLabel": ["tl"], "TargetBBox": ["tb"],
         "BBoxInsideWeight": ["biw"], "ForegroundNumber": ["fg"]},
        {"positive_overlap": 0.5, "negative_overlap": 0.4})
    fg = int(np.asarray(got["fg"]).reshape(-1)[0])
    assert fg >= 1  # the perfect-overlap anchor is foreground
    assert got["tb"].shape[-1] == 4
    assert np.isfinite(got["tb"]).all()


def test_generate_mask_labels_invariants():
    """generate_mask_labels_op.cc: mask targets for fg rois — resolution²
    mask ints in {-1,0,...,C-1} layout, roi rows finite."""
    im_info = np.array([[32.0, 32.0, 1.0]], "float32")
    gt_classes = np.array([[1]], "int64")
    is_crowd = np.array([[0]], "int64")
    # dense gt bitmap [N, G, H, W] (this framework's documented form —
    # the reference takes polygons, rasterized on the host first)
    gt_segms = np.zeros((1, 1, 32, 32), "float32")
    gt_segms[0, 0, 2:12, 2:12] = 1.0
    rois = np.array([[[2.0, 2.0, 12.0, 12.0]]], "float32")
    lbls = np.array([[1]], "int32")
    got = _run_one_op(
        "generate_mask_labels",
        {"ImInfo": [("ii", im_info)], "GtClasses": [("gc", gt_classes)],
         "IsCrowd": [("ic", is_crowd)], "GtSegms": [("gs", gt_segms)],
         "Rois": [("r", rois)], "LabelsInt32": [("li", lbls)]},
        {"MaskRois": ["mr"], "RoiHasMaskInt32": ["rhm"],
         "MaskInt32": ["mi"]},
        {"num_classes": 2, "resolution": 4})
    assert got["mr"].shape[-1] == 4
    assert np.isfinite(got["mr"]).all()
    assert got["mi"].min() >= -1


def test_deformable_psroi_pooling_zero_trans_finite():
    """deformable_psroi_pooling_op.cc: with zero offsets the pool reduces
    to position-sensitive roi pooling — finite, correct shape, and values
    drawn from the input range."""
    rng = np.random.RandomState(2)
    x = rng.rand(1, 8, 6, 6).astype("float32")  # C = out_ch * ph * pw = 2*2*2
    rois = np.array([[0.0, 0.0, 4.0, 4.0]], "float32")  # corner box
    trans = np.zeros((1, 2, 2, 2), "float32")
    bidx = np.array([0], "int32")
    got = _run_one_op(
        "deformable_psroi_pooling",
        {"Input": [("x", x)], "ROIs": [("r", rois)],
         "Trans": [("t", trans)], "RoisBatchIdx": [("bi", bidx)]},
        {"Output": ["o"], "TopCount": ["tc"]},
        {"output_dim": 2, "pooled_height": 2, "pooled_width": 2,
         "group_size": [2, 2], "spatial_scale": 1.0, "part_size": [2, 2],
         "sample_per_part": 2, "trans_std": 0.1, "no_trans": True})
    assert got["o"].shape == (1, 2, 2, 2)
    assert np.isfinite(got["o"]).all()
    assert got["o"].min() >= -1e-6 and got["o"].max() <= 1.0 + 1e-6


def _dual_int8_recon(hi, lo, scale):
    # independent rendering of the dual-int8 format (docs/KERNELS.md
    # "int8 KV"): x ~ (hi + lo/254) * scale, one scale per head_dim vector
    return ((hi.astype("float32") + lo.astype("float32") / 254.0)
            * scale.astype("float32"))


def test_kv_cache_write_quant_scatter_and_resolution():
    """decode_ops.py kv_cache_write_quant: quantize new [B, n, d] per
    (slot, head) vector and scatter hi/lo/scale at (page_idx[b],
    offset[b]); untouched slots keep their bytes, written slots
    reconstruct within dual-int8 resolution (~14.6 bits)."""
    rng = np.random.RandomState(3)
    P, pgs, n, d = 3, 4, 2, 8
    # the pool's one shape: heads side by side in the last dimension,
    # one scale a head
    hi = np.ones((P, pgs, n * d), "int8") * 7
    lo = np.ones((P, pgs, n * d), "int8") * -3
    sc = np.full((P, pgs, n), 0.5, "float32")
    new = (rng.randn(2, n, d) * 4).astype("float32")
    page_idx = np.array([2, 0], "int32")
    offset = np.array([1, 3], "int32")
    got = _run_one_op(
        "kv_cache_write_quant",
        {"Hi": [("h", hi)], "Lo": [("l", lo)], "Scale": [("s", sc)],
         "New": [("nw", new)], "PageIdx": [("pi", page_idx)],
         "Offset": [("of", offset)]},
        {"HiOut": ["ho"], "LoOut": ["lu"], "ScaleOut": ["so"]})
    ho, lu, so = got["ho"], got["lu"], got["so"]
    assert ho.dtype == np.int8 and lu.dtype == np.int8
    assert ho.shape == hi.shape and so.shape == sc.shape
    recon = _dual_int8_recon(ho.reshape(P, pgs, n, d),
                             lu.reshape(P, pgs, n, d), so[..., None])
    for b in range(2):
        p, o = int(page_idx[b]), int(offset[b])
        np.testing.assert_allclose(
            recon[p, o], new[b],
            atol=float(np.abs(new[b]).max()) * 1e-4)
    untouched = np.ones((P, pgs), bool)
    untouched[page_idx, offset] = False
    np.testing.assert_array_equal(ho[untouched], hi[untouched])
    np.testing.assert_array_equal(so[untouched], sc[untouched])
    # fp-pool misuse fails by name (the dtype guard)
    with pytest.raises(ValueError, match="int8 pool"):
        _run_one_op(
            "kv_cache_write_quant",
            {"Hi": [("h", hi.astype("float32"))], "Lo": [("l", lo)],
             "Scale": [("s", sc)], "New": [("nw", new)],
             "PageIdx": [("pi", page_idx)], "Offset": [("of", offset)]},
            {"HiOut": ["ho"], "LoOut": ["lu"], "ScaleOut": ["so"]})


def test_kv_cache_write_pages_quant_whole_pages():
    """decode_ops.py kv_cache_write_pages_quant: a prefill chunk [C, n, d]
    (C a multiple of the page size) lands as C/pgs whole quantized pages;
    a non-multiple chunk fails by name."""
    rng = np.random.RandomState(4)
    P, pgs, n, d = 4, 2, 2, 8
    hi = np.zeros((P, pgs, n * d), "int8")
    lo = np.zeros((P, pgs, n * d), "int8")
    sc = np.ones((P, pgs, n), "float32")
    new = (rng.randn(4, n, d) * 2).astype("float32")  # 2 whole pages
    page_idx = np.array([3, 1], "int32")
    got = _run_one_op(
        "kv_cache_write_pages_quant",
        {"Hi": [("h", hi)], "Lo": [("l", lo)], "Scale": [("s", sc)],
         "New": [("nw", new)], "PageIdx": [("pi", page_idx)]},
        {"HiOut": ["ho"], "LoOut": ["lu"], "ScaleOut": ["so"]})
    recon = _dual_int8_recon(got["ho"].reshape(P, pgs, n, d),
                             got["lu"].reshape(P, pgs, n, d),
                             got["so"][..., None])
    chunk = new.reshape(2, pgs, n, d)
    for i, p in enumerate((3, 1)):
        np.testing.assert_allclose(
            recon[p], chunk[i],
            atol=float(np.abs(chunk[i]).max()) * 1e-4)
    assert not got["ho"][0].any() and not got["ho"][2].any()
    with pytest.raises(ValueError, match="whole pages"):
        _run_one_op(
            "kv_cache_write_pages_quant",
            {"Hi": [("h", hi)], "Lo": [("l", lo)], "Scale": [("s", sc)],
             "New": [("nw", new[:3])], "PageIdx": [("pi", page_idx)]},
            {"HiOut": ["ho"], "LoOut": ["lu"], "ScaleOut": ["so"]})
