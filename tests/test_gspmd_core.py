"""GSPMD-native sharding core (ISSUE 9): sharding policies over the
named mesh, the one jit-partitioned executor, and the quantized gradient
hook.

Acceptance contract: the GSPMD DP path matches transpiler-path losses on
a 20-step run (<= 1e-5 fp32-exact; <= 1e-3 with the quant hook + ZeRO-1
policy), a 2-D (batch, model) tensor-parallel program compiles and runs
on a 2x2 mesh, and compiled-HLO inspection proves XLA inserted the
collectives — the GSPMD-built PROGRAM contains no c_allreduce ops —
while the quant hook keeps int8 bytes on the wire per ``wire_bytes``.

Container caveat (ROADMAP): jaxlib-0.4.3x XLA:CPU nondeterministically
corrupts the heap on multi-device GSPMD programs, so every multi-device
GSPMD test here runs SUBPROCESS-ISOLATED following the
tests/test_ring_collectives.py pattern — a bad roll skips instead of
killing the session, and the new core keeps executed coverage instead of
hiding behind test_hybrid's blanket skip.  The 1-device degenerate-mesh
tests run un-isolated (a 1-device partition is a no-op for the
partitioner and does not trigger the corruption).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import cpu_mesh  # noqa: F401  (8-device CPU mesh before jax import)

from paddle_tpu import fluid
from paddle_tpu.parallel import mesh as pmesh
from paddle_tpu.parallel.gspmd import (DataParallelPolicy, GSPMDExecutor,
                                       TensorParallelPolicy, Zero1Policy,
                                       hlo_collective_bytes,
                                       hlo_collective_counts, policy_for,
                                       resolve_quant_impl)

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))


def _run_child(code, timeout=600, tag="GSPMD_RESULT"):
    """Subprocess-isolation harness (test_ring_collectives precedent):
    run `code` in a fresh interpreter on the 8-device CPU mesh, parse the
    tagged JSON line, skip when the known nondeterministic 0.4.3x abort
    kills the child by signal."""
    prelude = (
        "import sys\n"
        f"sys.path.insert(0, {TESTS_DIR!r})\n"
        "import cpu_mesh  # noqa: F401\n")
    r = subprocess.run(
        [sys.executable, "-c", prelude + code],
        capture_output=True, text=True, timeout=timeout,
        cwd=os.path.dirname(TESTS_DIR))
    lines = [ln for ln in r.stdout.splitlines()
             if ln.startswith(tag + " ")]
    if r.returncode != 0 and not lines:
        if r.returncode < 0:
            pytest.skip(f"GSPMD child died with signal {-r.returncode} "
                        "(0.4.3x XLA:CPU heap corruption)")
        raise AssertionError(
            f"gspmd child failed rc={r.returncode}\n{r.stderr[-3000:]}")
    return json.loads(lines[-1][len(tag) + 1:])


# ---------------------------------------------------------------------------
# policy layer (no compilation — runs in-process)
# ---------------------------------------------------------------------------


def _mesh(shape):
    import jax

    return pmesh.build_mesh(shape, devices=jax.devices())


def test_axis_aliases_resolve_to_canonical_names():
    assert pmesh.canonical_axis("batch") == pmesh.DATA_AXIS
    assert pmesh.canonical_axis("model") == pmesh.MODEL_AXIS
    assert pmesh.canonical_axis("dp") == "dp"
    assert pmesh.canonical_axis(None) is None


def test_build_2d_mesh_shapes():
    m = pmesh.build_2d_mesh(batch=4, model=2)
    assert dict(m.shape) == {pmesh.DATA_AXIS: 4, pmesh.MODEL_AXIS: 2}
    m1 = pmesh.build_2d_mesh(model=2)  # batch fills the remainder
    assert m1.shape[pmesh.DATA_AXIS] * 2 == 8


def _toy_program(opt="sgd"):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data("x", [-1, 8], False, dtype="float32")
        y = fluid.data("y", [-1, 1], False, dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu",
                            param_attr=fluid.ParamAttr(name="g_w1"))
        pred = fluid.layers.fc(h, size=1,
                               param_attr=fluid.ParamAttr(name="g_w2"))
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        opt_cls = {"sgd": lambda: fluid.optimizer.SGD(0.1),
                   "adam": lambda: fluid.optimizer.Adam(0.01),
                   "momentum": lambda: fluid.optimizer.Momentum(0.1, 0.9)}
        opt_cls[opt]().minimize(loss)
    return main, startup, loss


def test_zero1_policy_shards_optimizer_state_only():
    main, _startup, _loss = _toy_program("adam")
    mesh = _mesh({"dp": 4})
    pol = Zero1Policy()
    blk = main.global_block()
    m1 = next(n for n in blk.vars if n.endswith("_moment1_0")
              and n.startswith("g_w1"))
    v = blk.vars[m1]
    assert pol.param_spec(main, m1, tuple(v.shape), mesh)[0] == "dp"
    # the parameter itself stays replicated
    assert pol.param_spec(main, "g_w1",
                          tuple(blk.vars["g_w1"].shape), mesh) == ()
    # beta pows (shape [1], not divisible by 4) stay replicated
    b1p = next(n for n in blk.vars if "beta1_pow" in n)
    assert not any(pol.param_spec(main, b1p,
                                  tuple(blk.vars[b1p].shape), mesh))


def test_tensor_parallel_policy_specs_and_constraints():
    from paddle_tpu.parallel import ShardingRule

    main, _s, _l = _toy_program()
    mesh = _mesh({"dp": 4, "mp": 2})
    rules = ShardingRule([(r"^g_w1$", (None, "model")),
                          (r"^g_w2$", ("model", None))])
    pol = TensorParallelPolicy(rules=rules)
    blk = main.global_block()
    assert pol.param_spec(main, "g_w1",
                          tuple(blk.vars["g_w1"].shape), mesh) == \
        (None, "mp")  # alias resolved to the canonical axis name
    assert pol.uses_model_axis(main, mesh)
    cons = pol.activation_constraints(main, mesh)
    # the column-split fc's activation is pinned to the model axis
    assert any(spec[-1] == "mp" for spec in cons.values())
    # no model axis in the mesh -> no constraints
    assert pol.activation_constraints(main, _mesh({"dp": 8})) == {}


def test_policy_for_is_the_thin_selection():
    mesh_dp = _mesh({"dp": 8})
    mesh_2d = _mesh({"dp": 4, "mp": 2})
    assert isinstance(policy_for(mesh_dp), DataParallelPolicy)
    assert isinstance(policy_for(mesh_dp, zero_stage=1), Zero1Policy)
    assert isinstance(policy_for(mesh_2d), TensorParallelPolicy)


def test_resolve_quant_impl_validates():
    assert resolve_quant_impl("shard_map") == "shard_map"
    assert resolve_quant_impl("custom_partitioning") == \
        "custom_partitioning"
    assert resolve_quant_impl() in ("shard_map", "custom_partitioning")
    with pytest.raises(ValueError, match="gspmd_quant_impl"):
        resolve_quant_impl("bogus")


def test_hlo_inspection_helpers():
    hlo = (
        "  %ar = f32[128]{0} all-reduce(f32[128]{0} %x), replica_groups={}\n"
        "  %cp = s8[64]{0} collective-permute(s8[64]{0} %q)\n"
        "  %ag = (f32[32]{0}, f32[32]{0}) all-gather(f32[16]{0} %a, f32[16]{0} %b)\n"
        "  %mm = f32[8,8]{1,0} dot(f32[8,8]{1,0} %l, f32[8,8]{1,0} %r)\n")
    counts = hlo_collective_counts(hlo)
    assert counts == {"all-reduce": 1, "collective-permute": 1,
                      "all-gather": 1}
    assert hlo_collective_bytes(hlo) == 128 * 4 + 64 + 2 * 32 * 4
    # async -start forms (TPU start/done pairs): the tuple aliases the
    # operand beside the result, so the bytes HALVE — else on-chip
    # numbers double-count vs the CPU sync forms; -done is not a
    # separate collective
    async_hlo = (
        "  %s = (f32[1024]{0}, f32[1024]{0}) all-reduce-start(f32[1024]{0} %g)\n"
        "  %d = f32[1024]{0} all-reduce-done((f32[1024]{0}, f32[1024]{0}) %s)\n")
    assert hlo_collective_bytes(async_hlo) == 1024 * 4
    assert hlo_collective_counts(async_hlo) == {"all-reduce": 1}


def test_feed_spec_divisibility_gate():
    """A feed whose batch does not divide the axis replicates gracefully
    (the _fits gate) instead of erroring deep in XLA — resolved against
    the REAL feed shape by the executor."""
    main, _s, _l = _toy_program()
    mesh = _mesh({"dp": 8})
    pol = DataParallelPolicy()
    assert pol.feed_spec(main, "x", (16, 8), mesh) == ("dp", None)
    assert not any(pol.feed_spec(main, "x", (10, 8), mesh))


def test_policy_for_empty_rules_on_batch_mesh_stays_dp():
    """An EMPTY rule set on a batch-only mesh must not select the TP
    policy (its per-var regex scan would run for nothing) — the drift
    guard policy_for exists for, now that both runners call it."""
    from paddle_tpu.parallel import ShardingRule

    mesh = _mesh({"dp": 8})
    assert isinstance(policy_for(mesh, rules=ShardingRule([])),
                      DataParallelPolicy)
    assert isinstance(policy_for(mesh, rules=ShardingRule([]),
                                 zero_stage=1), Zero1Policy)
    assert isinstance(
        policy_for(mesh, rules=ShardingRule([("w", ("mp",))])),
        TensorParallelPolicy)


# ---------------------------------------------------------------------------
# 1-device degenerate mesh (un-isolated: no multi-device partitioning)
# ---------------------------------------------------------------------------


def _init_scope(startup):
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
    return scope


def _copy_scope(scope):
    s = fluid.Scope()
    for k in scope.keys():
        v = scope.get(k)
        if v is not None:
            s.set(k, np.asarray(v).copy())
    return s


def test_degenerate_mesh_matches_single_device_exactly():
    """mesh {dp: 1}: the partitioned executor is a bit-exact identity of
    the plain Executor — and its program carries no collective ops."""
    import jax

    rng = np.random.RandomState(0)
    xd = rng.randn(8, 8).astype("float32")
    yd = rng.randn(8, 1).astype("float32")
    main, startup, loss = _toy_program("adam")
    scope1 = _init_scope(startup)
    scope2 = _copy_scope(scope1)

    ref = []
    with fluid.scope_guard(scope1):
        exe = fluid.Executor(fluid.CPUPlace())
        for _ in range(3):
            ref.append(float(exe.run(main, feed={"x": xd, "y": yd},
                                     fetch_list=[loss.name])[0]))
    mesh = pmesh.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    ex = GSPMDExecutor(main, mesh, DataParallelPolicy(), scope=scope2)
    got = [float(np.asarray(ex.run(feed={"x": xd, "y": yd},
                                   fetch_list=[loss.name])[0]).reshape(-1)[0])
           for _ in range(3)]
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    # program purity: nothing inserted c_allreduce ops
    assert not [op.type for op in main.global_block().ops
                if op.type.startswith("c_allreduce")]
    # 1-device HLO carries no cross-device collectives
    assert ex.last_hlo is not None
    assert hlo_collective_counts(ex.last_hlo) == {}


def test_degenerate_mesh_quant_hook_demotes_quietly():
    """dp=1: plan_quant_hook returns None (nothing to reduce) and the
    executor stays exact — the wire counter books nothing."""
    import jax

    main, startup, loss = _toy_program()
    scope = _init_scope(startup)
    mesh = pmesh.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    ex = GSPMDExecutor(main, mesh, DataParallelPolicy(), scope=scope,
                       quant_hook=True)
    xd = np.random.RandomState(1).randn(4, 8).astype("float32")
    yd = np.zeros((4, 1), "float32")
    ex.run(feed={"x": xd, "y": yd}, fetch_list=[loss.name])
    (cb,) = ex.compiled_blocks()
    assert cb.qplan is None
    assert cb.wire_bytes_per_step == 0


def test_degenerate_mesh_cost_analysis_shared_plumbing():
    """The gspmd block shares _JitExecutable: cost_analysis works and
    publishes the per-signature gauges."""
    import jax

    main, startup, loss = _toy_program()
    scope = _init_scope(startup)
    mesh = pmesh.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    ex = GSPMDExecutor(main, mesh, DataParallelPolicy(), scope=scope)
    feed = {"x": np.zeros((4, 8), "float32"),
            "y": np.zeros((4, 1), "float32")}
    ex.run(feed=feed, fetch_list=[loss.name])
    out = ex.cost_analysis(feed, fetch_list=[loss.name])
    assert out["cost"].get("flops", 0) > 0
    with pytest.raises(ValueError, match="run the step once first"):
        ex.cost_analysis({"x": np.zeros((2, 8), "float32"),
                          "y": np.zeros((2, 1), "float32")},
                         fetch_list=[loss.name])


def test_gspmd_run_steps_validates_n_steps():
    """The gspmd lane keeps the classic lane's n_steps contract: < 1
    raises at the call site instead of silently returning None."""
    import jax

    from paddle_tpu.parallel import HybridParallelRunner

    main, startup, loss = _toy_program()
    scope = _init_scope(startup)
    mesh = pmesh.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    r = HybridParallelRunner(main, mesh, scope=scope, gspmd=True)
    with pytest.raises(ValueError, match="n_steps"):
        r.run_steps({"x": np.zeros((4, 8), "float32"),
                     "y": np.zeros((4, 1), "float32")}, 0,
                    fetch_list=[loss.name])


def test_describe_policy_table():
    import jax

    main, startup, _loss = _toy_program("adam")
    scope = _init_scope(startup)
    mesh = pmesh.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    ex = GSPMDExecutor(main, mesh, Zero1Policy(), scope=scope)
    table = {p.name: p for p in ex.describe_policy()}
    assert table["g_w1"].role == "param"
    m1 = next(n for n in table if n.endswith("_moment1_0"))
    assert table[m1].role == "opt_state"


# ---------------------------------------------------------------------------
# multi-device parity gates (subprocess-isolated)
# ---------------------------------------------------------------------------

_PARITY_CHILD = r"""
import json
import numpy as np
from paddle_tpu import fluid
from paddle_tpu.parallel import DataParallelRunner, HybridParallelRunner, build_hybrid_mesh
from paddle_tpu.parallel.gspmd import hlo_collective_counts

fluid.set_flags({"FLAGS_quant_allreduce_block_size": 16})
rng = np.random.RandomState(0)
xs = rng.randn(16, 8).astype("float32")
ys = rng.randint(0, 3, (16, 1)).astype("int64")
STEPS = 20

def build(seed=5):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        np.random.seed(seed)
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, size=6, act="relu")
        pred = fluid.layers.fc(h, size=3, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, y))
        fluid.optimizer.Adam(0.01).minimize(loss)
    return main, startup, loss

def run_dp(gspmd, quant):
    main, startup, loss = build()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        r = DataParallelRunner(main, loss.name, gspmd=gspmd,
                               quant_grads=quant)
        losses = [float(np.mean(r.run(exe, {"x": xs, "y": ys},
                                      [loss.name], scope)[0]))
                  for _ in range(STEPS)]
        prog_ops = [op.type for op in r.program.global_block().ops]
        hlo = r._gspmd_exec.last_hlo if gspmd else None
    return losses, prog_ops, hlo

def run_zero1_quant():
    fluid.set_flags({"FLAGS_quant_allreduce": True})
    try:
        main, startup, loss = build()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            r = HybridParallelRunner(main, build_hybrid_mesh(8, mp=1),
                                     scope=scope, zero_stage=1, gspmd=True)
            losses = [float(np.asarray(
                r.run(feed={"x": xs, "y": ys},
                      fetch_list=[loss.name])[0]).reshape(-1).mean())
                for _ in range(STEPS)]
            specs = {p.name: list(p.spec) for p in
                     r._gspmd_exec.describe_policy()}
            hlo = r._gspmd_exec.last_hlo
            prog_ops = [op.type for op in
                        r.program.global_block().ops]
    finally:
        fluid.set_flags({"FLAGS_quant_allreduce": False})
    return losses, specs, hlo, prog_ops

lt, _, _ = run_dp(False, False)
lg, ops_g, hlo_g = run_dp(True, False)
lq, ops_q, hlo_q = run_dp(True, True)
lz, specs_z, hlo_z, ops_z = run_zero1_quant()

# BuildStrategy/CompiledProgram threading of the gspmd knob
main, startup, loss = build()
bs = fluid.compiler.BuildStrategy()
bs.gspmd_executor = True
scope = fluid.Scope()
with fluid.scope_guard(scope):
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    prog = fluid.CompiledProgram(main, build_strategy=bs) \
        .with_data_parallel(loss_name=loss.name)
    exe.run(prog, feed={"x": xs, "y": ys}, fetch_list=[loss])
    cp_gspmd = prog._dp_runner._gspmd_exec is not None

from paddle_tpu import observability as obs
snap = obs.snapshot()
payload = snap.get("pt_collective_payload_bytes_total", {}).get("samples", {})
reshard = snap.get("pt_gspmd_resharding_bytes", {}).get("samples", {})
cache = snap.get("pt_compile_cache_total", {}).get("samples", {})

print("GSPMD_RESULT " + json.dumps({
    "transpiler": lt, "gspmd": lg, "gspmd_quant": lq, "zero1_quant": lz,
    "gspmd_prog_has_allreduce": any(t.startswith("c_allreduce")
                                    for t in ops_g + ops_q + ops_z),
    "hlo_gspmd": hlo_collective_counts(hlo_g),
    "hlo_quant": hlo_collective_counts(hlo_q),
    "hlo_zero1": hlo_collective_counts(hlo_z),
    "quant_int8_on_wire": "s8[" in hlo_q,
    "zero1_int8_on_wire": "s8[" in hlo_z,
    "moment_specs": {k: v for k, v in specs_z.items() if "moment" in k},
    "payload_booked": ["c_allreduce_quant"] in
        [list(k) for k in payload],
    "reshard_gauges": len(reshard),
    "gspmd_cache_path": any(k[0] == "gspmd" for k in cache),
    "cp_gspmd": cp_gspmd,
}))
"""


def test_gspmd_dp_parity_and_hlo_proof_subprocess():
    """The core acceptance gate, 20 steps on the 8-device CPU mesh:

    - fp32 GSPMD DP tracks the transpiler path <= 1e-5;
    - the quant hook and the quant+ZeRO-1 policy track <= 1e-3 with int8
      payloads visible in the compiled HLO (`wire_bytes` booked on the
      shared payload counter);
    - the GSPMD-built programs contain NO c_allreduce ops while their
      HLO contains XLA-inserted collectives — the "XLA placed the
      collectives" proof;
    - ZeRO-1 moment vars resolve dp-sharded specs and the weight-update
      all-gather appears in the HLO (arXiv:2004.13336 as a spec);
    - BuildStrategy.gspmd_executor threads through CompiledProgram.
    """
    res = _run_child(_PARITY_CHILD)
    lt = np.asarray(res["transpiler"])
    assert np.max(np.abs(lt - np.asarray(res["gspmd"]))) <= 1e-5
    assert np.max(np.abs(lt - np.asarray(res["gspmd_quant"]))) <= 1e-3
    assert np.max(np.abs(lt - np.asarray(res["zero1_quant"]))) <= 1e-3
    assert lt[-1] < lt[0]  # it trains
    assert not res["gspmd_prog_has_allreduce"]
    assert sum(res["hlo_gspmd"].values()) > 0
    assert sum(res["hlo_quant"].values()) > 0
    assert res["quant_int8_on_wire"]
    assert res["zero1_int8_on_wire"]
    assert "all-gather" in res["hlo_zero1"]  # the ZeRO-1 update gather
    moment_specs = res["moment_specs"]
    assert moment_specs and any(s and s[0] == "dp"
                                for s in moment_specs.values())
    assert res["payload_booked"]
    assert res["reshard_gauges"] >= 2
    assert res["gspmd_cache_path"]
    assert res["cp_gspmd"]


_TP_FC_CHILD = r"""
import json
import numpy as np
from paddle_tpu import fluid
from paddle_tpu.fluid.executor import Scope, scope_guard
from paddle_tpu.parallel import (HybridParallelRunner, ShardingRule,
                                 build_hybrid_mesh)
from paddle_tpu.parallel.gspmd import hlo_collective_counts

rng = np.random.RandomState(7)
xd = rng.uniform(-1, 1, (16, 8)).astype("float32")
yd = (xd @ rng.randn(8, 1)).astype("float32")

main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup), fluid.unique_name.guard():
    x = fluid.data("x", [-1, 8], False, dtype="float32")
    y = fluid.data("y", [-1, 1], False, dtype="float32")
    h = fluid.layers.fc(x, size=16, act="relu",
                        param_attr=fluid.ParamAttr(name="tp_w1"))
    h2 = fluid.layers.fc(h, size=8, act="relu",
                         param_attr=fluid.ParamAttr(name="tp_w2"))
    pred = fluid.layers.fc(h2, size=1,
                           param_attr=fluid.ParamAttr(name="tp_w3"))
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)

scope1 = Scope()
with scope_guard(scope1):
    fluid.Executor(fluid.CPUPlace()).run(startup)
scope2 = Scope()
for k in scope1.keys():
    v = scope1.get(k)
    if v is not None:
        scope2.set(k, np.asarray(v).copy())

with scope_guard(scope1):
    exe = fluid.Executor(fluid.CPUPlace())
    ref = [float(np.asarray(exe.run(main, feed={"x": xd, "y": yd},
                                    fetch_list=[loss.name])[0])
                 .reshape(-1)[0]) for _ in range(4)]

# column-split then row-split over 'model' — the classic megatron pair,
# written with the paper-idiom axis spellings
rules = ShardingRule([(r"^tp_w1$", (None, "model")),
                      (r"^tp_w2$", ("model", None))])
mesh = build_hybrid_mesh(4, mp=2)  # 2-D (batch, model) 2x2
runner = HybridParallelRunner(main, mesh, rules=rules, scope=scope2,
                              gspmd=True)
par = [float(np.asarray(runner.run(feed={"x": xd, "y": yd},
                                   fetch_list=[loss.name])[0])
             .reshape(-1)[0]) for _ in range(4)]
specs = {p.name: list(p.spec) for p in runner._gspmd_exec.describe_policy()}
cons = runner._gspmd_exec.policy.activation_constraints(main, mesh)
hlo = runner._gspmd_exec.last_hlo
print("GSPMD_RESULT " + json.dumps({
    "ref": ref, "par": par,
    "mesh_shape": {k: int(v) for k, v in mesh.shape.items()},
    "w1_spec": specs["tp_w1"], "w2_spec": specs["tp_w2"],
    "constraints": {k: list(v) for k, v in cons.items()},
    "collectives": hlo_collective_counts(hlo),
    "prog_has_allreduce": any(
        op.type.startswith("c_allreduce")
        for op in runner.program.global_block().ops),
}))
"""


def test_gspmd_tensor_parallel_2x2_fc_subprocess():
    """The acceptance 2-D gate: a column-split + row-split FC pair on
    the (batch, model) 2x2 mesh — a layout the transpiler path cannot
    express — compiles under the ONE GSPMD executor, matches the
    single-device run, and the collectives in the HLO are all
    XLA-inserted (the program has none)."""
    res = _run_child(_TP_FC_CHILD)
    assert res["mesh_shape"] == {"dp": 2, "mp": 2}
    assert res["w1_spec"] == [None, "mp"]  # 'model' alias resolved
    assert res["w2_spec"] == ["mp", None]
    assert any(v[-1] == "mp" for v in res["constraints"].values())
    np.testing.assert_allclose(np.asarray(res["ref"]),
                               np.asarray(res["par"]),
                               rtol=2e-3, atol=2e-3)
    assert sum(res["collectives"].values()) > 0
    assert not res["prog_has_allreduce"]


_TP_BERT_CHILD = r"""
import json
import numpy as np
from paddle_tpu import fluid
from paddle_tpu.fluid.executor import Scope, scope_guard
from paddle_tpu.models import bert
from paddle_tpu.parallel import (HybridParallelRunner, megatron_rules,
                                 build_hybrid_mesh)
from paddle_tpu.parallel.gspmd import hlo_collective_counts

def build(seed=3):
    cfg = bert.BertConfig.tiny(hidden_dropout=0.0, attn_dropout=0.0)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, loss, mlm, acc = bert.build_bert_pretrain(cfg, is_test=False)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    batches = [bert.make_fake_batch(cfg, batch=8, seq_len=16, seed=seed + i)
               for i in range(3)]
    return main, startup, loss, batches

def init_scope(startup):
    s = Scope()
    with scope_guard(s):
        fluid.Executor(fluid.CPUPlace()).run(startup)
    return s

def copy_scope(scope):
    s = Scope()
    for k in scope.keys():
        v = scope.get(k)
        if v is not None:
            s.set(k, np.asarray(v).copy())
    return s

main, startup, loss, batches = build()
scope1 = init_scope(startup)
scope2 = copy_scope(scope1)

ref = []
with scope_guard(scope1):
    exe = fluid.Executor(fluid.CPUPlace())
    for b in batches:
        ref.append(float(np.asarray(
            exe.run(main, feed=b, fetch_list=[loss.name])[0]).reshape(-1)[0]))

# the 2-D (batch, model) mesh the transpiler lane cannot express:
# BERT-tiny FC layers split over 'model', batch over 'batch', 2x2
mesh = build_hybrid_mesh(4, mp=2)
runner = HybridParallelRunner(main, mesh, rules=megatron_rules(),
                              scope=scope2, gspmd=True)
par = [float(np.asarray(runner.run(feed=b, fetch_list=[loss.name])[0])
             .reshape(-1)[0]) for b in batches]

pol = runner._gspmd_exec.policy
specs = {p.name: list(p.spec) for p in runner._gspmd_exec.describe_policy()}
mp_params = {k: v for k, v in specs.items() if "mp" in v}
cons = pol.activation_constraints(main, mesh)
hlo = runner._gspmd_exec.last_hlo

print("GSPMD_RESULT " + json.dumps({
    "ref": ref, "par": par,
    "mesh_shape": {k: int(v) for k, v in mesh.shape.items()},
    "mp_params": len(mp_params),
    "constraints": len(cons),
    "collectives": hlo_collective_counts(hlo),
    "prog_has_allreduce": any(
        op.type.startswith("c_allreduce")
        for op in runner.program.global_block().ops),
}))
"""


def test_gspmd_tensor_parallel_2x2_bert_subprocess():
    """BERT-tiny on the 2-D (batch, model) 2x2 mesh, FC/QKV weights
    megatron-split over the model axis, compiled by the ONE GSPMD
    executor — the ISSUE's named demo.  KNOWN CONTAINER LIMIT: the
    bert-sized multi-axis GSPMD program is the documented 0.4.3x
    XLA:CPU heap-corruption trigger (tests/test_hybrid.py's blanket
    skip); subprocess isolation turns that abort into a SKIP here while
    the smaller FC gate above keeps the 2x2 layout under real executed
    coverage.  On a healthy backend (real TPU) this runs and gates."""
    res = _run_child(_TP_BERT_CHILD)
    assert res["mesh_shape"] == {"dp": 2, "mp": 2}
    assert res["mp_params"] > 0  # megatron rules actually split weights
    assert res["constraints"] > 0  # activations pinned by the policy
    np.testing.assert_allclose(np.asarray(res["ref"]),
                               np.asarray(res["par"]),
                               rtol=2e-3, atol=2e-3)
    assert sum(res["collectives"].values()) > 0
    assert not res["prog_has_allreduce"]


_BERT20_CHILD = r"""
import json
import os
import numpy as np
from paddle_tpu import fluid
from paddle_tpu.fluid.executor import Scope, scope_guard
from paddle_tpu.fluid.param_attr import ParamAttr
from paddle_tpu.models import bert
from paddle_tpu.parallel import (DataParallelRunner, HybridParallelRunner,
                                 build_hybrid_mesh)

STEPS = 20

def build(seed=3):
    # BERT-tiny encoder + pooled classifier head.  Deliberately NOT the
    # pretrain graph: its mask_pos feed holds GLOBAL flat positions,
    # which per-device row-sharding (transpiler DP and the quant island
    # alike) reinterprets as local indices — a pre-existing workload
    # incompatibility (NaN on clean HEAD), not a lane difference.  The
    # classifier's feeds are all row-shardable, so the three lanes are
    # mathematically comparable.
    cfg = bert.BertConfig.tiny(hidden_dropout=0.0, attn_dropout=0.0)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        src = fluid.data("src_ids", [-1, -1], False, dtype="int64")
        pos = fluid.data("pos_ids", [-1, -1], False, dtype="int64")
        sent = fluid.data("sent_ids", [-1, -1], False, dtype="int64")
        mask = fluid.data("input_mask", [-1, -1], False, dtype="float32")
        labels = fluid.data("labels", [-1, 1], False, dtype="int64")
        enc = bert.bert_encoder(src, pos, sent, mask, cfg, is_test=False)
        first = fluid.layers.slice(enc, axes=[1], starts=[0], ends=[1])
        pooled = fluid.layers.fc(
            fluid.layers.reshape(first, shape=[-1, cfg.hidden_size]),
            size=cfg.hidden_size, act="tanh",
            param_attr=ParamAttr(name="pooled_fc.w_0"))
        logits = fluid.layers.fc(
            pooled, size=2, param_attr=ParamAttr(name="cls_fc.w_0"))
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, labels))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    rngs = [np.random.RandomState(seed + i) for i in range(STEPS)]
    batches = []
    for rng in rngs:
        b = bert.make_fake_batch(cfg, batch=16, seq_len=16,
                                 seed=int(rng.randint(1 << 30)))
        batches.append({k: b[k] for k in ("src_ids", "pos_ids",
                                          "sent_ids", "input_mask")}
                       | {"labels": b["labels"]})
    return main, startup, loss, batches

# ONE arm per child: the 0.4.3x heap corruption odds grow with each big
# compile in a process, so every arm gets a fresh heap.  Parity across
# processes holds because np.random.seed pins the startup init.
np.random.seed(11)
main, startup, loss, batches = build()
scope = Scope()
with scope_guard(scope):
    fluid.Executor(fluid.CPUPlace()).run(startup)

ARM = os.environ["PT_GSPMD_ARM"]
if ARM == "transpiler":
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        r = DataParallelRunner(main, loss.name, gspmd=False)
        out = [float(np.mean(r.run(exe, b, [loss.name], scope)[0]))
               for b in batches]
elif ARM == "gspmd":
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        r = DataParallelRunner(main, loss.name, gspmd=True)
        out = [float(np.mean(r.run(exe, b, [loss.name], scope)[0]))
               for b in batches]
elif ARM == "quant_zero1":
    # block 64: finer per-block scales keep the dual-int8 ring's error
    # inside the 1e-3 acceptance bound on bert-grade gradients (the
    # default 256 lands at ~1.1e-3 on this 20-step run)
    fluid.set_flags({"FLAGS_quant_allreduce": True,
                     "FLAGS_quant_allreduce_block_size": 64})
    with scope_guard(scope):
        r = HybridParallelRunner(main, build_hybrid_mesh(8, mp=1),
                                 scope=scope, zero_stage=1, gspmd=True)
        out = [float(np.asarray(
            r.run(feed=b, fetch_list=[loss.name])[0])
            .reshape(-1).mean()) for b in batches]
else:
    raise SystemExit(f"unknown arm {ARM}")
print("GSPMD_RESULT " + json.dumps({"arm": ARM, "losses": out}))
"""


def _run_bert_arm(arm):
    prelude = (
        "import sys, os\n"
        f"sys.path.insert(0, {TESTS_DIR!r})\n"
        f"os.environ['PT_GSPMD_ARM'] = {arm!r}\n"
        "import cpu_mesh  # noqa: F401\n")
    r = subprocess.run(
        [sys.executable, "-c", prelude + _BERT20_CHILD],
        capture_output=True, text=True, timeout=1500,
        cwd=os.path.dirname(TESTS_DIR))
    lines = [ln for ln in r.stdout.splitlines()
             if ln.startswith("GSPMD_RESULT ")]
    if r.returncode != 0 and not lines:
        if r.returncode < 0:
            pytest.skip(f"GSPMD bert arm {arm!r} died with signal "
                        f"{-r.returncode} (0.4.3x XLA:CPU heap "
                        "corruption)")
        raise AssertionError(
            f"bert arm {arm!r} failed rc={r.returncode}\n"
            f"{r.stderr[-3000:]}")
    return json.loads(lines[-1][len("GSPMD_RESULT "):])["losses"]


def test_gspmd_bert_tiny_20_step_acceptance_subprocess():
    """The ISSUE's verbatim acceptance run: 20-step BERT-tiny
    (encoder + pooled classifier head), GSPMD DP vs the transpiler path
    <= 1e-5 fp32-exact, and <= 1e-3 with the quant hook + ZeRO-1
    policy (block 64).  One subprocess per arm — each large compile
    gets a fresh heap, shrinking the window for the known 0.4.3x abort
    (one process running all three arms died 3/3; per-arm processes
    pass); identical seeded init keeps the arms comparable across
    processes.  ~37 s on the 2-vCPU container."""
    lt = np.asarray(_run_bert_arm("transpiler"))
    lg = np.asarray(_run_bert_arm("gspmd"))
    lz = np.asarray(_run_bert_arm("quant_zero1"))
    # each step's loss is on a fresh noisy batch, so the descent check
    # compares means, not two single steps
    assert len(lt) == 20 and lt[-5:].mean() < lt[:5].mean()
    assert np.max(np.abs(lt - lg)) <= 1e-5
    assert np.max(np.abs(lt - lz)) <= 1e-3


_REPL_FEED_CHILD = r"""
import json
import numpy as np
from paddle_tpu import fluid
from paddle_tpu.parallel import mesh as pmesh
from paddle_tpu.parallel.gspmd import DataParallelPolicy, GSPMDExecutor

fluid.set_flags({"FLAGS_quant_allreduce_block_size": 16})
rng = np.random.RandomState(2)
xs = rng.randn(16, 8).astype("float32")
tt = rng.randn(8, 8).astype("float32")   # a table fed WHOLE (replicated)
yd = (xs @ tt @ rng.randn(8, 1) / 8.0).astype("float32")

def run(hook):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        np.random.seed(4)
        x = fluid.data("x", [-1, 8], False, dtype="float32")
        t = fluid.data("t", [8, 8], False, dtype="float32")
        y = fluid.data("y", [-1, 1], False, dtype="float32")
        h = fluid.layers.matmul(x, t)
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.01).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        ex = GSPMDExecutor(main, pmesh.build_mesh({"dp": 8}),
                           DataParallelPolicy(), scope=scope,
                           feed_specs={"t": ()}, quant_hook=hook)
        return [float(np.asarray(
            ex.run(feed={"x": xs, "t": tt, "y": yd},
                   fetch_list=[loss.name])[0]).reshape(-1).mean())
            for _ in range(3)]

off = run(False)
on = run(True)
print("GSPMD_RESULT " + json.dumps({"off": off, "on": on}))
"""


def test_quant_island_honors_replicated_feed_subprocess():
    """A feed declared replicated (feed_specs={'t': ()}) enters the
    quant island WHOLE — the island's in_specs project the executor's
    resolved feed placement onto the batch axis instead of slicing
    every feed on dim 0.  With the old behavior the table was
    row-sliced per device and the first-step loss already diverged
    wildly from the hook-off run."""
    res = _run_child(_REPL_FEED_CHILD)
    off, on = np.asarray(res["off"]), np.asarray(res["on"])
    # forward identical up to float associativity (the hook-on fetch is
    # the mean of stacked local means, hook-off the global-view mean);
    # a SLICED table would diverge at ~1e0 relative here
    np.testing.assert_allclose(off[0], on[0], rtol=1e-6)
    np.testing.assert_allclose(on, off, rtol=1e-3)  # quant-bound after
