"""Graph-optimization pass layer (ISSUE 12, docs/PASSES.md):
pattern-matcher unit coverage (match/no-match on causal mask,
dropout-on/off, head-dim/shape edge cases), pass idempotence + ordering,
the flash-attention kernel-boundary proof, 20-step training parity on
bert-tiny, the measured per-pass cost attribution (the
pt_pass_bytes_saved_total surface), lane wiring (Executor, run_steps,
DP, serving load path) and the GSPMD fused-update leg (subprocess, per
the ring-test isolation pattern)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu import fluid, passes
from paddle_tpu.models import bert, gpt
from paddle_tpu.passes.framework import (PassContext, PassManager,
                                         pin_random_streams)

HERE = os.path.dirname(os.path.abspath(__file__))


def _flags_guard():
    return fluid.get_flags("FLAGS_graph_passes")["FLAGS_graph_passes"]


def _build_bert(num_layers=1, attn_dropout=0.0, hidden_dropout=0.0,
                seed=3, optimizer=True):
    cfg = bert.BertConfig.tiny(use_flash_attention=False,
                               num_layers=num_layers,
                               attn_dropout=attn_dropout,
                               hidden_dropout=hidden_dropout)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        np.random.seed(seed)
        feeds, loss, mlm, nsp = bert.build_bert_pretrain(cfg,
                                                         is_test=False)
        if optimizer:
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return cfg, main, startup, loss


def _types(program):
    return [op.type for op in program.global_block().ops]


# ---------------------------------------------------------------------------
# selection grammar + ordering
# ---------------------------------------------------------------------------


def test_resolve_passes_grammar():
    assert passes.resolve_passes("none") == []
    assert passes.resolve_passes("") == []
    assert passes.resolve_passes("default") == passes.DEFAULT_PASSES
    assert passes.resolve_passes("auto") == passes.DEFAULT_PASSES
    assert passes.resolve_passes("fuse_attention") == ["fuse_attention"]
    # "-name" drops from the default set (implies the default base)
    assert passes.resolve_passes("-fuse_attention") == \
        ["fuse_bias_act_dropout", "fuse_softmax_cross_entropy"]
    assert passes.resolve_passes("default,-fuse_bias_act_dropout") == \
        ["fuse_attention", "fuse_softmax_cross_entropy"]
    with pytest.raises(KeyError):
        passes.resolve_passes("no_such_pass")


def test_pass_order_contract():
    """The ordering between fusion passes and the DP/health transpiles
    is declared in ONE place; a pipeline violating it is rejected."""
    assert passes.PASS_ORDER == [
        "fuse_attention", "fuse_bias_act_dropout",
        "fuse_softmax_cross_entropy", "int8_weight_storage",
        "data_parallel_transpile", "health_sentinel"]
    # the adapters registered (the existing rewriters ARE passes now)
    for name in passes.PASS_ORDER:
        assert name in passes.list_program_passes()
    with pytest.raises(ValueError):
        PassManager(["fuse_bias_act_dropout", "fuse_attention"])
    with pytest.raises(ValueError):
        passes.resolve_passes("health_sentinel,fuse_attention")


def test_ir_registry_mirror():
    """Enumeration parity with the reference-style registry: the new
    program passes appear in fluid.ir.PassRegistry too."""
    from paddle_tpu.fluid import ir

    for name in ("fuse_attention", "fuse_bias_act_dropout"):
        assert ir.PassRegistry.has(name)


# ---------------------------------------------------------------------------
# fuse_attention matcher
# ---------------------------------------------------------------------------


def test_fuse_attention_matches_bert_and_is_idempotent():
    _cfg, main, _startup, _loss = _build_bert(num_layers=1)
    before = _types(main)
    rep = PassManager(["fuse_attention"]).run(main, PassContext(),
                                             selfcheck=True)
    e = rep[-1]
    assert e["changed"] and e["sites"] == 1 and e["bias_sites"] == 1
    after = _types(main)
    assert after.count("flash_attention") == 1
    assert after.count("flash_attention_grad") == 1
    # the matched pattern's softmax is gone; the NSP-head softmax stays
    assert after.count("softmax") == before.count("softmax") - 1
    assert after.count("matmul") == before.count("matmul") - 2
    # op-inventory delta recorded in the report
    assert e["op_delta"]["flash_attention"] == 1
    assert e["op_delta"]["softmax"] == -1
    # second run: no-op (the idempotence contract, also selfchecked)
    rep2 = PassManager(["fuse_attention"]).run(main, PassContext())
    assert rep2[-1]["changed"] is False


def test_fuse_attention_causal_gpt():
    cfg = gpt.GPTConfig.tiny(num_layers=1, hidden_dropout=0.0,
                             use_flash_attention=False)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        np.random.seed(5)
        feeds, loss = gpt.build_gpt_lm(cfg)
        fluid.optimizer.Adam(1e-3).minimize(loss)
    rep = PassManager(["fuse_attention"]).run(main, PassContext(),
                                              selfcheck=True)
    assert rep[-1]["sites"] == 1 and rep[-1]["causal_sites"] == 1
    fused = [op for op in main.global_block().ops
             if op.type == "flash_attention"]
    assert fused[0].attrs["causal"] is True
    assert "softmax_mask_fuse_upper_triangle" not in _types(main)


def test_no_match_on_training_attention_dropout():
    """Probs dropout is not expressible in the kernel: a TRAINING
    program with attention dropout keeps the exact composed path."""
    _cfg, main, _startup, _loss = _build_bert(num_layers=1,
                                              attn_dropout=0.1)
    rep = PassManager(["fuse_attention"]).run(main, PassContext())
    assert rep[-1]["changed"] is False
    assert "flash_attention" not in _types(main)


def test_is_test_dropout_absorbed_in_clone():
    """clone(for_test) keeps the dropout op with is_test=True
    (upscale_in_train = identity) — the inference program still fuses."""
    cfg, main, _startup, _loss = _build_bert(num_layers=1,
                                             attn_dropout=0.1,
                                             optimizer=False)
    test_prog = main.clone(for_test=True)
    rep = PassManager(["fuse_attention"]).run(test_prog, PassContext(),
                                              selfcheck=True)
    assert rep[-1]["sites"] == 1
    assert "dropout" not in [
        op.type for op in test_prog.global_block().ops
        if op.inputs.get("X", [""])[0].startswith("softmax")]


def test_keep_vars_pins_fetch_target():
    """A fetch target must keep its producer: naming the softmax output
    in keep_vars vetoes the match."""
    _cfg, main, _startup, _loss = _build_bert(num_layers=1)
    weights = [op.output("Out")[0]
               for op in main.global_block().ops
               if op.type == "softmax"][0]
    rep = PassManager(["fuse_attention"]).run(
        main, PassContext(keep_vars=[weights]))
    assert rep[-1]["changed"] is False


def test_no_match_on_mismatched_qk_shapes():
    """A decode-step query against a longer KV cache (q S=1, k S=16)
    must not match — the kernel computes self-attention over equal
    [B, n, S, d]."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = fluid.layers.data(name="q", shape=[2, 1, 8], dtype="float32")
        k = fluid.layers.data(name="k", shape=[2, 16, 8],
                              dtype="float32")
        v = fluid.layers.data(name="v", shape=[2, 16, 8],
                              dtype="float32")
        s = fluid.layers.matmul(q, k, transpose_y=True, alpha=0.35)
        w = fluid.layers.softmax(s)
        _out = fluid.layers.matmul(w, v)
    rep = PassManager(["fuse_attention"]).run(main, PassContext())
    assert rep[-1]["changed"] is False


def test_no_match_on_full_rank_bias():
    """A [B, n, S, S] additive bias is not expressible as the kernel's
    key bias — dims 1 and 2 must be 1."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = fluid.layers.data(name="q", shape=[2, 8, 8], dtype="float32")
        k = fluid.layers.data(name="k", shape=[2, 8, 8], dtype="float32")
        v = fluid.layers.data(name="v", shape=[2, 8, 8], dtype="float32")
        b = fluid.layers.data(name="b", shape=[2, 8, 8], dtype="float32")
        s = fluid.layers.matmul(q, k, transpose_y=True, alpha=0.35)
        s = fluid.layers.elementwise_add(s, b)
        w = fluid.layers.softmax(s)
        _out = fluid.layers.matmul(w, v)
    rep = PassManager(["fuse_attention"]).run(main, PassContext())
    assert rep[-1]["changed"] is False


# ---------------------------------------------------------------------------
# fuse_bias_act_dropout matcher
# ---------------------------------------------------------------------------


def _build_ffn(dropout_prob=0.0, act="gelu"):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        np.random.seed(7)
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        h = fluid.layers.fc(x, size=8, act=act)
        if dropout_prob:
            h = fluid.layers.dropout(
                h, dropout_prob=dropout_prob,
                dropout_implementation="upscale_in_train")
        loss = fluid.layers.mean(h)
        fluid.optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss


def test_fuse_bias_act_matches_and_absorbs_dropout():
    main, _s, _l = _build_ffn(dropout_prob=0.3)
    rep = PassManager(["fuse_bias_act_dropout"]).run(main, PassContext(),
                                                     selfcheck=True)
    e = rep[-1]
    assert e["sites"] == 1 and e["dropout_sites"] == 1
    t = _types(main)
    assert "fused_bias_act_dropout" in t
    assert "fused_bias_act_dropout_grad" in t
    assert "gelu" not in t and "dropout" not in t
    fused = [op for op in main.global_block().ops
             if op.type == "fused_bias_act_dropout"][0]
    assert fused.attrs["dropout_prob"] == 0.3
    # the absorbed dropout's pre-fusion stream identity rides along
    assert "rng_op_index" in fused.attrs
    # the mask output survives for the backward
    assert fused.outputs.get("Mask")


def test_relu_and_residual_adds_not_matched():
    main, _s, _l = _build_ffn(act="relu")
    rep = PassManager(["fuse_bias_act_dropout"]).run(main, PassContext())
    assert rep[-1]["changed"] is False
    # residual add (rank-N + rank-N) then gelu: bias must be rank-1
    main2, startup2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(main2, startup2), fluid.unique_name.guard():
        a = fluid.layers.data(name="a", shape=[4, 8], dtype="float32")
        b = fluid.layers.data(name="b", shape=[4, 8], dtype="float32")
        h = fluid.layers.elementwise_add(a, b)
        g = fluid.layers.gelu(h)
        _loss = fluid.layers.mean(g)
    rep2 = PassManager(["fuse_bias_act_dropout"]).run(main2,
                                                      PassContext())
    assert rep2[-1]["changed"] is False


def test_dropout_mask_stream_parity():
    """The fused program draws the SAME dropout masks the unfused one
    would (rng_op_index pin) — 5 training steps agree bit-exactly."""
    def run(spec):
        prior = _flags_guard()
        fluid.set_flags({"FLAGS_graph_passes": spec})
        try:
            main, startup, loss = _build_ffn(dropout_prob=0.3)
            data = {"x": np.random.RandomState(0).randn(8, 16)
                    .astype("float32")}
            scope = fluid.Scope()
            out = []
            with fluid.scope_guard(scope):
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startup)
                for _ in range(5):
                    (lv,) = exe.run(main, feed=data,
                                    fetch_list=[loss.name])
                    out.append(float(np.asarray(lv)))
            return out
        finally:
            fluid.set_flags({"FLAGS_graph_passes": prior})

    a, b = run("none"), run("default")
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# parity + attribution (the acceptance gates)
# ---------------------------------------------------------------------------


def test_bert_tiny_20_step_training_parity():
    """ISSUE 12 acceptance: 20-step loss parity <= 1e-5 fp32 between the
    fused (passes-on) and unfused bert-tiny training runs (measured
    bit-exact on the CPU reference path)."""
    def run(spec):
        prior = _flags_guard()
        fluid.set_flags({"FLAGS_graph_passes": spec})
        try:
            cfg, main, startup, loss = _build_bert(num_layers=2)
            data = bert.make_fake_batch(cfg, batch=4, seq_len=32, seed=7)
            scope = fluid.Scope()
            out = []
            with fluid.scope_guard(scope):
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(startup)
                for _ in range(20):
                    (lv,) = exe.run(main, feed=data,
                                    fetch_list=[loss.name])
                    out.append(float(np.asarray(lv)))
            return out
        finally:
            fluid.set_flags({"FLAGS_graph_passes": prior})

    unfused, fused = run("none"), run("default")
    assert max(abs(a - b) for a, b in zip(unfused, fused)) <= 1e-5
    assert fused[-1] < fused[0]  # it actually trained


def test_cost_attribution_books_bytes_reduction(monkeypatch):
    """ISSUE 12 acceptance: the pass report books a NONZERO
    bytes_accessed reduction from cost_analysis for fuse_attention
    (CPU-measurable across the kernel boundary — PT_FLASH_FORCE_PALLAS
    engages the blockwise kernel in interpret mode, so the S×S tensor's
    absence is visible to the cost model), and the measured delta lands on
    pt_pass_bytes_saved_total{pass}."""
    from paddle_tpu import observability as obs

    monkeypatch.setenv("PT_FLASH_FORCE_PALLAS", "1")
    cfg = bert.BertConfig.tiny(use_flash_attention=False,
                               attn_dropout=0.0, hidden_dropout=0.0,
                               num_layers=1, max_position=256)
    data = bert.make_fake_batch(cfg, batch=2, seq_len=256, seed=7)

    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), \
                fluid.unique_name.guard():
            np.random.seed(3)
            feeds, loss, _m, _n = bert.build_bert_pretrain(
                cfg, is_test=False)
            fluid.optimizer.Adam(1e-3).minimize(loss)
        return main, startup, loss

    main, _s, loss = build()
    out = passes.attribute_costs(build, data, [loss.name],
                                 spec="default")
    per = {e["pass"]: e for e in out["per_pass"]}
    assert per["fuse_attention"]["bytes_accessed_delta"] > 0
    assert out["final"]["bytes_accessed"] < \
        out["baseline"]["bytes_accessed"]
    snap = obs.snapshot()
    saved = snap.get("pt_pass_bytes_saved_total", {}).get("samples", {})
    assert any("fuse_attention" in k for k in saved)
    applied = snap.get("pt_pass_applied_total", {}).get("samples", {})
    assert applied


def test_jaxpr_flash_kernel_boundary(monkeypatch):
    """The kernel-boundary proof (the test_fused_update jaxpr-precedent,
    CPU-expressible form of the HLO custom-call assertion): with the
    Pallas path engaged (interpret mode off-TPU), the fused program's
    traced step crosses the kernel boundary in forward AND backward —
    the attention subgraph lowers to pallas_calls, not to the composed
    softmax chain."""
    import jax

    from paddle_tpu.fluid.executor import BlockPlan

    monkeypatch.setenv("PT_FLASH_FORCE_PALLAS", "1")
    _cfg, main, startup, loss = _build_bert(num_layers=1)
    PassManager(["fuse_attention"]).run(main, PassContext())
    cfg = bert.BertConfig.tiny(use_flash_attention=False, num_layers=1,
                               attn_dropout=0.0, hidden_dropout=0.0)
    data = bert.make_fake_batch(cfg, batch=2, seq_len=32, seed=1)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        plan = BlockPlan(main, main.global_block(), list(data.keys()),
                         [loss.name], scope)
        body = plan.make_body()
        donated = {n: scope.get(n) for n in plan.donated_names}
        readonly = {n: scope.get(n) for n in plan.readonly_names}
        jaxpr = jax.make_jaxpr(
            lambda d, r, f: body(d, r, f, np.uint32(0)))(
            donated, readonly,
            {k: np.asarray(v) for k, v in data.items()})
    txt = str(jaxpr)
    # forward (1 kernel) + backward (dq and dk/dv kernels) all cross
    # the boundary; the grad op's vjp re-trace adds another fwd call
    assert txt.count("pallas_call") >= 3


# ---------------------------------------------------------------------------
# lane wiring
# ---------------------------------------------------------------------------


def test_off_configuration_is_bit_identical():
    """FLAGS_graph_passes=none: the program the executor compiles is
    op-for-op identical to the pre-pass-layer one."""
    prior = _flags_guard()
    fluid.set_flags({"FLAGS_graph_passes": "none"})
    try:
        _cfg, main, startup, loss = _build_bert(num_layers=1)
        before = [(op.type, dict(op.attrs)) for op in
                  main.global_block().ops]
        cfg = bert.BertConfig.tiny(use_flash_attention=False,
                                   num_layers=1, attn_dropout=0.0,
                                   hidden_dropout=0.0)
        data = bert.make_fake_batch(cfg, batch=2, seq_len=32, seed=1)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            exe.run(main, feed=data, fetch_list=[loss.name])
        after = [(op.type, dict(op.attrs)) for op in
                 main.global_block().ops]
        assert before == after
        assert main._graph_passes_done == ()
        assert getattr(main, "_pass_report", None) is None
    finally:
        fluid.set_flags({"FLAGS_graph_passes": prior})


def test_flag_flip_after_compile_warns_not_rewrites():
    prior = _flags_guard()
    fluid.set_flags({"FLAGS_graph_passes": "none"})
    try:
        main, startup, loss = _build_ffn()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            data = {"x": np.zeros((2, 16), "float32")}
            exe.run(main, feed=data, fetch_list=[loss.name])
            fluid.set_flags({"FLAGS_graph_passes": "default"})
            with pytest.warns(UserWarning, match="FLAGS_graph_passes"):
                exe.run(main, feed=data, fetch_list=[loss.name])
        assert "fused_bias_act_dropout" not in _types(main)
    finally:
        fluid.set_flags({"FLAGS_graph_passes": prior})


def test_executor_and_chain_lanes_apply_passes():
    prior = _flags_guard()
    fluid.set_flags({"FLAGS_graph_passes": "default"})
    try:
        main, startup, loss = _build_ffn()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            data = {"x": np.zeros((2, 16), "float32")}
            exe.run_steps(main, feed=data, n_steps=2,
                          fetch_list=[loss.name])
        assert "fused_bias_act_dropout" in _types(main)
        assert main._pass_report and main._graph_passes_done == \
            tuple(passes.DEFAULT_PASSES)
    finally:
        fluid.set_flags({"FLAGS_graph_passes": prior})


def test_serving_load_path_applies_passes(tmp_path):
    """The AnalysisPredictor load path (serving engine's model load)
    rewrites a loaded inference program — the motivation case: an
    exported program built from the plain layers API gets the fused
    kernels, predictions matching the passes-off load <= 1e-5."""
    from paddle_tpu.fluid.executor import Scope, scope_guard
    from paddle_tpu.inference import (AnalysisConfig,
                                      create_paddle_predictor,
                                      PaddleTensor)

    d = str(tmp_path)
    cfg = bert.BertConfig.tiny(use_flash_attention=False, num_layers=1)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        np.random.seed(3)
        src = fluid.data("src_ids", [-1, -1], False, dtype="int64")
        pos = fluid.data("pos_ids", [-1, -1], False, dtype="int64")
        sent = fluid.data("sent_ids", [-1, -1], False, dtype="int64")
        mask = fluid.data("input_mask", [-1, -1], False, dtype="float32")
        enc = bert.bert_encoder(src, pos, sent, mask, cfg, is_test=True)
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(
            d, ["src_ids", "pos_ids", "sent_ids", "input_mask"], [enc],
            exe, main_program=main)

    data = bert.make_fake_batch(cfg, batch=2, seq_len=32, seed=9)
    feeds = [PaddleTensor(data[n], name=n)
             for n in ("src_ids", "pos_ids", "sent_ids", "input_mask")]

    def load(spec):
        prior = _flags_guard()
        fluid.set_flags({"FLAGS_graph_passes": spec})
        try:
            config = AnalysisConfig(d)
            config.disable_gpu()
            p = create_paddle_predictor(config)
            (out,) = p.run(feeds)
            return p, out.as_ndarray()
        finally:
            fluid.set_flags({"FLAGS_graph_passes": prior})

    p_off, out_off = load("none")
    p_on, out_on = load("default")
    t = [op.type for op in p_on._program.global_block().ops]
    assert "flash_attention" in t
    assert "fused_bias_act_dropout" in t
    np.testing.assert_allclose(out_on, out_off, atol=1e-5, rtol=0)


def test_dp_runner_applies_passes():
    """The DP lane applies passes BEFORE the transpile (the declared
    PASS_ORDER): the transpiled program carries both the fused op and
    the DP collectives."""
    from paddle_tpu.parallel import DataParallelRunner

    prior = _flags_guard()
    fluid.set_flags({"FLAGS_graph_passes": "default"})
    try:
        main, startup, loss = _build_ffn()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            DataParallelRunner(main, loss.name)
        t = _types(main)
        assert "fused_bias_act_dropout" in t
        assert any(x.startswith("c_allreduce") for x in t)
    finally:
        fluid.set_flags({"FLAGS_graph_passes": prior})


# ---------------------------------------------------------------------------
# idempotence enforcement + stream pinning
# ---------------------------------------------------------------------------


def test_selfcheck_catches_non_idempotent_pass():
    from paddle_tpu.passes.framework import (_PASS_REGISTRY, ProgramPass,
                                             register_program_pass)

    @register_program_pass
    class _BadPass(ProgramPass):
        name = "_test_bad_pass"

        def apply(self, program, ctx):
            return {"changed": True, "sites": 1}  # "changes" every time

    try:
        main, _s, _l = _build_ffn()
        with pytest.raises(AssertionError, match="idempotence"):
            PassManager(["_test_bad_pass"]).run(main, PassContext(),
                                                selfcheck=True)
    finally:
        _PASS_REGISTRY.pop("_test_bad_pass", None)


def test_pin_random_streams_stamps_block0_random_ops():
    main, _s, _l = _build_ffn(dropout_prob=0.2)
    pin_random_streams(main)
    drops = [op for op in main.global_block().ops
             if op.type == "dropout"]
    idx = [i for i, op in enumerate(main.global_block().ops)
           if op.type == "dropout"]
    assert drops and all(
        op.attrs["rng_op_index"] == i for op, i in zip(drops, idx))


# ---------------------------------------------------------------------------
# GSPMD fused-update leg (subprocess, 8-device CPU mesh)
# ---------------------------------------------------------------------------

_GSPMD_FUSED_CHILD = r"""
import cpu_mesh  # noqa: F401
import json
import numpy as np
from paddle_tpu import fluid
from paddle_tpu.parallel import DataParallelRunner

fluid.set_flags({"FLAGS_quant_allreduce_block_size": 16})
rng = np.random.RandomState(0)
xs = rng.randn(16, 8).astype("float32")
ys = rng.randint(0, 3, (16, 1)).astype("int64")

def build(seed=5):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        np.random.seed(seed)
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, size=6, act="relu")
        pred = fluid.layers.fc(h, size=3, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, y))
        fluid.optimizer.AdamW(0.01, weight_decay=0.01).minimize(loss)
    return main, startup, loss

def run(gspmd, fused):
    fluid.set_flags({"FLAGS_fused_update": fused})
    main, startup, loss = build()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        r = DataParallelRunner(main, loss.name, gspmd=gspmd,
                               quant_grads=True)
        losses = [float(np.mean(r.run(exe, {"x": xs, "y": ys},
                                      [loss.name], scope)[0]))
                  for _ in range(15)]
        qp = (r._gspmd_exec.compiled_blocks()[0].qplan if gspmd
              else None)
        prog_ops = [op.type for op in r.program.global_block().ops]
    return losses, qp, prog_ops

lt, _, ops_t = run(False, True)       # transpiler fused lane
lg, qp, ops_g = run(True, True)       # gspmd fused leg
lp, qp2, _ = run(True, False)         # gspmd plain quant

from paddle_tpu import observability as obs
snap = obs.snapshot()
saved = snap.get("pt_fused_update_bytes_saved_total",
                 {}).get("samples", {})
print("GSPMD_FUSED_RESULT " + json.dumps({
    "fused_grads": qp.fused_grads,
    "plain_lane_fused_grads": qp2.fused_grads,
    "bucket_fused": [b.get("fused_update") for b in qp.bucket_report],
    "bytes_saved_plan": qp.fused_bytes_saved,
    "bytes_saved_booked": bool(saved),
    "prog_has_allreduce": any(t.startswith("c_allreduce")
                              for t in ops_g),
    "transpiler_has_fused_adamw": "fused_adamw_quant_grad" in ops_t,
    "max_fused_vs_transpiler": max(abs(a - b)
                                   for a, b in zip(lt, lg)),
    "max_fused_vs_plain": max(abs(a - b) for a, b in zip(lp, lg)),
    "trained": lg[-1] < lg[0],
}))
"""


def test_gspmd_fused_update_leg_subprocess():
    """The fused dequant→update→requant rewrite ported to the GSPMD
    optimizer leg (ROADMAP: the blocker for flipping
    FLAGS_gspmd_executor): eligible optimizer ops consume the keep-quant
    wire triple at the plan level (program untouched — no c_allreduce
    ops appear), losses match the transpiler fused lane <= 1e-3, and
    the saved bytes book on pt_fused_update_bytes_saved_total."""
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + \
        os.path.dirname(HERE) + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", _GSPMD_FUSED_CHILD],
                       capture_output=True, text=True, timeout=600,
                       env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("GSPMD_FUSED_RESULT ")][0]
    res = json.loads(line.split(" ", 1)[1])
    assert res["fused_grads"], res
    assert res["plain_lane_fused_grads"] == []
    assert res["bucket_fused"] == [True]
    assert res["bytes_saved_plan"] > 0 and res["bytes_saved_booked"]
    assert not res["prog_has_allreduce"]
    assert res["transpiler_has_fused_adamw"]
    assert res["max_fused_vs_transpiler"] <= 1e-3
    assert res["max_fused_vs_plain"] <= 1e-3
    assert res["trained"]


# ---------------------------------------------------------------------------
# review-hardening regressions
# ---------------------------------------------------------------------------


def test_unknown_exclusion_rejected():
    """A typo'd "-name" must fail loudly, not silently leave the pass
    enabled (the operator set it to RULE OUT a pass while debugging)."""
    with pytest.raises(KeyError):
        passes.resolve_passes("-fuse_attenton")  # sic


def test_sub_block_consumer_ends_the_chain():
    """A chain op living in a sub-block (while/cond body) must never be
    absorbed: the walk stops at the block boundary instead of crashing
    the rewrite's block-0 index (regression: KeyError out of
    _match/_rewrite when the dropout after gelu sat in a sub-block)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        h = fluid.layers.fc(x, size=8, act="gelu")
    blk = main.global_block()
    sub = main._create_block()
    out = sub.create_var(name="sub_out", shape=[-1, 8], dtype="float32")
    sub.append_op("dropout", inputs={"X": [h.name]},
                  outputs={"Out": [out],
                           "Mask": [sub.create_var(
                               name="sub_mask", shape=[-1, 8],
                               dtype="uint8")]},
                  attrs={"dropout_prob": 0.3,
                         "dropout_implementation": "upscale_in_train"})
    main._rollback()
    rep = PassManager(["fuse_bias_act_dropout"]).run(main, PassContext(),
                                                     selfcheck=True)
    # add->gelu fused in block 0; the sub-block dropout untouched and
    # still reading the (re-emitted) gelu output name
    assert rep[-1]["sites"] == 1 and rep[-1]["dropout_sites"] == 0
    assert "fused_bias_act_dropout" in [op.type for op in blk.ops]
    assert [op.type for op in main.block(sub.idx).ops] == ["dropout"]


def test_attention_mask_fetch_pin():
    """fuse_attention drops an absorbed identity-dropout's Mask, so a
    Mask named in keep_vars (a fetch target) vetoes the match."""
    cfg, main, _s, _l = _build_bert(num_layers=1, attn_dropout=0.1,
                                    optimizer=False)
    test_prog = main.clone(for_test=True)
    masks = [op.outputs["Mask"][0]
             for op in test_prog.global_block().ops
             if op.type == "dropout"]
    rep = PassManager(["fuse_attention"]).run(
        test_prog, PassContext(keep_vars=masks))
    assert rep[-1]["changed"] is False


def test_downgrade_dropout_impl_rejected():
    """A hand-built fused_bias_act_dropout desc with downgrade dropout
    semantics fails loudly at trace time — the kernel and the
    mask-replay backward bake the upscale factor in."""
    from paddle_tpu.fluid import registry

    info = registry.get_op("fused_bias_act_dropout")
    ctx = registry.LowerContext()
    ctx.program = None
    ctx.op_index = 0
    with pytest.raises(NotImplementedError, match="upscale_in_train"):
        info.lower(ctx, np.zeros((2, 8), "float32"),
                   np.zeros((8,), "float32"),
                   attrs={"dropout_prob": 0.3,
                          "dropout_implementation": "downgrade_in_infer"})


def test_hot_path_skips_grammar_resolution():
    """After a program's pass decision, re-entry with the unchanged flag
    string is one attribute compare — resolve_passes must not re-run
    per step (regression for the ±2% step-overhead bar)."""
    from unittest import mock

    main, _s, _l = _build_ffn()
    passes.apply_graph_passes(main, lane="single")
    with mock.patch.object(passes.framework, "resolve_passes",
                           side_effect=AssertionError("resolved")) as _m:
        passes.apply_graph_passes(main, lane="single")


# ---------------------------------------------------------------------------
# fuse_softmax_cross_entropy (ISSUE 15 satellite)
# ---------------------------------------------------------------------------


def _build_sce(soft_label=False, act_softmax=True, optimizer=True,
               seed=5):
    """The classifier-head spelling: fc → softmax → cross_entropy —
    the book-script/MLM-head composition the pass targets."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        np.random.seed(seed)
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        if soft_label:
            y = fluid.layers.data(name="y", shape=[4], dtype="float32")
        else:
            y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, size=16, act="relu")
        logits = fluid.layers.fc(h, size=4)
        probs = fluid.layers.softmax(logits)
        ce = fluid.layers.cross_entropy(probs, y, soft_label=soft_label)
        loss = fluid.layers.mean(ce)
        if optimizer:
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _sce_data(soft_label=False, batch=16, seed=0):
    rng = np.random.RandomState(seed)
    xb = rng.uniform(-1, 1, (batch, 8)).astype("float32")
    if soft_label:
        yl = rng.uniform(0, 1, (batch, 4)).astype("float32")
        yl /= yl.sum(axis=1, keepdims=True)
    else:
        yl = rng.randint(0, 4, (batch, 1)).astype("int64")
    return {"x": xb, "y": yl}


def test_fuse_softmax_cross_entropy_matches_and_is_idempotent():
    main, _s, loss = _build_sce()
    rep = PassManager(["fuse_softmax_cross_entropy"]).run(
        main, PassContext(keep_vars=[loss.name]), selfcheck=True)
    entry = rep[-1]
    assert entry["changed"] and entry["sites"] == 1
    # dynamic batch dim -> no static model (honest accounting); a
    # static-shape build books the probs write+read
    assert entry["modeled_bytes_saved"] == 0
    static_main, _s2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(static_main, _s2), fluid.unique_name.guard():
        xs = fluid.data("x", [16, 8], False, dtype="float32")
        ys = fluid.data("y", [16, 1], False, dtype="int64")
        probs = fluid.layers.softmax(fluid.layers.fc(xs, size=4))
        fluid.layers.mean(fluid.layers.cross_entropy(probs, ys))
    srep = PassManager(["fuse_softmax_cross_entropy"]).run(
        static_main, PassContext())
    assert srep[-1]["modeled_bytes_saved"] == 8 * 16 * 4
    types = _types(main)
    assert "fused_softmax_cross_entropy" in types
    assert "fused_softmax_cross_entropy_grad" in types
    assert "cross_entropy" not in types
    assert "softmax_grad" not in types
    assert "cross_entropy_grad" not in types
    # the softmax op is RETAINED (the probs are the model\'s prediction
    # surface — book scripts export them); it is now consumer-less, so
    # per-fetch pruning drops it from loss-only executables
    assert types.count("softmax") == 1


def test_fuse_softmax_cross_entropy_bit_exact_20_steps():
    """The satellite's acceptance: 20-step training parity between the
    fused and composed spellings is BIT-EXACT (the fused lowering is
    the literal composition of the two originals), for hard and soft
    labels."""
    for soft in (False, True):
        def run(spec):
            prior = _flags_guard()
            fluid.set_flags({"FLAGS_graph_passes": spec})
            try:
                main, startup, loss = _build_sce(soft_label=soft)
                data = _sce_data(soft_label=soft)
                scope = fluid.Scope()
                out = []
                with fluid.scope_guard(scope):
                    exe = fluid.Executor(fluid.CPUPlace())
                    exe.run(startup)
                    for _ in range(20):
                        (lv,) = exe.run(main, feed=data,
                                        fetch_list=[loss.name])
                        out.append(float(np.asarray(lv)))
                return out
            finally:
                fluid.set_flags({"FLAGS_graph_passes": prior})

        unfused = run("none")
        fused = run("fuse_softmax_cross_entropy")
        np.testing.assert_array_equal(np.asarray(unfused),
                                      np.asarray(fused))
        assert fused[-1] < fused[0]  # it actually trained


def test_fuse_softmax_cross_entropy_vetoes_second_reader():
    # a second forward reader of the probabilities (an accuracy head)
    # vetoes the match — its backward would be a partial-grad
    # accumulation the single fused grad cannot replace
    main2, _s2, loss2 = _build_sce(optimizer=False)
    with fluid.program_guard(main2):
        probs2 = next(op.output("Out")[0]
                      for op in main2.global_block().ops
                      if op.type == "softmax")
        fluid.layers.reduce_max(main2.global_block().var(probs2))
    rep2 = PassManager(["fuse_softmax_cross_entropy"]).run(
        main2, PassContext(keep_vars=[loss2.name]))
    assert not rep2[-1]["changed"]
    assert "softmax" in _types(main2)


def test_fuse_softmax_cross_entropy_probs_fetch_survives():
    """The book-script regression (recognize_digits/word2vec/...): the
    probs var is the model\'s PREDICTION, fetched/exported AFTER
    training ran with a loss-only fetch list.  The retained softmax op
    keeps its producer alive for that second signature (and the
    inference clone), while the loss-only executable prunes it."""
    prior = _flags_guard()
    fluid.set_flags({"FLAGS_graph_passes": "fuse_softmax_cross_entropy"})
    try:
        main, startup, loss = _build_sce()
        probs = next(op.output("Out")[0]
                     for op in main.global_block().ops
                     if op.type == "softmax")
        data = _sce_data()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            for _ in range(3):
                exe.run(main, feed=data, fetch_list=[loss.name])
            assert "fused_softmax_cross_entropy" in _types(main)
            # the prediction fetch (a NEW signature) still resolves
            (pv,) = exe.run(main, feed=data, fetch_list=[probs])
            pv = np.asarray(pv)
            assert pv.shape == (16, 4)
            np.testing.assert_allclose(pv.sum(axis=1), 1.0, rtol=1e-5)
            # and the inference clone keeps the producer too
            infer = main.clone(for_test=True)
            (pv2,) = exe.run(infer, feed={"x": data["x"]},
                             fetch_list=[probs])
            assert np.asarray(pv2).shape == (16, 4)
    finally:
        fluid.set_flags({"FLAGS_graph_passes": prior})


def test_fuse_softmax_cross_entropy_in_default_pipeline():
    assert "fuse_softmax_cross_entropy" in passes.DEFAULT_PASSES
    # declared ordering: after the attention/FFN fusions, before the
    # transpile adapters
    order = passes.PASS_ORDER
    assert order.index("fuse_softmax_cross_entropy") > \
        order.index("fuse_bias_act_dropout")
    assert order.index("fuse_softmax_cross_entropy") < \
        order.index("data_parallel_transpile")
    # the default lane application fuses the classifier head
    main, startup, loss = _build_sce()
    prior = _flags_guard()
    fluid.set_flags({"FLAGS_graph_passes": "default"})
    try:
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            exe.run(main, feed=_sce_data(), fetch_list=[loss.name])
        assert "fused_softmax_cross_entropy" in _types(main)
    finally:
        fluid.set_flags({"FLAGS_graph_passes": prior})


# ---------------------------------------------------------------------------
# int8_weight_storage (ISSUE 17: dual-int8 weight storage at rest)
# ---------------------------------------------------------------------------


def _build_mlp():
    """Plain inference MLP: two fc weights (eligible), two biases +
    an embedding table (ineligible).  Deterministic names under
    unique_name.guard — two builds claim the same weight set."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ids = fluid.data("ids", [4, 6], False, dtype="int64")
        x = fluid.layers.embedding(ids, size=[32, 16])
        x = fluid.layers.reduce_mean(x, dim=1)
        h = fluid.layers.fc(x, size=24, act="relu")
        out = fluid.layers.fc(h, size=8)
    return main, startup, out


def _int8_saved_weights():
    from paddle_tpu import observability as obs

    fam = obs.REGISTRY.get("pt_int8_bytes_saved_total")
    samples = fam._snapshot()["samples"] if fam else {}
    return samples.get(("weights",), 0.0)


def _claimed(program):
    return {op.output("Out")[0]
            for op in program.global_block().ops
            if op.type == "dequantize_weight_storage"}


def test_int8_weight_storage_rewrite_and_parity():
    """The at-rest weight rewrite end to end: 2 fc weights claimed, the
    dequantize_weight_storage producers installed, scope fp32 arrays
    swapped for dual-int8 triples, the counter booked — and the
    program's output matches the fp32 run (~14.6 significant bits)."""
    from paddle_tpu.passes.int8_weights import (quantize_scope_weights,
                                                storage_var_names)

    main, startup, out = _build_mlp()
    feed = {"ids": np.random.RandomState(0).randint(
        0, 32, (4, 6)).astype(np.int64)}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        (ref,) = exe.run(main, feed=feed, fetch_list=[out.name])

        PassManager(["int8_weight_storage"]).run(
            main, PassContext(lane="single"))
        pr = main._pass_report[-1]
        assert pr["changed"] and pr["sites"] == 2
        names = sorted(_claimed(main))
        assert len(names) == 2
        # biases (1-D) and the embedding table (lookup_table consumer)
        # keep full precision; the claimed weights lose persistability
        for nm in names:
            v = main.global_block().vars[nm]
            assert len(v.shape) == 2 and not v.persistable
        # modeled saving: 4rc - (2rc + 4r) per weight
        modeled = sum(2 * v.shape[0] * v.shape[1] - 4 * v.shape[0]
                      for v in (main.global_block().vars[n]
                                for n in names))
        assert pr["modeled_bytes_saved"] == modeled

        # idempotent: a second application claims nothing new
        PassManager(["int8_weight_storage"]).run(
            main, PassContext(lane="single"))
        assert not main._pass_report[-1]["changed"]
        assert len(_claimed(main)) == 2

        before = _int8_saved_weights()
        info = quantize_scope_weights(scope, main)
        assert info["weights"] == 2
        assert _int8_saved_weights() - before == info["bytes_saved"] > 0
        for nm in names:
            assert scope.get(nm) is None, "fp32 weight survived"
            assert all(scope.get(s) is not None
                       for s in storage_var_names(nm))
        # second conversion is a no-op (triples already installed)
        assert quantize_scope_weights(scope, main)["weights"] == 0

        (got,) = exe.run(main, feed=feed, fetch_list=[out.name])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0, atol=1e-2)


def test_int8_weight_storage_vetoes():
    """Backward consumers veto (training programs are untouched) and
    keep_vars veto (a pinned weight keeps fp32 storage)."""
    # training program: every fc weight also feeds its grad op
    _, train_main, _, _ = _build_bert(optimizer=True)
    PassManager(["int8_weight_storage"]).run(
        train_main, PassContext(lane="single"))
    pr = train_main._pass_report[-1]
    assert not pr["changed"] and pr["sites"] == 0

    # learn the claimable set, then pin one of them
    probe, _, _ = _build_mlp()
    PassManager(["int8_weight_storage"]).run(
        probe, PassContext(lane="single"))
    full = _claimed(probe)
    assert len(full) == 2
    pinned = sorted(full)[0]
    main, _, _ = _build_mlp()
    PassManager(["int8_weight_storage"]).run(
        main, PassContext(lane="single", keep_vars={pinned}))
    assert _claimed(main) == full - {pinned}
