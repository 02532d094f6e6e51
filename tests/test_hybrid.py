"""Hybrid (dp × mp × sp GSPMD) parallel training parity vs single device.

Mirrors the reference's TestParallelExecutorBase.check_network_convergence
(parallel_executor_test_base.py:31-33): same model, same init, run
single-device and multi-device, assert per-step losses match.
"""

import numpy as np

from paddle_tpu import fluid
from paddle_tpu.fluid.executor import Scope, scope_guard
from paddle_tpu.models import bert
from paddle_tpu.parallel import (HybridParallelRunner, ShardingRule,
                                 build_hybrid_mesh, megatron_rules)
from paddle_tpu.parallel import mesh as pmesh


def _build(seed=3):
    cfg = bert.BertConfig.tiny(hidden_dropout=0.0, attn_dropout=0.0)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, loss, mlm, acc = bert.build_bert_pretrain(cfg, is_test=False)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    batches = [bert.make_fake_batch(cfg, batch=8, seq_len=16, seed=seed + i)
               for i in range(3)]
    return main, startup, loss, batches


def _init_scope(startup):
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
    return scope


def _copy_scope(scope):
    s = Scope()
    for k in scope.keys():
        v = scope.get(k)
        if v is not None:
            s.set(k, np.asarray(v).copy())
    return s


def test_hybrid_matches_single_device():
    main, startup, loss, batches = _build()
    scope1 = _init_scope(startup)
    scope2 = _copy_scope(scope1)

    # single device
    ref_losses = []
    with scope_guard(scope1):
        exe = fluid.Executor(fluid.CPUPlace())
        for b in batches:
            ref_losses.append(exe.run(main, feed=b, fetch_list=[loss.name])[0])

    # 8-device hybrid mesh with Megatron TP + batch + sequence sharding
    mesh = build_hybrid_mesh(8, mp=2, sp=2)
    seq_spec = (pmesh.DATA_AXIS, pmesh.SEQ_AXIS)
    runner = HybridParallelRunner(
        main, mesh, rules=megatron_rules(),
        feed_specs={n: seq_spec for n in
                    ("src_ids", "pos_ids", "sent_ids", "input_mask")})
    par_losses = [runner.run(scope2, b, [loss.name])[0] for b in batches]

    for r, p in zip(ref_losses, par_losses):
        np.testing.assert_allclose(np.asarray(r), np.asarray(p),
                                   rtol=2e-3, atol=2e-3)


def test_params_stay_sharded_across_steps():
    main, startup, loss, batches = _build(seed=11)
    scope = _init_scope(startup)
    mesh = build_hybrid_mesh(8, mp=2)
    runner = HybridParallelRunner(main, mesh, rules=megatron_rules())
    runner.run(scope, batches[0], [loss.name])
    w = scope.get("encoder_layer_0_multi_head_att_query_fc.w_0")
    # column-parallel weight should remain sharded over mp after the step
    assert not w.sharding.is_fully_replicated


def test_sharding_rule_guards():
    rule = megatron_rules()
    mesh = build_hybrid_mesh(8, mp=2)
    # weight sharded on columns
    assert rule.spec_for("encoder_layer_0_multi_head_att_query_fc.w_0",
                         shape=(64, 64), mesh=mesh) == (None, "mp")
    # its adam moment accumulator follows the same layout
    assert rule.spec_for(
        "encoder_layer_0_multi_head_att_query_fc.w_0_moment1_0",
        shape=(64, 64), mesh=mesh) == (None, "mp")
    # scalar beta-pow accumulator must NOT be sharded despite the name match
    assert rule.spec_for(
        "encoder_layer_0_multi_head_att_query_fc.b_0_beta1_pow_acc_0",
        shape=(1,), mesh=mesh) == (None,)
    # unmatched name → replicated
    assert rule.spec_for("pre_encoder_ln_scale", shape=(64,), mesh=mesh) == ()


def test_zero1_optimizer_state_sharding():
    """ZeRO-1: accumulators shard over dp, loss matches the replicated run."""
    import jax

    from paddle_tpu import fluid
    from paddle_tpu.parallel import HybridParallelRunner, build_hybrid_mesh

    rng = np.random.RandomState(0)
    xd = rng.uniform(-1, 1, (16, 8)).astype("float32")
    yd = (xd @ rng.randn(8, 1)).astype("float32")

    def build_and_run(zero_stage):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = fluid.data("x", [-1, 8], False, dtype="float32")
            y = fluid.data("y", [-1, 1], False, dtype="float32")
            h = fluid.layers.fc(x, size=16, act="relu",
                                param_attr=fluid.ParamAttr(name="z_w1"))
            pred = fluid.layers.fc(h, size=1,
                                   param_attr=fluid.ParamAttr(name="z_w2"))
            loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
        mesh = build_hybrid_mesh(4, mp=1)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            runner = HybridParallelRunner(main, mesh, scope=scope,
                                          zero_stage=zero_stage)
            losses = []
            for _ in range(5):
                (lv,) = runner.run(feed={"x": xd, "y": yd},
                                   fetch_list=[loss.name])
                losses.append(float(np.asarray(lv).reshape(-1)[0]))
            moment = next(scope.get(n) for n in main.global_block().vars
                          if "z_w1_moment1" in n and scope.get(n) is not None)
        return losses, moment

    l0, m0 = build_and_run(zero_stage=0)
    l1, m1 = build_and_run(zero_stage=1)
    np.testing.assert_allclose(l1, l0, rtol=1e-4, atol=1e-5)
    # the zero-1 accumulator is actually dp-sharded on the mesh
    spec = m1.sharding.spec
    assert spec and spec[0] == "dp", spec
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m0),
                               rtol=1e-4, atol=1e-6)
    # the zero_gather_quant (quantized weight-update gather) end-to-end
    # test lives in tests/test_ring_collectives.py, subprocess-isolated —
    # this module's blanket heap-corruption skip would leave the feature
    # with zero executed coverage on the CPU mesh


def test_capture_hlo_shows_expected_collectives():
    """The optimized (post-GSPMD) HLO of a dp×mp step must contain the
    collectives the sharding implies: all-reduce for dp grad sync, and
    all-gather or reduce-scatter from the Megatron mp partitioning
    (reference analog: multi_devices_graph_pass.cc:594 inserting
    allreduce ops — here XLA's SPMD partitioner does the inserting and we
    assert on its output)."""
    main, startup, loss, batches = _build(seed=5)
    scope = _init_scope(startup)
    mesh = build_hybrid_mesh(8, dp=2, mp=2, sp=2)
    assert mesh.shape[pmesh.DATA_AXIS] == 2
    seq_spec = (pmesh.DATA_AXIS, pmesh.SEQ_AXIS)
    runner = HybridParallelRunner(
        main, mesh, rules=megatron_rules(),
        feed_specs={n: seq_spec for n in
                    ("src_ids", "pos_ids", "sent_ids", "input_mask")})
    runner.capture_hlo = True
    (lv,) = runner.run(scope, batches[0], [loss.name])
    assert np.isfinite(np.asarray(lv)).all()
    hlo = runner.last_hlo
    assert hlo is not None and len(hlo) > 1000
    assert "all-reduce" in hlo
    assert "all-gather" in hlo or "reduce-scatter" in hlo


def test_hybrid_run_steps_chained_parity():
    """n GSPMD steps in ONE jitted fori_loop (run_steps) == n run() calls:
    same losses and same final sharded params, on a dp=2 x mp=2 x sp=2
    mesh with stacked feeds sharded on (None, dp, sp)."""
    main, startup, loss, batches = _build(seed=23)
    scope_seq = _init_scope(startup)
    scope_chain = _copy_scope(scope_seq)

    mesh = build_hybrid_mesh(8, mp=2, sp=2)
    seq_spec = (pmesh.DATA_AXIS, pmesh.SEQ_AXIS)
    feed_specs = {n: seq_spec for n in
                  ("src_ids", "pos_ids", "sent_ids", "input_mask")}

    r1 = HybridParallelRunner(main, mesh, rules=megatron_rules(),
                              feed_specs=feed_specs)
    seq_last = None
    for b in batches:
        seq_last = r1.run(scope_seq, b, [loss.name])[0]

    r2 = HybridParallelRunner(main, mesh, rules=megatron_rules(),
                              feed_specs=feed_specs)
    stacked = {k: np.stack([np.asarray(b[k]) for b in batches])
               for k in batches[0]}
    chain_last, = r2.run_steps(stacked, n_steps=len(batches),
                               fetch_list=[loss.name], scope=scope_chain,
                               stacked_feed=True)
    assert r2._step == len(batches)

    np.testing.assert_allclose(np.asarray(seq_last),
                               np.asarray(chain_last), rtol=2e-3,
                               atol=2e-3)
    # every trained parameter matches between the two dispatch modes
    checked = 0
    for k in sorted(scope_seq.keys()):
        v = scope_seq.get(k)
        if v is None or not hasattr(v, "dtype") or \
                str(np.asarray(v).dtype) not in ("float32", "bfloat16"):
            continue
        np.testing.assert_allclose(np.asarray(scope_seq.get(k)),
                                   np.asarray(scope_chain.get(k)),
                                   rtol=2e-3, atol=2e-3, err_msg=k)
        checked += 1
    assert checked > 10  # params + opt state actually compared
