"""Curated on-chip pytest subset (chip_smoke.py is the full-width proof;
these are tiny-shape correctness checks).

Run on the chip (`make test-onchip`):
    PADDLE_TPU_TEST_REAL=1 python -m pytest tests/test_onchip_smoke.py \
        -m onchip -q

Without PADDLE_TPU_TEST_REAL the same tests run on the CPU mesh, so the
subset is continuously exercised; on the chip they demonstrate correctness
where the reference's OpTest discipline runs each op on every place
(tests/unittests/op_test.py:495).  Shapes are tiny to keep first-compile
time bounded.
"""

import os

import numpy as np
import pytest

from paddle_tpu import fluid

pytestmark = pytest.mark.onchip

ON_CHIP = bool(os.environ.get("PADDLE_TPU_TEST_REAL"))


def _place():
    return fluid.TPUPlace(0) if ON_CHIP else fluid.CPUPlace()


def test_train_step_fit_a_line():
    """book/01 shape: linear regression must reduce loss in 30 steps."""
    rng = np.random.RandomState(0)
    w_true = rng.randn(13, 1).astype("float32")
    xs = rng.randn(64, 13).astype("float32")
    ys = xs @ w_true + 0.01 * rng.randn(64, 1).astype("float32")

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data("x", [-1, 13], False, dtype="float32")
        y = fluid.data("y", [-1, 1], False, dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    exe = fluid.Executor(_place())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = [float(exe.run(main, feed={"x": xs, "y": ys},
                                fetch_list=[loss])[0]) for _ in range(30)]
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
    assert np.isfinite(losses[-1])


def test_bert_tiny_train_step():
    """One fwd+bwd+Adam step of BERT-tiny produces a finite, decreasing loss."""
    from paddle_tpu.models import bert

    cfg = bert.BertConfig.tiny()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        feeds, loss, mlm, acc = bert.build_bert_pretrain(cfg, is_test=False)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    batch = bert.make_fake_batch(cfg, batch=4, seq_len=32, seed=1)
    exe = fluid.Executor(_place())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        l0 = float(exe.run(main, feed=batch, fetch_list=[loss])[0])
        for _ in range(5):
            ln = float(exe.run(main, feed=batch, fetch_list=[loss])[0])
    assert np.isfinite(l0) and np.isfinite(ln)
    assert ln < l0, (l0, ln)  # same batch 6x must overfit downward


def test_flash_vs_reference_attention():
    """Pallas flash attention (interpret-mode off-TPU) matches the XLA
    reference path — on chip this exercises the real Mosaic kernel."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_attention import flash_attention

    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 2, 128, 64), jnp.float32)
    k = jnp.asarray(rng.randn(2, 2, 128, 64), jnp.float32)
    v = jnp.asarray(rng.randn(2, 2, 128, 64), jnp.float32)

    ref = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                  force="reference"))
    fl = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                 force="pallas"))
    np.testing.assert_allclose(np.asarray(fl(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               rtol=2e-2, atol=2e-2)


def test_param_donation_updates_in_place():
    """Adam step donates param buffers — after a step the scope holds NEW
    values (no aliasing surprises) and a second step still runs (donated
    buffers were not left dangling)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data("x", [-1, 8], False, dtype="float32")
        y = fluid.data("y", [-1, 1], False, dtype="float32")
        pred = fluid.layers.fc(x, size=1, name="donchk")
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.1).minimize(loss)
    rng = np.random.RandomState(1)
    feed = {"x": rng.randn(16, 8).astype("float32"),
            "y": rng.randn(16, 1).astype("float32")}
    exe = fluid.Executor(_place())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        w0 = np.asarray(scope.get("donchk.w_0")).copy()
        exe.run(main, feed=feed, fetch_list=[loss])
        w1 = np.asarray(scope.get("donchk.w_0"))
        exe.run(main, feed=feed, fetch_list=[loss])
        w2 = np.asarray(scope.get("donchk.w_0"))
    assert not np.allclose(w0, w1)
    assert not np.allclose(w1, w2)
    assert np.isfinite(w2).all()


def test_save_load_roundtrip(tmp_path):
    """save_persistables → load_persistables reproduces identical params and
    identical next-step losses."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data("x", [-1, 6], False, dtype="float32")
        y = fluid.data("y", [-1, 1], False, dtype="float32")
        pred = fluid.layers.fc(x, size=1, name="slchk")
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    rng = np.random.RandomState(2)
    feed = {"x": rng.randn(8, 6).astype("float32"),
            "y": rng.randn(8, 1).astype("float32")}
    exe = fluid.Executor(_place())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        fluid.io.save_persistables(exe, str(tmp_path), main_program=main)
        w_saved = np.asarray(scope.get("slchk.w_0")).copy()
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe.run(startup)
        fluid.io.load_persistables(exe, str(tmp_path), main_program=main)
        np.testing.assert_allclose(np.asarray(scope2.get("slchk.w_0")),
                                   w_saved, rtol=1e-6)
        l_after = float(exe.run(main, feed=feed, fetch_list=[loss])[0])
    with fluid.scope_guard(scope):
        l_ref = float(exe.run(main, feed=feed, fetch_list=[loss])[0])
    np.testing.assert_allclose(l_after, l_ref, rtol=1e-5)


def test_bf16_policy_step_finite():
    """One bf16-policy BERT step: loss finite and close to fp32 (the A/B
    perf comparison is the benchmark's job; this is correctness)."""
    from paddle_tpu.models import bert

    cfg = bert.BertConfig.tiny()

    def run(policy):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            feeds, loss, mlm, acc = bert.build_bert_pretrain(cfg, is_test=False)
            fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
        if policy:
            from paddle_tpu.fluid.contrib import mixed_precision as mp

            mp.enable_bf16_policy(main)
        batch = bert.make_fake_batch(cfg, batch=4, seq_len=32, seed=5)
        exe = fluid.Executor(_place())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            return float(exe.run(main, feed=batch, fetch_list=[loss])[0])

    l32, l16 = run(False), run(True)
    assert np.isfinite(l32) and np.isfinite(l16)
    np.testing.assert_allclose(l16, l32, rtol=0.05)


def test_run_steps_chain_on_chip():
    """4 steps in ONE compiled call (Executor.run_steps) on the real
    device must match 4 per-step run() calls (deterministic init, same
    feed): same final loss, same final weights — the chain-dispatch
    path works on-chip, not just the CPU mesh."""
    def build():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            pred = fluid.layers.fc(fluid.layers.fc(x, size=16, act="relu"),
                                   size=1)
            loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.Momentum(learning_rate=0.05,
                                     momentum=0.9).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(3)
    feed = {"x": rng.rand(16, 8).astype("float32"),
            "y": rng.rand(16, 1).astype("float32")}

    main, startup, loss = build()
    seq = chain = None
    w_name = "fc_0.w_0"
    w_seq = w_chain = None
    exe = fluid.Executor(_place())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(4):
            seq, = exe.run(main, feed=feed, fetch_list=[loss])
        w_seq = np.asarray(scope.get(w_name)).copy()
    exe2 = fluid.Executor(_place())
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2.run(startup)
        chain, = exe2.run_steps(main, feed=feed, n_steps=4,
                                fetch_list=[loss])
        w_chain = np.asarray(scope2.get(w_name))
    np.testing.assert_allclose(float(chain), float(seq), rtol=1e-5)
    np.testing.assert_allclose(w_chain, w_seq, rtol=1e-5, atol=1e-6)


def test_tensor_array_while_decode_on_chip():
    """The LoDTensorArray while-loop machinery (r4) compiles and runs on
    the chip: init write → loop read/compute/write → length + final read.
    One lax.while XLA computation, fixed-capacity buffers."""
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.executor import Scope, scope_guard

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[4], dtype="float32")
        arr = layers.create_array("float32", capacity=6)
        i = layers.fill_constant(shape=[1], dtype="int64", value=0)
        layers.array_write(x, i, array=arr)
        n = layers.fill_constant(shape=[1], dtype="int64", value=3)
        cond = layers.less_than(i, n)
        w = layers.While(cond)
        with w.block():
            prev = layers.array_read(arr, i)
            nxt = layers.scale(prev, scale=2.0)
            i2 = layers.increment(i, value=1, in_place=True)
            layers.array_write(nxt, i2, array=arr)
            layers.less_than(i2, n, cond=cond)
        ln = layers.array_length(arr)
        last = layers.array_read(arr, n)
    exe = fluid.Executor(_place())
    xv = np.full((2, 4), 1.5, "float32")
    with scope_guard(Scope()):
        exe.run(startup)
        out_len, out_last = exe.run(main, feed={"x": xv},
                                    fetch_list=[ln, last])
    assert int(np.asarray(out_len)[0]) == 4
    np.testing.assert_allclose(np.asarray(out_last), xv * 8, rtol=1e-6)


def test_double_grad_penalty_on_chip():
    """Grad-of-grad (WGAN-GP shape) compiles and stays finite on the
    chip — the lazily materialized *_grad_grad path under real XLA:TPU."""
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.executor import Scope, scope_guard

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[4], dtype="float32")
        x.stop_gradient = False
        h = layers.fc(x, size=8, act="tanh")
        y = layers.fc(h, size=1)
        (dx,) = fluid.gradients(y, x)
        gp = layers.mean(layers.square(
            layers.sqrt(layers.reduce_sum(layers.square(dx), dim=1))
            - 1.0))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(gp)
    exe = fluid.Executor(_place())
    rng = np.random.RandomState(0)
    with scope_guard(Scope()):
        exe.run(startup)
        for _ in range(3):
            (g,) = exe.run(main,
                           feed={"x": rng.randn(4, 4).astype("float32")},
                           fetch_list=[gp])
    assert np.isfinite(float(np.asarray(g)))


def test_int8_matmul_on_chip():
    """The PTQ int8-compute contraction (int8 x int8 -> int32 on the MXU)
    lowers and runs on the chip, tracking fp32 within 8-bit error — the
    serving-speed path must not be a CPU-only artifact."""
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.contrib import ptq
    from paddle_tpu.fluid.executor import Scope, scope_guard

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data(name="x", shape=[64], dtype="float32")
        h = layers.fc(x, size=128, act="relu", param_attr="i8c_w1",
                      bias_attr="i8c_b1")
        out = layers.fc(h, size=16, param_attr="i8c_w2",
                        bias_attr="i8c_b2")
    rng = np.random.RandomState(0)
    xv = rng.randn(32, 64).astype("float32")
    exe = fluid.Executor(_place())
    with scope_guard(Scope()):
        exe.run(startup)
        (base,) = exe.run(main, feed={"x": xv}, fetch_list=[out])
        base = np.asarray(base).copy()
        from paddle_tpu.fluid import ir

        ir.apply_pass(main, "fc_fuse_pass", keep_vars=[out.name])
        cfg = ptq.PTQConfig(calibration_feeds=[{"x": xv}])
        scales = ptq.calibrate(exe, main, cfg)
        n = ptq.apply_int8_compute(main, scales)
        assert n == 2
        (got,) = exe.run(main, feed={"x": xv}, fetch_list=[out.name])
    err = np.abs(np.asarray(got) - base).max()
    assert err < 0.05 * np.abs(base).max() + 0.05, err
