"""Control flow: While / Switch / StaticRNN / lr schedulers.

Reference test analogs: tests/unittests/test_while_op.py,
test_learning_rate_scheduler.py, test_recurrent_op.py.
"""

import numpy as np
import pytest

from paddle_tpu import fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.executor import Scope, scope_guard


def _fresh():
    return fluid.Program(), fluid.Program()


def test_while_loop_sum():
    main, startup = _fresh()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        i = layers.fill_constant([1], "int64", 0)
        limit = layers.fill_constant([1], "int64", 10)
        acc = layers.fill_constant([1], "float32", 0.0)
        cond = layers.less_than(i, limit)
        w = fluid.layers.While(cond)
        with w.block():
            layers.assign(acc + layers.cast(i, "float32"), output=acc)
            layers.increment(i, value=1)
            layers.less_than(i, limit, cond=cond)
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        (out,) = exe.run(main, fetch_list=[acc.name])
    assert float(out[0]) == sum(range(10))


def test_while_requires_condition_update():
    main, startup = _fresh()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        i = layers.fill_constant([1], "int64", 0)
        limit = layers.fill_constant([1], "int64", 10)
        cond = layers.less_than(i, limit)
        w = fluid.layers.While(cond)
        with pytest.raises(ValueError, match="condition"):
            with w.block():
                layers.increment(i, value=1)


def test_piecewise_decay_switch():
    main, startup = _fresh()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        lr = layers.piecewise_decay(boundaries=[3, 6], values=[1.0, 0.5, 0.1])
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        seen = [float(exe.run(main, fetch_list=[lr.name])[0][0])
                for _ in range(8)]
    # steps 1..8 → lr 1.0 while step<3, 0.5 while step<6, else 0.1
    np.testing.assert_allclose(seen, [1.0, 1.0, 0.5, 0.5, 0.5, 0.1, 0.1, 0.1],
                               rtol=1e-6)


def test_linear_lr_warmup():
    main, startup = _fresh()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        lr = layers.linear_lr_warmup(0.1, warmup_steps=4, start_lr=0.0,
                                     end_lr=0.1)
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        seen = [float(exe.run(main, fetch_list=[lr.name])[0][0])
                for _ in range(6)]
    np.testing.assert_allclose(seen, [0.025, 0.05, 0.075, 0.1, 0.1, 0.1],
                               rtol=1e-6)


def test_exponential_decay_in_optimizer():
    main, startup = _fresh()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data("x", [-1, 4], False, dtype="float32")
        y = fluid.data("y", [-1, 1], False, dtype="float32")
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        lr = layers.exponential_decay(0.1, decay_steps=1, decay_rate=0.5)
        fluid.optimizer.SGD(learning_rate=lr).minimize(loss)
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {"x": np.ones((2, 4), "float32"), "y": np.ones((2, 1), "float32")}
        lrs = [float(exe.run(main, feed=feed, fetch_list=[lr.name])[0][0])
               for _ in range(3)]
    np.testing.assert_allclose(lrs, [0.05, 0.025, 0.0125], rtol=1e-6)


def _np_rnn(x, w, h0):
    # tanh(x_t @ w + h_{t-1} @ w2?) — simple: tanh(x_t + h_{t-1}) @ nothing
    T = x.shape[0]
    h = h0
    outs = []
    for t in range(T):
        h = np.tanh(x[t] @ w + h)
        outs.append(h)
    return np.stack(outs), h


def test_static_rnn_forward_matches_numpy():
    T, B, H = 5, 3, 4
    x_np = np.random.RandomState(0).randn(T, B, H).astype("float32")
    w_np = np.random.RandomState(1).randn(H, H).astype("float32")
    main, startup = _fresh()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data("x", [T, B, H], False, dtype="float32")
        h0 = layers.fill_constant([B, H], "float32", 0.0)
        rnn = fluid.layers.StaticRNN()
        with rnn.step():
            x_t = rnn.step_input(x)
            h_prev = rnn.memory(init=h0)
            proj = layers.fc(
                x_t, size=H, bias_attr=False,
                param_attr=fluid.ParamAttr(
                    name="rnn_w",
                    initializer=fluid.initializer.NumpyArrayInitializer(w_np)))
            h = layers.tanh(proj + h_prev)
            rnn.update_memory(h_prev, h)
            rnn.step_output(h)
        out = rnn()
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        (res,) = exe.run(main, feed={"x": x_np}, fetch_list=[out.name])
    expect, _ = _np_rnn(x_np, w_np, np.zeros((B, H), "float32"))
    np.testing.assert_allclose(res, expect, rtol=1e-5, atol=1e-5)


def test_static_rnn_trains():
    """Gradients flow through lax.scan to the cell weights (Extra capture)."""
    T, B, H = 4, 2, 3
    rng = np.random.RandomState(2)
    x_np = rng.randn(T, B, H).astype("float32")
    main, startup = _fresh()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data("x", [T, B, H], False, dtype="float32")
        h0 = layers.fill_constant([B, H], "float32", 0.0)
        rnn = fluid.layers.StaticRNN()
        with rnn.step():
            x_t = rnn.step_input(x)
            h_prev = rnn.memory(init=h0)
            h = layers.tanh(layers.fc(x_t, size=H, bias_attr=False,
                                      param_attr=fluid.ParamAttr(name="w_cell"))
                            + h_prev)
            rnn.update_memory(h_prev, h)
            rnn.step_output(h)
        out = rnn()
        loss = layers.mean(layers.square(out))
        fluid.optimizer.SGD(learning_rate=0.5).minimize(loss)
    with scope_guard(Scope()) as _:
        sc = fluid.global_scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        w_before = np.asarray(sc.get("w_cell")).copy()
        losses = [float(np.asarray(exe.run(main, feed={"x": x_np},
                                           fetch_list=[loss.name])[0]).reshape(-1)[0])
                  for _ in range(10)]
        w_after = np.asarray(sc.get("w_cell"))
    assert losses[-1] < losses[0] * 0.9, losses
    assert not np.allclose(w_before, w_after)


def test_conditional_block_output_reads_the_block_or_zeros():
    """`ConditionalBlock.output(inner)`: the block's own value where it
    ran, zeros where it did not, initialised AHEAD of the
    conditional op though declared inside the block; a value of unknown
    shape, and a call outside `block()`, are refused."""
    main, startup = _fresh()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data("x", [2, 3], False, dtype="float32")
        flag = fluid.data("flag", [1], False, dtype="int32")
        cb = fluid.layers.ConditionalBlock([flag])
        with cb.block():
            doubled = cb.output(layers.scale(x, scale=2.0))
            best = cb.output(layers.argmax(x, axis=-1))
            ragged = fluid.data("ragged", [-1, 3], False, dtype="float32")
            with pytest.raises(ValueError, match="static shape"):
                cb.output(ragged)
        with pytest.raises(ValueError, match="inside block"):
            cb.output(x)
    ops = [op.type for op in main.global_block().ops]
    assert ops[-3:] == ["fill_constant", "fill_constant", "conditional_block"]
    assert tuple(doubled.shape) == (2, 3) and tuple(best.shape) == (2,)
    x_np = np.arange(6, dtype="float32").reshape(2, 3)
    exe = fluid.Executor(fluid.CPUPlace())
    for on, want in ((1, (2 * x_np, [2, 2])), (0, (np.zeros((2, 3)), [0, 0]))):
        got = exe.run(main, feed={"x": x_np, "flag": np.array([on], "int32")},
                      fetch_list=[doubled.name, best.name], scope=Scope())
        np.testing.assert_array_equal(np.asarray(got[0]), want[0])
        np.testing.assert_array_equal(np.asarray(got[1]), want[1])


def test_conditional_block_grad():
    """Grad flows through lax.cond into weights used inside the block."""
    main, startup = _fresh()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data("x", [2, 4], False, dtype="float32")
        flag = fluid.data("flag", [1], False, dtype="bool")
        out = layers.fill_constant([2, 1], "float32", 0.0)
        cb = fluid.layers.ConditionalBlock([flag])
        with cb.block():
            y = layers.fc(x, size=1, bias_attr=False,
                          param_attr=fluid.ParamAttr(name="w_cond"))
            layers.assign(y, output=out)
        loss = layers.mean(out)
        fluid.optimizer.SGD(learning_rate=1.0).minimize(loss)
    with scope_guard(Scope()):
        sc = fluid.global_scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        w0 = np.asarray(sc.get("w_cond")).copy()
        feed = {"x": np.ones((2, 4), "float32"),
                "flag": np.array([True])}
        exe.run(main, feed=feed, fetch_list=[loss.name])
        w1 = np.asarray(sc.get("w_cond")).copy()
        assert not np.allclose(w0, w1)  # branch taken → grads applied
        feed["flag"] = np.array([False])
        exe.run(main, feed=feed, fetch_list=[loss.name])
        w2 = np.asarray(sc.get("w_cond"))
        np.testing.assert_allclose(w1, w2)  # branch skipped → zero grad
