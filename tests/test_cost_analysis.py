"""Executor cost-analysis introspection:
Executor.compiled_for + _CompiledBlock.cost_analysis expose XLA's cost
model (flops / bytes accessed) and memory analysis for a compiled step —
the whole-program TPU analog of the reference's per-op profiler tables
(platform/profiler.cc, profiler.proto)."""

import numpy as np

from paddle_tpu import fluid
from paddle_tpu.fluid.executor import Scope, scope_guard


def _build(hidden=32):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data("x", [-1, 16], False, dtype="float32")
        y = fluid.data("y", [-1, 1], False, dtype="float32")
        h = fluid.layers.fc(x, size=hidden, act="relu")
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square(pred - y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def test_cost_analysis_counts_step_flops():
    main, startup, loss = _build()
    feed = {"x": np.random.rand(8, 16).astype("float32"),
            "y": np.random.rand(8, 1).astype("float32")}
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        blocks = exe.compiled_for(main)
        assert len(blocks) == 1, "one feed/fetch signature → one executable"
        # public wrapper: coerces the feed and routes to the executable
        # run() compiled for this exact (program, feed, fetch) signature
        rec = exe.cost_analysis(main, feed, fetch_list=[loss])
        flops = rec["cost"].get("flops", 0.0)
        # fwd 2*(8*16*32 + 8*32) ≈ 8.7k; with bwd+SGD the step is several
        # times that — the exact count is XLA's business, the order isn't
        assert flops > 5e3, rec["cost"]
        assert rec["cost"].get("bytes accessed", 0.0) > 0.0
        # memory analysis present on CPU/TPU PJRT backends
        if rec["memory"]:
            assert rec["memory"]["argument_size_in_bytes"] > 0

    # a second feed signature compiles a second executable
    with scope_guard(scope):
        exe.run(main, feed={"x": feed["x"][:4], "y": feed["y"][:4]},
                fetch_list=[loss])
        assert len(exe.compiled_for(main)) == 2
        # a signature that never ran is a named error, not a silent compile
        import pytest

        with pytest.raises(ValueError, match="run the step once first"):
            exe.cost_analysis(main, {"x": feed["x"][:3], "y": feed["y"][:3]},
                              fetch_list=[loss])


def test_compiled_for_ignores_other_programs():
    main, startup, loss = _build()
    feed = {"x": np.zeros((2, 16), "float32"),
            "y": np.zeros((2, 1), "float32")}
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        assert exe.compiled_for(startup) != exe.compiled_for(main)
        assert all(hasattr(cb, "cost_analysis")
                   for cb in exe.compiled_for(main))
