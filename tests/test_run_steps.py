"""Executor.run_steps: n training steps chained in ONE compiled call
(lax.fori_loop threading scope writes into the next iteration's reads) —
the reference C++ trainer's no-Python-between-steps loop
(multi_trainer.cc).  Must be semantically identical to n run() calls:
same params, same random streams, same step counter."""

import numpy as np
import pytest

from paddle_tpu import fluid
from paddle_tpu.fluid.executor import Scope, scope_guard


def _build(with_dropout=True, seed=7):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = seed
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, size=16, act="relu")
        if with_dropout:
            h = fluid.layers.dropout(h, dropout_prob=0.3,
                                     dropout_implementation="upscale_in_train")
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Momentum(learning_rate=0.05,
                                 momentum=0.9).minimize(loss)
    return main, startup, loss


def _feed(rng):
    return {"x": rng.rand(16, 8).astype("float32"),
            "y": rng.rand(16, 1).astype("float32")}


def _params(scope, main):
    return {v.name: np.asarray(scope.get(v.name))
            for v in main.global_block().vars.values()
            if getattr(v, "persistable", False)
            and scope.get(v.name) is not None}


def test_run_steps_matches_sequential_runs():
    """4 chained steps == 4 run() calls: identical final params AND
    identical final loss, dropout streams included (same step numbering
    feeds op_rng_key)."""
    main, startup, loss = _build(with_dropout=True)
    feed = _feed(np.random.RandomState(0))

    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        seq_losses = [float(exe.run(main, feed=feed,
                                    fetch_list=[loss])[0])
                      for _ in range(4)]
        seq_params = _params(fluid.global_scope(), main)

    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        chain_last, = exe.run_steps(main, feed=feed, n_steps=4,
                                    fetch_list=[loss])
        chain_params = _params(fluid.global_scope(), main)
        assert exe._step == 5  # startup + 4 chained

    assert seq_params.keys() == chain_params.keys() and seq_params
    for name in seq_params:
        np.testing.assert_allclose(seq_params[name], chain_params[name],
                                   rtol=1e-6, atol=1e-7, err_msg=name)
    # run_steps returns the FINAL step's fetches
    np.testing.assert_allclose(float(chain_last), seq_losses[-1],
                               rtol=1e-5)


def test_fetch_free_steps_train_and_keep_two_signatures():
    """The training pattern the benchmark's trainer uses: steps dispatched
    WITHOUT a fetch pipeline through the donated parameter chain and the
    last one fetches the loss.  That is two executables (no fetch, fetch),
    a repeat compiles neither again, and the fetch-free steps train:
    3 of them + 1 fetching step == 4 fetching steps."""
    main, startup, loss = _build(with_dropout=False)
    feed = _feed(np.random.RandomState(1))

    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        seq = [float(exe.run(main, feed=feed, fetch_list=[loss])[0])
               for _ in range(4)]
        assert len(exe.compiled_for(main)) == 1

    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for round_ in range(2):
            for _ in range(3):
                assert exe.run(main, feed=feed, fetch_list=[]) == []
            last, = exe.run(main, feed=feed, fetch_list=[loss])
            assert len(exe.compiled_for(main)) == 2, round_
            if round_ == 0:
                np.testing.assert_allclose(float(last), seq[-1], rtol=1e-6)


def test_run_steps_stacked_feed_matches_distinct_batches():
    main, startup, loss = _build(with_dropout=False)
    rng = np.random.RandomState(1)
    batches = [_feed(rng) for _ in range(3)]

    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for b in batches:
            seq_last = float(exe.run(main, feed=b, fetch_list=[loss])[0])
        seq_params = _params(fluid.global_scope(), main)

    stacked = {k: np.stack([b[k] for b in batches]) for k in batches[0]}
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        chain_last, = exe.run_steps(main, feed=stacked, n_steps=3,
                                    fetch_list=[loss], stacked_feed=True)
        chain_params = _params(fluid.global_scope(), main)

    for name in seq_params:
        np.testing.assert_allclose(seq_params[name], chain_params[name],
                                   rtol=1e-6, atol=1e-7, err_msg=name)
    np.testing.assert_allclose(float(chain_last), seq_last, rtol=1e-5)


def test_run_steps_validates_inputs():
    main, startup, loss = _build(with_dropout=False)
    feed = _feed(np.random.RandomState(2))
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        with pytest.raises(ValueError, match="n_steps"):
            exe.run_steps(main, feed=feed, n_steps=0, fetch_list=[loss])
        with pytest.raises(ValueError, match="leading"):
            exe.run_steps(main, feed=feed, n_steps=3, fetch_list=[loss],
                          stacked_feed=True)
        # n_steps=1 is the degenerate chain; still one dispatch
        one, = exe.run_steps(main, feed=feed, n_steps=1,
                             fetch_list=[loss])
        assert np.isfinite(float(one))


def test_run_steps_check_nan_inf_flag():
    """FLAGS_check_nan_inf applies to chained runs too: a NaN born inside
    the chain propagates to the final state and is reported by name."""
    from paddle_tpu.fluid import flags as fl

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(pred)
        fluid.optimizer.SGD(learning_rate=1.0).minimize(loss)
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        bad = {"x": np.full((2, 4), np.nan, np.float32)}
        old = fl.get_flags("FLAGS_check_nan_inf")
        fl.set_flags({"FLAGS_check_nan_inf": True})
        try:
            with pytest.raises(RuntimeError, match="check_nan_inf"):
                exe.run_steps(main, feed=bad, n_steps=3,
                              fetch_list=[loss])
        finally:
            fl.set_flags(old)


def test_run_steps_rejects_host_ops():
    """A program containing a host op (here: a PS-mode `send`, which must
    run on the host between steps) is rejected with the typed error at
    plan time — before anything could dial a pserver."""
    from paddle_tpu.fluid.executor import HostOpsUnsupported

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, size=1))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        main.global_block().append_op(
            "send", inputs={"X": [loss]}, outputs={},
            attrs={"epmap": ["127.0.0.1:0"]})
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {"x": np.ones((2, 4), np.float32)}
        with pytest.raises(HostOpsUnsupported, match="host"):
            exe.run_steps(main, feed=feed, n_steps=2, fetch_list=[loss])


def test_run_steps_rejects_compiled_program():
    from paddle_tpu.fluid import compiler

    main, startup, loss = _build(with_dropout=False)
    cp = compiler.CompiledProgram(main)
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        with pytest.raises(ValueError, match="CompiledProgram"):
            exe.run_steps(cp, feed=_feed(np.random.RandomState(3)),
                          n_steps=2, fetch_list=[loss])


def test_run_steps_visible_to_compiled_for():
    """Chain executables share the introspection surface: compiled_for()
    lists them and cost_analysis works on the chain object."""
    main, startup, loss = _build(with_dropout=False)
    feed = _feed(np.random.RandomState(4))
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run_steps(main, feed=feed, n_steps=3, fetch_list=[loss])
        chains = [cb for cb in exe.compiled_for(main)
                  if "chain" in cb.label]
        assert len(chains) == 1
        cost = chains[0].cost_analysis(fluid.global_scope(),
                                       exe._coerce_feed(main, feed))
        assert cost["cost"].get("flops", 0) > 0


def test_run_steps_matches_sequential_under_bf16_policy():
    """The chained dispatch × the bf16 dtype policy (the on-chip
    bf16_chain32 leg's correctness counterpart): identical final params
    and loss vs per-step runs — bit-for-bit, since both paths trace the
    same policy-applied lowerings with the same step numbering."""
    from paddle_tpu.fluid.contrib import mixed_precision as mp

    results = {}
    for tag in ("seq", "chain"):
        main, startup, loss = _build(with_dropout=True)
        mp.enable_bf16_policy(main)
        feed = _feed(np.random.RandomState(0))
        with scope_guard(Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            if tag == "seq":
                for _ in range(4):
                    (last,) = exe.run(main, feed=feed, fetch_list=[loss])
            else:
                (last,) = exe.run_steps(main, feed=feed, n_steps=4,
                                        fetch_list=[loss])
            results[tag] = (float(np.asarray(last)),
                            _params(fluid.global_scope(), main))
    assert (results["seq"][1].keys() == results["chain"][1].keys()
            and results["seq"][1])
    for name in results["seq"][1]:
        # semantic identity at the sibling fp32 test's tolerance — the
        # chain and per-step paths are separate XLA compilations
        np.testing.assert_allclose(results["seq"][1][name],
                                   results["chain"][1][name],
                                   rtol=1e-6, atol=1e-7, err_msg=name)
    np.testing.assert_allclose(results["seq"][0], results["chain"][0],
                               rtol=1e-6)
