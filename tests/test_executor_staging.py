"""A scope array is staged onto the run's device ONCE (fluid/executor.py
``_stage_scope_reads``): resident committed arrays reach the executable by
identity, an uncommitted resident one through a committed view of its buffer
that the executable keeps, host values and arrays resident elsewhere keep
being put on every run — each program keeps one signature, and the scope is
left as it was for the lanes that share it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.fluid import executor as executor_mod
from paddle_tpu.fluid.executor import Scope, scope_guard
from paddle_tpu.fluid.framework import Place
from paddle_tpu.models import gpt

LANES = ("single", "chain")


def _train_program():
    """Donated (w, b), read-only (the learning rate) and fed (x, y)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    feed = {"x": np.ones((8, 4), "float32"), "y": np.ones((8, 1), "float32")}
    return main, startup, loss, feed


def _run(exe, lane, main, feed, loss):
    if lane == "single":
        return exe.run(main, feed=feed, fetch_list=[loss.name])
    return exe.run_steps(main, feed=feed, n_steps=2, fetch_list=[loss.name])


def _compiled(exe, lane):
    """The lane's one executable in `exe`'s cache."""
    kind = {"single": executor_mod._CompiledBlock,
            "chain": executor_mod._CompiledChain}[lane]
    (cb,) = [v for v in exe._cache.values()
             if isinstance(v, kind) and v.donated_names]
    return cb


@pytest.fixture
def puts(monkeypatch):
    """Every value handed to ``jax.device_put`` while the fixture lives."""
    seen = []
    real = jax.device_put

    def counted(x, *a, **kw):
        seen.append(x)
        return real(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", counted)
    return seen


def _staged(lane, kind):
    fam = obs.REGISTRY.snapshot().get("pt_exec_staged_arrays_total", {})
    return (fam.get("samples") or {}).get((lane, kind), 0)


def _started():
    main, startup, loss, feed = _train_program()
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup)
    return main, loss, feed, scope, exe


@pytest.mark.parametrize("lane", LANES)
def test_committed_resident_array_is_passed_by_identity(lane, puts):
    main, loss, feed, scope, exe = _started()
    dev = jax.devices("cpu")[0]
    for n in list(scope.keys()):
        scope.set(n, jax.device_put(scope.get(n), dev))
    with scope_guard(scope):
        _run(exe, lane, main, feed, loss)  # compiles
        cb = _compiled(exe, lane)
        names = list(cb.donated_names) + list(cb.readonly_names)
        assert cb.donated_names and cb.readonly_names
        held = {n: scope.get(n) for n in names}
        assert all(v.committed for v in held.values())
        seen, jitted = {}, cb._jitted

        def spy(donated, readonly, feeds, step):
            seen.update(donated)
            seen.update(readonly)
            return jitted(donated, readonly, feeds, step)

        cb._jitted = spy
        del puts[:]
        put0, any0 = _staged(lane, "put"), _staged(lane, "any")
        _run(exe, lane, main, feed, loss)
    assert all(seen[n] is held[n] for n in names)
    # the two feeds, and nothing that lives in the scope
    assert len(puts) == len(feed)
    assert all(isinstance(v, np.ndarray) for v in puts)
    assert _staged(lane, "put") - put0 == len(feed)
    assert _staged(lane, "any") - any0 == len(names) + len(feed)


@pytest.mark.parametrize("lane", LANES)
def test_uncommitted_array_is_put_once_and_one_signature(lane, puts):
    main, loss, feed, scope, exe = _started()
    # what a jitted initializer leaves (startup) and what jnp.zeros makes
    (lr,) = [n for n in scope.keys() if "learning_rate" in n]
    scope.set(lr, jnp.zeros((1,), "float32") + 0.05)
    assert not any(scope.get(n).committed for n in scope.keys())
    with scope_guard(scope):
        del puts[:]
        _run(exe, lane, main, feed, loss)
        cb = _compiled(exe, lane)
        names = list(cb.donated_names) + list(cb.readonly_names)
        assert cb.donated_names and list(cb.readonly_names) == [lr]
        assert len(puts) == len(names) + len(feed)
        # the step's own outputs come back committed; what it only read
        # stays in the scope as it was, its committed view in the executable
        assert all(scope.get(n).committed for n in cb.donated_names)
        held = scope.get(lr)
        assert not held.committed
        view = cb._views[lr][1]
        assert view.committed and (view.unsafe_buffer_pointer()
                                   == held.unsafe_buffer_pointer())
        for _ in range(2):
            del puts[:]
            _run(exe, lane, main, feed, loss)
            assert len(puts) == len(feed)
            assert all(isinstance(v, np.ndarray) for v in puts)
            assert scope.get(lr) is held
        assert cb._jitted._cache_size() == 1
        # another array under the name: put once more, the view follows
        scope.set(lr, jnp.zeros((1,), "float32") + 0.05)
        del puts[:], held
        _run(exe, lane, main, feed, loss)
        assert sum(v is scope.get(lr) for v in puts) == 1
        assert cb._views[lr][0]() is scope.get(lr)
        # and it goes with the array it views
        del puts[:]
        scope.set(lr, np.full((1,), 0.05, "float32"))
        assert lr not in cb._views
        _run(exe, lane, main, feed, loss)
    assert cb._jitted._cache_size() == 1


@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("how", ["scope_set", "tensor_set"])
def test_host_value_is_put_every_run_and_stays_in_scope(lane, how, puts):
    main, loss, feed, scope, exe = _started()
    (lr,) = [n for n in scope.keys() if "learning_rate" in n]
    (w,) = [n for n in scope.keys() if n.endswith("w_0")]
    host = np.zeros((1,), "float32")
    if how == "scope_set":
        scope.set(lr, host)
    else:
        scope.find_var(lr).get_tensor().set(host)
        host = scope.get(lr)
    assert isinstance(host, np.ndarray)
    with scope_guard(scope):
        _run(exe, lane, main, feed, loss)
        w0 = np.asarray(scope.get(w)).copy()
        for _ in range(2):
            del puts[:]
            _run(exe, lane, main, feed, loss)
            assert sum(v is host for v in puts) == 1
            assert scope.get(lr) is host
        # learning rate 0: nothing moved; changed in place: the next run
        # trains
        np.testing.assert_array_equal(np.asarray(scope.get(w)), w0)
        if how == "scope_set":
            host[0] = 0.05
        else:
            scope.find_var(lr).get_tensor().set(
                np.full((1,), 0.05, "float32"))
            assert isinstance(scope.get(lr), np.ndarray)
        _run(exe, lane, main, feed, loss)
        assert not np.array_equal(np.asarray(scope.get(w)), w0)


@pytest.mark.parametrize("lane", LANES)
def test_absent_variable_raises_with_its_name(lane):
    main, loss, feed, scope, exe = _started()
    with scope_guard(scope):
        _run(exe, lane, main, feed, loss)
    (lr,) = [n for n in scope.keys() if "learning_rate" in n]
    del scope._vars[lr]
    with scope_guard(scope):
        with pytest.raises(ValueError, match=lr + ".*absent from the"):
            _run(exe, lane, main, feed, loss)


@pytest.mark.parametrize("lane", LANES)
def test_value_on_another_device_is_moved_every_run(lane):
    dev0, dev1 = jax.devices("cpu")[:2]
    main, loss, feed, scope, _ = _started()
    (lr,) = [n for n in scope.keys() if "learning_rate" in n]
    for n in list(scope.keys()):
        scope.set(n, jax.device_put(scope.get(n), dev0))
    held = scope.get(lr)
    # the base Place is a CPU ordinal: the second of cpu_mesh's eight
    exe = fluid.Executor(Place(1))
    put0 = _staged(lane, "put")
    with scope_guard(scope):
        if lane == "single":
            (out,) = exe.run(main, feed=feed, fetch_list=[loss.name],
                             return_numpy=False)
            assert out.devices() == {dev1}
        else:
            _run(exe, lane, main, feed, loss)
        cb = _compiled(exe, lane)
        names = list(cb.donated_names) + list(cb.readonly_names)
        assert _staged(lane, "put") - put0 == len(names) + len(feed)
        # the step's outputs live where it ran; what it only read is the
        # owner's copy on device 0, untouched, and is moved again
        for n in cb.donated_names:
            assert scope.get(n).devices() == {dev1}, n
        assert scope.get(lr) is held and not cb._views
        put1 = _staged(lane, "put")
        _run(exe, lane, main, feed, loss)
        assert _staged(lane, "put") - put1 == 1 + len(feed)
    assert cb._jitted._cache_size() == 1


def test_decode_engine_steady_turn_puts_feeds_only(puts):
    cfg = gpt.GPTConfig.tiny(num_layers=2, hidden_dropout=0.0,
                             use_flash_attention=False)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        gpt.build_gpt_lm(cfg)
    scope = Scope()
    with scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=2, page_size=4,
                               prefill_chunk=4, max_len=32, name="staging",
                               auto_start=False)
    try:
        eng.warmup()
        blocks = [v for v in eng._exe._cache.values()
                  if isinstance(v, executor_mod._CompiledBlock)]
        assert len(blocks) == 2  # prefill_chunk, decode_step
        resident = sum(len(b.donated_names) + len(b.readonly_names)
                       for b in blocks)
        # the pool comes back committed from the steps that write it; the
        # weights stay as startup left them, viewed by both executables
        assert all(scope.get(n).committed for b in blocks
                   for n in b.donated_names)
        assert all(set(b._views) == set(b.readonly_names) for b in blocks)

        def compiles():
            fam = obs.REGISTRY.snapshot()["pt_compile_cache_total"]
            return sum(v for k, v in fam["samples"].items()
                       if k[-1] == "miss")

        # a prompt of one chunk: the turn runs both programs
        req = eng.submit_request([5, 6, 7], 4)
        miss0 = compiles()
        del puts[:]
        put0, any0 = _staged("single", "put"), _staged("single", "any")
        eng._step_once()
        n_put = _staged("single", "put") - put0
        assert n_put == len(puts) > 0
        assert all(isinstance(v, np.ndarray) for v in puts)
        # every weight and pool tensor of both programs went as it was
        assert _staged("single", "any") - any0 == resident + n_put
        while not req.future.done():
            eng._step_once()
        assert compiles() == miss0
        assert all(b._jitted._cache_size() == 1 for b in blocks)
    finally:
        eng.close()


def _dp_program():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, size=32, act="relu", param_attr="hs_w1",
                            bias_attr="hs_b1")
        pred = fluid.layers.fc(h, size=1, param_attr="hs_w2")
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        test = main.clone(for_test=True)
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(16, 16).astype("float32"),
            "y": rng.randn(16, 1).astype("float32")}
    return main, startup, test, loss, feed


@pytest.mark.parametrize("first", ["infer", "dp"])
def test_dp_lane_trains_on_a_scope_the_single_lane_evaluated(first):
    """An eval on the single lane between (or before) data-parallel steps
    leaves the scope's arrays as their owner made them: the dp step's jit
    refuses an argument committed to one device."""
    main, startup, test, loss, feed = _dp_program()
    dp = fluid.CompiledProgram(main).with_data_parallel(loss_name=loss.name)

    def losses(evals):
        exe = fluid.Executor(fluid.CPUPlace())  # startup draws by its step
        scope, trained, evaluated = Scope(), [], 0
        with scope_guard(scope):
            exe.run(startup)
            for step in range(3):
                if evals and (first == "infer" or step):
                    held = {n: scope.get(n) for n in scope.keys()}
                    (ev,) = exe.run(test, feed=feed, fetch_list=[loss.name])
                    assert all(scope.get(n) is v for n, v in held.items())
                    assert np.isfinite(ev).all()
                    evaluated += 1
                (tr,) = exe.run(dp, feed=feed, fetch_list=[loss.name])
                trained.append(float(np.mean(tr)))
        return trained, evaluated

    trained, evaluated = losses(True)
    assert evaluated == (3 if first == "infer" else 2)
    # the evals changed nothing: the dp losses are those of a run without
    assert trained == losses(False)[0]
    assert trained[-1] < trained[0]


def test_hybrid_runner_takes_a_scope_the_single_lane_read():
    """The hybrid lane's jit shards some weights over `mp` and takes them
    uncommitted, as startup left them, after a single-lane run too."""
    from paddle_tpu.parallel import (HybridParallelRunner, ShardingRule,
                                     build_hybrid_mesh)

    _, startup, test, loss, feed = _dp_program()
    rules = ShardingRule([(r"^hs_w1", (None, "mp")), (r"^hs_b1", ("mp",)),
                          (r"^hs_w2", ("mp", None))])
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(scope):
        exe.run(startup)
        (want,) = exe.run(test, feed=feed, fetch_list=[loss.name])
    assert not scope.get("hs_b1").committed
    runner = HybridParallelRunner(test, build_hybrid_mesh(8, dp=2, mp=4),
                                  rules=rules)
    (got,) = runner.run(scope, feed, [loss.name])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
