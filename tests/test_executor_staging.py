"""Staging costs what changed since the last run, not what the program reads
(fluid/executor.py ``_stage_args``).  A scope array is staged onto the run's
device ONCE: resident committed arrays reach the executable by identity, an
uncommitted resident one through a committed view of its buffer, and the
executor keeps what each name was staged as for as long as the scope holds
that very object (``_kept``); host values and arrays resident elsewhere keep
being put on every run.  Host feeds ride the call where a scope argument
pins it, and are put where nothing else does.  Each program keeps one
signature, and the scope is left as it was for the lanes that share it.
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


def _compiled(exe, lane, program=None):
    """The lane's one executable in `exe`'s cache (of `program`, if given:
    otherwise the one that donates)."""
    kind = {"single": executor_mod._CompiledBlock,
            "chain": executor_mod._CompiledChain}[lane]
    (cb,) = [v for v in exe._cache.values() if isinstance(v, kind)
             and (v.donated_names if program is None
                  else v.plan.program is program)]
    return cb


def _names(cb):
    return list(cb.donated_names) + list(cb.readonly_names)


def _spy(cb):
    """What `cb`'s jitted body is called with, by argument name."""
    seen, jitted = {}, cb._jitted

    def spy(donated, readonly, feeds, step):
        seen.clear()
        seen.update(donated)
        seen.update(readonly)
        seen.update(feeds)
        return jitted(donated, readonly, feeds, step)

    spy._cache_size = jitted._cache_size
    cb._jitted = spy
    return seen


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


KINDS = ("put", "kept", "host", "any")


def _staged(lane, kind=None):
    """The counter's reading of one kind, or of all four."""
    fam = obs.REGISTRY.snapshot().get("pt_exec_staged_arrays_total", {})
    samples = fam.get("samples") or {}
    if kind is not None:
        return samples.get((lane, kind), 0)
    return {k: samples.get((lane, k), 0) for k in KINDS}


def _since(lane, before):
    now = _staged(lane)
    return {k: now[k] - before[k] for k in KINDS}


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
        names = _names(cb)
        assert cb.donated_names and cb.readonly_names
        held = {n: scope.get(n) for n in names}
        assert all(v.committed for v in held.values())
        seen = _spy(cb)
        del puts[:]
        before = _staged(lane)
        _run(exe, lane, main, feed, loss)
    assert all(seen[n] is held[n] for n in names)
    # nothing is put: the scope's arrays pin the call, the feeds ride it
    assert not puts
    assert all(seen[k] is v for k, v in feed.items())
    assert _since(lane, before) == {
        "put": 0, "kept": len(names), "host": len(feed),
        "any": len(names) + len(feed)}


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
        names = _names(cb)
        assert cb.donated_names and list(cb.readonly_names) == [lr]
        # the scope reads, once; the feeds ride the call from the first run
        assert len(puts) == len(names)
        # the step's own outputs come back committed; what it only read
        # stays in the scope as it was, its committed view with the executor
        assert all(scope.get(n).committed for n in cb.donated_names)
        held = scope.get(lr)
        assert not held.committed
        view = exe._kept[lr][1]
        assert view.committed and (view.unsafe_buffer_pointer()
                                   == held.unsafe_buffer_pointer())
        for _ in range(2):
            del puts[:]
            before = _staged(lane)
            _run(exe, lane, main, feed, loss)
            assert not puts and scope.get(lr) is held
            assert _since(lane, before) == {
                "put": 0, "kept": len(names), "host": len(feed),
                "any": len(names) + len(feed)}
        assert cb._jitted._cache_size() == 1
        # another array under the name: put once more, the view follows
        scope.set(lr, jnp.zeros((1,), "float32") + 0.05)
        del puts[:], held
        _run(exe, lane, main, feed, loss)
        assert [v is scope.get(lr) for v in puts] == [True]
        assert exe._kept[lr][0]() is scope.get(lr)
        # and it goes with the array it views
        del puts[:]
        scope.set(lr, np.full((1,), 0.05, "float32"))
        assert lr not in exe._kept
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
        names = _names(cb)
        # every scope read is moved; once moved they pin the call to the
        # second device and the feeds ride it there
        assert _staged(lane, "put") - put0 == len(names)
        # the step's outputs live where it ran; what it only read is the
        # owner's copy on device 0, untouched, never kept, and moved again
        for n in cb.donated_names:
            assert scope.get(n).devices() == {dev1}, n
        assert scope.get(lr) is held and lr not in exe._kept
        before = _staged(lane)
        _run(exe, lane, main, feed, loss)
        assert _since(lane, before) == {
            "put": 1, "kept": len(names) - 1, "host": len(feed),
            "any": len(names) + len(feed)}
    assert cb._jitted._cache_size() == 1


def _feed_only_program():
    """No scope read at all: what it is fed is all that places it."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        out = fluid.layers.scale(x, scale=2.0)
    return main, out, {"x": np.ones((8, 4), "float32")}


def _run_out(exe, lane, main, feed, out):
    """One run on `lane` that fetches `out` as the device array it is."""
    if lane == "single":
        (got,) = exe.run(main, feed=feed, fetch_list=[out.name],
                         return_numpy=False)
    else:
        (got,) = exe.run_steps(main, feed=feed, n_steps=2,
                               fetch_list=[out.name], return_numpy=False)
    return got


@pytest.mark.parametrize("lane", LANES)
def test_program_without_scope_reads_puts_its_feeds_on_its_place(lane, puts):
    dev1 = jax.devices("cpu")[1]
    main, out, feed = _feed_only_program()
    exe = fluid.Executor(Place(1))  # not the default device
    with scope_guard(Scope()):
        for _ in range(2):
            del puts[:]
            before = _staged(lane)
            got = _run_out(exe, lane, main, feed, out)
            # nothing else pins: the feed is put, and places the run
            assert got.devices() == {dev1}
            assert [v is feed["x"] for v in puts] == [True]
            assert _since(lane, before) == {
                "put": 1, "kept": 0, "host": 0, "any": 1}
        cb = _compiled(exe, lane, main)
        assert not _names(cb) and cb._jitted._cache_size() == 1
    np.testing.assert_array_equal(np.asarray(got), 2 * feed["x"])


@pytest.mark.parametrize("lane", LANES)
def test_device_feed_goes_by_identity_if_committed_there_else_is_put(
        lane, puts):
    dev0, dev1 = jax.devices("cpu")[:2]
    main, loss, feed, scope, exe = _started()
    there = {k: jax.device_put(v, dev0) for k, v in feed.items()}
    loose = {k: jnp.asarray(v) for k, v in feed.items()}
    away = {k: jax.device_put(v, dev1) for k, v in feed.items()}
    assert not any(v.committed for v in loose.values())
    with scope_guard(scope):
        _run(exe, lane, main, there, loss)
        cb = _compiled(exe, lane)
        names, seen = _names(cb), _spy(cb)
        del puts[:]
        before = _staged(lane)
        _run(exe, lane, main, there, loss)
        # as the dataset prefetcher leaves them: neither put nor host
        assert not puts and all(seen[k] is v for k, v in there.items())
        assert _since(lane, before) == {
            "put": 0, "kept": len(names), "host": 0,
            "any": len(names) + len(feed)}
        # an uncommitted one, or one that lives elsewhere, arrives as the
        # first did: committed to the run's device
        for fed in (loose, away):
            del puts[:]
            before = _staged(lane)
            _run(exe, lane, main, fed, loss)
            assert len(puts) == len(feed)
            assert all(seen[k].committed and seen[k].devices() == {dev0}
                       for k in feed)
            assert _since(lane, before)["put"] == len(feed)
    assert cb._jitted._cache_size() == 1


@pytest.mark.parametrize("lane", LANES)
def test_numpy_feed_changed_in_place_is_seen(lane):
    main, loss, feed, scope, exe = _started()
    (lr,) = [n for n in scope.keys() if "learning_rate" in n]
    scope.set(lr, np.zeros((1,), "float32"))  # nothing trains
    with scope_guard(scope):
        (first,) = _run(exe, lane, main, feed, loss)
        (again,) = _run(exe, lane, main, feed, loss)
        feed["y"][:] = 5.0
        (moved,) = _run(exe, lane, main, feed, loss)
        (fresh,) = _run(exe, lane, main,
                        {"x": feed["x"], "y": np.full((8, 1), 5.0, "float32")},
                        loss)
    np.testing.assert_array_equal(first, again)
    assert not np.allclose(first, moved)
    np.testing.assert_array_equal(moved, fresh)
    assert _compiled(exe, lane)._jitted._cache_size() == 1


@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("how", ["scope_set", "tensor_set", "dead", "moved"])
def test_change_of_one_scope_object_is_seen_and_drops_its_kept_staging_only(
        lane, how):
    """The learning rate, kept over steady runs, is replaced by 0 in each
    way a scope object can change under the executor: the very next run
    must not train, and every other name is still served from `_kept`."""
    dev0, dev1 = jax.devices("cpu")[:2]
    main, loss, feed, scope, exe = _started()
    (lr,) = [n for n in scope.keys() if "learning_rate" in n]
    (w,) = [n for n in scope.keys() if n.endswith("w_0")]
    zero = np.zeros((1,), "float32")
    with scope_guard(scope):
        for _ in range(2):
            _run(exe, lane, main, feed, loss)
        cb = _compiled(exe, lane)
        names = _names(cb)
        before = _staged(lane)
        _run(exe, lane, main, feed, loss)  # steady, and it trains
        assert _since(lane, before)["kept"] == len(names)
        w0 = np.asarray(scope.get(w)).copy()
        was = exe._kept[lr]
        if how == "scope_set":  # a committed array: goes by identity
            scope.set(lr, jax.device_put(zero, dev0))
            put = [0, 0]
        elif how == "tensor_set":  # a host value: put on every run
            scope.find_var(lr).get_tensor().set(zero)
            put = [1, 1]
        elif how == "dead":  # startup's array dies, its view with it
            assert was[1] is not None
            scope.set(lr, jnp.zeros((1,), "float32"))
            assert lr not in exe._kept
            put = [1, 0]
        else:  # lives on another device: moved on every run, never kept
            scope.set(lr, jax.device_put(zero, dev1))
            put = [1, 1]
        now = scope.get(lr)
        for i, n_put in enumerate(put):
            before = _staged(lane)
            _run(exe, lane, main, feed, loss)
            assert scope.get(lr) is now  # staging writes no scope
            kept_lr = n_put == 0 and i > 0
            assert _since(lane, before) == {
                "put": n_put, "kept": len(names) - 1 + kept_lr,
                "host": len(feed), "any": len(names) + len(feed)}
        np.testing.assert_array_equal(np.asarray(scope.get(w)), w0)
    assert cb._jitted._cache_size() == 1


@pytest.mark.parametrize("lane", LANES)
@pytest.mark.parametrize("writer", ["same_executor", "another_executor"])
def test_donated_names_take_what_another_program_wrote_back(lane, writer):
    """Two programs over the same names and scope, as the decode lane's
    chunk and step over one pool: each donates what the other left."""
    main, loss, feed, scope, exe = _started()
    other, _, other_loss, _ = _train_program()  # the same names
    other_exe = exe if writer == "same_executor" else fluid.Executor(
        fluid.CPUPlace())
    ref_scope, ref_exe = Scope(), fluid.Executor(fluid.CPUPlace())
    with scope_guard(ref_scope):
        for n in scope.keys():
            ref_scope.set(n, np.asarray(scope.get(n)))
        want = [_run(ref_exe, lane, main, feed, loss)[0] for _ in range(4)]
    with scope_guard(scope):
        got = [_run(exe, lane, main, feed, loss)[0],
               _run(other_exe, lane, other, feed, other_loss)[0]]
        cb = _compiled(exe, lane, main)
        names = _names(cb)
        before = _staged(lane)
        got.append(_run(exe, lane, main, feed, loss)[0])
        mine = _since(lane, before)
        got.append(_run(other_exe, lane, other, feed, other_loss)[0])
    # the four runs trained one set of weights, in turn
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    assert mine["put"] == 0 and mine["host"] == len(feed)
    # this executor's programs note their write-backs for one another; what
    # another executor (or lane) wrote is looked at again, and taken
    assert mine["kept"] == (len(names) if writer == "same_executor"
                            else len(cb.readonly_names))
    assert cb._jitted._cache_size() == 1


def test_aot_step_returns_uncommitted_outputs_and_they_are_not_kept(
        tmp_path, puts):
    """An AOT-compiled step (FLAGS_aot_cache_dir) hands its outputs back
    uncommitted: they are staged as any uncommitted array, every run,
    and a jitted program of the same executor keeps its one signature."""
    main, loss, feed, scope, exe = _started()
    other, _, other_loss, _ = _train_program()  # the same names, jitted
    fluid.set_flags({"FLAGS_aot_cache_dir": str(tmp_path)})
    try:
        with scope_guard(scope):
            _run(exe, "single", main, feed, loss)
    finally:
        fluid.set_flags({"FLAGS_aot_cache_dir": ""})
    cb = _compiled(exe, "single", main)
    assert cb._aot is not None
    with scope_guard(scope):
        for _ in range(2):
            del puts[:]
            before = _staged("single")
            _run(exe, "single", main, feed, loss)
            assert not any(scope.get(n).committed for n in cb.donated_names)
            assert len(puts) == len(cb.donated_names)
            assert _since("single", before) == {
                "put": len(cb.donated_names), "host": len(feed),
                "kept": len(cb.readonly_names),
                "any": len(_names(cb)) + len(feed)}
        # the jitted program takes them through a put too, and hands back
        # committed arrays, which the AOT step then takes as they are
        for _ in range(2):
            _run(exe, "single", other, feed, other_loss)
        del puts[:]
        _run(exe, "single", main, feed, loss)
        assert not puts
    assert _compiled(exe, "single", other)._jitted._cache_size() == 1


def test_decode_engine_steady_turn_puts_nothing(puts):
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
        resident = sum(len(_names(b)) for b in blocks)
        fed = sum(len(b.feed_names) for b in blocks)
        assert resident and fed
        # the pool comes back committed from the steps that write it; the
        # weights stay as startup left them, one view each for both programs
        assert all(scope.get(n).committed for b in blocks
                   for n in b.donated_names)
        weights = {n for b in blocks for n in b.readonly_names}
        assert {n for n, hit in eng._exe._kept.items()
                if hit[1] is not None} == weights

        def compiles():
            fam = obs.REGISTRY.snapshot()["pt_compile_cache_total"]
            return sum(v for k, v in fam["samples"].items()
                       if k[-1] == "miss")

        # a prompt of one chunk: the turn runs both programs, each on the
        # pool the other wrote last
        req = eng.submit_request([5, 6, 7], 4)
        miss0 = compiles()
        del puts[:]
        before = _staged("single")
        eng._step_once()
        # every weight and pool tensor of both programs was kept, every
        # feed rode its call
        assert not puts
        assert _since("single", before) == {
            "put": 0, "kept": resident, "host": fed,
            "any": resident + fed}
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
