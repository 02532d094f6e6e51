"""Names and host spans (ISSUE 25): every jitted body and kernel has a
stable name, the span primitive always records and never blocks, the
decode scheduler's turn is one span tree, and every token carries a
stamp."""

import cpu_mesh  # noqa: F401  (must precede any jax import)

import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.fluid.executor import Scope, scope_guard
from paddle_tpu.models import gpt
from paddle_tpu.observability import profiling

TURN_CHILDREN = {"prefill.pages", "prefill.feed_build", "prefill.run",
                 "admit", "decode.pages", "decode.feed_build", "decode.run",
                 "emit"}
EXECUTOR_SPANS = {"lookup", "feed_prep", "dispatch", "device_wait",
                  "fetch_sync", "fetch_wait"}
NAME, LANE, T0, T1, ID, PARENT, NUMBER, NOTE = range(8)


@pytest.fixture(scope="module")
def engine():
    cfg = gpt.GPTConfig.tiny(num_layers=2, hidden_dropout=0.0,
                             use_flash_attention=False)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        gpt.build_gpt_lm(cfg)
    scope = Scope()
    with scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=2, page_size=4,
                               prefill_chunk=4, max_len=32, name="spans",
                               auto_start=False)
    eng.warmup()
    yield eng
    eng.close()


def _train_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        pred = fluid.layers.fc(x, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    feed = {"x": np.ones((8, 4), "float32"), "y": np.ones((8, 1), "float32")}
    return main, startup, loss, feed


# ---------------------------------------------------------------------------
# the turn as a span tree, and a stamp on every token
# ---------------------------------------------------------------------------


def test_one_turn_is_one_span_tree(engine):
    # a prompt of one chunk: the turn prefills it, admits it and decodes
    req = engine.submit_request([5, 6, 7], 5)
    profiling.reset()
    engine._step_once()
    spans = profiling.spans()
    by_id = {s[ID]: s for s in spans}
    assert len(by_id) == len(spans), "span ids repeat"
    (turn,) = [s for s in spans if s[NAME] == "turn"]
    assert turn[PARENT] == 0 and turn[NUMBER] == engine._turns
    children = [s for s in spans if s[PARENT] == turn[ID]]
    assert {s[NAME] for s in children} == TURN_CHILDREN
    runs = {s[ID] for s in children if s[NAME].endswith(".run")}
    assert len(runs) == 2
    for name in EXECUTOR_SPANS:
        under = [s for s in spans if s[NAME] == name]
        # both runs carry every executor span (fetch_sync twice)
        assert {s[PARENT] for s in under} == runs, name
        assert all(s[LANE] == "single" for s in under)
    for s in spans:
        assert s[T0] <= s[T1]
        assert s[NUMBER] == turn[NUMBER]
        if s[PARENT]:
            p = by_id[s[PARENT]]
            assert p[T0] <= s[T0] and s[T1] <= p[T1], (s[NAME], p[NAME])
    # the turn's own counters, at the same boundaries
    snap = obs.REGISTRY.snapshot()
    parts = snap["pt_decode_turn_seconds_total"]["samples"]
    run_s = sum(s[T1] - s[T0] for s in children
                if s[NAME].endswith(".run")) / 1e9
    assert parts[("spans", "prefill_run")] + parts[
        ("spans", "decode_run")] == pytest.approx(run_s, rel=1e-6)
    assert parts[("spans", "sched")] > 0
    assert snap["pt_decode_turns_total"]["samples"][("spans",)] >= 1
    # every element of `generated` has its stamp, the seed token too
    while not req.future.done():
        engine._step_once()
        assert len(req.token_times) == len(req.generated)
    assert len(req.token_times) == len(req.future.result()) == 5
    assert req.token_times == sorted(req.token_times)
    assert req.t_arrival <= req.t_admit <= req.t_first == req.token_times[0]
    for fam, n in (("pt_decode_queue_wait_seconds", 1),
                   ("pt_decode_ttft_seconds", 1),
                   ("pt_decode_token_gap_seconds", 4)):
        assert snap_count(fam, ("spans",)) >= n, fam


def snap_count(family, key):
    return obs.REGISTRY.snapshot()[family]["samples"][key]["count"]


def test_resumed_request_keeps_one_stamp_per_token(engine):
    req = engine.submit_request([5, 6, 7], 5, prefix=[9, 8])
    assert len(req.token_times) == len(req.generated) == 2
    while not req.future.done():
        engine._step_once()
    assert len(req.token_times) == len(req.generated) == 5
    assert req.t_first is None  # a replay is not a first token


# ---------------------------------------------------------------------------
# always recorded, never blocking, bounded, cheap
# ---------------------------------------------------------------------------


def test_executor_records_phases_with_flag_off_and_blocks_nothing(
        monkeypatch):
    import jax

    prior = fluid.get_flags(["FLAGS_profile_phases"])
    fluid.set_flags({"FLAGS_profile_phases": False})
    try:
        main, startup, loss, feed = _train_program()
        with scope_guard(Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[loss.name])  # compiles
            calls = []
            monkeypatch.setattr(jax, "block_until_ready",
                                lambda x: calls.append(1) or x)
            profiling.reset()
            for _ in range(3):
                exe.run(main, feed=feed, fetch_list=[loss.name],
                        return_numpy=False)
        assert not calls
        names = [s[NAME] for s in profiling.spans()]
        assert names == ["lookup", "feed_prep", "dispatch", "device_wait",
                         "fetch_sync", "fetch_sync"] * 3
        # roots: each carries the executor's step number
        assert [s[NUMBER] for s in profiling.spans()][::6] == [2, 3, 4]
    finally:
        fluid.set_flags(prior)


def test_first_run_is_a_compile_span_with_name_and_outcome():
    main, startup, loss, feed = _train_program()
    profiling.reset()
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss.name])
        exe.run(main, feed=feed, fetch_list=[loss.name])
    spans = profiling.spans()
    compiles = [s for s in spans if s[NAME] == "compile"]
    assert [s[NOTE].split(":")[0] for s in compiles] == ["startup",
                                                         "train_step"]
    outcomes = {"miss", "persistent_hit", "persistent_miss", "aot_hit"}
    assert all(s[NOTE].split(":")[1] in outcomes for s in compiles)
    # the first run's phases are the compile span's children
    first = [s for s in spans if s[PARENT] == compiles[1][ID]]
    assert {"feed_prep", "dispatch"} <= {s[NAME] for s in first}
    booked = obs.REGISTRY.snapshot()[
        "pt_program_compile_seconds_total"]["samples"]
    assert any(k[0] == "train_step" and v > 0 for k, v in booked.items())


def test_ring_stays_bounded():
    profiling.reset()
    for _ in range(profiling.SPAN_RING + 100):
        with profiling.span("x", "test"):
            pass
    assert len(profiling.spans()) == profiling.SPAN_RING
    wall, perf = profiling.span_clock()
    assert abs((time.time_ns() - wall) - (time.perf_counter_ns() - perf)) \
        < 5e9  # the pair reads both clocks at one instant


def test_ring_holds_the_half_window_a_reader_looks_back_over(engine):
    """The benchmark's reader takes the ring at the END of a run and needs
    the spans of an interval traced 20 s before the window closed
    (benchmark/readers/host_gap.py).  A turn that runs a chunk and a step,
    at the 70 turns a second of the benchmark's fastest serve cell since
    PR 34, with as much again to spare for faster turns: PR 25's 32768
    entries held 19.5 s and the cell lost three metrics."""
    req = engine.submit_request([5, 6, 7], 5)
    profiling.reset()
    engine._step_once()
    a_turn = len(profiling.spans())
    assert a_turn >= 23  # 9 of the scheduler, 7 under each of two runs
    assert profiling.SPAN_RING >= a_turn * 70 * 20 * 2
    while not req.future.done():
        engine._step_once()


def test_a_span_costs_under_5_microseconds():
    def batch(n=2000):
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with profiling.span("cost", "test"):
                pass
        return (time.perf_counter_ns() - t0) / n

    # both states of the in-flight ledger: with a program in flight a span
    # pays it one comparison at each end; after this thread's wait proved
    # the newest finished, every span splits a known-empty stretch
    k = profiling.enqueued("jit_in_flight")
    batch(200)  # the histogram child exists now
    in_flight = min(batch() for _ in range(7))
    profiling.done(k)
    known_empty = min(batch() for _ in range(7))
    assert profiling._ledger.empty.under["cost"] > 0  # it did split
    assert in_flight < 5000, f"a span cost {in_flight:.0f} ns"
    assert known_empty < 5000, f"a span cost {known_empty:.0f} ns"
    profiling.reset()  # the stretch goes unbooked: no {under="cost"} series


def test_spans_nest_per_thread_and_survive_an_exception():
    profiling.reset()
    with pytest.raises(RuntimeError):
        with profiling.span("outer", "test", number=7):
            with profiling.span("inner", "test"):
                raise RuntimeError("boom")
    with profiling.span("after", "test"):
        pass
    inner, outer, after = profiling.spans()
    assert inner[PARENT] == outer[ID] and inner[NUMBER] == 7
    assert after[PARENT] == 0  # the stack unwound


# ---------------------------------------------------------------------------
# names on the device side
# ---------------------------------------------------------------------------


def test_decode_programs_compile_under_their_names(engine):
    prefill, decode = engine.lower()
    assert "module @jit_prefill_chunk" in prefill.as_text()
    assert "module @jit_decode_step" in decode.as_text()


def test_train_startup_chain_and_plain_programs_are_named():
    from paddle_tpu.fluid import executor as ex

    main, startup, loss, feed = _train_program()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        assert "module @jit_train_step" in exe.lower(
            main, feed, [loss.name]).as_text()
        # forward only: the optimizer's ops are pruned away
        test_prog = main.clone(for_test=True)
        assert "module @jit_program" in exe.lower(
            test_prog, feed, [loss.name]).as_text()
        exe.run_steps(main, feed=feed, n_steps=2, fetch_list=[loss.name])
        (chain,) = [c for c in exe.compiled_for(main)
                    if isinstance(c, ex._CompiledChain)]
        assert "module @jit_train_chain" in chain.lower(
            scope, feed).as_text()
    assert ex.jit_name(startup, startup.global_block().ops) == "startup"
    main.name = "my step/1"
    assert ex.jit_name(main, ()) == "my_step_1"


def test_health_gate_keeps_the_name():
    from paddle_tpu.health import wrap_body

    class P:
        _health_plan = {"gate": True, "found_var": "f"}

    def train_step(d, r, f, s):
        return [], {}

    assert wrap_body(P(), train_step).__name__ == "train_step"


def test_data_parallel_step_is_named():
    from paddle_tpu.parallel import DataParallelRunner

    main, startup, loss, feed = _train_program()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        runner = DataParallelRunner(main, loss.name)
        profiling.reset()
        runner.run(exe, feed, [loss.name], scope)
        (cb,) = runner._cache.values()
        assert "module @jit_train_step" in cb.lower(scope, feed).as_text()
    # (the lowering above traces once more: `xla.*` spans of its own)
    names = [s[NAME] for s in profiling.spans() if s[LANE] != "host"]
    assert names[0] == "lookup" and "compile" in names
    assert names[-1] == "fetch_wait"


def test_pallas_call_receives_the_spec_name(monkeypatch):
    from jax.experimental import pallas as pl

    from paddle_tpu.kernels import paged_attention as pa

    seen = []
    real = pl.pallas_call

    def spy(kernel, *a, **kw):
        seen.append(kw.get("name"))
        return real(kernel, *a, **kw)

    monkeypatch.setattr(pl, "pallas_call", spy)
    rng = np.random.RandomState(0)
    pgs, n, d = 4, 2, 8
    kp = rng.randn(8, pgs, n * d).astype("float32")
    vp = rng.randn(8, pgs, n * d).astype("float32")
    pt = np.array([[1, 2, 3], [4, 5, 0]], np.int32)
    qs = np.array([9, 5], np.int32)
    q = rng.randn(2, n, 1, d).astype("float32")
    pa.paged_attention(q, kp, vp, pt, qs, force="pallas")  # interpret here
    assert seen == ["paged_attention"]


# ---------------------------------------------------------------------------
# export: only inside the program's own profiler session
# ---------------------------------------------------------------------------


def test_spans_reach_the_profiler_session_and_only_it(monkeypatch):
    import jax

    from paddle_tpu.fluid import profiler as prof

    built = []

    class Annotation:
        def __init__(self, name):
            built.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    with profiling.span("outside", "test"):
        pass
    assert not built  # no session: no annotation object is built
    prof.start_profiler(trace_dir="unused")
    try:
        with profiling.span("turn", "decode"):
            with profiling.span("admit", "decode"):
                pass
    finally:
        prof.stop_profiler(profile_path="/dev/null")
    assert built == ["turn", "admit"]
    phases = [(k, n) for k, n, _, _ in prof.get_events() if k == "phase"]
    assert phases == [("phase", "decode:admit"), ("phase", "decode:turn")]
    with profiling.span("after", "test"):
        pass
    assert built == ["turn", "admit"]
    # a session without a trace_dir records the events, builds nothing
    prof.start_profiler()
    try:
        with profiling.span("plain", "test"):
            pass
    finally:
        prof.stop_profiler(profile_path="/dev/null")
    assert built == ["turn", "admit"]
    assert ("phase", "test:plain") in [(k, n) for k, n, _, _ in
                                       prof.get_events()]
