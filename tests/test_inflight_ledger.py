"""The in-flight ledger (ISSUE 51): every program a lane hands to the
device gets a name and an ordinal on its ``dispatch`` span, every host
wait says which program it proved finished, and the seconds the chip is
KNOWN to stand empty are booked under the span that was open."""

import cpu_mesh  # noqa: F401  (must precede any jax import)

import re
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.fluid.executor import Scope, scope_guard
from paddle_tpu.models import gpt
from paddle_tpu.observability import profiling

from test_spans import _train_program

NAME, LANE, T0, T1, ID, PARENT, NUMBER, NOTE = range(8)
FAMILY = "pt_device_starved_seconds_total"


def starved():
    """{under: seconds} of the counter as it stands."""
    fam = obs.REGISTRY.snapshot().get(FAMILY, {})
    return {k[0]: v for k, v in (fam.get("samples") or {}).items()}


def ledger():
    """The record as it stands: the two ordinals, and whether the chip
    is known to stand empty."""
    led = profiling._ledger
    return {"enqueued": led.enqueued, "done": led.done,
            "empty": led.empty is not None}


def gained(before):
    return {k: round((v - before.get(k, 0.0)) * 1e9)
            for k, v in starved().items() if v != before.get(k, 0.0)}


class Clock:
    """`time` as profiling sees it, with a `perf_counter_ns` the test
    sets."""

    def __init__(self):
        self.ns = 1_000_000

    def perf_counter_ns(self):
        return self.ns

    def at(self, ns):
        self.ns = 1_000_000 + ns

    def __getattr__(self, name):
        return getattr(time, name)


@pytest.fixture
def clock(monkeypatch):
    clk = Clock()
    monkeypatch.setattr(profiling, "time", clk)
    profiling.install_runtime_hooks()
    # whatever the tests before left: a program in flight, nothing booked
    profiling.enqueued("jit_before")
    yield clk
    profiling.reset()


# ---------------------------------------------------------------------------
# (a) the ledger alone
# ---------------------------------------------------------------------------


def run_wait_gap_run(clock):
    k = profiling.enqueued("jit_a")
    clock.at(100)
    assert profiling.done(k) == f"done#{k}"
    assert ledger() == {"enqueued": k, "done": k, "empty": True}
    clock.at(400)
    assert profiling.enqueued("jit_b") == k + 1
    assert not ledger()["empty"]
    return {"none": 300, "any": 300}


def two_enqueued_and_the_first_done(clock):
    k = profiling.enqueued("jit_a")
    profiling.enqueued("jit_b")
    clock.at(100)
    profiling.done(k)
    assert not ledger()["empty"]
    clock.at(400)
    with profiling.span("turn", "decode"):
        clock.at(500)
    profiling.enqueued("jit_c")
    return {}


def the_newest_done_counts_from_that_instant(clock):
    k = profiling.enqueued("jit_a")
    profiling.enqueued("jit_b")
    clock.at(100)
    profiling.done(k + 1)
    clock.at(150)
    profiling.done(k)  # an older program: already known, moves nothing
    assert ledger()["done"] == k + 1
    clock.at(400)
    profiling.enqueued("jit_c")
    return {"none": 300, "any": 300}


def nested_spans_split_the_stretch_by_innermost(clock):
    with profiling.span("turn", "decode"):
        k = profiling.enqueued("jit_a")
        clock.at(50)
        with profiling.span("fetch_wait", "single"):
            clock.at(100)
            profiling.done(k)
            clock.at(130)
        clock.at(200)
        with profiling.span("emit", "decode"):
            clock.at(260)
        clock.at(300)
    clock.at(400)
    with profiling.span("turn", "decode"):
        clock.at(450)
        with profiling.span("dispatch", "single"):
            clock.at(480)
            profiling.enqueued("jit_b")
            clock.at(999)  # in flight again: the span's rest books nothing
    return {"fetch_wait": 30, "turn": 70 + 40 + 50, "emit": 60,
            "none": 100, "dispatch": 30, "any": 380}


def a_span_on_another_thread_books_nothing(clock):
    k = profiling.enqueued("jit_a")
    clock.at(100)
    profiling.done(k)

    def elsewhere():
        with profiling.span("elsewhere", "test"):
            clock.at(300)

    worker = threading.Thread(target=elsewhere)
    worker.start()
    worker.join()
    clock.at(400)
    with profiling.span("turn", "decode"):
        clock.at(450)
        profiling.enqueued("jit_b")
    return {"none": 300, "turn": 50, "any": 350}


def another_threads_enqueue_ends_the_stretch(clock):
    k = profiling.enqueued("jit_a")
    clock.at(100)
    profiling.done(k)

    def elsewhere():
        with profiling.span("dispatch", "single"):
            clock.at(300)
            profiling.enqueued("jit_b")

    worker = threading.Thread(target=elsewhere)
    worker.start()
    worker.join()
    clock.at(500)
    with profiling.span("turn", "decode"):
        clock.at(600)  # this thread's spans find the ledger not empty
    profiling.enqueued("jit_c")
    return {"dispatch": 200, "any": 200}


def reset_keeps_the_ordinals_and_forgets_the_stretch(clock):
    k = profiling.enqueued("jit_a")
    clock.at(100)
    profiling.done(k)
    clock.at(200)
    profiling.reset()
    assert ledger() == {"enqueued": k, "done": k, "empty": False}
    clock.at(300)
    with profiling.span("turn", "decode"):
        clock.at(350)
    assert profiling.enqueued("jit_b") == k + 1
    # a program enqueued before the reset may be waited for after it
    clock.at(400)
    profiling.done(k + 1)
    clock.at(450)
    profiling.enqueued("jit_c")
    return {"none": 50, "any": 50}


def a_new_registry_epoch_starts_every_series_at_zero(clock):
    k = profiling.enqueued("jit_a")
    clock.at(100)
    profiling.done(k)
    with profiling.span("emit", "decode"):
        clock.at(150)
    obs.REGISTRY.reset()  # the stretch under way outlives the registry
    clock.at(400)
    profiling.enqueued("jit_b")
    got = starved()
    assert {"any", "none", "dispatch", "fetch_wait"} <= set(got)
    assert got["dispatch"] == got["fetch_wait"] == 0.0
    return {"emit": 50, "none": 250, "any": 300}


CASES = [run_wait_gap_run, two_enqueued_and_the_first_done,
         the_newest_done_counts_from_that_instant,
         nested_spans_split_the_stretch_by_innermost,
         a_span_on_another_thread_books_nothing,
         another_threads_enqueue_ends_the_stretch,
         reset_keeps_the_ordinals_and_forgets_the_stretch,
         a_new_registry_epoch_starts_every_series_at_zero]


@pytest.mark.parametrize("case", CASES, ids=[c.__name__ for c in CASES])
def test_known_empty_seconds_are_booked_under_the_open_span(clock, case):
    before = starved()
    expected = case(clock)
    if case is a_new_registry_epoch_starts_every_series_at_zero:
        before = {}
    got = gained(before)
    assert got == expected
    assert sum(v for k, v in got.items() if k != "any") == got.get("any", 0)


def test_every_series_stands_at_zero_after_install():
    obs.REGISTRY.reset()
    profiling.install_runtime_hooks()
    got = starved()
    assert got["any"] == got["none"] == 0.0
    assert set(got) == set(profiling._STARVED_UNDER)


# ---------------------------------------------------------------------------
# (c) the executor's two lanes write it
# ---------------------------------------------------------------------------


def _notes(name, lane=None):
    return [s[NOTE] for s in profiling.spans()
            if s[NAME] == name and lane in (None, s[LANE])]


def fetching_run(exe, main, loss, feed):
    exe.run(main, feed=feed, fetch_list=[loss.name])
    k = exe.ordinal
    assert _notes("dispatch") == [f"jit_train_step#{k}"]
    assert _notes("fetch_wait") == [f"done#{k}"]
    assert _notes("device_wait") == _notes("fetch_sync")[:1] == [None]
    assert ledger()["empty"]


def run_that_fetches_nothing(exe, main, loss, feed):
    exe.run(main, feed=feed, fetch_list=[])
    assert _notes("dispatch") == [f"jit_train_step#{exe.ordinal}"]
    # no output was read: the wait span is there and proves nothing
    assert _notes("fetch_wait") == [None]
    assert ledger()["done"] < exe.ordinal


def run_that_fetches_a_feed(exe, main, loss, feed):
    exe.run(main, feed=feed, fetch_list=["x"])
    # a feed handed back is no output of the program's ops
    assert _notes("fetch_wait") == [None]
    assert ledger()["done"] < exe.ordinal


def enqueue_only_run(exe, main, loss, feed):
    before = ledger()["enqueued"]
    (out,) = exe.run(main, feed=feed, fetch_list=[loss.name],
                     return_numpy=False)
    assert exe.ordinal == before + 1
    assert _notes("dispatch") == [f"jit_train_step#{exe.ordinal}"]
    assert _notes("fetch_wait") == []
    assert not ledger()["empty"]
    np.asarray(out)
    profiling.done(exe.ordinal)  # the caller's own wait
    assert ledger()["empty"]


def chained_run(exe, main, loss, feed):
    exe.run_steps(main, feed=feed, n_steps=3, fetch_list=[loss.name])
    exe.run_steps(main, feed=feed, n_steps=3, fetch_list=[loss.name])
    k = exe.ordinal
    assert _notes("dispatch", "chain") == [f"jit_train_chain#{k - 1}",
                                           f"jit_train_chain#{k}"]
    assert _notes("fetch_wait", "chain") == [f"done#{k - 1}", f"done#{k}"]


def run_under_profile_phases(exe, main, loss, feed):
    prior = fluid.get_flags(["FLAGS_profile_phases"])
    fluid.set_flags({"FLAGS_profile_phases": True})
    try:
        exe.run(main, feed=feed, fetch_list=[loss.name],
                return_numpy=False)
    finally:
        fluid.set_flags(prior)
    assert _notes("device_wait") == [f"done#{exe.ordinal}"]
    assert ledger()["empty"]


def run_under_flags_benchmark(exe, main, loss, feed):
    prior = fluid.get_flags(["FLAGS_benchmark"])
    fluid.set_flags({"FLAGS_benchmark": True})
    try:
        exe.run(main, feed=feed, fetch_list=[loss.name],
                return_numpy=False)
    finally:
        fluid.set_flags(prior)
    assert _notes("fetch_sync") == [None, f"done#{exe.ordinal}"]
    assert ledger()["empty"]


def run_inside_a_profiler_session(exe, main, loss, feed):
    from paddle_tpu.fluid import profiler

    profiler.start_profiler()
    try:
        exe.run(main, feed=feed, fetch_list=[loss.name],
                return_numpy=False)
    finally:
        profiler.stop_profiler(profile_path=None)
    # timed_run blocked as it closed; the tail span says so
    assert _notes("fetch_sync") == [None, f"done#{exe.ordinal}"]


RUNS = [fetching_run, run_that_fetches_nothing, run_that_fetches_a_feed,
        enqueue_only_run, chained_run, run_under_profile_phases,
        run_under_flags_benchmark, run_inside_a_profiler_session]


@pytest.fixture(scope="module")
def trainer():
    main, startup, loss, feed = _train_program()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        profiling.reset()
        exe.run(startup)
        assert _notes("dispatch") == [f"jit_startup#{exe.ordinal}"]
        exe.run(main, feed=feed, fetch_list=[loss.name])  # compiles
    return exe, scope, main, loss, feed


@pytest.mark.parametrize("run", RUNS, ids=[r.__name__ for r in RUNS])
def test_the_executor_names_what_it_enqueues_and_what_a_wait_proved(
        trainer, run):
    exe, scope, main, loss, feed = trainer
    with scope_guard(scope):
        if run is chained_run:
            exe.run_steps(main, feed=feed, n_steps=3,
                          fetch_list=[loss.name])  # compiles
        profiling.reset()
        run(exe, main, loss, feed)
    ordinals = [int(n.rsplit("#", 1)[1]) for n in _notes("dispatch")]
    assert ordinals == list(range(ordinals[0], ordinals[0] + len(ordinals)))


def test_a_lane_that_marks_nothing_done_leaves_the_chip_not_known_empty():
    """The data-parallel lane counts its programs in and waits nowhere it
    could say so: after a fetching run of lane `single` emptied the
    ledger, its step ends the stretch and nothing is booked while it runs."""
    from paddle_tpu.parallel import DataParallelRunner

    main, startup, loss, feed = _train_program()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        runner = DataParallelRunner(main, loss.name)
        runner.run(exe, feed, [loss.name], scope)  # compiles
        exe.run(main.clone(for_test=True), feed=feed,
                fetch_list=[loss.name])
        assert ledger()["empty"]
        before = ledger()["enqueued"]
        runner.run(exe, feed, [loss.name], scope)
        assert ledger()["enqueued"] == before + 1
        assert not ledger()["empty"]
        booked = starved()
        runner.run(exe, feed, [loss.name], scope)
        with profiling.span("turn", "decode"):
            pass
        assert starved() == booked


# ---------------------------------------------------------------------------
# (b) a decode engine's turns
# ---------------------------------------------------------------------------


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
    eng = serving.DecodeEngine(cfg, scope=scope, pool_slots=3, page_size=4,
                               prefill_chunk=4, max_len=48, name="ledger",
                               auto_start=False)
    eng.warmup()
    yield eng
    eng.close()


PROMPTS = [(3, 40), (14, 8), (9, 8), (17, 8), (11, 8)]  # (length, new tokens)


def _serve(eng):
    """A live row throughout (the first request decodes 40 tokens), and
    four prompts of three to five chunks arriving over the turns."""
    rng = np.random.RandomState(51)
    futures = []
    for length, new in PROMPTS:
        futures.append(eng.submit(list(rng.randint(1, 50, size=length)), new))
        eng._step_once()
        eng._step_once()
    for _ in range(400):
        if all(f.done() for f in futures):
            break
        eng._step_once()
    return [f.result(timeout=0) for f in futures]


def test_a_decode_engines_turns_keep_the_devices_ledger(engine, monkeypatch):
    before, turn0 = starved(), engine._turns
    profiling.reset()
    t0 = time.perf_counter_ns()
    served = _serve(engine)
    wall_s = (time.perf_counter_ns() - t0) / 1e9
    assert engine._turns - turn0 >= 30
    spans = profiling.spans()

    # every program enqueued has a name and the next ordinal, in ring order
    enqueued = [s for s in spans if s[NAME] == "dispatch"]
    assert all(s[LANE] == "single" for s in enqueued)
    parsed = [re.fullmatch(r"(jit_[a-z_]+)#(\d+)", s[NOTE] or "")
              for s in enqueued]
    assert all(parsed), [s[NOTE] for s in enqueued if not s[NOTE]]
    assert {m.group(1) for m in parsed} == {"jit_prefill_chunk",
                                            "jit_decode_step"}
    ordinals = [int(m.group(2)) for m in parsed]
    assert ordinals == list(range(ordinals[0], ordinals[0] + len(ordinals)))

    # every wait says what it proved: the executor's, and the scheduler's
    waits = [s for s in spans if (s[NOTE] or "").startswith("done#")]
    assert {s[NAME] for s in waits} == {"fetch_wait", "prefill.await"}
    assert all(s[NOTE] for s in spans if s[NAME] == "fetch_wait")
    awaits = [s for s in spans if s[NAME] == "prefill.await"]
    assert awaits and all(s[LANE] == "decode" and s[NOTE] for s in awaits)

    # a chunk enqueued and not waited for (its dispatch lies directly
    # under the turn) is covered by a wait on it or on a later program
    # before the turn after ends: here a live row's step of the same turn
    turns = {s[ID]: s for s in spans if s[NAME] == "turn"}
    ring = {s[ID]: i for i, s in enumerate(spans)}
    unawaited = [(s, k) for s, k in zip(enqueued, ordinals)
                 if s[PARENT] in turns]
    assert len(unawaited) >= 8
    for s, k in unawaited:
        cover = next(w for w in waits if ring[w[ID]] > ring[s[ID]]
                     and int(w[NOTE][5:]) >= k)
        assert cover[NUMBER] <= s[NUMBER] + 1, (s[NOTE], cover[NOTE])
        assert cover[T1] <= max(t[T1] for t in turns.values()
                                if t[NUMBER] <= s[NUMBER] + 1)

    # known-empty seconds: some, the sum of their parts, within the wall
    profiling.enqueued("jit_after")  # the stretch under way ends here
    got = {k: v - before.get(k, 0.0) for k, v in starved().items()}
    parts = sum(v for k, v in got.items() if k != "any")
    assert got["any"] > 0
    assert parts == pytest.approx(got["any"], rel=1e-9)
    assert got["any"] <= wall_s
    # under the scheduler's own spans and under the executor's
    assert got["turn"] > 0 and got["emit"] > 0 and got["fetch_wait"] > 0
    assert got["dispatch"] > 0 and got["decode.feed_build"] > 0

    # the same requests with the ledger's two calls taken out (what the
    # scheduler did before there was one): the same tokens
    monkeypatch.setattr(profiling, "enqueued", lambda program: 0)
    monkeypatch.setattr(profiling, "done", lambda ordinal: None)
    assert _serve(engine) == served
    assert [len(t) for t in served] == [new for _, new in PROMPTS]
