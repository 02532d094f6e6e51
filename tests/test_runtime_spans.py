"""What the runtime does beneath the program's spans (ISSUE 36): XLA's
trace, lower, compile and cache-read stages enter the one span ring as
children of the span that was open, with a counter beside them; the
interpreter's collections are counted; a slow step's flight record
names the stages that ran beneath it."""

import cpu_mesh  # noqa: F401  (must precede any jax import)

import gc
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.fluid.executor import Scope, scope_guard
from paddle_tpu.observability import profiling

NAME, LANE, T0, T1, ID, PARENT, NUMBER, NOTE = range(8)
GENERATIONS = ("0", "1", "2", "any")
STAGES = ("trace", "lower", "backend_compile", "cache_read")
UNDER = ("compile", "dispatch", "other", "none")


def samples(family):
    return obs.REGISTRY.snapshot()[family]["samples"]


def xla(stage, under):
    return samples("pt_xla_stage_seconds_total")[(stage, under)]


def under_total(under):
    return sum(xla(stage, under) for stage in STAGES)


@pytest.fixture()
def quiet_gc():
    """No collection but the test's own: a young collection set off by an
    allocation would move the counters between two readings."""
    gc.collect()
    gc.disable()
    yield
    gc.enable()


def _scale_program():
    """mean(x * w): ``w`` is read from the scope, so its shape is no part
    of the executor's cache key."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        w = fluid.layers.create_global_var(
            shape=[4], value=1.0, dtype="float32", persistable=True,
            name="w")
        loss = fluid.layers.mean(fluid.layers.elementwise_mul(x, w))
    return main, startup, loss, {"x": np.ones((8, 4), "float32")}


# ---------------------------------------------------------------------------
# the hooks and their series
# ---------------------------------------------------------------------------


def test_every_series_stands_at_zero_after_install():
    obs.REGISTRY.reset()
    profiling.install_runtime_hooks()
    got = samples("pt_host_gc_seconds_total")
    assert set(got) == {(g,) for g in GENERATIONS}
    # a collection may have run since; what never ran reads 0
    assert all(v >= 0.0 for v in got.values())
    got = samples("pt_xla_stage_seconds_total")
    assert set(got) == {(s, u) for s in STAGES for u in UNDER}
    assert len(got) == 16 and all(v == 0.0 for v in got.values())


def test_installing_twice_registers_once():
    from jax._src import monitoring

    profiling.install_runtime_hooks()
    profiling.install_runtime_hooks()
    assert gc.callbacks.count(profiling._on_gc) == 1
    durations = monitoring.get_event_duration_listeners()
    assert durations.count(profiling._on_xla_stage) == 1


def test_reset_leaves_the_hooks_installed_and_the_ring_empty(quiet_gc):
    with profiling.span("before", "test"):
        jax.jit(lambda a: a * 13)(jnp.ones(2))
    assert profiling.spans()
    profiling.reset()
    assert profiling.spans() == []
    assert gc.callbacks.count(profiling._on_gc) == 1
    full0 = samples("pt_host_gc_seconds_total")[("2",)]
    gc.collect(2)
    assert samples("pt_host_gc_seconds_total")[("2",)] > full0
    jax.jit(lambda a: a * 17)(jnp.ones(2))
    assert "xla.trace" in [s[NAME] for s in profiling.spans()]


# ---------------------------------------------------------------------------
# child_span
# ---------------------------------------------------------------------------


def test_child_span_keeps_the_tuple_and_takes_the_open_spans_number():
    profiling.reset()
    with profiling.span("outer", "test", number=41) as outer:
        sid = profiling.child_span("reported", "host", 1000, 4000, note="n")
    orphan = profiling.child_span("reported", "host", 5000, 6000)
    child, parent, alone = profiling.spans()
    assert len(child) == len(parent) == 8
    assert child == ("reported", "host", 1000, 4000, sid, outer.id, 41, "n")
    assert parent[NAME] == "outer" and sid != outer.id
    assert alone[ID] == orphan and alone[PARENT] == 0
    assert alone[NUMBER] is None and alone[NOTE] is None
    # the same histogram family as span()
    hist = samples("pt_step_phase_seconds")[("reported", "host")]
    assert hist["count"] == 2
    assert hist["sum"] == pytest.approx(4e-6)


def test_child_span_parents_to_its_own_threads_span():
    profiling.reset()
    seen = {}

    def other():
        seen["id"] = profiling.child_span("reported", "host", 1, 2)

    with profiling.span("outer", "test"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
    (child,) = [s for s in profiling.spans() if s[ID] == seen["id"]]
    assert child[PARENT] == 0  # the other thread had no span open


# ---------------------------------------------------------------------------
# collections
# ---------------------------------------------------------------------------


def test_a_collection_is_counted_by_its_generation_and_is_no_span(quiet_gc):
    profiling.reset()
    secs0 = samples("pt_host_gc_seconds_total")
    junk = []
    for _ in range(1000):  # cycles for the collector to find
        a, b = [], []
        a.append(b)
        b.append(a)
        junk.append(a)
    del junk, a, b
    with profiling.span("outer", "test") as outer:
        gc.collect(2)
    secs = samples("pt_host_gc_seconds_total")
    spent = secs[("2",)] - secs0[("2",)]
    assert 0.0 < spent <= (outer.t1 - outer.t0) / 1e9
    assert secs[("any",)] - secs0[("any",)] == pytest.approx(spent)
    assert secs[("0",)] == secs0[("0",)] and secs[("1",)] == secs0[("1",)]
    gc.collect(0)
    young = samples("pt_host_gc_seconds_total")
    assert young[("0",)] > secs[("0",)] and young[("2",)] == secs[("2",)]
    assert young[("any",)] - secs[("any",)] == pytest.approx(
        young[("0",)] - secs[("0",)])
    # counted only: the ring holds the test's own span and nothing else
    assert [s[NAME] for s in profiling.spans()] == ["outer"]


def test_a_collection_after_a_registry_reset_registers_nothing(quiet_gc):
    """The callback runs wherever an allocation interrupted its thread,
    a scrape's iteration over the registry included: it bumps series
    that exist and creates none."""
    profiling.install_runtime_hooks()
    obs.REGISTRY.reset()
    gc.collect(2)
    assert obs.REGISTRY.get("pt_host_gc_seconds_total") is None
    profiling.install_runtime_hooks()
    assert samples("pt_host_gc_seconds_total")[("2",)] == 0.0
    gc.collect(2)
    assert samples("pt_host_gc_seconds_total")[("2",)] > 0.0


# ---------------------------------------------------------------------------
# compile stages
# ---------------------------------------------------------------------------


def test_a_first_run_books_its_stages_under_its_compile_span():
    main, startup, loss, feed = _scale_program()
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        profiling.reset()
        before = {s: xla(s, "compile") for s in STAGES}
        dispatch0 = under_total("dispatch")
        exe.run(main, feed=feed, fetch_list=[loss.name])
    spans = profiling.spans()
    by_id = {s[ID]: s for s in spans}
    (compile_span,) = [s for s in spans if s[NAME] == "compile"]

    def ancestors(sp):
        while sp[PARENT]:
            sp = by_id[sp[PARENT]]
            yield sp[NAME]

    stages = [s for s in spans if s[NAME].startswith("xla.")]
    assert stages and all(s[LANE] == "host" for s in stages)
    assert all("compile" in ancestors(s) for s in stages)
    assert all(compile_span[T0] <= s[T0] + 50_000_000
               and s[T1] <= compile_span[T1] for s in stages)
    # the whole step's stages carry the name the program was jitted under
    named = {s[NAME]: s[NOTE] for s in stages
             if s[NOTE] in ("program", "jit(program)")}
    assert named["xla.trace"] == "program"
    assert named["xla.lower"] == "jit(program)"
    assert named["xla.backend_compile"] == "jit(program)"
    for stage in ("trace", "lower", "backend_compile"):
        assert xla(stage, "compile") > before[stage]
    assert under_total("dispatch") == dispatch0


def test_a_retrace_inside_a_warm_run_is_booked_under_dispatch():
    """A feed whose shape changes is a new signature to the executor (a
    `compile` span); a SCOPE array whose shape changes is not, so the
    jitted call retraces inside the `dispatch` phase: the compile nobody
    asked for."""
    main, startup, loss, feed = _scale_program()
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss.name])
        before = {s: xla(s, "dispatch") for s in STAGES}
        compile0 = under_total("compile")
        scope.set("w", np.ones((1, 4), "float32"))
        profiling.reset()
        (out,) = exe.run(main, feed=feed, fetch_list=[loss.name])
    assert out == pytest.approx(1.0)
    spans = profiling.spans()
    assert not [s for s in spans if s[NAME] == "compile"]
    (dispatch,) = [s for s in spans if s[NAME] == "dispatch"]
    stages = [s for s in spans if s[NAME].startswith("xla.")]
    assert {s[NAME] for s in stages} >= {"xla.trace", "xla.lower",
                                         "xla.backend_compile"}
    assert all(s[PARENT] == dispatch[ID] for s in stages)
    for stage in ("trace", "lower", "backend_compile"):
        assert xla(stage, "dispatch") > before[stage]
    assert under_total("compile") == compile0


def test_jax_under_another_span_and_under_none():
    profiling.reset()
    none0, other0 = under_total("none"), under_total("other")
    jax.jit(lambda a: a * 3 + 1)(jnp.ones(3))
    assert under_total("none") > none0 and under_total("other") == other0
    bare = [s for s in profiling.spans() if s[NAME].startswith("xla.")]
    assert bare and all(s[PARENT] == 0 for s in bare)
    none1 = under_total("none")
    with profiling.span("emit", "decode") as emit:
        jax.jit(lambda a: a * 5 - 2)(jnp.ones(3))
    assert under_total("other") > other0 and under_total("none") == none1
    inner = [s for s in profiling.spans()
             if s[NAME].startswith("xla.") and s not in bare]
    assert inner and all(s[PARENT] == emit.id for s in inner)


def test_nested_traces_sum_to_the_outer_traces_wall_time():
    @jax.jit
    def inner(a):
        return jnp.sin(a) * 2

    def outer(a):
        return inner(a) + inner(a * 2) + jnp.where(a > 0, a, 0).sum()

    arg = jnp.ones(7)  # its own trace is not the one measured
    profiling.reset()
    secs0 = xla("trace", "none")
    jax.jit(outer).lower(arg)
    traces = [s for s in profiling.spans() if s[NAME] == "xla.trace"]
    (whole,) = [s for s in traces if s[NOTE] == "outer"]
    # every nested trace reports itself; its seconds are not booked twice
    booked = xla("trace", "none") - secs0
    assert booked == pytest.approx((whole[T1] - whole[T0]) / 1e9, rel=1e-3)
    # a nested trace under RING_MIN_NS stays out of the ring
    for s in traces:
        assert s is whole or s[T1] - s[T0] >= profiling.RING_MIN_NS


def test_a_cache_read_is_a_stage_of_its_own(tmp_path):
    """A second process-like compile of the same function comes off the
    persistent cache: jax reports the read, inside the backend-compile
    stage."""
    prior = jax.config.jax_compilation_cache_dir
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        def fn(a):
            return jnp.cos(a) * 7.25 + a

        jax.jit(fn)(jnp.ones(5))
        jax.clear_caches()
        profiling.reset()
        reads0 = xla("cache_read", "none")
        compiles0 = xla("backend_compile", "none")
        jax.jit(fn)(jnp.ones(5))
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor)
    # the read is a part of the backend compile it served, not an addend
    assert 0.0 < xla("cache_read", "none") - reads0 <= xla(
        "backend_compile", "none") - compiles0
    spans = profiling.spans()
    (compiled,) = [s for s in spans if s[NAME] == "xla.backend_compile"
                   and s[NOTE] == "jit(fn)"]
    # (the argument's own little programs come off the cache as well)
    reads = [s for s in spans if s[NAME] == "xla.cache_read"
             and compiled[T0] <= s[T0] + 5_000_000 and s[T1] <= compiled[T1]]
    assert len(reads) == 1 and reads[0][LANE] == "host"


# ---------------------------------------------------------------------------
# the slow step's record
# ---------------------------------------------------------------------------


def test_a_slow_steps_flight_record_says_what_ran_beneath(tmp_path,
                                                          quiet_gc):
    names = ["FLAGS_profile_slow_step_zscore", "FLAGS_flight_recorder_dir"]
    prior = fluid.get_flags(names)
    fluid.set_flags({"FLAGS_profile_slow_step_zscore": 4.0,
                     "FLAGS_flight_recorder_dir": str(tmp_path)})
    profiling.reset()
    try:
        for _ in range(20):
            profiling.note_step("dp", 0.01, first_run=False)
        # long ago: not inside the slow step's interval
        profiling.child_span("xla.lower", "host", 0, 1, note="old")
        old = profiling.spans()[-1]
        # move it out of the interval: the ring is the only record
        now = time.perf_counter_ns()
        profiling._ring[-1] = old[:T0] + (now - 10**10,
                                          now - 10**10 + 1) + old[ID:]
        gc.collect(2)  # counted, and no span
        jax.jit(lambda a: a - 11)(jnp.ones(2))
        profiling.note_step("dp", 2.0, first_run=False)
        fr = profiling.flight_recorder()
        assert fr.dumps == 1 and fr.last_dump_reason == "slow_step"
        _meta, records = profiling.read_flight_record(fr.last_dump_path)
    finally:
        fluid.set_flags(prior)
        profiling.reset()
    slow = records[-1]
    assert slow["slow_step"]["z"] > 4.0
    beneath = slow["beneath"]
    assert {"xla.trace", "xla.backend_compile"} <= {b["name"]
                                                     for b in beneath}
    assert all(b["name"].startswith("xla.") for b in beneath)
    assert all(b.get("note") != "old" for b in beneath)
    assert all(b["ms"] >= 0.0 for b in beneath)
    assert any(b.get("note") == "jit(<lambda>)" for b in beneath)
    # an ordinary step's record carries no such list
    assert all("beneath" not in r for r in records[:-1])
