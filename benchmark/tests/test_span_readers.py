"""The readers of ISSUE 25 on hand-made events: ``trace_module`` on
program executions, ``host_gap`` on spans and device intervals whose
offset is known.  Device events are (name, start_ns, duration_ns); spans
are the program's tuples (name, lane, start_ns, end_ns, id, parent_id,
number, note) on another clock; the trace counts from its own start."""

import pytest

from benchmark import harness

MS = 1_000_000
START = 1_700_000_000 * 1_000_000_000  # UNIX ns of the trace's zero
OFFSET = 7_000 * MS  # the spans' clock reads 1000 ms where the trace reads 8000
PAIRS = {r"^jit_decode_step(\(|$)": "decode.run",
         r"^jit_prefill_chunk(\(|$)": "prefill.run"}
SCHED = r"^turn$|^admit$|^emit$|^(prefill|decode)\.(pages|feed_build|run)$"
EXEC = (r"^(lookup|compile|feed_prep|dispatch|device_wait|fetch_sync|"
        r"fetch_wait)$")

trace_module = harness.load_module("readers", "trace_module.py")
host_gap = harness.load_module("readers", "host_gap.py")


def test_trace_module_means_the_matching_programs():
    # the window cut the first and the last execution: they are left out
    modules = [("jit_decode_step(17)", 0, 31 * MS),
               ("jit_decode_step(17)", 100 * MS, 100 * MS),
               ("jit_prefill_chunk(3)", 200 * MS, 60 * MS),
               ("jit_decode_step(17)", 300 * MS, 104 * MS),
               ("jit_prefill_chunk(3)", 410 * MS, 7 * MS)]
    reduced = {"first": {"modules": modules, "ops": []}}
    spec = {"module": r"^jit_decode_step(\(|$)"}
    assert trace_module.read(spec, {}, reduced, {}) == 102.0
    spec = {"module": r"^jit_prefill_chunk(\(|$)"}
    assert trace_module.read(spec, {}, reduced, {}) == 60.0
    # a program whose bodies are all jit_fn: nothing to read, no number
    reduced = {"first": {"modules": [("jit_fn(9)", k * MS, MS)
                                     for k in range(4)], "ops": []}}
    assert trace_module.read(spec, {}, reduced, {}) is None


def turns(n, jitter=lambda k: 0, unspanned=0):
    """``n`` scheduler turns of 100 ms on the host's clock and what the
    device did meanwhile.  A turn: 2 ms of pages, then a run of 90 ms
    (lookup 1, feed_prep 4, dispatch 2, device_wait 0, fetch_sync 1,
    fetch_wait 82), then 8 ms of emit, of which the last ``unspanned`` ms
    lie outside every span.  The program runs on the device from 1 ms
    into ``dispatch`` until 0.3 ms (+ jitter) before ``fetch_wait`` ends.
    """
    spans, modules, ids = [], [], iter(range(1, 10**6))
    for k in range(n):
        t = 1000 * MS + k * 100 * MS
        turn, run = next(ids), next(ids)
        kind = "decode" if k % 2 else "prefill"

        def add(name, a, b, parent, lane="decode"):
            spans.append((name, lane, t + a, t + b, next(ids), parent, k,
                          None))

        add(f"{kind}.pages", 0, 2 * MS, turn)
        for name, a, b in (("lookup", 2, 3), ("feed_prep", 3, 7),
                           ("dispatch", 7, 9), ("device_wait", 9, 9),
                           ("fetch_sync", 9, 10), ("fetch_wait", 10, 92)):
            add(name, a * MS, b * MS, run, "single")
        spans.append((f"{kind}.run", "decode", t + 2 * MS, t + 92 * MS, run,
                      turn, k, None))
        add("emit", 92 * MS, (100 - unspanned) * MS, turn)
        spans.append(("turn", "decode", t, t + (100 - unspanned) * MS, turn,
                      0, k, None))
        start = t + 8 * MS + OFFSET
        end = t + 92 * MS - 300_000 + jitter(k) + OFFSET
        modules.append((f"jit_{kind}_{'step' if k % 2 else 'chunk'}(5)",
                        start, end - start))
    return spans, modules


def setup(monkeypatch, spans, modules, coarse_error=3 * MS):
    """The reader's two inputs.  Device operations: each program is one
    operation.  The clock pair is off by ``coarse_error``, as a wall clock
    is."""
    clock = (START + OFFSET + coarse_error, 0)
    monkeypatch.setattr(host_gap, "program_spans", lambda: (spans, clock))
    return {"first": {"ops": list(modules), "modules": modules},
            "profile_start_ns": START}


def shares(reduced):
    return [host_gap.read(spec, {}, reduced, {}) for spec in (
        {"pairs": PAIRS, "spans": SCHED}, {"pairs": PAIRS, "spans": EXEC},
        {"pairs": PAIRS, "mode": "unattributed"})]


def test_host_gap_recovers_the_offset_and_attributes_every_gap(
        monkeypatch, capsys):
    spans, modules = turns(12)
    reduced = setup(monkeypatch, spans, modules)
    found = host_gap.align(spans, (START + OFFSET + 3 * MS, 0), START,
                           modules, PAIRS)
    # the offset is the clocks' distance less the 0.3 ms of transfer
    assert found["offset_ns"] == OFFSET - 300_000
    assert found["pairs"] == 12 and found["spread_ns"] == 0
    sched, exe, none = shares(reduced)
    assert sched + exe + none == pytest.approx(100.0, abs=1e-9)
    # between two programs the device idles 16.3 ms (the 0.3 ms of
    # transfer went into the offset): 8 of emit, 2 of pages, 1 lookup,
    # 4 feed_prep, 1.3 of dispatch
    assert none == 0.0
    assert sched == pytest.approx(100 * 10 / 16.3)
    assert exe == pytest.approx(100 * 6.3 / 16.3)
    out = capsys.readouterr().out
    assert "clock alignment over 12 pairs" in out
    assert out.count("INFO host_gap: idle 16.300 ms under") == 5


def test_host_gap_time_under_no_span_is_unattributed(monkeypatch):
    spans, modules = turns(12, unspanned=3)
    sched, exe, none = shares(setup(monkeypatch, spans, modules))
    assert none == pytest.approx(100 * 3 / 16.3)
    assert sched + exe + none == pytest.approx(100.0, abs=1e-9)


def test_host_gap_refuses_a_clock_it_cannot_trust(monkeypatch, capsys):
    # residuals that spread by 3 ms: no number
    spans, modules = turns(12, jitter=lambda k: -(k % 2) * 3 * MS)
    assert shares(setup(monkeypatch, spans, modules)) == [None] * 3
    assert "alignment refused" in capsys.readouterr().out
    # fewer than ten pairs: no number
    spans, modules = turns(8)
    assert shares(setup(monkeypatch, spans, modules)) == [None] * 3
    # the coarse clock is a whole turn off: the pairs would agree on a
    # wrong offset, so the first pair's distance decides
    spans, modules = turns(12)
    reduced = setup(monkeypatch, spans, modules, coarse_error=100 * MS)
    assert shares(reduced) == [None] * 3
    # a program that ran before its dispatch span opened
    spans, modules = turns(12)
    modules = [(n, s - 5 * MS, d + 5 * MS) for n, s, d in modules]
    assert shares(setup(monkeypatch, spans, modules)) == [None] * 3


def test_host_gap_reads_nothing_from_a_program_without_spans(monkeypatch):
    _, modules = turns(12)
    monkeypatch.setattr(host_gap, "program_spans", lambda: None)
    reduced = {"first": {"ops": list(modules), "modules": modules},
               "profile_start_ns": START}
    assert shares(reduced) == [None] * 3


def test_the_trace_file_says_when_its_clock_read_zero(monkeypatch, tmp_path):
    import time

    import jax.numpy as jnp

    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    assert host_gap.profile_start_ns({}) is None  # no trace was taken
    before = time.time_ns()
    with harness.tracing(str(tmp_path / ".bench_trace")):
        jnp.ones((8, 8)).sum().block_until_ready()
    start = host_gap.profile_start_ns({})
    assert before <= start <= time.time_ns()
    assert host_gap.profile_start_ns({"profile_start_ns": 5}) == 5
