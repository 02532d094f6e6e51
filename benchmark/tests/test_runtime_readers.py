"""The per-layer metrics of ISSUE 36 (layer ``host runtime``): the
``sum_ratio`` reader on hand-made numbers, ``host_gap`` on a synthetic
ring that holds an encoder run, the new data files against
BENCHMARK.json, and the tiny CPU cells filling every one of them (the
CPU has no device trace: the reduction is stood in for)."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import harness, metrics

TINY = os.path.join(harness.HERE, "tests", "tiny")
MS = 1_000_000
NEW = {"gc_ms_per_turn.serve", "gc_ms_per_step.train",
       "xla_ms_per_turn.serve", "xla_ms_per_step.train",
       "idle_in_encode_share.serve"}

sum_ratio = harness.load_module("readers", "sum_ratio.py")
host_gap = harness.load_module("readers", "host_gap.py")
# the synthetic turns of the older file
span_readers = harness.load_module("tests", "test_span_readers.py")
PROGRAMS = {"decode.run": "jit_decode_step", "prefill.run": "jit_prefill_chunk",
            "encode.run": "jit_vision_encoder"}


def _spec(name):
    return harness.load_json("layer_metrics", name + ".json")


# ---------------------------------------------------------------------------
# sum_ratio
# ---------------------------------------------------------------------------


def test_sum_ratio_sums_by_pattern_and_divides():
    spec = _spec("xla_ms_per_turn.serve")
    numbers = {
        "pt_xla_stage_seconds_total{trace,compile}": 0.25,
        "pt_xla_stage_seconds_total{backend_compile,dispatch}": 1.5,
        "pt_xla_stage_seconds_total{lower,other}": 0.25,
        # a part of the backend compile above, the benchmark's own jax
        # and another family: not summed
        "pt_xla_stage_seconds_total{cache_read,dispatch}": 1.25,
        "pt_xla_stage_seconds_total{backend_compile,none}": 40.0,
        "pt_program_compile_seconds_total{single}": 7.0,
        "pt_decode_turns_total{bench}": 100.0,
    }
    assert sum_ratio.read(spec, numbers, {}, {}) == pytest.approx(20.0)
    train = _spec("xla_ms_per_step.train")
    assert sum_ratio.read(train, dict(numbers, **{"host.steps": 400.0}),
                          {}, {}) == pytest.approx(5.0)


def test_sum_ratio_reads_zero_where_nothing_compiled():
    spec = _spec("xla_ms_per_turn.serve")
    numbers = {f"pt_xla_stage_seconds_total{{{s},{u}}}": 0.0
               for s in ("trace", "lower", "backend_compile", "cache_read")
               for u in ("compile", "dispatch", "other", "none")}
    numbers["pt_decode_turns_total{bench}"] = 2500.0
    value = sum_ratio.read(spec, numbers, {}, {})
    assert value == 0.0 and value is not None


def test_sum_ratio_reads_nothing_without_its_inputs():
    spec = _spec("xla_ms_per_turn.serve")
    series = {"pt_xla_stage_seconds_total{trace,compile}": 0.5}
    # no turn counted, or none at all
    assert sum_ratio.read(spec, dict(
        series, **{"pt_decode_turns_total{bench}": 0.0}), {}, {}) is None
    assert sum_ratio.read(spec, series, {}, {}) is None
    # a program without the family (the parent of ISSUE 36)
    assert sum_ratio.read(
        spec, {"pt_decode_turns_total{bench}": 9.0}, {}, {}) is None


def test_gc_ratios_read_zero_from_a_window_without_a_collection():
    ratio = harness.load_module("readers", "ratio.py")
    numbers = {"pt_host_gc_seconds_total{any}": 0.0,
               "pt_decode_turns_total{bench}": 2500.0, "host.steps": 300.0}
    assert ratio.read(_spec("gc_ms_per_turn.serve"), numbers, {}, {}) == 0.0
    assert ratio.read(_spec("gc_ms_per_step.train"), numbers, {}, {}) == 0.0
    numbers["pt_host_gc_seconds_total{any}"] = 0.75
    assert ratio.read(_spec("gc_ms_per_turn.serve"), numbers, {},
                      {}) == pytest.approx(0.3)
    assert ratio.read(_spec("gc_ms_per_step.train"), numbers, {},
                      {}) == pytest.approx(2.5)


# ---------------------------------------------------------------------------
# host_gap with an encoder run in the ring
# ---------------------------------------------------------------------------


def test_host_gap_reads_zero_where_no_encoder_ran_in_the_trace(monkeypatch):
    spans, modules = span_readers.turns(12)
    reduced = span_readers.setup(monkeypatch, spans, modules)
    assert host_gap.read(_spec("idle_in_encode_share.serve"), {}, reduced,
                         {}) == 0.0


def test_encode_spans_own_time_is_its_own_share(monkeypatch):
    """An encoder run in the 6 ms that no span of turn 4 covers: its feed
    (2 ms) and its run's self time around the executor's span (1 ms of
    4) fall to the encoder's share, the executor's 3 ms stay the
    executor's, and nothing is left unattributed there."""
    spans, modules = span_readers.turns(12, unspanned=6)
    none0 = span_readers.shares(
        span_readers.setup(monkeypatch, spans, modules))[2]
    turn4 = [s for s in spans if s[0] == "turn"][4]
    t, tid = turn4[3], turn4[4]  # the turn's end: inside an idle gap
    spans = spans + [
        ("encode.feed_build", "decode", t, t + 2 * MS, 10**7, tid, 4, None),
        ("encode.run", "decode", t + 2 * MS, t + 6 * MS, 10**7 + 1, tid, 4,
         None),
        ("dispatch", "single", t + 2 * MS, t + 5 * MS, 10**7 + 2,
         10**7 + 1, 4, None)]
    reduced = span_readers.setup(monkeypatch, spans, modules)
    got = host_gap.read(_spec("idle_in_encode_share.serve"), {}, reduced,
                        {})
    idle = 11 * 16.3
    assert got == pytest.approx(100 * 3 / idle)
    sched, exe, none = span_readers.shares(reduced)
    assert none == pytest.approx(none0 - 100 * 6 / idle)
    assert sched + exe + none + got == pytest.approx(100.0, abs=1e-9)


# ---------------------------------------------------------------------------
# the data files and BENCHMARK.json
# ---------------------------------------------------------------------------


def test_every_new_metric_has_its_entry_with_a_workloads_list():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    reports = {e["name"]: set(e.get("workloads", cells))
               for e in bench["end_to_end"]}
    assert NEW <= set(entries)
    # appended, in the order of the issue's table
    assert [m["name"] for m in bench["per_layer"][-5:]] == [
        "gc_ms_per_turn.serve", "gc_ms_per_step.train",
        "xla_ms_per_turn.serve", "xla_ms_per_step.train",
        "idle_in_encode_share.serve"]
    for name in NEW:
        m, spec = entries[name], _spec(name)
        assert m["workloads"] and set(m["workloads"]) <= set(cells)
        assert m["moves"] != "setup_s"
        assert set(m["workloads"]) <= reports[m["moves"]]
        assert m["source"] == spec["source"] and m["better"] == "lower"
        kind = "train" if name.endswith(".train") else "serve"
        want = ("train_tokens_per_s_chip" if kind == "train"
                else "decode_tokens_per_s")
        assert m["moves"] == want
        assert os.path.exists(os.path.join(
            harness.HERE, "readers", spec["reader"] + ".py"))
    serve = reports["decode_tokens_per_s"]
    for name in ("gc_ms_per_turn.serve", "xla_ms_per_turn.serve"):
        assert set(entries[name]["workloads"]) == serve
    for name in ("gc_ms_per_step.train", "xla_ms_per_step.train"):
        assert set(entries[name]["workloads"]) == reports[
            "train_tokens_per_s_chip"]
    assert entries["idle_in_encode_share.serve"]["workloads"] == [
        "kimi-vl-a3b-ep1.closed16-vl-2k-32k"]
    assert {entries[n]["layer"] for n in NEW
            if n != "idle_in_encode_share.serve"} == {"host runtime"}


# ---------------------------------------------------------------------------
# the tiny cells fill them
# ---------------------------------------------------------------------------


def fake_reduction(out, devices):
    """test_runs_spans_cpu.py's stand-in with the encoder's runs: every
    executor run of the program is one "device" execution, from 1 ms into
    its ``dispatch`` span to 0.2 ms before the end of its ``fetch_wait``
    (of its ``dispatch`` where nothing is fetched), on a clock 0.3 ms off
    the wall clock."""
    from paddle_tpu.observability import profiling

    spans = profiling.spans()
    wall, perf = profiling.span_clock()
    start = wall - 10**9
    to_trace = wall - perf - start + 300_000
    kids = {}
    for sp in spans:
        kids.setdefault(sp[5], {})[sp[0]] = sp
    modules = []
    for sp in spans:
        name, number, mine = sp[0], sp[6], kids.get(sp[4], {})
        if name in PROGRAMS and "dispatch" in mine:  # not a first run
            t0 = mine["dispatch"][2] + 1_000_000
            t1 = max(mine.get("fetch_wait", mine["dispatch"])[3] - 200_000,
                     t0 + 1)
            program = PROGRAMS[name]
        elif name == "dispatch" and sp[1] == "single" and not sp[5]:
            program, t0, t1 = "jit_train_step", sp[2] + 1000, sp[3]
        else:
            continue
        modules.append((f"{program}({number})", t0 + to_trace, t1 - t0))
    modules.sort(key=lambda m: m[1])
    busy = sum(d for _, _, d in modules) / 1e9
    window = (modules[-1][1] + modules[-1][2] - modules[0][1]) / 1e9
    return {"busy_s": busy, "window_s": window, "profile_start_ns": start,
            "first": {"ops": list(modules), "modules": modules,
                      "busy_s": busy},
            "breakdown": {"device_ops": [], "idle_gaps": []}}


@pytest.fixture()
def run_traced(monkeypatch, capsys):
    monkeypatch.setattr(harness, "BENCHMARK_PATH",
                        os.path.join(TINY, "BENCHMARK.runtime.json"))
    monkeypatch.setattr(harness, "TRAFFIC_DIRS",
                        harness.TRAFFIC_DIRS + [TINY])
    monkeypatch.setattr(metrics, "reduce_trace", fake_reduction)
    spec = importlib.util.spec_from_file_location(
        "bench_run_runtime_under_test",
        os.path.join(harness.HERE, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)

    def call(workload, seconds):
        from paddle_tpu.observability import profiling

        profiling.reset()  # the ring may hold another test's spans
        rc = run.main(["--workload", workload, "--seed", str(2**31 + 36),
                       "--seconds", str(seconds), "--trace", "1"],
                      devices_for=lambda chips: jax.devices()[:chips])
        assert rc == 0
        out = capsys.readouterr().out
        line = json.loads(out.strip().splitlines()[-1])
        return {k: v["value"] for k, v in line["metrics"].items()}, line, out

    return call


def test_train_cell_fills_its_two(run_traced):
    got, line, out = run_traced("bert-tiny.tiny-train", 1.0)
    assert line["correct"] is True, out
    assert set(got) == {"gc_ms_per_step.train", "xla_ms_per_step.train"}
    assert got["gc_ms_per_step.train"] >= 0.0
    # nothing compiles inside a window
    assert got["xla_ms_per_step.train"] == 0.0


def test_decode_cell_fills_its_two_and_the_shares_still_sum(run_traced):
    got, line, out = run_traced("gpt-tiny.tiny-closed", 2.0)
    assert line["correct"] is True, out
    assert set(got) == {
        "gc_ms_per_turn.serve",
        "xla_ms_per_turn.serve", "idle_in_sched_share.serve",
        "idle_in_exec_share.serve", "idle_unattributed_share.serve"}, out
    assert got["gc_ms_per_turn.serve"] >= 0.0
    assert got["xla_ms_per_turn.serve"] == 0.0
    # a collection is counted and is no span: the three shares are whole
    shares = [got[f"idle_{k}_share.serve"]
              for k in ("in_sched", "in_exec", "unattributed")]
    assert sum(shares) == pytest.approx(100.0, abs=0.1)


def test_image_cell_fills_the_encoders_share_too(run_traced):
    got, line, out = run_traced("kimi-vl-tiny.tiny-closed-vl", 2.0)
    assert line["correct"] is True, out
    assert NEW - {"gc_ms_per_step.train",
                  "xla_ms_per_step.train"} <= set(got), out
    assert got["idle_in_encode_share.serve"] > 0.0
    assert got["xla_ms_per_turn.serve"] == 0.0
    shares = [got[f"idle_{k}_share.serve"]
              for k in ("in_sched", "in_exec", "unattributed", "in_encode")]
    assert sum(shares) == pytest.approx(100.0, abs=0.1)
