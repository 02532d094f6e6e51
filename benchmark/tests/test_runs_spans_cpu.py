"""The traced path of both runners at tiny size on the CPU, under a tiny
benchmark file that lists the per-layer metrics of ISSUE 25.  The CPU has
no device trace, so the reduction is stood in for: every executor run of
the program becomes one "device" program execution, laid from 1 ms into
its ``dispatch`` span to the end of its ``fetch_wait`` (or, where nothing
is fetched, of its ``dispatch``) on a clock 0.3 ms off the wall clock (a tiny run is 3 ms long).  The
``ratio`` metrics read the program's own counters and must come out; the
two trace readers must make sense of what the spans say."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import harness, metrics

TINY = os.path.join(harness.HERE, "tests", "tiny")
SKEW = 300_000


def fake_reduction(out, devices):
    from paddle_tpu.observability import profiling

    spans = profiling.spans()
    wall, perf = profiling.span_clock()
    start = wall - 10**9  # the "trace" began a second before the pair
    to_trace = wall - perf - start + SKEW
    kids = {}
    for sp in spans:
        kids.setdefault(sp[5], {})[sp[0]] = sp
    modules = []
    for sp in spans:
        name, number = sp[0], sp[6]
        if name.endswith(".run"):
            mine = kids.get(sp[4], {})
            if "dispatch" not in mine:  # a first run: under `compile`
                continue
            program = {"decode.run": "jit_decode_step",
                       "prefill.run": "jit_prefill_chunk"}[name]
            t0 = mine["dispatch"][2] + 1_000_000
            t1 = max(mine["fetch_wait"][3] - 200_000, t0 + 1)
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
                        os.path.join(TINY, "BENCHMARK.spans.json"))
    monkeypatch.setattr(harness, "TRAFFIC_DIRS",
                        harness.TRAFFIC_DIRS + [TINY])
    monkeypatch.setattr(metrics, "reduce_trace", fake_reduction)
    spec = importlib.util.spec_from_file_location(
        "bench_run_spans_under_test", os.path.join(harness.HERE, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)

    def call(workload, seconds):
        from paddle_tpu.observability import profiling

        profiling.reset()  # the ring may hold another test's spans
        rc = run.main(["--workload", workload, "--seed", str(2**31 + 29),
                       "--seconds", str(seconds), "--trace", "1"],
                      devices_for=lambda chips: jax.devices()[:chips])
        assert rc == 0
        out = capsys.readouterr().out
        return json.loads(out.strip().splitlines()[-1]), out

    return call


def test_train_cell_reports_its_three_metrics(run_traced):
    line, out = run_traced("bert-tiny.tiny-train", 1.0)
    assert line["correct"] is True, out
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == {"train_step_device_ms.train", "exec_stage_ms.train",
                        "exec_enqueue_ms.train"}
    assert all(v > 0 for v in got.values())


def test_decode_cell_reports_its_eight_metrics(run_traced):
    line, out = run_traced("gpt-tiny.tiny-closed", 2.0)
    assert line["correct"] is True, out
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == {
        "decode_step_device_ms.serve", "prefill_chunk_device_ms.serve",
        "turn_sched_ms.serve", "exec_stage_ms.serve",
        "exec_enqueue_ms.serve", "idle_in_sched_share.serve",
        "idle_in_exec_share.serve", "idle_unattributed_share.serve"}, out
    for name in ("turn_sched_ms.serve", "exec_stage_ms.serve",
                 "exec_enqueue_ms.serve", "decode_step_device_ms.serve"):
        assert got[name] > 0
    shares = [got[f"idle_{k}_share.serve"]
              for k in ("in_sched", "in_exec", "unattributed")]
    assert sum(shares) == pytest.approx(100.0, abs=0.1)
    # the scheduler thread is inside a turn whenever it has work: what no
    # span covers is the loop's own bookkeeping between two turns
    assert shares[2] < 10.0, out
    assert "INFO host_gap: clock alignment over" in out
