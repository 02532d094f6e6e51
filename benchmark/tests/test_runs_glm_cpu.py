"""The GLM-5 decode-lane cell's tiny twin end to end on the CPU: the
runner, the configuration's shape, the reference and `correct`, with
every context past the tiny ``index_topk`` so that selection is live.
The TPU requirement is lifted here as in test_runs_cpu.py."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import generator, harness, metrics

TINY = os.path.join(harness.HERE, "tests", "tiny")


@pytest.fixture()
def run_main(monkeypatch, capsys):
    monkeypatch.setattr(harness, "BENCHMARK_PATH",
                        os.path.join(TINY, "BENCHMARK.glm.json"))
    monkeypatch.setattr(harness, "TRAFFIC_DIRS",
                        harness.TRAFFIC_DIRS + [TINY])
    spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", os.path.join(harness.HERE, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)

    def call(workload, seed=2**31 + 17, seconds=1.0, trace=0):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      devices_for=lambda chips: jax.devices()[:chips])
        assert rc == 0
        out = capsys.readouterr().out
        return json.loads(out.strip().splitlines()[-1]), out

    return call


def test_glm_cell_end_to_end(run_main):
    line, out = run_main("glm-tiny.tiny-closed-long")
    assert line["correct"] is True, out
    assert set(line["metrics"]) == {"decode_tokens_per_s", "tpot_p95_ms",
                                    "setup_s"}
    assert line["attempted"] >= 3 and line["failed"] == 0
    assert "CHECK ok   served_logit_gap:" in out
    assert "CHECK ok   served_logit_gap_median:" in out


def test_a_traced_run_reads_the_device_counters_at_the_edges(
        run_main, monkeypatch):
    """The CPU has no device trace, so the reduction is stood in for; the
    pick counters are the program's own, read off the device at the two
    edges of the traced interval and nowhere else."""
    from paddle_tpu import serving

    calls = []
    real = serving.DecodeEngine.book_device_counters
    monkeypatch.setattr(serving.DecodeEngine, "book_device_counters",
                        lambda self: (calls.append(1), real(self))[1])
    monkeypatch.setattr(metrics, "reduce_trace", lambda out, devices: {
        "busy_s": 1.0, "window_s": 1.0,
        "first": {"ops": [("x", 0, 1)], "modules": [], "busy_s": 1.0},
        "breakdown": {"device_ops": [], "idle_gaps": []}})
    line, out = run_main("glm-tiny.tiny-closed-long", seconds=2.0, trace=1)
    assert line["correct"] is True, out
    assert len(calls) == 2
    # the metric's file scales the held share of the picks by the real
    # configuration's 8 picks a token; 4 of the tiny one's 8 experts are held
    assert 0.1 < line["metrics"]["moe_held_picks_per_token.serve"][
        "value"] / 8.0 < 0.9
    line, out = run_main("glm-tiny.tiny-closed-long")      # untraced: none
    assert len(calls) == 2 and line["correct"] is True


def test_the_first_wave_keeps_its_whole_outputs():
    runner = harness.load_module("runners", "glm_decode_lane.py")
    mix = harness.load_json("tests", "tiny", "tiny-closed-long.json")
    sizes = generator.request_sizes(mix)
    cut = generator.closed_loop_requests(mix, 5, 96)
    whole = runner.whole_first_wave(
        generator.closed_loop_requests(mix, 5, 96), mix)
    for c in range(mix["clients"]):
        (p0, o0), (p1, o1) = next(cut[c]), next(whole[c])
        assert p0 == p1 and o0 <= o1 == sizes[c][1]
        assert next(cut[c]) == next(whole[c])          # later requests as is
    assert any(next(iter(generator.closed_loop_requests(mix, 5, 96)[c]))[1]
               < sizes[c][1] for c in range(mix["clients"]))


def test_the_control_separates_fp8_by_both_limits():
    """glm_decode_lane.control at the tiny size: one reference pass a
    precision; the sound program reads far under the tiny limits, the
    fp8 control over them, by the largest and by the median gap."""
    runner = harness.load_module("runners", "glm_decode_lane.py")
    lowprec = harness.load_module("reference", "lowprec.py")
    config = harness.load_json("tests", "tiny", "glm-tiny.json")
    mix = harness.load_json("tests", "tiny", "tiny-closed-long.json")
    (row,) = runner.control(config, mix, jax.devices()[:1], [31], lowprec,
                            1.0)
    limits = config["correct"]
    assert row["requests"] == 3 and row["served_tokens"] > 9
    assert row["program"] < limits["served_logit_gap"] < row["control_fp8"]
    assert (row["program_median"] < limits["served_logit_gap_median"]
            < row["control_fp8_median"])


def test_a_wrong_selection_is_not_correct(run_main, monkeypatch):
    """The indexer's head weights dropped (every head counts alike): the
    selected sets change, and with them the served logits."""
    from paddle_tpu.kernels import primitives as prims
    from paddle_tpu.kernels.primitives import dsa

    real = dsa.dsa_indexer_scores_reference
    monkeypatch.setattr(
        dsa, "dsa_indexer_scores_reference",
        lambda q, w, *rest: real(q, abs(w) * 0 + 1, *rest))
    line, out = run_main("glm-tiny.tiny-closed-long")
    assert line["correct"] is False, out
    del prims


def test_the_work_counts_of_the_real_configuration():
    """glm_work.py at the committed configuration: the issue's numbers."""
    work = harness.load_module("glm_work.py")
    with open(os.path.join(harness.HERE, "configs",
                           "glm-5-ep16.json")) as f:
        config = json.load(f)
    assert work.index_bytes_per_context_token(config) == 5 * 128 * 2
    assert work.selected_latent_bytes_per_query(config) == 5 * 2048 * 1152
    assert work.selected_latent_bytes_per_query(config, 100) == 5 * 100 * 1152
    assert work.expert_bytes(config) == 3 * 6144 * 2048 * 2
