"""The Olmo-Hybrid decode-lane cell's tiny twin end to end on the CPU: the
runner, the configuration's shape, the reference and `correct`, with
prompts of one to six chunks of 8 tokens, so that the recurrent state
and the convolution's tail are carried from chunk to chunk and into the
decode steps, and state blocks go from one sequence to the next.  The
TPU requirement is lifted here as in test_runs_cpu.py."""

import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from benchmark import harness, metrics

TINY = os.path.join(harness.HERE, "tests", "tiny")
CELL = "olmo-hybrid-tiny.tiny-closed-state"


@pytest.fixture()
def run_main(monkeypatch, capsys):
    monkeypatch.setattr(harness, "BENCHMARK_PATH",
                        os.path.join(TINY, "BENCHMARK.olmo.json"))
    monkeypatch.setattr(harness, "TRAFFIC_DIRS",
                        harness.TRAFFIC_DIRS + [TINY])
    spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", os.path.join(harness.HERE, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)

    def call(workload, seed=2**31 + 23, seconds=1.0, trace=0):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      devices_for=lambda chips: jax.devices()[:chips])
        assert rc == 0
        out = capsys.readouterr().out
        return json.loads(out.strip().splitlines()[-1]), out

    return call


def test_olmo_cell_end_to_end(run_main):
    line, out = run_main(CELL)
    assert line["correct"] is True, out
    assert set(line["metrics"]) == {"decode_tokens_per_s", "tpot_p95_ms",
                                    "setup_s"}
    assert line["attempted"] >= 3 and line["failed"] == 0
    assert "CHECK ok   served_logit_gap:" in out
    assert "CHECK ok   served_logit_gap_mean:" in out
    # the second pass, under the reference in the stated precision
    assert "CHECK ok   stated_gap_mean:" in out


def test_a_traced_run_values_the_state_kind_and_the_work(run_main,
                                                         monkeypatch):
    """The CPU has no device trace, so the reduction is stood in for; the
    pool's counters are the program's own, and the work numbers come from
    the traced steps' rows and the positions prefilled between the traced
    interval's edges."""
    seen = {}
    real_read = metrics.read

    def read(name, numbers, reduced, devices):
        seen.update(numbers)
        return real_read(name, numbers, reduced, devices)

    monkeypatch.setattr(metrics, "read", read)
    monkeypatch.setattr(metrics, "reduce_trace", lambda out, devices: {
        "busy_s": 1.0, "window_s": 1.0,
        "first": {"ops": [("x", 0, 1)], "modules": [], "busy_s": 1.0},
        "breakdown": {"device_ops": [], "idle_gaps": []}})
    line, out = run_main(CELL, seconds=2.0, trace=1)
    assert line["correct"] is True, out
    # a block a slot and one for the sequence that prefills: none evicted
    assert line["metrics"]["state_blocks_evicted_share.serve"]["value"] == 0
    assert seen["pt_kv_pages_alloc_total{bench,state}"] > 0
    # the kernels' shares have nothing to read without a device trace
    assert "gdn_step_roofline.serve" not in line["metrics"]
    config = harness.load_json("tests", "tiny", "olmo-hybrid-tiny.json")
    work = harness.load_module("olmo_work.py")
    per_row = work.state_bytes_per_row(config)
    assert per_row == 3 * 2 * 3 * 8 * 16 * 4
    rows = seen["work.gdn_step_bytes_per_decode_step"] / per_row
    assert 0 < rows <= config["engine"]["pool_slots"]
    assert seen["work.gdn_calls_per_decode_step"] == 3
    if "work.gdn_chunk_flop_per_chunk" in seen:   # a chunk ran while traced
        per_token = work.rule_flop_per_token(config)
        assert 0 < seen["work.gdn_chunk_flop_per_chunk"] <= 8 * per_token
    assert seen["work.kv_bytes_per_decode_step"] > 0


def test_a_state_that_is_not_carried_across_chunks_is_not_correct(
        run_main, monkeypatch):
    """Every chunk reads its state block as zeros: a prompt longer than
    one chunk forgets what came before."""
    from paddle_tpu.kernels.primitives import gdn

    real = gdn.gated_delta_chunk_reference
    monkeypatch.setattr(
        gdn, "gated_delta_chunk_reference",
        lambda q, k, v, g, beta, state, block, fresh: real(
            q, k, v, g, beta, state, block, True))
    line, out = run_main(CELL)
    assert line["correct"] is False, out
    assert "CHECK FAIL served_logit_gap" in out


def test_a_reused_block_that_is_not_reset_is_not_correct(run_main,
                                                         monkeypatch):
    """No chunk reads its block as zeros: a sequence that takes over a
    block starts from what the last one left there."""
    from paddle_tpu.kernels.primitives import gdn

    real = gdn.gated_delta_chunk_reference
    monkeypatch.setattr(
        gdn, "gated_delta_chunk_reference",
        lambda q, k, v, g, beta, state, block, fresh: real(
            q, k, v, g, beta, state, block, False))
    line, out = run_main(CELL)
    assert line["correct"] is False, out
    assert "CHECK FAIL served_logit_gap" in out


def test_the_control_fails_fp8_where_bf16_passes():
    """The control at the tiny size: one reference pass a precision.  The
    sound program (float32 here) reads under the tiny limits; the
    reference in bf16 stays under the fp8 control, by the largest gap and
    by the mean (the limits that separate a precision at the cell's size
    are the chip's, PERF.md section 2)."""
    runner = harness.load_module("runners", "olmo_hybrid_decode_lane.py")
    lowprec = harness.load_module("reference", "lowprec.py")
    config = harness.load_json("tests", "tiny", "olmo-hybrid-tiny.json")
    mix = harness.load_json("tests", "tiny", "tiny-closed-state.json")
    (row,) = runner.control(config, mix, jax.devices()[:1], [31], lowprec,
                            3.0)     # long enough for 3 requests under load
    limits = config["correct"]
    assert row["requests"] == 3 and row["served_tokens"] > 9
    assert row["program"] < limits["served_logit_gap"]
    assert row["program_mean"] < limits["served_logit_gap_mean"]
    assert row["bf16"] < row["control_fp8"]
    assert row["bf16_mean"] < row["control_fp8_mean"]
    assert row["control_fp8"] > limits["served_logit_gap"]
    assert {"bf16_state", "bf16_state_mean", "contexts"} <= set(row)
    # ... and both readings under the reference in the stated precision
    assert {"program_stated_mean", "bf16_state_stated_mean"} <= set(row)


def test_the_work_counts_of_the_real_configuration():
    """olmo_work.py at the committed configuration, against numbers worked
    by hand, and the issue's arithmetic of parameters, pages and blocks."""
    work = harness.load_module("olmo_work.py")
    with open(os.path.join(harness.HERE, "configs",
                           "olmo-hybrid-7b-pp4.json")) as f:
        config = json.load(f)
    assert work.layers_of(config, work.LINEAR) == 6
    assert work.layers_of(config, work.FULL) == 2
    assert work.kv_bytes_per_context_token(config) == 30720
    assert work.kv_bytes(config, [1000, 24]) == 1024 * 30720
    assert work.state_values(config) == 30 * 96 * 192 == 552960
    assert work.state_bytes_per_row(config) == 6 * 2 * 2211840
    assert work.rule_flop_per_token(config) == 6 * 4423680
    assert work.decode_contexts(5000, 0, 3) == [5001, 5002]
    ref = harness.load_module("reference", "olmo_hybrid.py")
    shapes = ref.param_shapes(config)
    n = sum(int(np.prod(s)) for s, _, _ in shapes.values())
    assert round(n / 1e6, 1) == 2435.7
    e = config["engine"]
    assert 16 * -(-e["max_len"] // e["page_size"]) + 1 == 1569
    from paddle_tpu.models import olmo_hybrid
    from paddle_tpu.serving.kv_pool import KVPool

    cfg = olmo_hybrid.OlmoHybridConfig(**config["builder"]["config_args"])
    lane = cfg.decode_lane()
    pool = KVPool(lane.num_layers, lane.cache_rows(None), 1569,
                  e["page_size"], 98, seq_state=lane.seq_state,
                  state_layers=lane.state_layers, state_blocks=18)
    kv = 2 * 2 * 1569 * 128 * 3840 * 2
    state = 6 * 18 * (96 * 5760 + 3 * 11520) * 4
    assert pool.modeled_bytes() == kv + state
    assert round(kv / 1e9, 2) == 6.17 and round(state / 1e9, 2) == 0.25
