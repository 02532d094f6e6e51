"""The Kimi-Linear decode-lane cell's tiny twin end to end on the CPU: the
runner, the configuration's shape, the reference and `correct`, with
prompts of one to six chunks of 8 tokens, so that the recurrent state
(a decay a key channel) and the convolution's tail are carried from
chunk to chunk and into the decode steps beside latent rows in one pool,
and 4 of 8 experts are held.  The TPU requirement is lifted here as in
test_runs_cpu.py."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import harness, metrics

TINY = os.path.join(harness.HERE, "tests", "tiny")
CELL = "kimi-linear-tiny.tiny-closed-state"


@pytest.fixture()
def run_main(monkeypatch, capsys):
    monkeypatch.setattr(harness, "BENCHMARK_PATH",
                        os.path.join(TINY, "BENCHMARK.kimi-linear.json"))
    monkeypatch.setattr(harness, "TRAFFIC_DIRS",
                        harness.TRAFFIC_DIRS + [TINY])
    spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", os.path.join(harness.HERE, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)

    def call(workload, seed=2**31 + 45, seconds=1.0, trace=0):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      devices_for=lambda chips: jax.devices()[:chips])
        assert rc == 0
        out = capsys.readouterr().out
        return json.loads(out.strip().splitlines()[-1]), out

    return call


def test_kimi_linear_cell_end_to_end(run_main):
    line, out = run_main(CELL)
    assert line["correct"] is True, out
    assert set(line["metrics"]) == {"decode_tokens_per_s", "tpot_p95_ms",
                                    "setup_s"}
    assert line["attempted"] >= 3 and line["failed"] == 0
    assert "CHECK ok   served_logit_gap:" in out
    assert "CHECK ok   served_logit_gap_mean:" in out
    # the second pass, under the reference in the stated precision
    assert "CHECK ok   stated_gap_mean:" in out


def test_a_traced_run_values_both_kinds_the_picks_and_the_work(run_main,
                                                               monkeypatch):
    """The CPU has no device trace, so the reduction is stood in for; the
    pool's counters and the picks are the program's own, and the work
    numbers come from the traced steps' rows and the positions prefilled
    between the traced interval's edges."""
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
    assert seen["pt_kv_pages_alloc_total{bench,full}"] > 0
    # 8 x held / all: the metric's constant is the published 8 picks; the
    # tiny router picks 2 of 8 with 4 held, so about 8 x 4 / 8
    picks = line["metrics"]["moe_held_picks_per_token.serve"]["value"]
    assert 2.0 < picks < 6.0
    # the kernels' shares have nothing to read without a device trace
    assert "kda_step_roofline.serve" not in line["metrics"]
    assert "kda_chunk_mxu_share.serve" not in line["metrics"]
    config = harness.load_json("tests", "tiny", "kimi-linear-tiny.json")
    work = harness.load_module("kimi_linear_work.py")
    per_row = work.state_bytes_per_row(config)
    assert per_row == 3 * 2 * 3 * 8 * 8 * 4
    rows = seen["work.kda_step_bytes_per_decode_step"] / per_row
    assert 0 < rows <= config["engine"]["pool_slots"]
    assert seen["work.kda_calls_per_decode_step"] == 3
    assert seen["work.grouped_calls_per_decode_step"] == 9
    assert seen["work.paged_calls_per_decode_step"] == 1
    if "work.kda_chunk_flop_per_chunk" in seen:   # a chunk ran while traced
        per_token = work.rule_flop_per_token(config)
        assert 0 < seen["work.kda_chunk_flop_per_chunk"] <= 8 * per_token
        assert seen["work.mla_chunk_flop_per_chunk"] > 0
        assert seen["work.attn_calls_per_chunk"] == 1
    assert seen["work.kv_bytes_per_decode_step"] > 0
    assert seen["work.mla_decode_bytes_per_decode_step"] > 0
    assert seen["work.moe_bytes_per_decode_step"] > 0


def test_a_state_that_is_not_carried_across_chunks_is_not_correct(
        run_main, monkeypatch):
    """Every chunk reads its state block as zeros: a prompt longer than
    one chunk forgets what came before (the timed path broken)."""
    from paddle_tpu.kernels.primitives import gdn

    real = gdn.gated_delta_chunk_reference
    monkeypatch.setattr(
        gdn, "gated_delta_chunk_reference",
        lambda q, k, v, g, beta, state, block, fresh: real(
            q, k, v, g, beta, state, block, True))
    line, out = run_main(CELL)
    assert line["correct"] is False, out
    assert "CHECK FAIL served_logit_gap" in out


def test_a_decay_spread_over_the_head_is_not_correct(run_main, monkeypatch):
    """The decay of a head's FIRST key channel laid over all of them (the
    scalar-decay rule under this model's name) serves other tokens."""
    from paddle_tpu.kernels.primitives import gdn, kda

    chunk, step = gdn.gated_delta_chunk_reference, \
        kda.gated_delta_step_reference
    monkeypatch.setattr(
        gdn, "gated_delta_chunk_reference",
        lambda q, k, v, g, *rest: chunk(q, k, v, g[..., 0], *rest))
    monkeypatch.setattr(
        kda, "gated_delta_step_reference",
        lambda q, k, v, g, *rest: step(q, k, v, g[..., 0], *rest))
    line, out = run_main(CELL)
    assert line["correct"] is False, out
    assert "CHECK FAIL served_logit_gap" in out


def test_the_control_fails_fp8_and_the_bf16_state_where_bf16_passes():
    """The control at the tiny size: one reference pass a precision.  The
    sound program (float32 here) reads under the tiny limits; the
    reference in bf16 stays under the fp8 control, by the largest gap and
    by the mean; the reference with a bfloat16 state reads over the tiny
    limit on the mean, and under the reference in the stated precision
    over the sound program (the limits that separate at the cell's size
    are the chip's, PERF.md section 2)."""
    runner = harness.load_module("runners", "kimi_linear_decode_lane.py")
    lowprec = harness.load_module("reference", "lowprec.py")
    config = harness.load_json("tests", "tiny", "kimi-linear-tiny.json")
    mix = harness.load_json("tests", "tiny", "tiny-closed-state.json")
    (row,) = runner.control(config, mix, jax.devices()[:1], [45], lowprec,
                            3.0)     # long enough for 3 requests under load
    limits = config["correct"]
    assert row["requests"] == 3 and row["served_tokens"] > 9
    assert row["program"] < limits["served_logit_gap"]
    assert row["program_mean"] < limits["served_logit_gap_mean"]
    assert row["bf16"] < row["control_fp8"]
    assert row["bf16_mean"] < row["control_fp8_mean"]
    assert row["control_fp8"] > limits["served_logit_gap"]
    # a state kept in bfloat16 is no rounding of the float32 program
    assert row["bf16_state_mean"] > limits["served_logit_gap_mean"]
    assert row["bf16_state_mean"] > 10 * row["program_mean"]
    assert {"program_stated_mean", "bf16_state_stated_mean",
            "contexts"} <= set(row)


def test_the_work_counts_of_the_real_configuration():
    """kimi_linear_work.py at the committed configuration, against numbers
    worked by hand (the parameters, pages, blocks and
    ``KVPool.modeled_bytes`` are tier-1's:
    tests/test_kimi_linear_decode.py)."""
    work = harness.load_module("kimi_linear_work.py")
    with open(os.path.join(harness.HERE, "configs",
                           "kimi-linear-48b-ep4.json")) as f:
        config = json.load(f)
    assert work.kda_layers(config) == 6 and work.latent_layers(config) == 2
    assert work.latent_bytes_per_context_token(config) == 2 * 1152
    assert work.latent_bytes(config, [1000, 24]) == 1024 * 2304
    assert work.visible_pairs(0, 4) == 10 and work.visible_pairs(2, 4) == 7
    assert work.chunk_attention_flop(config, [(0, 4)]) == 10 * 20480 * 2
    assert work.expert_bytes(config) == 14155776
    assert work.state_values(config) == 32 * 128 * 128 == 524288
    assert work.state_bytes_per_row(config) == 6 * 2 * 2097152
    assert work.rule_flop_per_token(config) == 6 * 4194304
    assert work.decode_contexts(5000, 0, 3) == [5001, 5002]
