"""The MiMo decode-lane cell's tiny twin end to end on the CPU: the
runner, the configuration's shape, the reference and `correct`, with
contexts from inside the tiny window (6 tokens, shorter than the 8-token
chunk) to nine windows deep, so that window pages go back to the pool
while their requests live.  The TPU requirement is lifted here as in
test_runs_cpu.py."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import harness, metrics

TINY = os.path.join(harness.HERE, "tests", "tiny")
CELL = "mimo-tiny.tiny-closed-window"


@pytest.fixture()
def run_main(monkeypatch, capsys):
    monkeypatch.setattr(harness, "BENCHMARK_PATH",
                        os.path.join(TINY, "BENCHMARK.mimo.json"))
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


def test_mimo_cell_end_to_end(run_main):
    line, out = run_main(CELL)
    assert line["correct"] is True, out
    assert set(line["metrics"]) == {"decode_tokens_per_s", "tpot_p95_ms",
                                    "setup_s"}
    assert line["attempted"] >= 3 and line["failed"] == 0
    assert "CHECK ok   served_logit_gap:" in out
    assert "CHECK ok   served_logit_gap_mean:" in out
    assert "CHECK ok   served_logit_gap_p90:" in out
    assert "CHECK ok   evictions_in_window:" in out


def test_a_traced_run_values_the_pool_and_the_work(run_main, monkeypatch):
    """The CPU has no device trace, so the reduction is stood in for; the
    pool's page counters are the program's own, and the work numbers come
    from the traced steps' contexts and the prompts' prefilled spans."""
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
    # the metric's file names the real configuration's window kind
    monkeypatch.setattr(harness, "load_json", lambda *parts, real=harness.
                        load_json: json.loads(json.dumps(real(*parts))
                                              .replace("window128",
                                                       "window6")))
    line, out = run_main(CELL, seconds=2.0, trace=1)
    assert line["correct"] is True, out
    freed = line["metrics"]["window128_pages_freed_share.serve"]["value"]
    assert 0.0 < freed < 100.0
    assert seen["work.full_attn_bytes_per_decode_step"] > 0
    # two full layers of 2 K/V heads against five window layers of 4: a
    # window layer's token is twice as wide and never more than the
    # context long
    assert (0 < seen["work.window_attn_bytes_per_decode_step"]
            <= 5 * seen["work.full_attn_bytes_per_decode_step"])
    assert seen["work.moe_bytes_per_decode_step"] > 0
    assert seen["work.asym_chunk_flop_per_chunk"] > 0
    assert seen["work.attn_calls_per_chunk"] == 7
    assert seen["pt_moe_picks_total{bench,any}"] > 0


def test_a_timed_path_that_drops_the_sink_is_not_correct(run_main,
                                                         monkeypatch):
    """Window layers served with the plain softmax: every probability of
    theirs is too large by the sink's share."""
    from paddle_tpu.kernels.primitives import paged

    real = paged.paged_attention_reference
    monkeypatch.setattr(
        paged, "paged_attention_reference",
        lambda *a, sinks=None, **kw: real(*a, sinks=None, **kw))
    line, out = run_main(CELL)
    assert line["correct"] is False, out
    assert "CHECK FAIL served_logit_gap" in out


def test_a_timed_path_that_ignores_the_window_is_not_correct(run_main,
                                                             monkeypatch):
    from paddle_tpu.kernels.primitives import paged

    real = paged.paged_attention_reference
    monkeypatch.setattr(
        paged, "paged_attention_reference",
        lambda *a, window=None, **kw: real(*a, window=None, **kw))
    line, out = run_main(CELL)
    assert line["correct"] is False, out


@pytest.mark.parametrize("broken", ["thetas_swapped", "whole_head_rotated"])
def test_a_timed_path_that_rotates_otherwise_is_not_correct(
        run_main, monkeypatch, broken):
    """The two kinds' thetas swapped, or the whole head rotated where the
    first rotary_dim entries should be."""
    from paddle_tpu.models import mimo

    real = mimo.layers.rope_half

    def rope(x, pos, theta, rotary_dim=None, name=None):
        if broken == "thetas_swapped":
            theta = 1e7 if theta == 1e4 else 1e4
        else:
            rotary_dim = None
        return real(x, pos, theta, rotary_dim=rotary_dim, name=name)

    monkeypatch.setattr(mimo.layers, "rope_half", rope)
    line, out = run_main(CELL)
    assert line["correct"] is False, out


def test_the_control_fails_fp8_where_bf16_passes():
    """The control at the tiny size: one reference pass a precision.  The
    sound program (float32 here) reads under the tiny limits; the fp8
    control reads over one by the largest gap and several times the
    reference in bf16 by the mean (seven layers of weights drawn at 0.3
    move a tiny model's logits far under bf16 too: 0.4-1.8 at the
    largest, two seeds; the limits that separate a precision at the
    cell's size are the chip's, PERF.md section 2)."""
    runner = harness.load_module("runners", "mimo_decode_lane.py")
    lowprec = harness.load_module("reference", "lowprec.py")
    config = harness.load_json("tests", "tiny", "mimo-tiny.json")
    mix = harness.load_json("tests", "tiny", "tiny-closed-window.json")
    (row,) = runner.control(config, mix, jax.devices()[:1], [31], lowprec,
                            4.0)     # long enough for 3 requests under load
    limits = config["correct"]
    assert row["requests"] == 3 and row["served_tokens"] > 9
    assert row["program"] < limits["served_logit_gap"]
    assert row["program_mean"] < limits["served_logit_gap_mean"]
    assert row["program_p90"] < limits["served_logit_gap_p90"]
    assert row["bf16"] < row["control_fp8"] and row["control_fp8"] > 1.0
    assert 4 * row["bf16_mean"] < row["control_fp8_mean"]
    assert {"program_under_share", "control_fp8_p99",
            "contexts"} <= set(row)


def test_the_work_counts_of_the_real_configuration():
    """mimo_work.py at the committed configuration, against bytes worked
    by hand: a full layer's token leaves K 4 x 192 and V 4 x 128
    bfloat16, a window layer's K 8 x 192 and V 8 x 128."""
    work = harness.load_module("mimo_work.py")
    with open(os.path.join(harness.HERE, "configs",
                           "mimo-v2.5-ep16.json")) as f:
        config = json.load(f)
    assert work.kv_bytes_per_token_per_layer(config, work.FULL) == 2560
    assert work.kv_bytes_per_token_per_layer(config, work.WINDOW) == 5120
    assert work.layers_of(config, work.FULL) == 2
    assert work.layers_of(config, work.WINDOW) == 5
    assert work.kv_bytes_per_context_token(config) == 5120
    assert work.decode_contexts(5000, 0, 3) == [5001, 5002]
    ctx = [100, 128, 5000, 32768]
    assert work.full_attn_bytes(config, ctx) == (100 + 128 + 5000
                                                 + 32768) * 2 * 2560
    assert work.window_attn_bytes(config, ctx) == (100 + 128 + 128
                                                   + 128) * 5 * 5120
    assert work.expert_bytes(config) == 3 * 4096 * 2048 * 2 == 50331648
    # a chunk of positions 512 .. 1023: full layers see q + 1 keys,
    # window layers 128
    pairs_full = sum(q + 1 for q in range(512, 1024))
    assert work.visible_pairs(512, 1024) == pairs_full
    assert work.visible_pairs(0, 200, 128) == sum(
        min(q + 1, 128) for q in range(200))
    assert work.chunk_attention_flop(config, [(512, 1024)]) == 40960 * (
        2 * pairs_full + 5 * 512 * 128)
    # the issue's arithmetic: 3429.9 M parameters, 4353 and 97 pages
    from paddle_tpu.serving import lane
    ref = harness.load_module("reference", "mimo.py")
    n = sum(int(__import__("numpy").prod(s))
            for s, _, _ in ref.param_shapes(config).values())
    assert round(n / 1e6, 1) == 3430.0
    e = config["engine"]
    assert 16 * lane.window_pages_per_seq(
        128, e["prefill_chunk"], e["page_size"]) + 1 == 97
    assert 16 * -(-e["max_len"] // e["page_size"]) + 1 == 4353


def test_the_configuration_keeps_the_catalogs_numbers():
    """Every key of the source that ``reduced`` does not name is in the
    file as published."""
    with open(os.path.join(harness.HERE, "configs",
                           "mimo-v2.5-ep16.json")) as f:
        config = json.load(f)
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "mimo-v2.5-ep16"][0]
    assert sorted(entry["reduced"]) == sorted(config["changed"])
    published = dict(
        hidden_size=4096, num_attention_heads=64, num_key_value_heads=4,
        swa_num_key_value_heads=8, head_dim=192, v_head_dim=128,
        swa_head_dim=192, swa_v_head_dim=128, sliding_window=128,
        moe_intermediate_size=2048, intermediate_size=16384,
        num_experts_per_tok=8, n_routed_experts_total=256,
        partial_rotary_factor=0.334, rope_theta=10000000,
        swa_rope_theta=10000, attention_value_scale=0.707)
    assert {k: config[k] for k in published} == published
    args = config["builder"]["config_args"]
    for key in ("hidden_size", "num_attention_heads", "head_dim",
                "v_head_dim", "sliding_window", "moe_intermediate_size",
                "intermediate_size", "num_experts_per_tok",
                "hybrid_layer_pattern", "moe_layer_freq", "vocab_size",
                "num_hidden_layers"):
        assert args[key] == config[key], key
    assert args["n_routed_experts"] == 256 and args["held_experts"] == 16
