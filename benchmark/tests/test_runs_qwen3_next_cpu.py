"""The Qwen3-Next decode-lane cell's tiny twin end to end on the CPU: the
runner, the configuration's shape, the reference and `correct`, with
prompts of one to six chunks of 8 tokens, so that the recurrent state
(4 value heads on 2 key heads) and the convolution's tail are carried
from chunk to chunk and into the decode steps beside K/V rows in one
pool, and 4 of 16 softmax-routed experts are held.  The TPU requirement
is lifted here as in test_runs_cpu.py."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import harness, metrics

TINY = os.path.join(harness.HERE, "tests", "tiny")
CELL = "qwen3-next-tiny.tiny-closed-state"


@pytest.fixture()
def run_main(monkeypatch, capsys):
    monkeypatch.setattr(harness, "BENCHMARK_PATH",
                        os.path.join(TINY, "BENCHMARK.qwen3-next.json"))
    monkeypatch.setattr(harness, "TRAFFIC_DIRS",
                        harness.TRAFFIC_DIRS + [TINY])
    spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", os.path.join(harness.HERE, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)

    def call(workload, seed=2**31 + 49, seconds=1.0, trace=0):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      devices_for=lambda chips: jax.devices()[:chips])
        assert rc == 0
        out = capsys.readouterr().out
        return json.loads(out.strip().splitlines()[-1]), out

    return call


def test_qwen3_next_cell_end_to_end(run_main):
    line, out = run_main(CELL)
    assert line["correct"] is True, out
    assert set(line["metrics"]) == {"decode_tokens_per_s", "tpot_p95_ms",
                                    "setup_s"}
    assert line["attempted"] >= 3 and line["failed"] == 0
    assert "CHECK ok   served_logit_gap:" in out
    assert "CHECK ok   served_logit_gap_mean:" in out
    assert "CHECK ok   evictions_in_window:" in out
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
    # 10 x held / all: the metric's constant is the published 10 picks;
    # the tiny router picks 3 of 16 with 4 held, so about 10 x 4 / 16
    picks = line["metrics"]["moe_held_picks_of10.serve"]["value"]
    assert 0.8 < picks < 5.0
    # held experts touched / (4 held x 4 layers x steps): a share
    share = line["metrics"]["moe_experts_touched_share.serve"]["value"]
    assert 0.0 < share <= 100.0
    # the kernels' shares and the routing's time have nothing to read
    # without a device trace: the line leaves them out
    for name in ("gdn_step_roofline.serve", "gdn_chunk_mxu_share.serve",
                 "full_attn_roofline.serve", "moe_ffn_roofline.serve",
                 "moe_route_ms.serve"):
        assert name not in line["metrics"]
    config = harness.load_json("tests", "tiny", "qwen3-next-tiny.json")
    work = harness.load_module("qwen3_next_work.py")
    per_row = work.state_bytes_per_row(config)
    assert per_row == 3 * 2 * 4 * 8 * 8 * 4
    rows = seen["work.gdn_step_bytes_per_decode_step"] / per_row
    assert 0 < rows <= config["engine"]["pool_slots"]
    assert seen["work.gdn_calls_per_decode_step"] == 3
    assert seen["work.grouped_calls_per_decode_step"] == 12
    assert seen["work.full_attn_calls_per_decode_step"] == 1
    assert seen["work.paged_calls_per_decode_step"] == 1
    assert seen["work.held_expert_reads_possible"] == (
        4 * 4 * seen["work.traced_steps"])
    if "work.gdn_chunk_flop_per_chunk" in seen:   # a chunk ran while traced
        per_token = work.rule_flop_per_token(config)
        assert 0 < seen["work.gdn_chunk_flop_per_chunk"] <= 8 * per_token
        assert seen["work.gdn_calls_per_chunk"] == 3
    assert seen["work.kv_bytes_per_decode_step"] > 0
    assert seen["work.full_attn_bytes_per_decode_step"] == \
        seen["work.kv_bytes_per_decode_step"]
    assert seen["work.moe_bytes_per_decode_step"] > 0


def test_the_route_pattern_reads_the_sorts_of_a_decode_step():
    """``moe_route_ms.serve``'s pattern on hand-made events (their names
    as the chip's trace spells them, PR 49): the expert layers' sorts
    inside decode steps (the top-10, a whole sort of 512 scores a row,
    and the picks' sorts), nothing else, and not a prefill chunk's."""
    reader = harness.load_module("readers", "trace_pattern.py")
    spec = harness.load_json("layer_metrics", "moe_route_ms.serve.json")
    ms = 1_000_000
    modules = [("jit_decode_step(123)", 0, 10 * ms),
               ("jit_prefill_chunk(456)", 20 * ms, 10 * ms)]
    ops = [
        ("%sort.12 = (s32[640]{0}, s32[640]{0}) sort(%a, %b), dimensions={0}",
         1 * ms, 2 * ms),
        ("%sort.2 = (f32[64,512], s32[64,512]) sort(f32[64,512] %fusion.284, "
         "s32[64,512] %iota.2), dimensions={1}, is_stable=true", 4 * ms,
         1 * ms),
        ("%fusion.3 = f32[64,512]{1,0} fusion(%p), kind=kLoop", 6 * ms, ms),
        ("%grouped_matmul.2 = f32[640,512]{1,0} custom-call(%q), "
         "custom_call_target=\"tpu_custom_call\"", 7 * ms, ms),
        ("%sort.12 = (s32[5120]{0}, s32[5120]{0}) sort(%a, %b), "
         "dimensions={0}", 21 * ms, 4 * ms),
    ]
    reduced = {"first": {"ops": ops, "modules": modules}}
    got = reader.read(spec, {"work.traced_steps": 2.0}, reduced, {})
    assert got == pytest.approx((2 + 1) / 2.0)
    assert reader.read(spec, {"work.traced_steps": 2.0},
                       {"first": {"ops": ops[2:4], "modules": modules}},
                       {}) is None


def test_a_sigmoid_router_is_not_correct(run_main, monkeypatch):
    """The expert layer routed by sigmoid scores (every other expert
    model's router under this model's name) serves other tokens."""
    from paddle_tpu.ops import mla_ops

    import jax.numpy as jnp

    monkeypatch.setattr(
        mla_ops, "route_softmax_topk",
        lambda x2, w, top_k, scaling, normalize: mla_ops.route_sigmoid_topk(
            x2, w, jnp.zeros(w.shape[1], jnp.float32), top_k, scaling,
            normalize))
    line, out = run_main(CELL)
    assert line["correct"] is False, out
    assert "CHECK FAIL served_logit_gap" in out


def test_value_heads_that_read_the_wrong_key_head_are_not_correct(
        run_main, monkeypatch):
    """Value head h on key head h % H_k (the heads interleaved instead of
    grouped) is another model: the timed path broken."""
    from paddle_tpu.kernels.primitives import gdn

    import jax.numpy as jnp

    monkeypatch.setattr(
        gdn, "_per_value_head",
        lambda x, heads: jnp.tile(x, (1,) * (x.ndim - 2)
                                  + (heads // x.shape[-2], 1)))
    line, out = run_main(CELL)
    assert line["correct"] is False, out
    assert "CHECK FAIL served_logit_gap" in out


def test_the_control_fails_fp8_and_the_bf16_state_where_bf16_passes():
    """The control at the tiny size: one reference pass a precision.  The
    sound program (float32 here) reads under the tiny limits; the
    reference in bf16 stays under the fp8 control, by the largest gap and
    by the mean; the reference with a bfloat16 state reads over the tiny
    limit on the mean (the limits that separate at the cell's size are
    the chip's, PERF.md section 2)."""
    runner = harness.load_module("runners", "qwen3_next_decode_lane.py")
    lowprec = harness.load_module("reference", "lowprec.py")
    config = harness.load_json("tests", "tiny", "qwen3-next-tiny.json")
    mix = harness.load_json("tests", "tiny", "tiny-closed-state.json")
    rows = list(runner.control(config, mix, jax.devices()[:1], [49, 52],
                               lowprec, 3.0))     # 3 requests under load
    limits = config["correct"]
    for row in rows:
        assert row["requests"] == 3 and row["served_tokens"] > 9
        assert row["program"] < limits["served_logit_gap"]
        assert row["program_mean"] < limits["served_logit_gap_mean"]
        assert row["bf16"] < row["control_fp8"]
        assert row["bf16_mean"] < row["control_fp8_mean"]
        assert row["control_fp8"] > limits["served_logit_gap"]
        assert {"program_stated_mean", "bf16_state_stated_mean",
                "contexts"} <= set(row)
    # a state kept in bfloat16 is no rounding of the float32 program: of
    # ~60 served tokens some are no longer the reference's first (which
    # requests a window finishes, and so the sample, follows the clock:
    # two seeds, so that one sample without a flip does not fail this)
    assert sum(r["bf16_state_mean"] for r in rows) > \
        limits["served_logit_gap_mean"]
    assert sum(r["bf16_state_mean"] for r in rows) > 10 * sum(
        r["program_mean"] for r in rows)


def test_the_work_counts_of_the_real_configuration():
    """qwen3_next_work.py at the committed configuration, against numbers
    worked by hand (the parameters, pages, blocks and
    ``KVPool.modeled_bytes`` are tier-1's:
    tests/test_qwen3_next_decode.py)."""
    work = harness.load_module("qwen3_next_work.py")
    with open(os.path.join(harness.HERE, "configs",
                           "qwen3-next-80b-ep4.json")) as f:
        config = json.load(f)
    assert work.linear_layers(config) == 6 and work.full_layers(config) == 2
    assert work.expert_layers(config) == 8
    assert work.kv_bytes_per_context_token(config) == 2 * 2048
    assert work.full_attn_bytes(config, [1000, 24]) == 1024 * 4096
    assert work.expert_bytes(config) == 6291456
    assert work.grouped_calls_per_decode_step(config) == 24 == \
        config["work"]["grouped_calls_per_decode_step"]
    assert work.held_expert_reads_possible(config, 10) == 128 * 8 * 10
    assert work.state_values(config) == 32 * 128 * 128 == 524288
    assert work.state_bytes_per_row(config) == 6 * 2 * 2097152
    assert work.rule_flop_per_token(config) == 6 * 8 * 128 * 128 * 32
    assert work.decode_contexts(5000, 0, 3) == [5001, 5002]
    # the cell: 64 clients = 64 slots, every request inside max_len
    mix = harness.load_json("traffic", "closed64-1k-16k-out1k.json")
    assert mix["clients"] == config["engine"]["pool_slots"] == 64
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] == \
        config["engine"]["max_len"]
