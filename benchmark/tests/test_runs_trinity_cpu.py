"""The Trinity decode-lane cell's tiny twin end to end on the CPU: the
runner, the configuration's shape, the reference and `correct`, with
contexts from inside the tiny window (8 tokens) to five windows deep, so
that window pages go back to the pool while their requests live.  The TPU
requirement is lifted here as in test_runs_cpu.py."""

import importlib.util
import json
import os

import jax
import pytest

from benchmark import harness, metrics

TINY = os.path.join(harness.HERE, "tests", "tiny")
CELL = "trinity-tiny.tiny-closed-window"


@pytest.fixture()
def run_main(monkeypatch, capsys):
    monkeypatch.setattr(harness, "BENCHMARK_PATH",
                        os.path.join(TINY, "BENCHMARK.trinity.json"))
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


def test_trinity_cell_end_to_end(run_main):
    line, out = run_main(CELL)
    assert line["correct"] is True, out
    assert set(line["metrics"]) == {"decode_tokens_per_s", "tpot_p95_ms",
                                    "setup_s"}
    assert line["attempted"] >= 3 and line["failed"] == 0
    assert "CHECK ok   served_logit_gap:" in out
    assert "CHECK ok   served_logit_gap_mean:" in out
    assert "CHECK ok   served_logit_gap_p90:" in out


def test_a_traced_run_values_the_pool_and_the_work(run_main, monkeypatch):
    """The CPU has no device trace, so the reduction is stood in for; the
    pool's page counters are the program's own, and the work numbers come
    from the traced steps' contexts."""
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
                                              .replace("window4096",
                                                       "window8")))
    line, out = run_main(CELL, seconds=2.0, trace=1)
    assert line["correct"] is True, out
    freed = line["metrics"]["window_pages_freed_share.serve"]["value"]
    assert 0.0 < freed < 100.0
    assert seen["work.full_attn_bytes_per_decode_step"] > 0
    # a window layer never needs more than the full layer's context: four
    # window layers to one full one
    assert (0 < seen["work.window_attn_bytes_per_decode_step"]
            <= 4 * seen["work.full_attn_bytes_per_decode_step"])
    assert seen["work.moe_bytes_per_decode_step"] > 0
    assert seen["pt_moe_picks_total{bench,any}"] > 0


def test_a_timed_path_that_ignores_the_window_is_not_correct(run_main,
                                                             monkeypatch):
    """Sliding layers served as full ones: every context past the window
    reads keys the model does not see."""
    from paddle_tpu.kernels.primitives import paged

    real = paged.paged_attention_reference
    monkeypatch.setattr(
        paged, "paged_attention_reference",
        lambda *a, window=None, **kw: real(*a, window=None, **kw))
    line, out = run_main(CELL)
    assert line["correct"] is False, out
    assert "CHECK FAIL served_logit_gap" in out


def test_full_layers_that_rotate_are_not_correct(run_main, monkeypatch):
    """RoPE on the full layers too: their scores change with position."""
    from paddle_tpu.models import trinity

    real = trinity._attention

    def rotated(x, pos, table, q_start, pools, write, shape, window, cfg,
                name, force):
        if window is not None:
            return real(x, pos, table, q_start, pools, write, shape, window,
                        cfg, name, force)
        hooked = []
        orig = trinity.layers.paged_attention

        def attn(q, *a, **kw):
            hooked.append(1)
            return orig(trinity.layers.transpose(trinity.layers.rope_half(
                trinity.layers.transpose(q, perm=[0, 2, 1, 3]), pos,
                theta=cfg.rope_theta), perm=[0, 2, 1, 3]), *a, **kw)

        monkeypatch.setattr(trinity.layers, "paged_attention", attn)
        try:
            return real(x, pos, table, q_start, pools, write, shape, window,
                        cfg, name, force)
        finally:
            monkeypatch.setattr(trinity.layers, "paged_attention", orig)

    monkeypatch.setattr(trinity, "_attention", rotated)
    line, out = run_main(CELL)
    assert line["correct"] is False, out


def test_the_control_fails_fp8_where_bf16_passes():
    """The control at the tiny size: one reference pass a precision.  The
    sound program (float32 here) reads under the tiny limits; the
    reference in bf16 stays under a tenth, the fp8 control reads over it,
    by the largest gap and by the mean (the limits that separate a
    precision at the cell's size are the chip's, PERF.md section 2)."""
    runner = harness.load_module("runners", "trinity_decode_lane.py")
    lowprec = harness.load_module("reference", "lowprec.py")
    config = harness.load_json("tests", "tiny", "trinity-tiny.json")
    mix = harness.load_json("tests", "tiny", "tiny-closed-window.json")
    (row,) = runner.control(config, mix, jax.devices()[:1], [31], lowprec,
                            3.0)     # long enough for 3 requests under load
    limits = config["correct"]
    assert row["requests"] == 3 and row["served_tokens"] > 9
    assert row["program"] < limits["served_logit_gap"]
    assert row["program_mean"] < limits["served_logit_gap_mean"]
    assert row["program_p90"] < limits["served_logit_gap_p90"]
    assert row["bf16"] < 0.1 < row["control_fp8"]
    assert row["bf16_mean"] < row["control_fp8_mean"]
    assert {"program_under_share", "control_fp8_p99",
            "contexts"} <= set(row)


def test_gap_statistics():
    runner = harness.load_module("runners", "trinity_decode_lane.py")
    assert runner.gap_stats([]) == {}
    st = runner.gap_stats([0.0] * 8 + [0.5, 1.5])
    assert st["max"] == 1.5 and st["median"] == 0.0
    assert st["mean"] == pytest.approx(0.2)
    assert st["under_share"] == pytest.approx(0.2)
    assert st["p90"] == pytest.approx(0.6)      # linear between 0.5 and 1.5


def test_the_work_counts_of_the_real_configuration():
    """trinity_work.py at the committed configuration, against bytes
    worked by hand: a token's K and V rows are 8 x 128 bfloat16 each."""
    work = harness.load_module("trinity_work.py")
    with open(os.path.join(harness.HERE, "configs",
                           "trinity-large-ep8.json")) as f:
        config = json.load(f)
    assert work.kv_bytes_per_token_per_layer(config) == 4096
    assert work.layers_of(config, work.FULL) == 1
    assert work.layers_of(config, work.SLIDING) == 4
    assert work.kv_bytes_per_context_token(config) == 4096
    # a request of 5000 prompt tokens, generated tokens 1 and 2: contexts
    # 5001 and 5002 (token 0 is the prefill's)
    assert work.decode_contexts(5000, 0, 3) == [5001, 5002]
    assert work.decode_contexts(5000, 2, 3) == [5002]
    ctx = [100, 4096, 5000, 32768]
    assert work.full_attn_bytes(config, ctx) == (100 + 4096 + 5000
                                                 + 32768) * 4096
    assert work.window_attn_bytes(config, ctx) == (100 + 4096 + 4096
                                                   + 4096) * 4 * 4096
    assert work.expert_bytes(config) == 3 * 3072 * 3072 * 2 == 56623104
    # the issue's arithmetic: 4321.8 M parameters, 593 and 4193 pages
    from paddle_tpu.serving import lane
    ref = harness.load_module("reference", "trinity.py")
    n = sum(int(__import__("numpy").prod(s))
            for s, _, _ in ref.param_shapes(config).values())
    assert round(n / 1e6, 1) == 4321.9
    e = config["engine"]
    assert 16 * lane.window_pages_per_seq(
        4096, e["prefill_chunk"], e["page_size"]) + 1 == 593
    assert 16 * -(-e["max_len"] // e["page_size"]) + 1 == 4193
