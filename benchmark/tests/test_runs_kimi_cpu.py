"""The Kimi-VL decode-lane cell's tiny twin end to end on the CPU: the
runner (prompts laid out with images, pixels from the seed, the images
sent with their requests), the configuration's shape, the reference and
`correct`; a timed path that ignores the images comes out not correct,
and so does a lower precision where bf16 passes.  The TPU requirement is
lifted here as in test_runs_cpu.py."""

import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from benchmark import harness, metrics

TINY = os.path.join(harness.HERE, "tests", "tiny")
CELL = "kimi-vl-tiny.tiny-closed-vl"


@pytest.fixture()
def run_main(monkeypatch, capsys):
    monkeypatch.setattr(harness, "BENCHMARK_PATH",
                        os.path.join(TINY, "BENCHMARK.kimi.json"))
    monkeypatch.setattr(harness, "TRAFFIC_DIRS",
                        harness.TRAFFIC_DIRS + [TINY])
    spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", os.path.join(harness.HERE, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)

    def call(workload, seed=2**31 + 33, seconds=1.0, trace=0):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      devices_for=lambda chips: jax.devices()[:chips])
        assert rc == 0
        out = capsys.readouterr().out
        return json.loads(out.strip().splitlines()[-1]), out

    return call


def _tiny():
    with open(os.path.join(TINY, "kimi-vl-tiny.json")) as f:
        config = json.load(f)
    with open(os.path.join(TINY, "tiny-closed-vl.json")) as f:
        return config, json.load(f)


def test_kimi_cell_end_to_end(run_main):
    line, out = run_main(CELL)
    assert line["correct"] is True, out
    assert set(line["metrics"]) == {"decode_tokens_per_s", "tpot_p95_ms",
                                    "setup_s"}
    assert line["attempted"] >= 3 and line["failed"] == 0
    for name in ("served_logit_gap", "served_logit_gap_mean",
                 "served_logit_gap_p90", "compiles_in_window"):
        assert f"CHECK ok   {name}:" in out
    share = float(out.split("image share ")[1].split()[0])
    assert 0.35 < share <= 0.6
    assert "'live_max':" in out


def test_a_timed_path_that_ignores_the_images_is_not_correct(run_main,
                                                             monkeypatch):
    """Every image position fed the placeholder's own embedding: the
    requests run to their ends and the logits are another model's."""
    from paddle_tpu.serving import decode

    monkeypatch.setattr(
        decode.DecodeEngine, "_stage_image_rows",
        lambda self, req, ctx_len, valid: np.full(
            (1, self.prefill_chunk), -1, np.int32))
    line, out = run_main(CELL)
    assert line["correct"] is False and line["failed"] == 0
    assert "CHECK FAIL served_logit_gap_mean:" in out
    assert "CHECK ok   failed_requests:" in out


def test_the_layout_keeps_the_share_and_the_rotation():
    config, mix = _tiny()
    runner = harness.load_module("runners", "kimi_vl_decode_lane.py")
    real = dict(mix["images"], pixels=[[896, 896], [896, 1344], [448, 448]],
                share_of_prompt=0.8, text_after_image=16, text_last_min=32)
    big = {"vision_config": {"patch_size": 14}}
    for length in (2048, 5000, 32768):
        parts = runner.lay_out(length, real, big)
        rows = [(1024, 1536, 256)[n] for kind, n in parts if kind == "image"]
        text = [n for kind, n in parts if kind == "text"]
        assert sum(rows) + sum(text) == length
        assert 0.75 <= sum(rows) / length <= 0.8
        assert text[-1] >= 32 and set(text[1:-1]) <= {16}
    parts = runner.lay_out(32768, real, big)
    picked = [n for kind, n in parts if kind == "image"]
    assert picked[:6] == [0, 1, 2, 0, 1, 2] and 20 <= len(picked) <= 30
    # the tiny mix: an image of each size in a prompt long enough
    parts = runner.lay_out(40, mix["images"], config)
    assert [n for kind, n in parts if kind == "image"][:2] == [0, 1]


def test_a_traced_run_values_the_new_work(run_main, monkeypatch):
    """The CPU has no device trace, so the reduction is stood in for;
    the work numbers come from the traced interval's own steps, chunks
    and encoder runs."""
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
    assert seen["work.mla_decode_bytes_per_decode_step"] > 0
    assert seen["work.mla_chunk_flop_per_chunk"] > 0
    assert seen["work.vit_attn_flop_per_run"] > 0
    assert seen["work.moe_bytes_per_decode_step"] > 0
    assert seen["work.grouped_calls_per_decode_step"] == 6
    # no pick is absent: every expert is held
    assert seen["pt_moe_picks_total{bench,absent}"] == 0
    assert line["metrics"]["slot_occupancy.serve"]["value"] > 0


def test_control_separates_fp8_from_bf16():
    config, mix = _tiny()
    runner = harness.load_module("runners", "kimi_vl_decode_lane.py")
    lowprec = harness.load_module("reference", "lowprec.py")
    (row,) = list(runner.control(config, mix, jax.devices()[:1], [77],
                                 lowprec, 1.0))
    limits = config["correct"]
    assert row["requests"] == 3 and sum(row["images"]) > 0
    assert row["program"] <= limits["served_logit_gap"]
    assert row["program_mean"] <= limits["served_logit_gap_mean"]
    assert row["control_fp8_mean"] > 10 * limits["served_logit_gap_mean"]
    assert row["control_fp8_mean"] > 3 * row["bf16_mean"]


def test_kimi_work_against_hand_worked_numbers():
    work = harness.load_module("kimi_work.py")
    with open(os.path.join(harness.HERE, "configs",
                           "kimi-vl-a3b-ep1.json")) as f:
        config = json.load(f)
    # a latent row: 576 values of 2 bytes, 6 layers
    assert work.latent_bytes_per_context_token(config) == 1152 * 6
    assert work.latent_bytes(config, [1000, 12000]) == 13000 * 6912
    assert work.decode_contexts(100, 0, 4) == [101, 102, 103]
    # an expert: three matrices of 2048 x 1408 in bfloat16
    assert work.expert_bytes(config) == 3 * 2048 * 1408 * 2 == 17301504
    # queries 0..511 see 1 + 2 + .. + 512 keys; 512..1023 the rest
    assert work.visible_pairs(0, 512) == 512 * 513 // 2
    assert (work.visible_pairs(0, 512) + work.visible_pairs(512, 1024)
            == work.visible_pairs(0, 1024))
    assert work.chunk_attention_flop(config, [(0, 512)]) == (
        131328 * 10240 * 6)
    # a chunk of 512 queries at context 16k: ~9.4 MFLOP x L less the
    # up-projection's share (ISSUE 33: 512 x 16 x L x 320 x 2 = 5.24 M x L)
    at_16k = work.chunk_attention_flop(config, [(16384, 16896)]) / 6
    assert abs(at_16k / (5.24288e6 * 16640) - 1) < 0.01
    # the tower: 896 x 896 is 64 x 64 patches and 1024 rows
    grid = (64, 64)
    assert work.image_rows(grid) == 1024
    assert work.tower_attention_flop(config, grid) == (
        4 * 4096 * 4096 * 1152 * 6)
    block = 2 * 4096 * (4 * 1152 ** 2 + 2 * 1152 * 4304)
    rest = 2 * 4096 * 588 * 1152 + 2 * 1024 * (4608 * 4608 + 4608 * 2048)
    assert work.tower_flop(config, grid) == (
        6 * block + work.tower_attention_flop(config, grid) + rest)
