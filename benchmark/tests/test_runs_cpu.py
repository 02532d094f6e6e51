"""Both runners end to end at tiny configuration files on the CPU.  The
TPU requirement is lifted here and only here: ``main`` is handed a device
lookup that takes what the CPU has.  The same drive, with the timed path
broken underneath, has to come out as not correct."""

import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from benchmark import harness

TINY = os.path.join(harness.HERE, "tests", "tiny")


@pytest.fixture()
def run_main(monkeypatch, capsys):
    monkeypatch.setattr(harness, "BENCHMARK_PATH",
                        os.path.join(TINY, "BENCHMARK.json"))
    monkeypatch.setattr(harness, "TRAFFIC_DIRS",
                        harness.TRAFFIC_DIRS + [TINY])
    spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", os.path.join(harness.HERE, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)

    def call(workload, seed=2**31 + 17, seconds=1.0):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0"],
                      devices_for=lambda chips: jax.devices()[:chips])
        assert rc == 0
        out = capsys.readouterr().out
        return json.loads(out.strip().splitlines()[-1]), out

    return call


def test_without_a_tpu_there_is_no_result():
    with pytest.raises(harness.NoChip):
        harness.require_devices(1)


def test_train_cell_end_to_end(run_main):
    line, out = run_main("bert-tiny.tiny-train")
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True, out
    assert set(line["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert line["metrics"]["train_tokens_per_s_chip"]["value"] > 0
    assert line["attempted"] > 3 and line["failed"] == 0
    assert "CHECK ok   grad_diff_all" in out


def test_train_cell_on_four_devices(run_main):
    if jax.device_count() < 4:
        pytest.skip("needs 4 virtual devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    line, out = run_main("bert-tiny.tiny-train-dp")
    assert line["correct"] is True, out
    assert "CHECK ok   replica_norm_spread" in out


def test_train_step_that_returns_its_state_unchanged_is_not_correct(
        run_main, monkeypatch):
    train = harness.load_module("runners", "train.py")
    real = train.Trainer.step

    def frozen(self):
        names = self.param_names
        keep = {n: np.asarray(self.scope.get(n)) for n in names}
        loss = real(self)
        for n, v in keep.items():
            self.scope.set(n, jax.numpy.asarray(v))
        return loss

    monkeypatch.setattr(train.Trainer, "step", frozen)
    line, out = run_main("bert-tiny.tiny-train")
    assert line["correct"] is False
    assert "CHECK FAIL delta_gap_all" in out


def test_decode_cell_end_to_end(run_main):
    line, out = run_main("gpt-tiny.tiny-closed", seconds=2.0)
    assert line["correct"] is True, out
    assert set(line["metrics"]) == {"decode_tokens_per_s", "tpot_p95_ms",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["attempted"] >= 3 and line["failed"] == 0


def test_decode_token_altered_where_it_is_produced_is_not_correct(
        run_main, monkeypatch):
    from paddle_tpu import serving

    real = serving.DecodeEngine._run_decode_feed

    def altered(self, active, warm=False):
        out = np.array(real(self, active, warm=warm))
        return (out + 1) % self.cfg.vocab_size

    monkeypatch.setattr(serving.DecodeEngine, "_run_decode_feed", altered)
    line, out = run_main("gpt-tiny.tiny-closed", seconds=2.0)
    assert line["correct"] is False
    assert "CHECK FAIL served_logit_gap" in out
